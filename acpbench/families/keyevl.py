"""The Keye-VL-2.0 family (`models/keye.py`; `model_type: KeyeVL2`), its
language model: GQA layers whose attention runs over the `sa_config.topk`
rows a learned indexer chooses for every query, three-axis rotary positions,
routed experts in every layer of which the chip holds `num_experts_held`
(experts 0 .. held-1 of `num_experts`, the router's width), no shared expert.

The file keeps the source's `config.json` keys as published (`assumed` says
what the source leaves out). Weights: `keyevl_weights.py`, bfloat16, the one
precision this family draws (`engine.quantize` must be absent). Reference:
`keyevl_reference.py`, given the same `held`. Its controls (`lower=`), each of
which the file's limits must refuse but "bf16" and "bf16_rest":

- `"int8"`: every matmul input, K, V and the indexer's keys rounded to int8
  per row, the precision below the configuration's; `"bf16"`: the same to
  bfloat16, the stated precision (it must pass); `"bf16_rest"`: besides,
  every tensor the program keeps at rest in bfloat16: the floor the
  program's own reading is held beside. These three are GIVEN the program's
  choices, as the reference itself is: left to choose, a bfloat16 pass
  chooses one row in twelve otherwise and reads 0.32-0.36 of the logits,
  what ANY free pass reads here, rounded or not (PERF.md section 6);
- `"recent"`: the most recent `topk` rows chosen (no indexer); `"w_one"`:
  the indexer's head weights 1; `"topk_half"`: half as many rows chosen;
  `"index_rope_off"`; `"index_norm_off"`; `"dense"`: no selection at all:
  each a free pass, which chooses otherwise by what it is.

The cache's own controls (keywords of `cached_logits`): `ik_int8=True` (the
pool's `ik` leaf holds what int8 keys would: rounded by a row's largest
value and back after the prefill and after each decode step), `kv_int8=True`
(the same of K and V a head), and `indexer=<fault>`: one of the reference's
six faults of the choice (`CHOICE_FAULTS`) PLANTED IN THE PROGRAM's indexer
while its programs are traced (`_planted`), so that the run is what a
program wrong in that way would serve, judged as every run is judged: its
logits against the reference given ITS choices (which pass: the arithmetic
is sound), its choices against the free reference's (which refuse it). The
limits on the choices rest on these readings (`keyevl_study`).

Structural controls that scale with the context (`check.py`'s one swapped
page is 16 tokens among 12k and moves no first choice). Of the cache:
`ik_crossed=True`, the third leaf ALONE crossed: after the prefills every
sequence's `ik` pages hold its neighbour's rows (K and V its own), so the
decode steps choose by another request's keys; `select_cache_miss` and the
decode steps' numbers against the free reference refuse it. Of the engine
path: `crossed_numbers`' `pages_crossed`, the reference in the engine's place
as `check.py`'s control is, reading a context whose every second page holds
another request's tokens (a table crossed with another slot's);
`greedy_regret`'s limit lies between the engine's own tokens' readings and
it.

**Choices are given where logits are compared, and compared themselves.**
Among 12k-16k index scores the 2,048th and the 2,049th lie a thousandth of
their spread apart, and among 128 router scores the eighth and the ninth
close: bfloat16 decides a few of a hundred of either choice, and a row or an
expert chosen otherwise moves its token's logits far more than all the
rounding in them. So:

- the prompt's prefill and the decode steps through the cache run FREE, as
  they serve, and tell what they chose (`tell=True`: rows packed a bit a key,
  experts by id). The second reading of every decode row, beside the cache,
  is GIVEN those choices, and so is the reference
  (`keyevl_reference.logits(select=, route=)`): `logit_rel_rms` and
  `cache_excess` then see arithmetic, layout and the cache, not thresholds.
  The second reading is ONE whole pass a sequence over the prompt plus the N
  forced tokens (`models/keye.py forward(rows=)`: the prefill's layers and
  its attention kernel, no pool), read at the N + 1 rows: causal, so row r
  of it is the last row of a prefill of r + 1 tokens, and sixteen prefills
  of 16,384 tokens a sequence were 8 s of a run's 360 for nothing else;
- the choices themselves are held to numbers of their own, beside
  `check.py`'s four, over the queries past `topk` rows. Against the float32
  reference's own choice (`keyevl_reference.choices(against=)`, a free pass):
  `select_miss_prefill` / `select_miss_decode`, the share of the rows the
  program chose (in the prompt's prefill; in the decode steps) that the free
  reference did not choose, and `missed_weight`, the attention weight (a
  head's mean, the larger of the two phases' means) the reference gave the
  rows it chose and the program missed; these three over the sample's first
  `CHOICE_SEQUENCES` sequence (the one that fills the bucket: a free pass
  of the reference is 5 s a sequence, most of it `top_k`, of a run's 360,
  and one sequence is 115,000 queries of a prefill). The bfloat16 stream decides about one
  row in ten at these widths (PERF.md section 6), so these three refuse an
  indexer that is wrong (another rope, another norm, no indexer), not one
  that is rounded. Against the program's own choice beside the cache:
  `select_cache_miss`, the share of the rows the decode steps' FIRST layer
  chose, through the pool's `ik` rows, that a free prefill of the same tokens
  does not choose for the same queries. Both read the same bfloat16 stream
  there, so a sound cache misses what the order of a sum decides, and int8
  index keys, a stale or a swapped `ik` page miss far more: the choices'
  `cache_excess` (deeper layers carry besides what the two attention paths
  round otherwise: `select_cache_miss_all`, over all layers, is printed by
  the study and held to nothing);
- the engine's own tokens (`check.py`'s `greedy_regret`) are judged by the
  reference given the choices the program makes serving the same tokens
  (`reference_logits`, for tokens `cached_logits` did not keep: its free
  prefill of the prompt and, for each emitted token, its decode step through
  the check's own pool, as the engine took them): a free float32 reference
  chooses one row in ten otherwise and would rate its own other first
  choice, and a prefill's choice for a decode row differs from the decode
  step's in one row of seventeen of the deeper layers (`select_cache_miss_all`).

The limits of these are the file's `check.select_limits`. `check.decide`
takes no number from a family (`acpbench/check.py` and `run.py` are not a
`model_config` PR's to edit), so `cached_logits` prints each beside its limit
as the harness prints its own, and past a limit hands back decode logits
that are not numbers: the run then reads `finite=False` and `correct` false
(`jamba.py`'s carrier, PERF.md section 7). The harness then asks
`reference_logits` for the same tokens, which finds the choices
`cached_logits` kept for them (`_GIVEN`: by the tokens' bytes) and gives
them to the reference; a control (`lower=`) runs free.

The prefills run one sequence a dispatch (a 16,384-token prefill's
temporaries stand beside an engine that holds 9.7 GB of a chip's 16), and
what they chose is kept on the host: 268 MB a sequence.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import check
from . import keyevl_reference, keyevl_weights

CHOICE_SEQUENCES = 1  # sequences of the sample whose choices are held against the free reference's
# the reference runs with the program's choices given under no control and under the controls of PRECISION, which
# ask what rounding alone moves; a control of the CHOICE (another rope, no indexer, ...) chooses freely by what it is
GIVEN_UNDER = (None, "int8", "bf16", "bf16_rest")
CHOICE_FAULTS = ("recent", "w_one", "topk_half", "index_rope_off", "index_norm_off", "dense")  # `indexer=` takes one
_GIVEN: dict = {}  # tokens' bytes -> (select, route) the program chose for them: the newest sample's alone
_CHOOSER: list = []  # [params, sequences, (tokens, lengths) -> (select, route)]: the newest sample's free prefill, kept warm


def _held(config: dict) -> tuple:
    return tuple(range(config.get("num_experts_held", config["num_experts"])))


def program_config(config: dict):
    from agentcontrolplane_tpu.models.keye import KeyeConfig

    sa, rope = config["sa_config"], config["rope_scaling"]
    for key, only in (("attention_bias", False), ("hidden_act", "silu"), ("mlp_only_layers", []),
                      ("decoder_sparse_step", 1), ("use_sliding_window", False)):
        if config[key] != only:
            raise ValueError(f"the keye family serves {key}={only!r} only; the file has {config[key]!r}")
    if sa["indexer_num_kv_heads"] != 1 or rope["rope_type"] != "default":
        raise ValueError("the keye family serves one indexer key head and the default rope type")
    if config["num_local_experts"] != config["num_experts"]:
        raise ValueError("num_local_experts is num_experts under another name in the source: they differ")
    return KeyeConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"], n_layers=config["num_hidden_layers"],
        expert_ffn_dim=config["moe_intermediate_size"], n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"], experts_held=_held(config),
        norm_topk_prob=config["norm_topk_prob"], norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]), mrope_section=tuple(rope["mrope_section"]),
        index_heads=sa["indexer_num_heads"], index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        max_seq_len=config["max_position_embeddings"], tie_embeddings=config["tie_word_embeddings"],
    )


def weights(config: dict, program_config, mesh, seed: int):
    precision = config["engine"].get("quantize")
    if precision is not None:
        raise ValueError(f"the keye family draws bfloat16 weights only; the file's engine.quantize is {precision!r}")
    return keyevl_weights.make(program_config, mesh, seed)


def _sizes(config: dict) -> dict:
    """What the plain reference needs, from the file's keys alone."""
    sa = config["sa_config"]
    return {
        "n_heads": config["num_attention_heads"], "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"], "norm_eps": config["rms_norm_eps"], "rope_theta": float(config["rope_theta"]),
        "mrope_section": tuple(config["rope_scaling"]["mrope_section"]), "index_heads": sa["indexer_num_heads"],
        "index_head_dim": sa["indexer_head_dim"], "topk": sa["topk"],
        "experts_per_token": config["num_experts_per_tok"], "held": _held(config),
        "norm_topk_prob": config["norm_topk_prob"],
    }


def reference_logits(config: dict, params, tokens, rows, lower: str | None = None):
    """The reference's logits. Under no control, and under a control of
    precision (`GIVEN_UNDER`), the program's choices are given (module text):
    those `cached_logits` kept for these very tokens, or, for other tokens
    over the same weights (the engine's own path: the prompts and what the
    engine emitted after them, `rows` ending at each sequence's last token),
    those the program makes serving them: its prefill of the prompt, its
    decode steps after it."""
    select = route = None
    clock = [time.monotonic()]
    if lower in GIVEN_UNDER:
        tokens = np.asarray(tokens)
        given = _GIVEN.get(tokens.tobytes())
        if given is None and _CHOOSER and _CHOOSER[0] is params and tokens.shape[0] == _CHOOSER[1]:
            given = _CHOOSER[2](tokens, np.asarray(rows)[:, -1] + 1)
        select, route = given if given else (None, None)
    clock.append(time.monotonic())
    out = keyevl_reference.logits(params, _sizes(config), tokens, rows, lower=lower, select=select, route=route)
    out.block_until_ready()
    print("[check] a reference pass's clock: the program's choices {:.1f}s, the pass {:.1f}s".format(
        clock[1] - clock[0], time.monotonic() - clock[1]), flush=True)
    return out


def _as_int8(leaf, heads: int):
    """A pool leaf with every row (a head) rounded to int8 by its largest
    value and back: what int8 pages would hold."""
    import jax.numpy as jnp

    rows = leaf.reshape(leaf.shape[:-1] + (heads, leaf.shape[-1] // heads)).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(rows), axis=-1, keepdims=True) / 127.0, 1e-8)
    return (jnp.clip(jnp.round(rows / scale), -127, 127) * scale).astype(leaf.dtype).reshape(leaf.shape)


def _fault(fault: str | None):
    if fault is not None and fault not in CHOICE_FAULTS:
        raise ValueError(f"no fault {fault!r} to plant in the indexer; there are {', '.join(CHOICE_FAULTS)}")
    return fault


def _planted_count(fault: str | None, c):
    """The program's config under `fault`: `topk_half` and `dense` are a count of rows to choose."""
    count = {"topk_half": c.index_topk // 2, "dense": c.max_seq_len}.get(_fault(fault))
    return c if count is None else dataclasses.replace(c, index_topk=count)


@contextlib.contextmanager
def _planted(fault: str | None, c):
    """The program's own modules with `fault` (one of `CHOICE_FAULTS`, the
    reference's controls of the choice by the same names) in its indexer for
    as long as its programs are traced: a function of the indexer put in the
    place of the program's own (the two counts: `_planted_count`)."""
    import jax.numpy as jnp

    from agentcontrolplane_tpu.models import keye
    from agentcontrolplane_tpu.ops import attention

    scores, mask, rows, rope = attention.index_scores, attention.topk_rows_mask, attention.topk_rows, keye.apply_rope
    by_position = lambda s: jnp.broadcast_to(jnp.arange(s.shape[-1], dtype=s.dtype), s.shape)  # noqa: E731
    put = {
        "w_one": {"index_scores": lambda qi, w, ki: scores(qi, jnp.ones_like(w), ki)},
        # the latest rows: a key's column is its position in both paths the check runs
        "recent": {"topk_rows_mask": lambda s, valid, k: mask(by_position(s), valid, k),
                   "topk_rows": lambda s, valid, k: rows(by_position(s), valid, k)},
        # q^I and k^I are the one thing turned that is `index_head_dim` wide (q and k are `head_dim`)
        "index_rope_off": {"apply_rope": lambda x, *a, **kw: x if x.shape[-1] == c.index_head_dim else rope(x, *a, **kw)},
        "index_norm_off": {"_layer_norm": lambda x, weight, bias, eps: x},
    }.get(_fault(fault), {})
    kept = [(module, name, getattr(module, name)) for module in (keye, attention) for name in put if hasattr(module, name)]
    try:
        for module, name, _ in kept:
            setattr(module, name, put[name])
        yield
    finally:
        for module, name, real in kept:
            setattr(module, name, real)


def _choice_numbers(stats: dict, lengths, N: int, topk: int, program_topk: int) -> dict:
    """The three numbers against the free reference from `keyevl_reference.choices`'
    counts `both` and `missed_weight` [layers, B, T], over the queries past
    the file's `topk` rows; a query of the program chose `program_topk` of
    its rows, or all of them (the file's `topk` but under a planted count)."""
    both, weight = np.asarray(stats["both"], np.float64), np.asarray(stats["missed_weight"], np.float64)
    t = np.arange(both.shape[-1])[None, :]
    lengths = np.asarray(lengths)[:, None]
    phases = {"prefill": (t >= topk) & (t < lengths), "decode": (t >= np.maximum(lengths, topk)) & (t < lengths + N)}
    chose = np.minimum(t + 1, program_topk)
    out, weights_ = {}, []
    for name, rows in phases.items():
        n = both.shape[0] * rows.sum()
        out[f"select_miss_{name}"] = float(1.0 - (both * rows).sum() / (both.shape[0] * (chose * rows).sum())) if n else 0.0
        weights_.append(float((weight * rows).sum() / n) if n else 0.0)
    out["missed_weight"] = max(weights_)
    return out


def refused_by(config: dict, chosen: dict) -> list[str]:
    """The file's limits on the choices that `chosen` (`cache_readings`' numbers) lies past, each printed beside its
    limit as the harness prints its own."""
    past = []
    for name, limit in config["check"]["select_limits"].items():
        within = bool(chosen[name] <= limit)
        past += [] if within else [name]
        print(f"[check] {name}={chosen[name]:.6g} limit={limit:.6g} {'ok' if within else 'EXCEEDED'}", flush=True)
    return past


def cached_logits(config: dict, program_config, params, mesh, s: dict, use_pallas: bool, **control):
    """`cache_readings`' logits, held to the file's limits on the choices
    (module text): past one the decode logits come back not numbers."""
    import jax.numpy as jnp

    pre, dec, chosen = cache_readings(config, program_config, params, mesh, s, use_pallas, **control)
    return pre, jnp.full_like(dec, jnp.nan) if refused_by(config, chosen) else dec


def cache_readings(config: dict, program_config, params, mesh, s: dict, use_pallas: bool,
                   ik_int8: bool = False, kv_int8: bool = False, ik_crossed: bool = False, indexer: str | None = None):
    """(pre [B, N+1, V], dec [B, N, V], the choices' numbers) float32 from
    the program: each prompt's prefill and N decode steps through the pool,
    free and telling what they chose; the decode rows' second reading with
    those choices given; and the decode rows once more from a free prefill,
    for `select_cache_miss` (module text). `indexer` plants a fault of the
    choice in the program (`_planted`). The choices are kept on the host
    (268 MB a sequence at 16,384 rows), a sequence's on the device at a time."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.keye import decode_step_paged, forward, init_paged_cache, prefill_paged_batch

    c = _planted_count(indexer, program_config)
    rep = NamedSharding(mesh, P())
    B, T, N, lengths, width = s["B"], s["T"], s["N"], s["lengths"], s["tokens"].shape[1]
    L, k, topk, T8, W8 = c.n_layers, c.experts_per_token, c.index_topk, -(-T // 8), -(-width // 8)
    cache = jax.jit(lambda: init_paged_cache(c, s["pool_pages"], s["P"], max_slots=B))()
    put = lambda a: jax.device_put(jnp.asarray(a), rep)  # noqa: E731
    tables, seqs = put(s["tables"]), np.arange(B)

    def prefill_free(p, ca, t, n, ids):
        return prefill_paged_batch(p, ca, t, n, ids, c, tell=True)

    def beside_given(p, t, select, route, rows):
        return forward(p, t, c, select=select, route=route, rows=rows)

    def decode(p, ca, t, n, tb):
        return decode_step_paged(p, ca, t, n, tb, jnp.ones(t.shape, bool), c, use_pallas=use_pallas, mesh=mesh,
                                 tell=True)

    # the three programs, traced here (with the fault, where one is planted) and compiled side by side on threads
    # (the compiler works outside the interpreter's lock: families/kanana.py)
    ints = lambda *shape: put(np.zeros(shape, np.int32))  # noqa: E731
    wanted = {"free": (prefill_free, (1,), (params, cache, ints(1, T), ints(1), ints(1, T // s["P"]))),
              "decode": (decode, (1,), (params, cache, ints(B), ints(B), tables)),
              "given": (beside_given, (), (params, ints(1, T), put(np.zeros((L, 1, T, T8), np.uint8)), ints(L, 1, T, k),
                                           ints(1, N + 1)))}
    clock = [time.monotonic()]
    with _planted(indexer, c):
        lowered = {name: jax.jit(fn, donate_argnums=donated).lower(*args) for name, (fn, donated, args) in wanted.items()}
    with ThreadPoolExecutor(max_workers=len(lowered) + 2) as pool:
        jobs = {name: pool.submit(low.compile) for name, low in lowered.items()}
        # the reference's two kinds of layer (given a choice; free and counted against one) compiled beside them
        ahead = [pool.submit(keyevl_reference.precompile, params, _sizes(config), width, given) for given in (True, False)]
        programs = {name: job.result() for name, job in jobs.items()}
        [job.result() for job in ahead]
    clock.append(time.monotonic())
    rounded = jax.jit(lambda ca: {**ca, **({"ik": _as_int8(ca["ik"], 1)} if ik_int8 else {}),
                                  **({n: _as_int8(ca[n], c.n_kv_heads) for n in ("k", "v")} if kv_int8 else {})},
                      donate_argnums=(0,))

    def free_prefill(tokens, ends, b: int):
        """Sequence b's first `ends[b]` of `tokens` through the serving prefill, free: (logits [1, V], (rows chosen
        [L, 1, T, T8], experts [L, 1, T, k]) on the device); its rows go to its pages."""
        nonlocal cache
        one = slice(b, b + 1)
        prompt = np.where(np.arange(T)[None, :] < ends[one, None], np.asarray(tokens)[one, :T], 0)
        cache, logits, told = programs["free"](params, cache, put(prompt.astype(np.int32)), put(ends[one].astype(np.int32)),
                                               put(check.page_ids(s, ends)[one].astype(np.int32)))
        return logits.astype(jnp.float32), told

    def chosen_by_prefills(tokens, ends):
        """(select [L, B, T, T8] uint8, route [L, B, T, k] int32, logits [B, V]) of every sequence's free prefill."""
        select, route, logits = np.zeros((L, B, T, T8), np.uint8), np.zeros((L, B, T, k), np.int32), []
        for b in range(B):
            out, (rows_b, experts_b) = free_prefill(tokens, ends, b)
            select[:, b], route[:, b] = np.asarray(rows_b)[:, 0], np.asarray(experts_b)[:, 0]
            logits.append(out)
        return select, route, jnp.concatenate(logits, axis=0)

    def decode_steps(tokens, n: int, select, route, after=lambda ca: ca):
        """`n` teacher-forced steps through the pool, free, from each sequence's prompt on: their logits, and into
        row `lengths + j` of `select` and `route` what step j chose: the positions (-1: none) packed, the experts."""
        nonlocal cache
        out = []
        for j in range(n):
            cache, logits, (rows_j, experts_j) = programs["decode"](
                params, cache, put(np.asarray(tokens)[seqs, lengths + j].astype(np.int32)),
                put((lengths + j).astype(np.int32)), tables)
            cache = after(cache)
            out.append(logits.astype(jnp.float32))
            rows_j = np.asarray(rows_j)
            marks = np.zeros((L, B, T8 * 8 + 1), bool)
            np.put_along_axis(marks, np.where(rows_j >= 0, rows_j, T8 * 8), True, axis=-1)
            select[:, seqs, lengths + j] = np.packbits(marks[..., :-1], axis=-1, bitorder="little")
            route[:, seqs, lengths + j] = np.asarray(experts_j)[:, :, 0]
        return out

    # each prompt's own prefill, free: its logits, the pool the decode steps go on from, and what it chose
    select, route, pre0 = chosen_by_prefills(s["tokens"], lengths)
    faulted = rounded if ik_int8 or kv_int8 else (lambda ca: ca)
    cache = faulted(cache)
    if ik_crossed:  # every sequence's `ik` pages hold its neighbour's rows: the third leaf alone crossed
        mine, theirs = s["tables"].reshape(-1), np.roll(s["tables"], 1, axis=0).reshape(-1)
        cache = jax.jit(lambda ca: {**ca, "ik": ca["ik"].at[:, mine].set(ca["ik"][:, theirs])}, donate_argnums=(0,))(cache)
    dec = decode_steps(s["tokens"], N, select, route, after=faulted)

    # every decode row's second reading, beside the cache: ONE whole pass a sequence over the prompt plus N tokens
    # with the choices given, read at the N + 1 rows (causal: row r of it is the last row of a prefill of r + 1 tokens)
    ends = lengths + N
    forced = np.where(np.arange(T)[None, :] < ends[:, None], s["tokens"][:, :T], 0).astype(np.int32)
    beside = jnp.concatenate([
        programs["given"](params, put(forced[b: b + 1]), put(select[:, b: b + 1]), put(route[:, b: b + 1]),
                          put(s["rows"][b: b + 1].astype(np.int32))).astype(jnp.float32) for b in range(B)], axis=0)
    pre = jnp.concatenate([pre0[:, None], beside[:, 1:]], axis=1)

    # the decode rows once more by a FREE prefill of the prompt plus N tokens: what the same program chooses for
    # them beside the cache (its logits are not compared)
    at = lengths[:, None] + np.arange(N)[None, :]  # [B, N]
    again = np.stack([np.asarray(free_prefill(s["tokens"], ends, b)[1][0][:, 0, at[b]]) for b in range(B)], axis=1)
    bits = lambda packed: np.unpackbits(packed, axis=-1, bitorder="little").astype(bool)  # noqa: E731
    chose_dec, chose_pre = bits(select[:, seqs[:, None], at]), bits(again)  # [L, B, N, T8 * 8]
    # the FIRST layer's choice: there both read the same stream (deeper layers carry what the two attention
    # paths round otherwise besides, a few rows in a hundred: `select_cache_miss_all`, printed and held to nothing)
    miss = lambda a, b: 1.0 - float((a & b).sum() / a.sum())  # noqa: E731
    cache_miss, cache_miss_all = miss(chose_dec[:1], chose_pre[:1]), miss(chose_dec, chose_pre)

    own = np.zeros((width, W8), np.uint8)
    own[np.arange(width), np.arange(width) // 8] = 1 << (np.arange(width) % 8)

    def as_the_reference_takes(select, route, ends):
        """The choices over the sample's T + N columns: a row past a sequence's end (no program chose for it, no
        compared row reads it) sees itself alone."""
        full = np.pad(select, ((0, 0), (0, 0), (0, width - T), (0, W8 - T8)))
        past = np.arange(width)[None, :] >= np.asarray(ends)[:, None]  # [B, width]
        full[:, past] = own[np.nonzero(past)[1]]
        return full, np.pad(route, ((0, 0), (0, 0), (0, width - T), (0, 0)))

    def choose(tokens, ends):
        """What the program chooses, serving, for other `tokens` [B, >= T] over the sample's prompts, up to `ends`
        (<= T): its free prefill of each prompt and, for the rows after it, its decode steps through the pool."""
        ends = np.asarray(ends, np.int32)
        chose, routed, _logits = chosen_by_prefills(tokens, lengths)
        decode_steps(tokens, int((ends - lengths).max()), chose, routed)
        return as_the_reference_takes(chose, routed, ends)

    given = as_the_reference_takes(select, route, ends)
    clock.append(time.monotonic())
    _GIVEN.clear()
    _GIVEN[np.asarray(s["tokens"]).tobytes()] = given
    _CHOOSER[:] = [params, B, choose]
    # against the free reference on the first `CHOICE_SEQUENCES` sequences alone: a pass is seconds (module text)
    F = min(B, CHOICE_SEQUENCES)
    free = keyevl_reference.choices(params, _sizes(config), s["tokens"][:F], against=given[0][:, :F])
    numbers = {**_choice_numbers(free, lengths[:F], N, config["sa_config"]["topk"], topk),
               "select_cache_miss": cache_miss, "select_cache_miss_all": cache_miss_all}
    clock.append(time.monotonic())
    print("[check] the cache check's clock: its three programs traced and compiled or loaded {:.1f}s, run {:.1f}s, "
          "the free reference {:.1f}s".format(*np.diff(clock)), flush=True)
    return pre, jnp.stack(dec, axis=1), numbers


def crossed_numbers(reference, s: dict, path: dict) -> dict:
    """The engine path's structural control (module text): the reference's
    own first choice at every position of a context whose every second page
    holds the neighbouring request's tokens, the engine's tokens as context
    (`reference` is `reference_logits` over this run, as `check.py`'s control
    calls it: given the choices the program makes serving that context),
    rated as `check.engine_numbers` rates the engine's: the largest
    regret over the tokens and, beside it, their median. Two sequences or
    more."""
    import jax.numpy as jnp

    B, P = s["B"], s["P"]
    if B < 2:
        raise ValueError("a cross takes two requests: check.sequences is 1")
    emitted = path["returned"]
    R = max(1, max(len(e) for e in emitted))
    width = max(s["tokens"].shape[1], int(s["lengths"].max()) + R)
    tokens, valid = np.zeros((B, width), np.int32), np.zeros((B, R), bool)
    for b, e in enumerate(emitted):
        n = int(s["lengths"][b])
        tokens[b, :n], tokens[b, n: n + len(e)], valid[b, : len(e)] = s["tokens"][b, :n], e, True
    rows = s["lengths"][:, None] - 1 + np.arange(R)[None, :]
    want = reference(tokens, rows)
    every_second_page = (np.arange(width)[None, :] < s["lengths"][:, None]) & (np.arange(width)[None, :] // P % 2 == 1)
    crossed = np.where(every_second_page, np.roll(tokens, 1, axis=0), tokens)
    picked = jnp.argmax(reference(crossed, rows), -1)
    regret = (jnp.max(want, -1) - jnp.take_along_axis(want, picked[..., None], axis=-1)[..., 0]) / jnp.std(want, -1)
    regret = np.asarray(regret)[valid]
    return {"pages_crossed": {"greedy_regret": float(regret.max()), "regret_median": float(np.median(regret))}}
