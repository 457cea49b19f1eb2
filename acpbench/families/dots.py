"""The dots3 family (`models/dots.py`; `model_type: dots3_note`), the language
model of dots3-note-prev: latent attention at two ranks, full layers over the
`index_topk` latent rows a learned indexer chooses for every query, sliding
layers over the last `sliding_window_size` latent rows kept in a ring a slot,
a gate a head on both; a leading dense layer, then `n_routed_experts` routed
experts (sigmoid scores, a selection bias, top `num_experts_per_tok`
renormalised) of which the chip holds `num_experts_held` (experts 0 ..
held-1), beside a shared expert.

The file keeps the source's `config.json` keys as published, plus
`num_experts` (= `n_routed_experts`, the name the harness's readers read)
and `num_experts_held`; `assumed` says what the source leaves out. Weights:
`dots_weights.py`, bfloat16, the one precision this family draws
(`engine.quantize` must be absent). Reference: `dots_reference.py`, given the
same `held`. Its controls (`lower=`):

- `"int8"`: every matmul input, the expanded K and V and the indexer's keys
  rounded to int8 per row, the precision below the configuration's;
  `"bf16"`: the same to bfloat16, the stated precision; `"bf16_rest"`:
  besides, every tensor the program keeps at rest in bfloat16: the floor the
  program's own reading is held beside. These three are GIVEN the program's
  choices, as the reference itself is (`families/keyevl.py` says why: left
  to choose, a rounded pass chooses other rows at the threshold and reads
  what ANY free pass reads). A name with `_free` after it (`"bf16_free"`) is
  the same pass left to choose: what a bfloat16 program with another order
  of sums would read against this one, logits and all;
- `"gate_off"` (no gate a head), `"rescale_off"` (`a_q = a_kv = 1`),
  `"window_off"`, `"shared_off"`: GIVEN the program's choices too, so that
  the reading is the control's own and not a choice's;
- `"recent"`, `"dense"`, `"index_rope_off"`, `"index_norm_off"`: each a free
  pass, which chooses otherwise by what it is.

The cache's own controls (keywords of `cached_logits`): `ik_int8=True` (the
pool's `ik` leaf holds what int8 keys would: rounded by a row's largest
value and back after the prefill and after each decode step), `kv_int8=True`
(the same of the full layers' latent rows), `wkv_int8=True` (of the rings'),
`ring_short=<rows>` (the decode steps' sliding layers see that many rows
fewer than the window: a ring one row short), `ik_crossed=True` (every
sequence's `ik` pages hold its neighbour's rows), and `indexer=<fault>`: one
of `CHOICE_FAULTS` PLANTED IN THE PROGRAM's indexer while its programs are
traced (`_planted`), judged as every run is judged.

**Choices are given where logits are compared, and compared themselves**,
as `families/keyevl.py` does and for its reasons: the prompt's prefill and
the decode steps through the pool and the rings run FREE, as they serve, and
tell what they chose (`tell=True`); the second reading of every decode row,
beside the cache, is ONE whole pass a sequence over the prompt plus the N
forced tokens with those choices given (`models/dots.py forward(rows=)`),
and so is the reference. The choices themselves are held to
`select_miss_prefill`, `select_miss_decode`, `missed_weight` (against the
free float32 reference's own choice, over the first `CHOICE_SEQUENCES`
sequence) and `select_cache_miss` (the decode steps' FIRST full layer's
choice through the pool's `ik` rows against a free prefill's of the same
tokens), whose limits are the file's `check.select_limits`; past one the
decode logits come back not numbers (`cached_logits`), the run reads
`finite=False` and `correct` false. The engine's own tokens are judged by
the reference given the choices the program makes serving the same tokens
(`_CHOOSER`).

Check prompts are longer than `index_topk` + the window, so both the choice
and the ring's wrap are in what is compared. The prefills run one sequence a
dispatch.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import check
from . import dots_reference, dots_weights
from .keyevl import _as_int8, _choice_numbers, crossed_numbers, refused_by  # noqa: F401  the same numbers of a choice

CHOICE_SEQUENCES = 1  # sequences of the sample whose choices are held against the free reference's
# the reference runs with the program's choices given under no control, under the controls of PRECISION and under the
# controls of what is not a choice; a control of the CHOICE chooses freely by what it is
GIVEN_UNDER = (None, "int8", "bf16", "bf16_rest", "gate_off", "rescale_off", "window_off", "shared_off")
CHOICE_FAULTS = ("recent", "topk_half", "index_rope_off", "index_norm_off")  # `indexer=` takes one
_GIVEN: dict = {}  # tokens' bytes -> (select, route) the program chose for them: the newest sample's alone
_CHOOSER: list = []  # [params, sequences, (tokens, lengths) -> (select, route)]: the newest sample's free prefill, kept warm
ONLY = (("attention_bias", False), ("hidden_act", "silu"), ("rope_scaling", None), ("scoring_func", "sigmoid"),
        ("topk_method", "noaux_tc"), ("moe_layer_freq", 1), ("attention_gate_type", "headwise"),
        ("swa_attention_gate_type", "headwise"), ("apply_mla_qkv_lora_rescale", True))


def _held(config: dict) -> tuple:
    return tuple(range(config.get("num_experts_held", config["n_routed_experts"])))


def program_config(config: dict):
    from agentcontrolplane_tpu.models.dots import DotsConfig

    if config["num_experts"] != config["n_routed_experts"]:
        raise ValueError("num_experts is n_routed_experts under the name the harness's readers read: they differ")
    for key, only in ONLY:
        if config[key] != only:
            raise ValueError(f"the dots family serves {key}={only!r} only; the file has {config[key]!r}")
    for key, same in (("num_key_value_heads", "num_attention_heads"), ("swa_num_key_value_heads", "swa_num_attention_heads")):
        if config[key] != config[same]:
            raise ValueError(f"latent attention keeps one row for all heads: {key} is {same} again in the source; they differ")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types has another length than num_hidden_layers")
    return DotsConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"], n_heads=config["num_attention_heads"],
        qk_nope_head_dim=config["qk_nope_head_dim"], qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        rope_theta=float(config["rope_theta"]), swa_n_heads=config["swa_num_attention_heads"],
        swa_qk_nope_head_dim=config["swa_qk_nope_head_dim"], swa_qk_rope_head_dim=config["swa_qk_rope_head_dim"],
        swa_v_head_dim=config["swa_v_head_dim"], swa_q_lora_rank=config["swa_q_lora_rank"],
        swa_kv_lora_rank=config["swa_kv_lora_rank"], swa_rope_theta=float(config["swa_rope_theta"]),
        sliding_window_size=config["sliding_window_size"], index_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"], index_topk=config["index_topk"],
        layer_types=tuple(config["layer_types"]), first_dense=config["first_k_dense_replace"],
        ffn_dim=config["intermediate_size"], expert_ffn_dim=config["moe_intermediate_size"],
        n_experts=config["n_routed_experts"], experts_per_token=config["num_experts_per_tok"],
        experts_held=_held(config), n_shared_experts=config["n_shared_experts"],
        norm_topk_prob=config["norm_topk_prob"], routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_eps=config["rms_norm_eps"], max_seq_len=config["max_position_embeddings"],
        tie_embeddings=config["tie_word_embeddings"],
    )


def weights(config: dict, program_config, mesh, seed: int):
    precision = config["engine"].get("quantize")
    if precision is not None:
        raise ValueError(f"the dots family draws bfloat16 weights only; the file's engine.quantize is {precision!r}")
    return dots_weights.make(program_config, mesh, seed)


def _sizes(config: dict) -> dict:
    """What the plain reference needs, from the file's keys alone."""
    def latent(prefix: str) -> dict:
        return {"n_heads": config[prefix + "num_attention_heads"], "nope": config[prefix + "qk_nope_head_dim"],
                "rope": config[prefix + "qk_rope_head_dim"], "v": config[prefix + "v_head_dim"],
                "q_rank": config[prefix + "q_lora_rank"], "kv_rank": config[prefix + "kv_lora_rank"],
                "theta": float(config[prefix + "rope_theta"])}

    return {
        "dim": config["hidden_size"], "full": latent(""), "swa": latent("swa_"), "index_heads": config["index_n_heads"],
        "index_head_dim": config["index_head_dim"], "topk": config["index_topk"], "window": config["sliding_window_size"],
        "norm_eps": config["rms_norm_eps"], "experts_per_token": config["num_experts_per_tok"], "held": _held(config),
        "norm_topk_prob": config["norm_topk_prob"], "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "first_dense": config["first_k_dense_replace"], "layer_types": tuple(config["layer_types"]),
    }


def reference_logits(config: dict, params, tokens, rows, lower: str | None = None):
    """The reference's logits. Under no control, and under a control of
    `GIVEN_UNDER`, the program's choices are given (module text): those
    `cached_logits` kept for these very tokens, or, for other tokens over the
    same weights (the engine's own path), those the program makes serving
    them: its prefill of the prompt, its decode steps after it. `<name>_free`
    is the control `<name>` left to choose."""
    select = route = None
    clock = [time.monotonic()]
    free = lower is not None and lower.endswith("_free")
    lower = lower[:-5] if free else lower
    if lower in GIVEN_UNDER and not free:
        tokens = np.asarray(tokens)
        given = _GIVEN.get(tokens.tobytes())
        if given is None and _CHOOSER and _CHOOSER[0] is params and tokens.shape[0] == _CHOOSER[1]:
            given = _CHOOSER[2](tokens, np.asarray(rows)[:, -1] + 1)
        select, route = given if given else (None, None)
    clock.append(time.monotonic())
    out = dots_reference.logits(params, _sizes(config), tokens, rows, lower=lower, select=select, route=route)
    out.block_until_ready()
    print("[check] a reference pass's clock: the program's choices {:.1f}s, the pass {:.1f}s".format(
        clock[1] - clock[0], time.monotonic() - clock[1]), flush=True)
    return out


def _fault(fault: str | None):
    if fault is not None and fault not in CHOICE_FAULTS:
        raise ValueError(f"no fault {fault!r} to plant in the indexer; there are {', '.join(CHOICE_FAULTS)}")
    return fault


def _planted_count(fault: str | None, c):
    """The program's config under `fault`: `topk_half` is a count of rows to choose."""
    return dataclasses.replace(c, index_topk=c.index_topk // 2) if _fault(fault) == "topk_half" else c


@contextlib.contextmanager
def _planted(fault: str | None, c):
    """The program's own modules with `fault` (one of `CHOICE_FAULTS`, the
    reference's controls of the choice by the same names) in its indexer for
    as long as its programs are traced: a function of the indexer put in the
    place of the program's own (`keyevl._planted`)."""
    import jax.numpy as jnp

    from agentcontrolplane_tpu.models import dots, keye
    from agentcontrolplane_tpu.ops import attention

    mask, rows = attention.topk_rows_mask, attention.topk_rows
    by_position = lambda s: jnp.broadcast_to(jnp.arange(s.shape[-1], dtype=s.dtype), s.shape)  # noqa: E731
    put = {
        # the latest rows: a key's column is its position in both paths the check runs
        "recent": {"topk_rows_mask": lambda s, valid, k: mask(by_position(s), valid, k),
                   "topk_rows": lambda s, valid, k: rows(by_position(s), valid, k)},
        "index_rope_off": {"_rope_first": lambda x, positions, theta, n: x},
        "index_norm_off": {"_layer_norm": lambda x, weight, bias, eps: x},
    }.get(_fault(fault), {})
    kept = [(module, name, getattr(module, name)) for module in (dots, keye, attention) for name in put if hasattr(module, name)]
    try:
        for module, name, _ in kept:
            setattr(module, name, put[name])
        yield
    finally:
        for module, name, real in kept:
            setattr(module, name, real)


def cached_logits(config: dict, program_config, params, mesh, s: dict, use_pallas: bool, **control):
    """`cache_readings`' logits, held to the file's limits on the choices
    (module text): past one the decode logits come back not numbers."""
    import jax.numpy as jnp

    pre, dec, chosen = cache_readings(config, program_config, params, mesh, s, use_pallas, **control)
    return pre, jnp.full_like(dec, jnp.nan) if refused_by(config, chosen) else dec


def cache_readings(config: dict, program_config, params, mesh, s: dict, use_pallas: bool, ik_int8: bool = False,
                   kv_int8: bool = False, wkv_int8: bool = False, ring_short: int = 0, ik_crossed: bool = False,
                   indexer: str | None = None):
    """(pre [B, N+1, V], dec [B, N, V], the choices' numbers) float32 from
    the program: each prompt's prefill and N decode steps through the pool
    and the rings, free and telling what they chose; the decode rows' second
    reading with those choices given; and the decode rows once more from a
    free prefill, for `select_cache_miss` (module text). The choices are kept
    on the host, a sequence's on the device at a time."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.dots import decode_step_paged, forward, init_paged_cache, prefill_paged_batch

    c = _planted_count(indexer, program_config)
    rep = NamedSharding(mesh, P())
    B, T, N, lengths, width = s["B"], s["T"], s["N"], s["lengths"], s["tokens"].shape[1]
    Lf, Le, k, topk = c.n_full, c.n_layers - c.first_dense, c.experts_per_token, c.index_topk
    T8, W8 = -(-T // 8), -(-width // 8)
    cache = jax.jit(lambda: init_paged_cache(c, s["pool_pages"], s["P"], max_slots=B))()
    put = lambda a: jax.device_put(jnp.asarray(a), rep)  # noqa: E731
    tables, seqs = put(s["tables"]), np.arange(B)
    window_rows = c.sliding_window_size - ring_short if ring_short else None

    def prefill_free(p, ca, t, n, ids, slot):
        return prefill_paged_batch(p, ca, t, n, ids, (slot, jnp.zeros_like(slot)), c, tell=True)

    def beside_given(p, t, select, route, rows):
        return forward(p, t, c, select=select, route=route, rows=rows)

    def decode(p, ca, t, n, tb):
        return decode_step_paged(p, ca, t, n, tb, jnp.ones(t.shape, bool), c, use_pallas=use_pallas, mesh=mesh, tell=True,
                                 window_rows=window_rows)

    # the three programs, traced here (with the fault, where one is planted) and compiled side by side on threads
    ints = lambda *shape: put(np.zeros(shape, np.int32))  # noqa: E731
    wanted = {"free": (prefill_free, (1,), (params, cache, ints(1, T), ints(1), ints(1, T // s["P"]), ints(1))),
              "decode": (decode, (1,), (params, cache, ints(B), ints(B), tables)),
              "given": (beside_given, (), (params, ints(1, T), put(np.zeros((Lf, 1, T, T8), np.uint8)), ints(Le, 1, T, k),
                                           ints(1, N + 1)))}
    clock = [time.monotonic()]
    with _planted(indexer, c):
        lowered = {name: jax.jit(fn, donate_argnums=donated).lower(*args) for name, (fn, donated, args) in wanted.items()}
    with ThreadPoolExecutor(max_workers=len(lowered) + 2) as pool:
        jobs = {name: pool.submit(low.compile) for name, low in lowered.items()}
        # the reference's kinds of layer (given a choice; free and counted against one) compiled beside them
        ahead = [pool.submit(dots_reference.precompile, params, _sizes(config), width, given) for given in (True, False)]
        programs = {name: job.result() for name, job in jobs.items()}
        [job.result() for job in ahead]
    clock.append(time.monotonic())
    rounded = jax.jit(lambda ca: {**ca, **{name: _as_int8(ca[name], 1) for name, on in
                                           (("ik", ik_int8), ("kv", kv_int8), ("wkv", wkv_int8)) if on}},
                      donate_argnums=(0,))

    def free_prefill(tokens, ends, b: int):
        """Sequence b's first `ends[b]` of `tokens` through the serving prefill, free: (logits [1, V], (rows chosen
        [Lf, 1, T, T8], experts [Le, 1, T, k]) on the device); its rows go to its pages and to slot b's ring."""
        nonlocal cache
        one = slice(b, b + 1)
        prompt = np.where(np.arange(T)[None, :] < ends[one, None], np.asarray(tokens)[one, :T], 0)
        cache, logits, told = programs["free"](params, cache, put(prompt.astype(np.int32)), put(ends[one].astype(np.int32)),
                                               put(check.page_ids(s, ends)[one].astype(np.int32)),
                                               put(np.asarray([b], np.int32)))
        return logits.astype(jnp.float32), told

    def chosen_by_prefills(tokens, ends):
        """(select [Lf, B, T, T8] uint8, route [Le, B, T, k] int32, logits [B, V]) of every sequence's free prefill."""
        select, route, logits = np.zeros((Lf, B, T, T8), np.uint8), np.zeros((Le, B, T, k), np.int32), []
        for b in range(B):
            out, (rows_b, experts_b) = free_prefill(tokens, ends, b)
            select[:, b], route[:, b] = np.asarray(rows_b)[:, 0], np.asarray(experts_b)[:, 0]
            logits.append(out)
        return select, route, jnp.concatenate(logits, axis=0)

    def decode_steps(tokens, n: int, select, route, after=lambda ca: ca):
        """`n` teacher-forced steps through the pool and the rings, free, from each sequence's prompt on: their
        logits, and into row `lengths + j` of `select` and `route` what step j chose: the positions (-1: none)
        packed, the experts."""
        nonlocal cache
        out = []
        for j in range(n):
            cache, logits, (rows_j, experts_j) = programs["decode"](
                params, cache, put(np.asarray(tokens)[seqs, lengths + j].astype(np.int32)),
                put((lengths + j).astype(np.int32)), tables)
            cache = after(cache)
            out.append(logits.astype(jnp.float32))
            rows_j = np.asarray(rows_j)
            marks = np.zeros((Lf, B, T8 * 8 + 1), bool)
            np.put_along_axis(marks, np.where(rows_j >= 0, rows_j, T8 * 8), True, axis=-1)
            select[:, seqs, lengths + j] = np.packbits(marks[..., :-1], axis=-1, bitorder="little")
            route[:, seqs, lengths + j] = np.asarray(experts_j)[:, :, 0]
        return out

    # each prompt's own prefill, free: its logits, the pool the decode steps go on from, and what it chose
    select, route, pre0 = chosen_by_prefills(s["tokens"], lengths)
    faulted = rounded if ik_int8 or kv_int8 or wkv_int8 else (lambda ca: ca)
    cache = faulted(cache)
    if ik_crossed:  # every sequence's `ik` pages hold its neighbour's rows: that leaf alone crossed
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
    chose_dec, chose_pre = bits(select[:, seqs[:, None], at]), bits(again)  # [Lf, B, N, T8 * 8]
    # the FIRST full layer's choice: there both read the same stream (`keyevl.py`)
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
    free = dots_reference.choices(params, _sizes(config), s["tokens"][:F], against=given[0][:, :F])
    numbers = {**_choice_numbers(free, lengths[:F], N, config["index_topk"], topk),
               "select_cache_miss": cache_miss, "select_cache_miss_all": cache_miss_all}
    clock.append(time.monotonic())
    print("[check] the cache check's clock: its three programs traced and compiled or loaded {:.1f}s, run {:.1f}s, "
          "the free reference {:.1f}s".format(*np.diff(clock)), flush=True)
    return pre, jnp.stack(dec, axis=1), numbers
