"""The K-EXAONE family (`models/exaone.py`; `model_type: exaone_moe`): GQA
layers of two kinds, `sliding_attention` (the last `sliding_window` tokens,
the one rope) and `full_attention` (every earlier token, no rotary); the
first `first_k_dense_replace` layers' FF dense, the others' `num_experts`
routed experts (sigmoid scores, a selection bias, top `num_experts_per_tok`
renormalised and times `routed_scaling_factor`) of which the chip holds
`num_experts_held` (experts 0 .. held-1) beside `num_shared_experts` shared;
and the multi-token-prediction module (`num_nextn_predict_layers` 1), which
the program serves as its drafter.

The file keeps the source's `config.json` keys as published (`assumed` says
what the source leaves out). Weights: `exaone_weights.py`, bfloat16
(`engine.quantize` must be absent). Reference: `exaone_reference.py`, given
the same `held`: `logits` and `mtp_logits`. Its controls (`lower=`):
"int8", "bf16" (must pass), "bf16_rest", "rope_on_full", "window_off",
"nonorm", "bias_off", "route_scale_off", "shared_off" and, for the drafted
logits, "mtp_prev_hidden_off".

**What the cache check runs is the timed program**: after the prompt's
prefill the rows are read through `verify_step_paged`, the engine's decode
step, two rows a lane, teacher-forced with a seeded pattern of kept and
refused drafts. A kept draft is the sequence's own next token, and both
rows' logits are compared; a refused draft is ANOTHER token, its row's
logits are thrown away, and its K/V in the full pages, the MTP layer and
the ring has to be rolled back by the count alone for the rows after it to
read right. So `logit_rel_rms` and `cache_excess` read the verify step and
its rollback. The drafted logits a step leaves are compared with
`mtp_logits` by the family's study and tests (`draft=True`), not by
`correct`, which takes no number from a family.

The cache's own controls (keywords of `cached_logits`):

- `window_minus_page=True`: the steps walk `sliding_window - page` rows of
  the ring;
- `draft_row_kept=True`: a refused row is left counted: the lane's length
  goes on by two where it committed one, so the refused token's K/V stays in
  every cache as if it were the sequence's;
- `kv_int8=True`: every cache holds what int8 pages would hold;
- `free_routing=True`: the routing left free on the rows read twice.

The routing is teacher-forced where the tokens are (the reference's own
choice, `exaone_reference.route`), as `kanana`'s is and for its reason. The
prefills run `SEQUENCES` sequences a dispatch.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import numpy as np

from .. import check
from . import exaone_reference, exaone_weights

SEQUENCES = 2  # sequences a prefill of the cache check takes
REFUSED_SHARE = 0.5  # of the cache check's drafts, refused (seeded)


def _held(config: dict) -> tuple:
    return tuple(range(config.get("num_experts_held", config["num_experts"])))


def program_config(config: dict):
    from agentcontrolplane_tpu.models.exaone import ExaoneConfig

    for key, only in (("n_group", 1), ("topk_group", 1), ("scoring_func", "sigmoid"), ("hidden_act", "silu"),
                      ("num_nextn_predict_layers", 1), ("mtp_layer_types", ["full_attention"])):
        if config[key] != only:
            raise ValueError(f"the exaone family serves {key}={only!r} only; the file has {config[key]!r}")
    dense = [kind == "dense" for kind in config["mlp_layer_types"]]
    if len(dense) != config["num_hidden_layers"] or dense != sorted(dense, reverse=True) \
            or sum(dense) != config["first_k_dense_replace"]:
        raise ValueError("mlp_layer_types is not first_k_dense_replace dense layers and then sparse ones")
    return ExaoneConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        layer_types=tuple(config["layer_types"]), window=config["sliding_window"],
        first_dense=config["first_k_dense_replace"], ffn_dim=config["intermediate_size"],
        expert_ffn_dim=config["moe_intermediate_size"], n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"], experts_held=_held(config),
        n_shared_experts=config["num_shared_experts"], norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=config["routed_scaling_factor"], norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]), max_seq_len=config["max_position_embeddings"],
        tie_embeddings=config["tie_word_embeddings"],
    )


def weights(config: dict, program_config, mesh, seed: int):
    precision = config["engine"].get("quantize")
    if precision is not None:
        raise ValueError(f"the exaone family draws bfloat16 weights only; the file's engine.quantize is {precision!r}")
    return exaone_weights.make(program_config, mesh, seed)


def _sizes(config: dict) -> dict:
    """What the plain reference needs, from the file's keys alone."""
    return {
        "n_heads": config["num_attention_heads"], "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"], "norm_eps": config["rms_norm_eps"],
        "rope_theta": float(config["rope_parameters"]["rope_theta"]), "window": config["sliding_window"],
        "experts_per_token": config["num_experts_per_tok"], "held": _held(config),
        "norm_topk_prob": config["norm_topk_prob"], "routed_scaling_factor": config["routed_scaling_factor"],
        "layer_types": tuple(config["layer_types"]),
    }


def reference_logits(config: dict, params, tokens, rows, lower: str | None = None):
    return exaone_reference.logits(params, _sizes(config), tokens, rows, lower=lower)


def reference_draft_logits(config: dict, params, tokens, rows, lower: str | None = None):
    return exaone_reference.mtp_logits(params, _sizes(config), tokens, rows, lower=lower)


def _as_int8_pages(cache: dict, n_kv_heads: int) -> dict:
    """Every cache with each row and head rounded to int8 and back."""
    import jax.numpy as jnp

    from agentcontrolplane_tpu.ops.quant import kv_dequantize, kv_quantize

    def rounded(a):
        heads = a.reshape(a.shape[:-1] + (n_kv_heads, a.shape[-1] // n_kv_heads)).astype(jnp.float32)
        return kv_dequantize(*kv_quantize(heads), a.dtype).reshape(a.shape)

    return {**cache, **{name: rounded(cache[name]) for name in ("k", "v", "wk", "wv")}}


def forced_sampler(drafts, kept, nxt):
    """The engine's sampler's place in a teacher-forced step: the draft is
    `drafts` [S], it is kept where `kept` [S], and the lane commits `nxt`
    [S, 2], the sequence's own next tokens (one where refused)."""
    import jax.numpy as jnp

    def accept(logits, draft, q_logits):
        out = jnp.stack([nxt[:, 0], jnp.where(kept, nxt[:, 1], -1)], axis=1)
        return out, jnp.where(kept, 2, 1).astype(jnp.int32), kept

    return SimpleNamespace(propose=lambda q_logits: (drafts, q_logits), accept=accept)


def pattern(s: dict, seed: int = 0) -> np.ndarray:
    """[B, N] bool: whether the draft put at row j of a sequence is kept (a
    row reached by a kept draft puts none: its entry is not read)."""
    return np.random.default_rng([int(s["tokens"][0, 0]), s["B"], seed, 5]).random((s["B"], s["N"])) >= REFUSED_SHARE


def cached_logits(config: dict, program_config, params, mesh, s: dict, use_pallas: bool,
                  window_minus_page: bool = False, draft_row_kept: bool = False, kv_int8: bool = False,
                  free_routing: bool = False, draft: bool = False, kept=None):
    """(pre [B, N+1, V], dec [B, N, V]) float32 from the program: prefills
    of the prompt and of the prompt plus 1..N forced tokens, then
    teacher-forced verify steps from the prompt's prefill until every
    sequence has its N rows (module docstring). `kept` [B, N] bool is the
    pattern (`pattern(s)` where None). With `draft`, a third output: the
    drafted logits `[B, N, V]` each step left for the row it started at
    (row j: drafted from position `length + j - 1`, over token `length + j +
    1`), and a fourth, [B, N] bool, which rows a step started at."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.exaone import init_paged_cache, prefill_paged_batch, verify_step_paged

    rep = NamedSharding(mesh, P())
    B = s["B"]
    cache = jax.jit(lambda: init_paged_cache(program_config, s["pool_pages"], s["P"], max_slots=B))()
    put = lambda a: jax.device_put(jnp.asarray(a), rep)  # noqa: E731
    T, N, lengths = s["T"], s["N"], s["lengths"]
    k, layers = program_config.experts_per_token, program_config.n_layers - program_config.first_dense
    forced_routing = not free_routing
    window_rows = program_config.window - s["P"] if window_minus_page else None

    def prefill(p, c, t, n, ids, slots, route=None):
        return prefill_paged_batch(p, c, t, n, ids, (slots, jnp.full(slots.shape, -1, jnp.int32)), program_config,
                                   route=route)

    def step(p, c, t, n, tb, live, drafts, keep, nxt, route=None):
        c, _out, _emitted, aux = verify_step_paged(
            p, c, t, n, tb, live, forced_sampler(drafts, keep, nxt), program_config, use_pallas=use_pallas,
            mesh=mesh, route=route, window_rows=window_rows)
        return c, aux["logits"].astype(jnp.float32), aux["draft_logits"].astype(jnp.float32)

    # the programs, traced here and compiled side by side on threads (the
    # compiler works outside the interpreter's lock) while this thread runs
    # the reference's pass for the routing, as `kanana`'s check does: one
    # after another at their first calls they were most of the 100 s a cold
    # run's check took (my chip run, PR 51, the committed files' proof)
    ints = lambda *shape: put(np.zeros(shape, np.int32))  # noqa: E731
    tables = put(s["tables"])
    one = (params, cache, ints(SEQUENCES, T), ints(SEQUENCES), ints(SEQUENCES, T // s["P"]), ints(SEQUENCES))
    lanes = (params, cache, ints(B), ints(B), tables, put(np.zeros(B, bool)), ints(B), put(np.zeros(B, bool)), ints(B, 2))
    wanted = {"prefill_free": (prefill, one), "step": (step, lanes + ((ints(layers, B, 2, k),) if forced_routing else ()))}
    if forced_routing:
        wanted["prefill"] = (prefill, one + (ints(layers, SEQUENCES, T, k),))
    lowered = {name: jax.jit(fn, donate_argnums=(1,)).lower(*args) for name, (fn, args) in wanted.items()}
    with ThreadPoolExecutor(max_workers=len(lowered)) as pool:
        compiling = {name: pool.submit(low.compile) for name, low in lowered.items()}
        # [sparse layers, B, T + N, k]: the reference's choice for every token
        route = np.asarray(exaone_reference.route(params, _sizes(config), s["tokens"])) if forced_routing else None
        programs = {name: job.result() for name, job in compiling.items()}
    int8_pages = jax.jit(lambda c: _as_int8_pages(c, program_config.n_kv_heads), donate_argnums=(0,))

    def prefilled(extra: int, forced: bool):
        nonlocal cache
        n = lengths + extra
        prompt = np.where(np.arange(T)[None, :] < n[:, None], s["tokens"][:, :T], 0)
        ids = check.page_ids(s, n)
        out = []
        for b in range(0, B, SEQUENCES):
            # one compiled shape: a last dispatch short of sequences is filled with empty rows in the slot nothing reads
            real = np.arange(b, b + SEQUENCES) < B
            rows = np.minimum(np.arange(b, b + SEQUENCES), B - 1)
            args = (params, cache, put(prompt[rows].astype(np.int32)), put(np.where(real, n[rows], 0).astype(np.int32)),
                    put(np.where(real[:, None], ids[rows], 0).astype(np.int32)),
                    put(np.where(real, rows, B).astype(np.int32)))
            if forced and route is not None:
                cache, logits = programs["prefill"](*args, put(route[:, rows, :T].astype(np.int32)))
            else:
                cache, logits = programs["prefill_free"](*args)
            out.append(logits.astype(jnp.float32)[: int(real.sum())])
        return jnp.concatenate(out, axis=0)

    pre = [prefilled(j, True) for j in range(N, 0, -1)][::-1]
    pre.insert(0, prefilled(0, False))
    if route is not None:
        prefilled(0, True)
    if kv_int8:
        cache = int8_pages(cache)
    kept = pattern(s) if kept is None else np.asarray(kept, bool)
    rows, V = np.arange(B), config["vocab_size"]
    tok = lambda at: s["tokens"][rows, np.minimum(at, s["tokens"].shape[1] - 1)]  # noqa: E731
    done = np.zeros(B, np.int64)  # rows each sequence has
    counted = np.zeros(B, np.int64)  # refused rows left counted (the control)
    started = np.zeros((B, N), bool)
    dec = [[None] * N for _ in range(B)]
    drafted = [[None] * N for _ in range(B)]
    while (done < N).any():
        live = done < N
        at = lengths + np.minimum(done, N - 1)
        keep = live & kept[rows, np.minimum(done, N - 1)] & (done + 2 <= N)
        nxt = np.stack([tok(at + 1), tok(at + 2)], axis=1)
        drafts = np.where(keep, nxt[:, 0], (nxt[:, 0] + 1 + at % 7) % V)  # a refused draft is another token
        given = () if route is None else (put(np.stack(
            [route[:, rows, at], route[:, rows, np.minimum(at + 1, route.shape[2] - 1)]], axis=2).astype(np.int32)),)
        cache, logits, q = programs["step"](
            params, cache, put(tok(at).astype(np.int32)), put((at + counted).astype(np.int32)), tables, put(live),
            put(drafts.astype(np.int32)), put(keep), put(nxt.astype(np.int32)), *given)
        if kv_int8:
            cache = int8_pages(cache)
        for b in np.nonzero(live)[0]:
            j = int(done[b])
            dec[b][j], drafted[b][j], started[b, j] = logits[b, 0], q[b], True
            if keep[b]:
                dec[b][j + 1] = logits[b, 1]
        if draft_row_kept:
            counted += live & ~keep
        done += np.where(live, np.where(keep, 2, 1), 0)
    dec = jnp.stack([jnp.stack(r) for r in dec])
    if not draft:
        return jnp.stack(pre, axis=1), dec
    zero = jnp.zeros_like(dec[0, 0])
    drafted = jnp.stack([jnp.stack([zero if q is None else q for q in r]) for r in drafted])
    return jnp.stack(pre, axis=1), dec, drafted, started
