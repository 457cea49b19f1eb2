"""The Kanana-2 family (`models/kanana.py`; `model_type: deepseek_v3`):
latent attention whose cache is one row of `kv_lora_rank + qk_rope_head_dim`
values a token and layer, `first_k_dense_replace` leading dense layers, then
layers of `n_routed_experts` routed experts (sigmoid scores, a selection
bias, top `num_experts_per_tok` renormalised and times
`routed_scaling_factor`) of which the chip holds `num_experts_held` (experts
0 .. held-1), beside a shared expert of `n_shared_experts x
moe_intermediate_size`.

The file keeps the source's `config.json` keys as published, plus
`num_experts` (= `n_routed_experts`, the name the harness's readers read)
and `num_experts_held`; `assumed` says what the source leaves out. Weights:
`kanana_weights.py`, bfloat16, the one precision this family draws
(`engine.quantize` must be absent). Reference: `kanana_reference.py`, the
published expanded form, given the same `held`. Its controls (`lower=`), each
of which the file's limits must refuse but "bf16" and "bf16_rest":

- `"int8_matmul_inputs"`: every matmul input, K and V rounded to int8 per
  row, the precision below the configuration's; `"bf16"`: the same to
  bfloat16, the stated precision (it must pass); `"bf16_rest"`: besides,
  every tensor the program keeps at rest in bfloat16: the floor the
  program's own reading is held beside;
- `"scale_128"`: scores over `sqrt(128)`, the width of `k_nope` alone;
- `"rope_all"`: rotary over all 192 of a key's and a query's values;
- `"kv_norm_off"`: the latent not normed;
- `"k_pe_unroped"`: the shared key as a row stored BEFORE rotary would hold
  it (the queries still turned);
- `"shared_off"`: no shared expert;
- `"route_scale_off"`: `routed_scaling_factor` 1;
- `"bias_off"`: the selection bias left out of the choice.

The cache's own controls (keywords of `cached_logits`):

- `kv_int8=True`: the pool holds what int8 latent rows would hold (every
  row rounded to int8 by its largest value and back, after the prefill and
  after each decode step): the control of `cache_excess`;
- `free_routing=True`: the routing left free on the rows read twice.

The cache check teacher-forces the routing where it forces the tokens, as
`lfm2`'s and `mellum`'s do and for their reason: among 128 sigmoid scores
the sixth and the seventh lie 0.01 apart, bfloat16 flips a choice in a few of
a hundred (token, layer) pairs, and a flipped choice of a held expert moves
its row far more than all the rounding in it. Every row read twice, through
the cache by the absorbed decode step and by the expanded prefill, is
computed with the reference's own choice of experts
(`kanana_reference.route`); the prompt's own prefill, the first of the
compared rows, routes freely, as every token the engine emits does.

The prefills run one sequence a dispatch: the check's cache and a prefill's
temporaries stand beside an engine that holds 13 GB of a chip's 16.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import check
from . import kanana_reference, kanana_weights

SEQUENCES = 1  # sequences a prefill of the cache check takes


def _held(config: dict) -> tuple:
    return tuple(range(config.get("num_experts_held", config["n_routed_experts"])))


def program_config(config: dict):
    from agentcontrolplane_tpu.models.kanana import KananaConfig

    if config["num_experts"] != config["n_routed_experts"]:
        raise ValueError("num_experts is n_routed_experts under the name the harness's readers read: they differ")
    for key, only in (("q_lora_rank", None), ("rope_scaling", None), ("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"), ("attention_bias", False),
                      ("hidden_act", "silu"), ("rope_interleave", True)):
        if config[key] != only:
            raise ValueError(f"the kanana family serves {key}={only!r} only; the file has {config[key]!r}")
    return KananaConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"], n_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"], qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"], v_head_dim=config["v_head_dim"],
        n_layers=config["num_hidden_layers"], first_dense=config["first_k_dense_replace"],
        ffn_dim=config["intermediate_size"], expert_ffn_dim=config["moe_intermediate_size"],
        n_experts=config["n_routed_experts"], experts_per_token=config["num_experts_per_tok"],
        experts_held=_held(config), n_shared_experts=config["n_shared_experts"],
        norm_topk_prob=config["norm_topk_prob"], routed_scaling_factor=config["routed_scaling_factor"],
        norm_eps=config["rms_norm_eps"], rope_theta=float(config["rope_theta"]),
        max_seq_len=config["max_position_embeddings"], tie_embeddings=config["tie_word_embeddings"],
    )


def weights(config: dict, program_config, mesh, seed: int):
    precision = config["engine"].get("quantize")
    if precision is not None:
        raise ValueError(f"the kanana family draws bfloat16 weights only; the file's engine.quantize is {precision!r}")
    return kanana_weights.make(program_config, mesh, seed)


def _sizes(config: dict) -> dict:
    """What the plain reference needs, from the file's keys alone."""
    return {
        "n_heads": config["num_attention_heads"], "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope_head_dim": config["qk_nope_head_dim"], "qk_rope_head_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"], "norm_eps": config["rms_norm_eps"],
        "rope_theta": float(config["rope_theta"]), "experts_per_token": config["num_experts_per_tok"],
        "held": _held(config), "norm_topk_prob": config["norm_topk_prob"],
        "routed_scaling_factor": config["routed_scaling_factor"],
    }


def reference_logits(config: dict, params, tokens, rows, lower: str | None = None):
    return kanana_reference.logits(params, _sizes(config), tokens, rows, lower=lower)


def _as_int8_rows(cache: dict) -> dict:
    """The pool with every latent row rounded to int8 by its largest value
    and back: what int8 latent rows would hold."""
    import jax.numpy as jnp

    rows = cache["kv"].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(rows), axis=-1, keepdims=True) / 127.0, 1e-8)
    return {**cache, "kv": (jnp.clip(jnp.round(rows / scale), -127, 127) * scale).astype(cache["kv"].dtype)}


def cached_logits(config: dict, program_config, params, mesh, s: dict, use_pallas: bool,
                  kv_int8: bool = False, free_routing: bool = False):
    """(pre [B, N+1, V], dec [B, N, V]) float32 from the program: expanded
    prefills of the prompt and of the prompt plus 1..N forced tokens, then N
    absorbed decode steps from the prompt's prefill through the latent pool.
    `pre[:, 0]` routes freely; `pre[:, 1:]` and `dec`, the rows read twice,
    and the prefill that leaves the pool the decode steps read, take the
    reference's choice of experts (module docstring)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.kanana import decode_step_paged, init_paged_cache, prefill_paged_batch

    rep = NamedSharding(mesh, P())
    B = s["B"]
    cache = jax.jit(lambda: init_paged_cache(program_config, s["pool_pages"], s["P"], max_slots=B))()
    put = lambda a: jax.device_put(jnp.asarray(a), rep)  # noqa: E731
    T, N, lengths = s["T"], s["N"], s["lengths"]
    tables = put(s["tables"])
    k = program_config.experts_per_token
    layers = program_config.n_layers - program_config.first_dense
    forced = not free_routing

    def prefill(p, c, t, n, ids, route=None):
        return prefill_paged_batch(p, c, t, n, ids, program_config, route=route)

    def decode(p, c, t, n, tb, route=None):
        return decode_step_paged(p, c, t, n, tb, jnp.ones(t.shape, bool), program_config, use_pallas=use_pallas,
                                 mesh=mesh, route=route)

    # the three programs, traced here and compiled side by side on threads
    # (the compiler works outside the interpreter's lock) while this thread
    # runs the reference's pass for the routing: one after another, each at
    # its first call, they were 50 s of a cold run's check (PERF.md, PR 44)
    ints = lambda *shape: put(np.zeros(shape, np.int32))  # noqa: E731
    one = (params, cache, ints(SEQUENCES, T), ints(SEQUENCES), ints(SEQUENCES, T // s["P"]))
    step = (params, cache, ints(B), ints(B), tables)
    wanted = {"prefill_free": (prefill, one), "decode": (decode, step + ((ints(layers, B, 1, k),) if forced else ()))}
    if forced:
        wanted["prefill"] = (prefill, one + (ints(layers, SEQUENCES, T, k),))
    lowered = {name: jax.jit(fn, donate_argnums=(1,)).lower(*args) for name, (fn, args) in wanted.items()}
    with ThreadPoolExecutor(max_workers=len(lowered)) as pool:
        compiling = {name: pool.submit(low.compile) for name, low in lowered.items()}
        # [expert layers, B, T + N, k]: the reference's choice for every token
        route = np.asarray(kanana_reference.route(params, _sizes(config), s["tokens"])) if forced else None
        programs = {name: job.result() for name, job in compiling.items()}
    int8_rows = jax.jit(_as_int8_rows, donate_argnums=(0,))

    def prefilled(extra: int, given: bool):
        nonlocal cache
        n = lengths + extra
        prompt = np.where(np.arange(T)[None, :] < n[:, None], s["tokens"][:, :T], 0)
        ids = check.page_ids(s, n)
        out = []
        for b in range(0, B, SEQUENCES):
            rows = slice(b, b + SEQUENCES)
            args = (params, cache, put(prompt[rows].astype(np.int32)), put(n[rows].astype(np.int32)),
                    put(ids[rows].astype(np.int32)))
            if given and forced:
                cache, logits = programs["prefill"](*args, put(route[:, rows, :T].astype(np.int32)))
            else:
                cache, logits = programs["prefill_free"](*args)
            out.append(logits.astype(jnp.float32))
        return jnp.concatenate(out, axis=0)

    # the longer prefills first; then the prompt's own twice: routed freely
    # for its logits, and with the routing given to leave the pool as a
    # request of `lengths` tokens would, where the decode steps go on from
    pre = [prefilled(j, True) for j in range(N, 0, -1)][::-1]
    pre.insert(0, prefilled(0, False))
    if forced:
        prefilled(0, True)
    if kv_int8:
        cache = int8_rows(cache)
    dec = []
    rows = np.arange(B)
    for j in range(N):
        given = (put(route[:, rows, lengths + j][:, :, None].astype(np.int32)),) if forced else ()
        cache, logits = programs["decode"](params, cache, put(s["tokens"][rows, lengths + j].astype(np.int32)),
                                           put((lengths + j).astype(np.int32)), tables, *given)
        if kv_int8:
            cache = int8_rows(cache)
        dec.append(logits.astype(jnp.float32))
    return jnp.stack(pre, axis=1), jnp.stack(dec, axis=1)
