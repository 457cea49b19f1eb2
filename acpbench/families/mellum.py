"""The Mellum2 family (`models/mellum.py`): GQA layers of two kinds by a
strict period, `sliding_attention` (the last `sliding_window` tokens, plain
rotary frequencies) and `full_attention` (every earlier token, YaRN), every
layer's FF routed experts of which the chip holds `num_experts_held`
(experts 0 .. held-1 of `num_experts`, the router's width).

The file keeps the source's `config.json` keys as published (`assumed` says
what the source leaves out). Weights: `mellum_weights.py`, bfloat16, the one
precision this family draws (`engine.quantize` must be absent). Reference:
`mellum_reference.py`, given the same `held`. Its controls (`lower=`):

- `"int8"`: every matmul input, K and V rounded to int8 per row, the
  precision below the configuration's; `"bf16"`: the same to bfloat16, the
  stated precision (it must pass); `"bf16_rest"`: besides, every tensor the
  program keeps at rest in bfloat16 (the stream, the outputs of matmuls,
  norms and rotary): the floor the program's own reading is held beside;
- `"window_off"`: the window layers see the whole context;
- `"one_rope"`: the plain frequencies in every layer (no YaRN in the full
  layers);
- `"nonorm"`: `norm_topk_prob` off.

The cache's own controls (keywords of `cached_logits`):

- `window_minus_page=True`: the decode steps walk `sliding_window - page`
  rows of the ring, what a ring that masks one page too many reads;
- `kv_int8=True`: both caches hold what int8 pages would hold (every row
  and head rounded to int8 and back after the prefill and after each decode
  step);
- `free_routing=True`: the routing left free on the rows read twice.

The cache check teacher-forces the routing where it forces the tokens, as
`lfm2`'s does and for its reason (`families/lfm2.py`): among 64 softmax
scores the eighth and the ninth lie close, bfloat16 flips a choice in a few
of a hundred (token, layer) pairs, and a flipped choice of a held expert
moves its row far more than all the rounding in it. Every row read twice,
through the caches and by prefill, is computed with the reference's own
choice of experts (`mellum_reference.route`); the prompt's own prefill, the
first of the compared rows, routes freely, as every token the engine emits
does.

`check.min_prompt` is over `sliding_window`, so every compared row lies past
the window and every decode step reads a ring that has wrapped. The
prefills run `SEQUENCES` sequences a dispatch: eight rows of 2,048 tokens
at once would need 2 GB of temporaries beside an engine that holds 13 GB.
"""

from __future__ import annotations

import numpy as np

from .. import check
from . import mellum_reference, mellum_weights

SEQUENCES = 2  # sequences a prefill of the cache check takes


def _held(config: dict) -> tuple:
    return tuple(range(config.get("num_experts_held", config["num_experts"])))


def _yarn(config: dict):
    full = config["rope_parameters"]["full_attention"]
    if full["rope_type"] == "default":
        return None
    if full["rope_type"] != "yarn":
        raise ValueError(f"the mellum family turns its full layers by yarn or default, not {full['rope_type']!r}")
    return (float(full["factor"]), int(full["original_max_position_embeddings"]), float(full["beta_fast"]),
            float(full["beta_slow"]), float(full["attention_factor"]))


def program_config(config: dict):
    from agentcontrolplane_tpu.models.mellum import MellumConfig

    return MellumConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], layer_types=tuple(config["layer_types"]), window=config["sliding_window"],
        ffn_dim=config["intermediate_size"], expert_ffn_dim=config["moe_intermediate_size"],
        n_experts=config["num_experts"], experts_per_token=config["num_experts_per_tok"],
        experts_held=_held(config), norm_topk_prob=config["norm_topk_prob"], norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_parameters"]["sliding_attention"]["rope_theta"]), yarn=_yarn(config),
        max_seq_len=config["max_position_embeddings"], tie_embeddings=config["tie_word_embeddings"],
    )


def weights(config: dict, program_config, mesh, seed: int):
    precision = config["engine"].get("quantize")
    if precision is not None:
        raise ValueError(f"the mellum family draws bfloat16 weights only; the file's engine.quantize is {precision!r}")
    return mellum_weights.make(program_config, mesh, seed)


def _sizes(config: dict) -> dict:
    """What the plain reference needs, from the file's keys alone."""
    return {
        "n_heads": config["num_attention_heads"], "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"], "norm_eps": config["rms_norm_eps"],
        "rope_theta": float(config["rope_parameters"]["sliding_attention"]["rope_theta"]), "yarn": _yarn(config),
        "window": config["sliding_window"], "experts_per_token": config["num_experts_per_tok"],
        "held": _held(config), "norm_topk_prob": config["norm_topk_prob"],
        "layer_types": tuple(config["layer_types"]),
    }


def reference_logits(config: dict, params, tokens, rows, lower: str | None = None):
    return mellum_reference.logits(params, _sizes(config), tokens, rows, lower=lower)


def _as_int8_pages(cache: dict, n_kv_heads: int) -> dict:
    """Both caches with every row and head rounded to int8 and back: what
    int8 pages would hold."""
    import jax.numpy as jnp

    from agentcontrolplane_tpu.ops.quant import kv_dequantize, kv_quantize

    def rounded(a):
        heads = a.reshape(a.shape[:-1] + (n_kv_heads, a.shape[-1] // n_kv_heads)).astype(jnp.float32)
        return kv_dequantize(*kv_quantize(heads), a.dtype).reshape(a.shape)

    return {**cache, **{name: rounded(cache[name]) for name in ("k", "v", "wk", "wv")}}


def cached_logits(config: dict, program_config, params, mesh, s: dict, use_pallas: bool,
                  window_minus_page: bool = False, kv_int8: bool = False, free_routing: bool = False):
    """(pre [B, N+1, V], dec [B, N, V]) float32 from the program: prefills
    of the prompt and of the prompt plus 1..N forced tokens, then N decode
    steps from the prompt's prefill, through the full layers' pages and the
    ring of sequence b in slot b. `pre[:, 0]` routes freely; `pre[:, 1:]` and
    `dec`, the rows read twice, and the prefill that leaves the caches the
    decode steps read, take the reference's choice of experts (module
    docstring)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.mellum import decode_step_paged, init_paged_cache, prefill_paged_batch

    rep = NamedSharding(mesh, P())
    B = s["B"]
    cache = jax.jit(lambda: init_paged_cache(program_config, s["pool_pages"], s["P"], max_slots=B))()
    put = lambda a: jax.device_put(jnp.asarray(a), rep)  # noqa: E731
    prefill = jax.jit(
        lambda p, c, t, n, ids, slots, route: prefill_paged_batch(
            p, c, t, n, ids, (slots, jnp.full(slots.shape, -1, jnp.int32)), program_config, route=route),
        donate_argnums=(1,))
    window_rows = program_config.window - s["P"] if window_minus_page else None
    decode = jax.jit(
        lambda p, c, t, n, tb, route: decode_step_paged(
            p, c, t, n, tb, jnp.ones(t.shape, bool), program_config, use_pallas=use_pallas, mesh=mesh,
            route=route, window_rows=window_rows),
        donate_argnums=(1,))
    int8_pages = jax.jit(lambda c: _as_int8_pages(c, program_config.n_kv_heads), donate_argnums=(0,))
    T, N, lengths = s["T"], s["N"], s["lengths"]
    # [layers, B, T + N, k]: the reference's choice for every token
    route = None if free_routing else np.asarray(mellum_reference.route(params, _sizes(config), s["tokens"]))

    def prefilled(extra: int, forced: bool):
        nonlocal cache
        n = lengths + extra
        prompt = np.where(np.arange(T)[None, :] < n[:, None], s["tokens"][:, :T], 0)
        ids = check.page_ids(s, n)
        out = []
        for b in range(0, B, SEQUENCES):
            rows = slice(b, b + SEQUENCES)
            given = put(route[:, rows, :T]) if forced and route is not None else None
            cache, logits = prefill(params, cache, put(prompt[rows]), put(n[rows]), put(ids[rows]),
                                    put(np.arange(B, dtype=np.int32)[rows]), given)
            out.append(logits.astype(jnp.float32))
        return jnp.concatenate(out, axis=0)

    # the longer prefills first; then the prompt's own twice: routed freely
    # for its logits, and with the routing given to leave the caches as a
    # request of `lengths` tokens would, where the decode steps go on from
    pre = [prefilled(j, True) for j in range(N, 0, -1)][::-1]
    pre.insert(0, prefilled(0, False))
    if route is not None:
        prefilled(0, True)
    if kv_int8:
        cache = int8_pages(cache)
    dec = []
    tables = put(s["tables"])
    rows = np.arange(B)
    for j in range(N):
        forced = s["tokens"][rows, lengths + j]
        given = None if route is None else put(route[:, rows, lengths + j][:, :, None])
        cache, logits = decode(params, cache, put(forced), put(lengths + j), tables, given)
        if kv_int8:
            cache = int8_pages(cache)
        dec.append(logits.astype(jnp.float32))
    return jnp.stack(pre, axis=1), jnp.stack(dec, axis=1)
