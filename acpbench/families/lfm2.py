"""The LFM2-MoE family (`models/lfm2.py`): gated short-conv and GQA layers
by a pattern, a dense SwiGLU in the leading layers and routed experts after,
of which the chip holds `num_experts_held` (experts 0 .. held-1 of
`num_experts`, the router's width).

The file keeps the source's `config.json` keys, and beside them `head_dim`
and `tie_word_embeddings`, which the source leaves out (`assumed` says why). Weights:
`lfm2_weights.py`, bfloat16, the one precision this family draws
(`engine.quantize` must be absent). Reference: `lfm2_reference.py`, given
the same `held`; its controls are `lower="int8"` (every matmul input, K and
V rounded), `"nobias"` (the selection bias left out), `"nonorm"`
(`norm_topk_prob` off) and `"capacity"` (experts with room for N k / 2E
tokens, half an even share, the overflow dropped). The cache's own controls: `quantize_kv=True`
(int8 pages) and `zero_state=True` (the conv state zeroed between the
prompt's prefill and the first decode step).

The cache check teacher-forces the routing where it forces the tokens
(`cached_logits`): among 64 sigmoid scores the fourth and the fifth lie
about 0.02 apart, so bfloat16 flips a choice in a few of a hundred (token,
layer) pairs, and one flipped choice of a held expert moves that row's
logits ten times as far as all the rounding in it. Left free, the paired
reading (`cache_excess`) is the difference of two sums each led by whichever
rows flipped: -0.52..1.46 on sound runs, with int8 pages inside that spread
(my chip runs, PR 31). So every row read twice, through the cache and by
prefill, is computed with the reference's own choice of experts
(`lfm2_reference.route`) handed to the program, and what is left between
the two readings is what the pages and the state add. The prompt's own
prefill, the first of the compared rows, routes freely, as every token the
engine emits does: a router that chooses wrongly shows there
(`prefill_rel_rms`, `greedy_regret`). `free_routing=True` is the unforced
comparison, kept for the record.
"""

from __future__ import annotations

import numpy as np

from .. import check
from . import lfm2_reference, lfm2_weights


def _held(config: dict) -> tuple:
    return tuple(range(config.get("num_experts_held", config["num_experts"])))


def program_config(config: dict):
    from agentcontrolplane_tpu.models.lfm2 import Lfm2Config

    return Lfm2Config(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        layer_types=tuple("attention" if t == "full_attention" else t for t in config["layer_types"]),
        num_dense_layers=config["num_dense_layers"], ffn_dim=config["intermediate_size"],
        expert_ffn_dim=config["moe_intermediate_size"], n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"], experts_held=_held(config),
        norm_topk_prob=config["norm_topk_prob"], use_expert_bias=config["use_expert_bias"],
        routed_scaling_factor=float(config["routed_scaling_factor"]), conv_taps=config["conv_L_cache"],
        norm_eps=config["norm_eps"], rope_theta=float(config["rope_parameters"]["rope_theta"]),
        max_seq_len=config["max_position_embeddings"], tie_embeddings=config["tie_word_embeddings"],
    )


def weights(config: dict, program_config, mesh, seed: int):
    precision = config["engine"].get("quantize")
    if precision is not None:
        raise ValueError(f"the lfm2 family draws bfloat16 weights only; the file's engine.quantize is {precision!r}")
    return lfm2_weights.make(program_config, mesh, seed)


def _sizes(config: dict) -> dict:
    """What the plain reference needs, from the file's keys alone."""
    return {
        "n_heads": config["num_attention_heads"], "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"], "norm_eps": config["norm_eps"],
        "rope_theta": float(config["rope_parameters"]["rope_theta"]),
        "experts_per_token": config["num_experts_per_tok"], "held": _held(config),
        "norm_topk_prob": config["norm_topk_prob"], "use_expert_bias": config["use_expert_bias"],
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "layer_types": tuple(config["layer_types"]), "num_dense_layers": config["num_dense_layers"],
    }


def reference_logits(config: dict, params, tokens, rows, lower: str | None = None):
    return lfm2_reference.logits(params, _sizes(config), tokens, rows, lower=lower)


def cached_logits(config: dict, program_config, params, mesh, s: dict, use_pallas: bool,
                  quantize_kv: bool = False, zero_state: bool = False, free_routing: bool = False):
    """(pre [B, N+1, V], dec [B, N, V]) float32 from the program: prefills
    of the prompt and of the prompt plus 1..N forced tokens, then N decode
    steps from the prompt's prefill, through the pages of the attention
    layers and the conv state of sequence b in slot b. `pre[:, 0]` routes
    freely; `pre[:, 1:]` and `dec`, the rows read twice, and the prefill that
    leaves the pages and the state the decode steps read, take the
    reference's choice of experts (module docstring)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.lfm2 import decode_step_paged, init_paged_cache, prefill_paged_batch

    rep = NamedSharding(mesh, P())
    B = s["B"]
    cache = jax.jit(lambda: init_paged_cache(program_config, s["pool_pages"], s["P"], quantize_kv=quantize_kv,
                                             max_slots=B))()
    put = lambda a: jax.device_put(jnp.asarray(a), rep)  # noqa: E731
    lanes = (put(np.arange(B, dtype=np.int32)), put(np.full(B, -1, np.int32)))
    prefill = jax.jit(
        lambda p, c, t, n, ids, route: prefill_paged_batch(p, c, t, n, ids, lanes, program_config, route=route),
        donate_argnums=(1,))
    decode = jax.jit(
        lambda p, c, t, n, tb, route: decode_step_paged(
            p, c, t, n, tb, jnp.ones(t.shape, bool), program_config,
            use_pallas=use_pallas and not quantize_kv, mesh=mesh, route=route),
        donate_argnums=(1,))
    T, N, lengths = s["T"], s["N"], s["lengths"]
    # [expert layers, B, T + N, k]: the reference's choice for every token
    route = None if free_routing else np.asarray(lfm2_reference.route(params, _sizes(config), s["tokens"]))

    def prefilled(extra: int, forced: bool):
        nonlocal cache
        n = lengths + extra
        prompt = np.where(np.arange(T)[None, :] < n[:, None], s["tokens"][:, :T], 0)
        given = put(route[:, :, :T]) if forced and route is not None else None
        cache, logits = prefill(params, cache, put(prompt), put(n), put(check.page_ids(s, n)), given)
        return logits.astype(jnp.float32)

    # the longer prefills first; then the prompt's own twice: routed freely
    # for its logits, and with the routing given to leave pages and state as
    # a request of `lengths` tokens would, where the decode steps go on from
    pre = [prefilled(j, True) for j in range(N, 0, -1)][::-1]
    pre.insert(0, prefilled(0, False))
    if route is not None:
        prefilled(0, True)
    if zero_state:
        cache["state"]["conv"] = jnp.zeros_like(cache["state"]["conv"])
    dec = []
    tables = put(s["tables"])
    rows = np.arange(B)
    for j in range(N):
        forced = s["tokens"][rows, lengths + j]
        given = None if route is None else put(route[:, rows, lengths + j][:, :, None])
        cache, logits = decode(params, cache, put(forced), put(lengths + j), tables, given)
        dec.append(logits.astype(jnp.float32))
    return jnp.stack(pre, axis=1), jnp.stack(dec, axis=1)
