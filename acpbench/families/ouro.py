"""The Ouro family (`models/ouro.py`; `model_type: ouro`): ONE stack of
`num_hidden_layers` dense layers run `total_ut_steps` times over the same
weights, a norm and an exit gate between the loops, a pool `total_ut_steps`
times as deep as the weights (cache layer `t * num_hidden_layers + l`).

The file keeps the source's `config.json` keys as published; `assumed` says
what the catalog's row leaves to the model's published modeling file.
Weights: `ouro_weights.py`, bfloat16, the one precision this family draws
(`engine.quantize` must be absent). Reference: `ouro_reference.py`. Its
controls (`lower=`), each of which the file's limits must refuse but
"bf16_rest":

- `"bf16_rest"`: every matmul input, K and V rounded to bfloat16 and besides
  every tensor the program keeps at rest in bfloat16: the stated precision
  (it must pass); `"int8_inputs"`: the precision below it, as a program
  would run it: the same at rest, every matmul input, K and V int8 a row;
- `"loops_3"`: the last loop left out; `"shared_cache"`: loops after the
  first read the first loop's K and V; `"no_loop_norm"`: the state carried
  un-normed between the loops; `"no_post_norms"`; `"exit_first"`: the head
  reads the first loop's state.

The cache's own control (a keyword of `cached_logits`): `kv_int8=True`, the
pool holds what int8 pages would hold (every K and V row rounded to int8 a
head by its largest value and back, after the prefill and after each decode
step): the control of `cache_excess`.

The cache check runs ONE sequence at a time through a pool of that
sequence's pages alone: a page is 25 MB over 192 cache layers, the sample's
four sequences would be 1.7 GB of pool beside an engine that holds 13.4 GB
of the chip's 16, and a 256-token prefill stacks 0.4 GB of new rows. What is
compared is unchanged: the prefill's row and the decode rows of each
sequence, through the compiled walk.
"""

from __future__ import annotations

import numpy as np

from . import ouro_reference, ouro_weights

LAYER_KINDS = ("full_attention",)


def program_config(config: dict):
    from agentcontrolplane_tpu.models.ouro import OuroConfig

    for key, only in (("hidden_act", "silu"), ("rope_scaling", None), ("use_sliding_window", False),
                      ("tie_word_embeddings", False)):
        if config[key] != only:
            raise ValueError(f"the ouro family serves {key}={only!r} only; the file has {config[key]!r}")
    if set(config["layer_types"]) - set(LAYER_KINDS) or len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("the ouro family serves full_attention layers only, one entry of layer_types a layer")
    if config["head_dim"] * config["num_attention_heads"] != config["hidden_size"]:
        raise ValueError("the ouro family serves head_dim = hidden_size / num_attention_heads")
    return OuroConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"], n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"], norm_eps=config["rms_norm_eps"], rope_theta=float(config["rope_theta"]),
        max_seq_len=config["max_position_embeddings"], tie_embeddings=config["tie_word_embeddings"],
        loops=config["total_ut_steps"], exit_threshold=float(config["early_exit_threshold"]),
    )


def weights(config: dict, program_config, mesh, seed: int):
    precision = config["engine"].get("quantize")
    if precision is not None:
        raise ValueError(f"the ouro family draws bfloat16 weights only; the file's engine.quantize is {precision!r}")
    return ouro_weights.make(program_config, mesh, seed)


def _sizes(config: dict) -> dict:
    """What the plain reference needs, from the file's keys alone."""
    return {"n_heads": config["num_attention_heads"], "norm_eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]), "loops": config["total_ut_steps"],
            "exit_threshold": float(config["early_exit_threshold"])}


def reference_logits(config: dict, params, tokens, rows, lower: str | None = None):
    return ouro_reference.logits(params, _sizes(config), tokens, rows, lower=lower)


def _as_int8_rows(cache: dict, heads: int) -> dict:
    """The pool with every K and V row rounded to int8 a head by its largest
    value and back: what int8 pages would hold."""
    import jax.numpy as jnp

    def rounded(a):
        rows = a.astype(jnp.float32).reshape(a.shape[:-1] + (heads, -1))
        scale = jnp.maximum(jnp.max(jnp.abs(rows), axis=-1, keepdims=True) / 127.0, 1e-8)
        return (jnp.clip(jnp.round(rows / scale), -127, 127) * scale).reshape(a.shape).astype(a.dtype)

    return {**cache, "k": rounded(cache["k"]), "v": rounded(cache["v"])}


def cached_logits(config: dict, program_config, params, mesh, s: dict, use_pallas: bool, kv_int8: bool = False):
    """(pre [B, N+1, V], dec [B, N, V]) float32 from the program, a sequence
    at a time (module docstring): prefills of the prompt and of the prompt
    plus 1..N forced tokens, then N decode steps from the prompt's prefill
    through the sequence's own pool of `loops x layers` cache layers."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.ouro import decode_step_paged, init_paged_cache, prefill_paged_batch

    rep = NamedSharding(mesh, P())
    put = lambda a: jax.device_put(jnp.asarray(a), rep)  # noqa: E731
    T, N, page = s["T"], s["N"], s["P"]
    per_seq = s["tables"].shape[1]
    table = 1 + np.arange(per_seq, dtype=np.int32)[None, :]  # the one sequence's pages; 0 is the trash page
    prefill = jax.jit(lambda p, c, t, n, ids: prefill_paged_batch(p, c, t, n, ids, program_config), donate_argnums=(1,))
    decode = jax.jit(
        lambda p, c, t, n, tb: decode_step_paged(p, c, t, n, tb, jnp.ones(t.shape, bool), program_config,
                                                 use_pallas=use_pallas, mesh=mesh),
        donate_argnums=(1,))
    int8_rows = jax.jit(functools.partial(_as_int8_rows, heads=program_config.n_kv_heads), donate_argnums=(0,))
    pre, dec = [], []
    for b in range(s["B"]):
        cache = jax.jit(lambda: init_paged_cache(program_config, per_seq + 1, page), out_shardings=rep)()
        length = int(s["lengths"][b])
        rows = []
        for extra in range(N, -1, -1):  # the longer prefills first: the last leaves the pool as the prompt would
            n = length + extra
            prompt = np.where(np.arange(T) < n, s["tokens"][b, :T], 0)[None, :].astype(np.int32)
            ids = np.where(np.arange(T // page) < -(-n // page), table[0, : T // page], 0)[None, :].astype(np.int32)
            cache, logits = prefill(params, cache, put(prompt), put(np.array([n], np.int32)), put(ids))
            rows.append(logits[0].astype(jnp.float32))
        pre.append(jnp.stack(rows[::-1]))
        if kv_int8:
            cache = int8_rows(cache)
        rows = []
        for j in range(N):
            forced = s["tokens"][b, length + j: length + j + 1].astype(np.int32)
            cache, logits = decode(params, cache, put(forced), put(np.array([length + j], np.int32)), put(table))
            if kv_int8:
                cache = int8_rows(cache)
            rows.append(logits[0].astype(jnp.float32))
        dec.append(jnp.stack(rows))
        del cache
    return jnp.stack(pre), jnp.stack(dec)
