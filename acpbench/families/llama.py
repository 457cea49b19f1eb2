"""The Llama family (`models/llama.py`: Llama, Mistral, Qwen2 by flags).

The file's `llama_config` block holds `LlamaConfig` fields under the
program's own names (`qkv_bias`). Weights: `llama_weights.py`, int8 with
`engine.quantize: "int8"`, the one precision this family draws. Reference:
`llama_reference.py`; its controls are `lower="int8"` and `"fp8"` (every
matmul input, K and V rounded), and `"nobias"` (the q, k and v biases left
out: a broken bias path). The cache's own control: `quantize_kv=True`, the
program's int8 KV pages. The pool is taken in whatever layout
`init_paged_cache` gives it (`families/__init__.py`, "The pool").
"""

from __future__ import annotations

import numpy as np

from .. import check
from . import llama_reference, llama_weights

FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "ffn_dim", "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "max_position_embeddings": "max_seq_len", "tie_word_embeddings": "tie_embeddings",
}
BIASES = ("bq", "bk", "bv")


def program_config(config: dict):
    """`LlamaConfig` from the source's `config.json` keys under the
    program's names, plus what the file states under `llama_config`."""
    from agentcontrolplane_tpu.models.llama import LlamaConfig

    kw = {ours: config[theirs] for theirs, ours in FIELDS.items() if theirs in config}
    kw.update(config.get("llama_config", {}))
    return LlamaConfig(**kw)


def weights(config: dict, program_config, mesh, seed: int):
    precision = config["engine"].get("quantize")
    if precision != "int8":
        raise ValueError(f"the llama family draws int8 weights only; the file's engine.quantize is {precision!r}")
    return llama_weights.make(program_config, mesh, seed)


def _sizes(config: dict) -> dict:
    """What the plain reference needs, from the source's keys alone."""
    return {
        "n_heads": config["num_attention_heads"], "n_kv_heads": config["num_key_value_heads"],
        "norm_eps": config["rms_norm_eps"], "rope_theta": config["rope_theta"],
    }


def reference_logits(config: dict, params, tokens, rows, lower: str | None = None):
    if lower == "nobias":
        layers = {k: v for k, v in params["layers"].items() if k not in BIASES}
        params, lower = dict(params, layers=layers), None
    elif lower is not None and lower not in llama_reference.ROUND:
        raise ValueError(f"the llama reference has no control {lower!r}")
    return llama_reference.logits(params, _sizes(config), tokens, rows, lower=lower)


def cached_logits(config: dict, program_config, params, mesh, s: dict, use_pallas: bool,
                  quantize_kv: bool = False):
    """(pre [B, N+1, V], dec [B, N, V]) float32 from the program: prefills
    of the prompt and of the prompt plus 1..N forced tokens (row j predicts
    token length + j), then N decode steps from the prompt's prefill
    (step j is the cache's reading of row j + 1)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agentcontrolplane_tpu.models.llama import (
        decode_step_paged, init_paged_cache, prefill_paged_batch,
    )

    rep = NamedSharding(mesh, P())
    # the pool as the program stores it (`families/__init__.py`, "The pool"): axis 3 over `tp` at any rank
    heads_sh = NamedSharding(mesh, P(None, None, None, "tp"))
    init = lambda: init_paged_cache(program_config, s["pool_pages"], s["P"], quantize_kv=quantize_kv)  # noqa: E731
    shardings = {name: jax.tree_util.tree_map(lambda _: rep if name == "state" else heads_sh, leaves)
                 for name, leaves in jax.eval_shape(init).items()}
    pool = jax.jit(init, out_shardings=shardings)()
    put = lambda a: jax.device_put(jnp.asarray(a), rep)  # noqa: E731
    prefill = jax.jit(lambda p, pages, t, n, ids: prefill_paged_batch(p, pages, t, n, ids, program_config),
                      donate_argnums=(1,))
    decode = jax.jit(
        lambda p, pages, t, n, tb: decode_step_paged(
            p, pages, t, n, tb, jnp.ones(t.shape, bool), program_config, use_pallas=use_pallas, mesh=mesh),
        donate_argnums=(1,))
    T, N, lengths = s["T"], s["N"], s["lengths"]

    def prefilled(extra: int):
        nonlocal pool
        n = lengths + extra
        prompt = np.where(np.arange(T)[None, :] < n[:, None], s["tokens"][:, :T], 0)
        pool, logits = prefill(params, pool, put(prompt), put(n), put(check.page_ids(s, n)))
        return logits.astype(jnp.float32)

    # the longer prefills first: the last one leaves the pool as a request
    # of `lengths` tokens would, and the decode steps go on from there
    pre = [prefilled(j) for j in range(N, -1, -1)][::-1]
    dec = []
    tables = put(s["tables"])
    for j in range(N):
        forced = s["tokens"][np.arange(s["B"]), lengths + j]
        pool, logits = decode(params, pool, put(forced), put(lengths + j), tables)
        dec.append(logits.astype(jnp.float32))
    return jnp.stack(pre, axis=1), jnp.stack(dec, axis=1)
