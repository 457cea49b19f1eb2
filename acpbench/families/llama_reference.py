"""The plain reference: a Llama-family decoder's forward pass in
`jax.numpy`, float32, matmuls at `highest` precision, no cache, no
batching tricks, no kernel. Written from the published description
(Qwen2.5 / Llama: RMSNorm, biased QKV, split-half RoPE, grouped-query
causal softmax attention, SwiGLU, untied head), not from the program.

It takes the weights `llama_weights.py` drew from the seed (int8 values times
their per-channel scales are the weights; there is nothing to "dequantize"
but a product) and token ids. Layers stream one at a time so that only one
layer's float32 copy exists at once.

`lower` computes the same pass in a stated lower precision and is the
control of the output check (see check.py): activations rounded to int8
per row before every matmul and for K and V ("int8"), or the same rounded
to float8 e4m3 ("fp8").
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HEAD_CHUNKS = 8  # the head's [dim, vocab] is multiplied a slice at a time


def _w(leaf):
    """A weight leaf as float32: int8 values times scale, or a cast."""
    if hasattr(leaf, "q"):
        return leaf.q.astype(jnp.float32) * leaf.scale.astype(jnp.float32)
    return leaf.astype(jnp.float32)


def _round_int8(x):
    """Symmetric per-row int8 rounding of an activation (the control)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-8)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _round_fp8(x):
    """Round to float8 e4m3 with a per-row scale that uses its range."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0, 1e-12)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


ROUND = {"int8": _round_int8, "fp8": _round_fp8}


def _mm(x, w, lower):
    if lower in ROUND:
        x = ROUND[lower](x)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, T, H, d]; HF's rotate_half convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # [T, d/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "eps", "theta", "lower"))
def _layer(x, layer, *, n_heads, n_kv_heads, eps, theta, lower):
    B, T, D = x.shape
    h = _rms(x, _w(layer["ln1"]), eps)
    q, k, v = (_mm(h, _w(layer[n]), lower) for n in ("wq", "wk", "wv"))
    if "bq" in layer:
        q, k, v = q + _w(layer["bq"]), k + _w(layer["bk"]), v + _w(layer["bv"])
    d = q.shape[-1] // n_heads
    q = _rope(q.reshape(B, T, n_heads, d), theta)
    k = _rope(k.reshape(B, T, n_kv_heads, d), theta)
    v = v.reshape(B, T, n_kv_heads, d)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    if lower in ROUND:
        k, v = ROUND[lower](k), ROUND[lower](v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=jax.lax.Precision.HIGHEST) / (d**0.5)
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(a.reshape(B, T, n_heads * d), _w(layer["wo"]), lower)
    h = _rms(x, _w(layer["ln2"]), eps)
    y = jax.nn.silu(_mm(h, _w(layer["w1"]), lower)) * _mm(h, _w(layer["w3"]), lower)
    return x + _mm(y, _w(layer["w2"]), lower)


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, head, *, eps, lower):
    x = _rms(x, _w(norm), eps)
    V = head.shape[-1]
    step = -(-V // HEAD_CHUNKS)
    parts = [_mm(x, _w(head[:, i: i + step]), lower) for i in range(0, V, step)]
    return jnp.concatenate(parts, axis=-1)


def logits(params: dict, model: dict, tokens, rows, lower: str | None = None):
    """Float32 logits [B, R, V] of `tokens` [B, T] at positions `rows`
    [B, R] (each predicts the token after it). `model` holds the
    configuration file's sizes. Sequences are left-aligned; what lies to
    the right of a row never reaches it (causal)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)
    L = params["layers"]["ln1"].shape[0]
    for l in range(L):
        layer = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        x = _layer(x, layer, n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
                   eps=model["norm_eps"], theta=model["rope_theta"], lower=lower)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    rows = jnp.asarray(rows, jnp.int32)
    picked = x[jnp.arange(x.shape[0])[:, None], rows]
    return _head(picked, params["norm"], head, eps=model["norm_eps"], lower=lower)
