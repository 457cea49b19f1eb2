"""The plain reference: a Jamba decoder's forward pass (dense: one expert) in
`jax.numpy`, float32, matmuls at `highest` precision, the recurrence as a
`lax.scan` over time one token at a time, full causal attention, no cache,
no state carried, no kernel. Written from the published description
(https://huggingface.co/ai21labs/AI21-Jamba2-3B, `model_type: jamba`; the
Mamba paper's algorithm 2 for the selective scan), not from the program, of
which it imports nothing.

Every layer: `h = x + Mixer(RMSNorm(x))`, `y = h + SwiGLU(RMSNorm(h))`; a
last RMSNorm; logits over the tied embedding.

- Mamba mixer: `[u, z] = x W_in`; `u'_t = silu(b + sum_j w[j] u_{t-3+j})`
  (depthwise, causal, `u` zero before the sequence); `[dt, B, C] = u' W_x`,
  each through its own RMSNorm with a weight; `delta = softplus(dt W_dt +
  b_dt)`; `A = -exp(A_log)`; for each channel c and state n
  `h_t[c, n] = exp(delta_t[c] A[c, n]) h_{t-1}[c, n] + delta_t[c] B_t[n] u'_t[c]`,
  `y_t[c] = sum_n h_t[c, n] C_t[n] + D[c] u'_t[c]`; `Mixer = (y * silu(z)) W_out`.
- Attention mixer: q, k, v without bias, no position encoding of any kind;
  causal softmax at `head_dim**-0.5`; output projection.

Departures from the published model: none in the mathematics. The weights
come in the layout they are served in (`jamba_weights.py`): `mamba` and
`attn` (each stacked over its own layers in order) and `ff` (over all
layers); `conv_w` is `[taps, d_inner]` and `A_log` `[d_state, d_inner]`, the
transposes of the published order, read here by their names.
`model["layer_types"]`, the file's list, says which mixer each layer takes.

The sequences are computed in blocks of `ROW_BLOCK` rows, so that eight
sequences of the published widths fit a chip beside the served model.

`lower` names a control: "int8" rounds the input of every matmul (and K and
V) to int8 per row; "bf16" rounds the same to bfloat16, the precision the
configuration states (it must pass); "h_bf16" carries the recurrence's `h`
in bfloat16 (rounded after every token: the precision below the one the
file states for it); "nonorm" leaves the dt, B and C norms out; "nobias"
leaves the conv's bias out.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HEAD_CHUNKS = 8
ROW_BLOCK = 4
CONTROLS = ("int8", "bf16", "h_bf16", "nonorm", "nobias")
HI = jax.lax.Precision.HIGHEST


def _w(leaf):
    return leaf.astype(jnp.float32)


def _round_int8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-8)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _round_bf16(x):
    # not a cast there and back: on the TPU the compiler may keep the excess precision and drop the pair
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


ROUND = {"int8": _round_int8, "bf16": _round_bf16}


def _mm(x, w, lower):
    if lower in ROUND:
        x = ROUND[lower](x)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mamba(h, layer, m, lower):
    B, T, _ = h.shape
    n, r, eps = m["d_state"], m["dt_rank"], m["norm_eps"]
    xz = _mm(h, _w(layer["in_proj"]), lower)
    di = xz.shape[-1] // 2
    u, z = xz[..., :di], xz[..., di:]
    taps = _w(layer["conv_w"])  # [taps, d_inner]
    k = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((B, k - 1, di), jnp.float32), u], axis=1)
    conv = sum(padded[:, j:j + T] * taps[j] for j in range(k))
    if m["conv_bias"] and lower != "nobias":
        conv = conv + _w(layer["conv_b"])
    u = jax.nn.silu(conv)
    dbc = _mm(u, _w(layer["x_proj"]), lower)
    dt, b, c = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
    if lower != "nonorm":
        dt, b, c = (_rms(x, _w(layer[name]), eps) for x, name in ((dt, "dt_norm"), (b, "b_norm"), (c, "c_norm")))
    delta = jax.nn.softplus(_mm(dt, _w(layer["dt_proj"]), lower) + _w(layer["dt_bias"]))
    a = -jnp.exp(_w(layer["A_log"])).T  # [d_inner, d_state], the published order

    def token(state, xs):
        delta_t, u_t, b_t, c_t = xs  # [B, d_inner], [B, d_inner], [B, n], [B, n]
        state = jnp.exp(delta_t[:, :, None] * a) * state + (delta_t * u_t)[:, :, None] * b_t[:, None, :]
        if lower == "h_bf16":
            state = _round_bf16(state)
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((B, di, n), jnp.float32),
                        tuple(jnp.swapaxes(x, 0, 1) for x in (delta, u, b, c)))
    y = jnp.swapaxes(y, 0, 1) + _w(layer["D"]) * u
    return _mm(y * jax.nn.silu(z), _w(layer["out_proj"]), lower)


def _attention(h, layer, m, lower):
    B, T, _ = h.shape
    H, Hkv, d = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = _mm(h, _w(layer["wq"]), lower).reshape(B, T, H, d)
    k = _mm(h, _w(layer["wk"]), lower).reshape(B, T, Hkv, d)
    v = _mm(h, _w(layer["wv"]), lower).reshape(B, T, Hkv, d)
    k, v = jnp.repeat(k, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2)
    if lower in ROUND:
        k, v = ROUND[lower](k), ROUND[lower](v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision=HI)
    return _mm(a.reshape(B, T, H * d), _w(layer["wo"]), lower)


@partial(jax.jit, static_argnames=("model", "lower"))
def _layer(x, layer, *, model, lower):
    m = dict(model)
    h = _rms(x, _w(layer["ln1"]), m["norm_eps"])
    x = x + (_mamba(h, layer, m, lower) if "in_proj" in layer else _attention(h, layer, m, lower))
    h = _rms(x, _w(layer["ln2"]), m["norm_eps"])
    y = jax.nn.silu(_mm(h, _w(layer["w1"]), lower)) * _mm(h, _w(layer["w3"]), lower)
    return x + _mm(y, _w(layer["w2"]), lower)


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, head, *, eps, lower):
    x = _rms(x, _w(norm), eps)
    V = head.shape[-1]
    step = -(-V // HEAD_CHUNKS)
    return jnp.concatenate([_mm(x, _w(head[:, i: i + step]), lower) for i in range(0, V, step)], axis=-1)


def layers_in_order(params: dict, layer_types):
    """The layer dicts one by one in the model's order: each layer's mixer
    from the stack of its kind and its SwiGLU from `ff`."""
    row = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    seen = {"full_attention": 0, "mamba": 0}
    for i, kind in enumerate(layer_types):
        mixer = row(params["attn" if kind == "full_attention" else "mamba"], seen[kind])
        seen[kind] += 1
        yield {**mixer, **row(params["ff"], i)}


def logits(params: dict, model: dict, tokens, rows, lower: str | None = None):
    """Float32 logits [B, R, V] of `tokens` [B, T] at positions `rows`
    [B, R]. `model` holds the configuration file's sizes."""
    if lower is not None and lower not in CONTROLS:
        raise ValueError(f"the jamba reference has no control {lower!r}")
    bad = set(model["layer_types"]) - {"mamba", "full_attention"}
    if bad:
        raise ValueError(f"unknown layer types {sorted(bad)}")
    static = tuple(sorted((k, v) for k, v in model.items() if k != "layer_types"))
    tokens, rows = jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    out = []
    for at in range(0, tokens.shape[0], ROW_BLOCK):
        x = params["embed"][tokens[at: at + ROW_BLOCK]].astype(jnp.float32)
        for layer in layers_in_order(params, model["layer_types"]):
            x = _layer(x, layer, model=static, lower=lower)
        picked = x[jnp.arange(x.shape[0])[:, None], rows[at: at + ROW_BLOCK]]
        out.append(_head(picked, params["norm"], head, eps=model["norm_eps"], lower=lower))
    return jnp.concatenate(out, axis=0)
