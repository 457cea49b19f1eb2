"""The plain reference: an Ouro (`model_type: ouro`) decoder's forward pass in
`jax.numpy`, float32, matmuls at `highest` precision, whole sequences, no
cache, no kernel, no batching tricks. Written from the published
configuration (https://huggingface.co/ByteDance/Ouro-2.6B, `config.json`:
`total_ut_steps`, `early_exit_threshold`) and the equations ISSUE 46 writes
out from the model's published modeling file, not from the program, of which
it imports nothing (nor of the other families' references).

`x = E[token]`. For loop `t = 0 .. total_ut_steps - 1` and layer `l`, the
SAME weights in every loop:

    x = x + RMSNorm(Attn_l(RMSNorm(x; ln1_l)); ln1_post_l)
    x = x + RMSNorm(MLP_l(RMSNorm(x; ln2_l)); ln2_post_l)

after the last layer of each loop `h_t = RMSNorm(x; norm)`, `g_t = w_g . h_t
+ b_g`, and `x = h_t` goes into the next loop. `Attn_l`: `q, k, v = W_q u,
W_k u, W_v u` as heads of `head_dim`, no bias; rotary over the whole head,
halves `(i, i + d/2)` turned by `pos * theta**(-2i/d)`; causal softmax of `q .
k / sqrt(d)` over this loop's own keys and values; `W_o`. `MLP_l(u) = W_2
(silu(W_1 u) * W_3 u)`. `RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w`.

The exit: `lambda_t = sigmoid(g_t)`; `p_t = lambda_t prod_{j<t} (1 -
lambda_j)`, the last loop taking what mass is left; `c_t = sum_{j<=t} p_j`;
the logits are `W_head h_e` at `e = min{t : c_t >= early_exit_threshold}`,
the last loop if none.

Departures from the published description: none in the arithmetic. The
weights come in the layout they are served in (`ouro_weights.py`): the
layers' matrices stacked, `wq` and `wk` outputs first as the source stores
them (`[L, H d, D]`: `q = W_q u`) and `wv`, `wo`, `w1`, `w3`, `w2` inputs
first (the source's transposed: `v = u W_v`), the four norms a
layer as `ln1`, `ln1_post`, `ln2`, `ln2_post` (the source's
`input_layernorm`, `input_layernorm_2`, `post_attention_layernorm`,
`post_attention_layernorm_2`), the gate as `gate_w` [D] and `gate_b` [1]. A
layer's weights are upcast as the layer is used, so that one layer's float32
copy exists at a time.

`lower` names a control. Precisions: "bf16_rest" rounds the input of every
matmul, and K and V, to bfloat16 and besides every tensor the program keeps
at rest in bfloat16 (the residual stream, the norms' and matmuls' outputs, K
and V): the stated precision, which must pass. "int8_inputs" is the
precision below it as a program would run it: the same rounding at rest, and
the input of every matmul, and K and V, int8 a row. (With the stream left in
float32 the int8 inputs alone read UNDER the stated precision where the
output norms' gains are small: what this model's bfloat16 costs is mostly
the rounding of its residual stream, 384 adds deep; my chip runs, PR 46.) Faults, the first four the
shortcuts a later change would be tempted by: "loops_3" (the last loop left
out: three quarters of the step), "shared_cache" (loops after the first read
the first loop's K and V: a quarter of the cache), "no_loop_norm" (the state
carried un-normed between the loops), "no_post_norms" (the sublayers'
outputs added as they are), "exit_first" (the head reads `h_0`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HEAD_CHUNKS = 8  # the head's [dim, vocab] is multiplied a slice at a time
PRECISIONS = ("int8_inputs", "bf16_rest")
FAULTS = ("loops_3", "shared_cache", "no_loop_norm", "no_post_norms", "exit_first")
CONTROLS = PRECISIONS + FAULTS
HI = jax.lax.Precision.HIGHEST


def _w(leaf):
    return leaf.astype(jnp.float32)


def _round_int8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-8)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _round_bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


ROUND = {"int8_inputs": _round_int8, "bf16_rest": _round_bf16}


def _rest(x, lower):
    """A tensor the program keeps at rest in bfloat16, under either precision control."""
    return _round_bf16(x) if lower in PRECISIONS else x


def _mm(x, w, lower):
    if lower in ROUND:
        x = ROUND[lower](x)
    return _rest(jnp.matmul(x, w, precision=HI), lower)


def _rms(x, w, eps, lower=None):
    return _rest(x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w, lower)


def _rope(x, theta):
    """x [B, T, H, d]: halves (i, i + d/2) turned by pos * theta**(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # [T, d/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@partial(jax.jit, static_argnames=("n_heads", "eps", "theta", "lower"))
def _layer(x, layer, given, *, n_heads, eps, theta, lower):
    """One layer over whole sequences. `given`: K and V to attend over in
    place of the layer's own ("shared_cache"), or None. -> (x, (k, v))."""
    B, T, D = x.shape
    post = (lambda y, w: y) if lower == "no_post_norms" else (lambda y, w: _rms(y, _w(w), eps, lower))
    h = _rms(x, _w(layer["ln1"]), eps, lower)
    q, k, v = _mm(h, _w(layer["wq"]).T, lower), _mm(h, _w(layer["wk"]).T, lower), _mm(h, _w(layer["wv"]), lower)
    d = q.shape[-1] // n_heads
    q = _rope(q.reshape(B, T, n_heads, d), theta)
    k = _rest(_rope(k.reshape(B, T, n_heads, d), theta), lower)
    v = v.reshape(B, T, n_heads, d)
    if lower in ROUND:
        k, v = ROUND[lower](k), ROUND[lower](v)
    own = (k, v)
    if given is not None:
        k, v = given
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / (d ** 0.5)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision=HI)
    x = _rest(x + post(_mm(a.reshape(B, T, n_heads * d), _w(layer["wo"]), lower), layer["ln1_post"]), lower)
    h = _rms(x, _w(layer["ln2"]), eps, lower)
    y = _mm(jax.nn.silu(_mm(h, _w(layer["w1"]), lower)) * _mm(h, _w(layer["w3"]), lower), _w(layer["w2"]), lower)
    return _rest(x + post(y, layer["ln2_post"]), lower), own


@partial(jax.jit, static_argnames=("lower",))
def _head(x, head, *, lower):
    V = head.shape[-1]
    step = -(-V // HEAD_CHUNKS)
    return jnp.concatenate([_mm(x, _w(head[:, i: i + step]), lower) for i in range(0, V, step)], axis=-1)


def exit_choice(gates, threshold: float):
    """`gates` [loops, ...] -> the loop whose state the head reads, a row."""
    n = gates.shape[0]
    lam = jax.nn.sigmoid(gates)
    left = jnp.ones_like(lam[0])  # prod_{j<t} (1 - lambda_j)
    reached = jnp.zeros_like(lam[0])  # c_t
    chosen = jnp.full(lam[0].shape, n - 1, jnp.int32)
    found = jnp.zeros(lam[0].shape, bool)
    for t in range(n):
        reached = reached + (lam[t] * left if t < n - 1 else left)
        hit = (reached >= threshold) & ~found
        chosen, found = jnp.where(hit, t, chosen), found | hit
        left = left * (1.0 - lam[t])
    return chosen


def states(params: dict, model: dict, tokens, rows, lower: str | None = None):
    """-> (every loop's state `h_t` at `rows` [loops, B, R, D], its gates
    [loops, B, R]) of `tokens` [B, T]."""
    if lower is not None and lower not in CONTROLS:
        raise ValueError(f"the ouro reference has no control {lower!r}; it has {', '.join(CONTROLS)}")
    tokens, rows = jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32)
    eps = model["norm_eps"]
    x = _rest(_w(params["embed"][tokens]), lower)
    L = params["layers"]["ln1"].shape[0]
    loops = model["loops"] - (1 if lower == "loops_3" else 0)
    first: dict = {}  # "shared_cache": the first loop's K and V, a layer
    hs, gates = [], []
    for t in range(loops):
        for l in range(L):
            layer = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
            x, own = _layer(x, layer, first.get(l), n_heads=model["n_heads"], eps=eps, theta=model["rope_theta"],
                            lower=lower)
            if lower == "shared_cache" and t == 0:
                first[l] = own
        h = _rms(x, _w(params["norm"]), eps, lower)
        picked = h[jnp.arange(h.shape[0])[:, None], rows]
        hs.append(picked)
        gates.append(jnp.matmul(picked, _w(params["gate_w"]), precision=HI) + _w(params["gate_b"])[0])
        if lower != "no_loop_norm":
            x = h
    return jnp.stack(hs), jnp.stack(gates)


def logits(params: dict, model: dict, tokens, rows, lower: str | None = None):
    """Float32 logits [B, R, V] of `tokens` [B, T] at positions `rows`
    [B, R] (each predicts the token after it). `model` holds the file's
    sizes. Sequences are left-aligned; what lies to the right of a row never
    reaches it (causal)."""
    hs, gates = states(params, model, tokens, rows, lower)
    e = jnp.zeros(gates.shape[1:], jnp.int32) if lower == "exit_first" else exit_choice(gates, model["exit_threshold"])
    chosen = jnp.take_along_axis(hs, e[None, ..., None], axis=0)[0]
    return _head(chosen, params["lm_head"], lower=lower)
