"""The plain reference: a Mellum2 decoder's forward pass in `jax.numpy`,
float32, matmuls at `highest` precision, whole sequences, no cache, no ring,
no kernel, no sort. Written from the published configuration
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, `config.json`)
and the equations ISSUE 40 derives from it, not from the program, of which
it imports nothing.

Every layer: `h = x + Attn(RMSNorm(x))`, `y = h + MoE(RMSNorm(h))`.

- `Attn`: q, k, v without bias; RMSNorm with a weight over each head of q
  and of k; rotary over half-split pairs; scores at `head_dim**-0.5` under
  a dense `[T, T]` mask computed a block of rows at a time: token `i` sees
  `j <= i` in a `full_attention` layer and `i - window < j <= i` in a
  `sliding_attention` layer; softmax; output projection.
- rotary: a window layer turns pair `k` by `theta**(-2k/d)`; a full layer
  by YaRN's frequencies: with `dim_at(r) = d ln(original / (2 pi r)) / (2 ln
  theta)`, `low = floor(dim_at(beta_fast))`, `high = ceil(dim_at(beta_slow))`
  clipped to `[0, d - 1]`, `ramp_k = clip((k - low) / (high - low), 0, 1)`,
  the frequency is `(1 - ramp_k) base_k + ramp_k base_k / factor`, and cos
  and sin are both multiplied by the attention factor.
- `MoE`: `s = softmax(x W_r)` over all experts in float32; the chosen are
  the top k of `s`; the weights are `s` at the chosen over their sum
  (`norm_topk_prob`); the sum of `w_k W2_e(silu(W1_e x) * W3_e x)` over the
  chosen experts that are HELD (`model["held"]`, global ids in the order of
  the weights' leading axis), a loop over the held experts: what absent
  experts would add is left out, as in the program.

The weights come in the layout they are served in (`mellum_weights.py`):
`win` and `full`, the attention weights stacked over the window layers and
over the full layers in order, and `ff` (norm, router and held experts)
over all layers; `model["layer_types"]`, the source's list, says which
stack each layer reads.

`lower` names a control: "int8" rounds the input of every matmul (and K
and V) to int8 per row, the precision below the configuration's; "bf16"
rounds the same to bfloat16, the precision it states (it must pass);
"bf16_rest" rounds besides every tensor the program keeps at rest in
bfloat16 (the residual stream after each sublayer, the outputs of every
matmul but the router's, of the norms and of rotary): what the stated
precision costs a plain forward pass, the floor the program's own reading
is held beside;
"window_off" lets the window layers see the whole context; "one_rope" turns
every layer by the plain frequencies; "nonorm" leaves the chosen scores
unnormalised.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HEAD_CHUNKS = 8
ROW_BLOCK = 512  # rows of the mask computed at a time
SEQUENCES = 2  # sequences a call of a layer takes
CONTROLS = ("int8", "bf16", "bf16_rest", "window_off", "one_rope", "nonorm")
HI = jax.lax.Precision.HIGHEST


def _w(leaf):
    return leaf.astype(jnp.float32)


def _round_int8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-8)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _round_bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


ROUND = {"int8": _round_int8, "bf16": _round_bf16, "bf16_rest": _round_bf16}


def _rest(x, lower):
    """A tensor the program keeps at rest in bfloat16, under "bf16_rest"."""
    return _round_bf16(x) if lower == "bf16_rest" else x


def _mm(x, w, lower, rest=True):
    if lower in ROUND:
        x = ROUND[lower](x)
    y = jnp.matmul(x, w, precision=HI)
    return _rest(y, lower) if rest else y


def _rms(x, w, eps, lower=None):
    return _rest(x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w, lower)


def frequencies(m: dict, full: bool):
    """(inverse frequencies [d/2], the factor on cos and sin) of a layer's kind."""
    d, theta = m["head_dim"], m["rope_theta"]
    base = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    yarn = m["yarn"]
    if not full or yarn is None:
        return base, 1.0
    factor, original, fast, slow, attention_factor = yarn

    def dim_at(r):
        return d * math.log(original / (2.0 * math.pi * r)) / (2.0 * math.log(theta))

    low, high = max(math.floor(dim_at(fast)), 0), min(math.ceil(dim_at(slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return (1.0 - ramp) * base + ramp * base / factor, attention_factor


def _rope(x, inv, scale):
    """x [B, T, H, d], positions 0..T-1; half-split pairs."""
    d = x.shape[-1]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :] * scale, jnp.sin(ang)[None, :, None, :] * scale
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(h, layer, m, full, lower):
    B, T, _ = h.shape
    H, Hkv, d = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = _mm(h, _w(layer["wq"]), lower).reshape(B, T, H, d)
    k = _mm(h, _w(layer["wk"]), lower).reshape(B, T, Hkv, d)
    v = _mm(h, _w(layer["wv"]), lower).reshape(B, T, Hkv, d)
    inv, scale = frequencies(m, full and lower != "one_rope")
    q = _rest(_rope(_rms(q, _w(layer["q_norm"]), m["norm_eps"], lower), inv, scale), lower)
    k = _rest(_rope(_rms(k, _w(layer["k_norm"]), m["norm_eps"], lower), inv, scale), lower)
    if lower in ROUND:
        k, v = ROUND[lower](k), ROUND[lower](v)
    window = 0 if full or lower == "window_off" else m["window"]
    # the dense [T, T] mask a block of rows at a time (one compiled block,
    # mapped over the blocks; rows past T are padding and are cut off)
    blocks = -(-T // ROW_BLOCK)
    q = jnp.pad(q.reshape(B, T, Hkv, H // Hkv, d), ((0, 0), (0, blocks * ROW_BLOCK - T)) + ((0, 0),) * 3)
    q = jnp.moveaxis(q.reshape(B, blocks, ROW_BLOCK, Hkv, H // Hkv, d), 1, 0)
    j = jnp.arange(T)[None, :]

    def rows(block):
        qb, r0 = block
        i = jnp.minimum(r0 + jnp.arange(ROW_BLOCK), T - 1)[:, None]  # a padding row repeats the last
        mask = (j <= i) & ((j > i - window) if window else True)  # [rows, T]
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k, precision=HI) * d ** -0.5
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        return jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, axis=-1), v, precision=HI)

    a = jax.lax.map(rows, (q, jnp.arange(blocks) * ROW_BLOCK))
    a = jnp.moveaxis(a, 0, 1).reshape(B, blocks * ROW_BLOCK, H * d)[:, :T]
    return _mm(a, _w(layer["wo"]), lower)


def _experts(h, layer, m, lower):
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    k, held = m["experts_per_token"], m["held"]
    s = jax.nn.softmax(_mm(x, _w(layer["router"]), lower, rest=False), axis=-1)  # [N, E]; the router is float32
    _, chosen = jax.lax.top_k(s, k)  # [N, k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m["norm_topk_prob"] and lower != "nonorm":
        w = w / jnp.sum(w, axis=-1, keepdims=True)

    def one(out, expert):  # the held experts one after another: a loop, compiled once
        w1, w3, w2, e = expert
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # [N]
        y = jax.nn.silu(_mm(x, _w(w1), lower)) * _mm(x, _w(w3), lower)
        return out + weight[:, None] * _mm(y, _w(w2), lower), None

    out, _ = jax.lax.scan(one, jnp.zeros((B * T, D), jnp.float32),
                          (layer["w1"], layer["w3"], layer["w2"], jnp.asarray(held, jnp.int32)))
    return _rest(out, lower).reshape(B, T, D), chosen.reshape(B, T, k)


@partial(jax.jit, static_argnames=("model", "full", "lower"))
def _layer(x, layer, *, model, full, lower):
    m = dict(model)
    x = _rest(x + _attention(_rms(x, _w(layer["ln1"]), m["norm_eps"], lower), layer, m, full, lower), lower)
    y, chosen = _experts(_rms(x, _w(layer["ln2"]), m["norm_eps"], lower), layer, m, lower)
    return _rest(x + y, lower), chosen


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, head, *, eps, lower):
    x = _rms(x, _w(norm), eps, lower)
    V = head.shape[-1]
    step = -(-V // HEAD_CHUNKS)
    return jnp.concatenate([_mm(x, _w(head[:, i: i + step]), lower) for i in range(0, V, step)], axis=-1)


def layers_in_order(params: dict, layer_types):
    """(layer dict, is it a full layer) one by one in the model's order: the
    attention weights from the stack of the layer's kind, the FF from `ff`."""
    row = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    seen = {"full_attention": 0, "sliding_attention": 0}
    for i, kind in enumerate(layer_types):
        op = row(params["full" if kind == "full_attention" else "win"], seen[kind])
        seen[kind] += 1
        yield {**op, **row(params["ff"], i)}, kind == "full_attention"


def _stack(params: dict, model: dict, tokens, lower):
    """-> (the stream after the last layer [B, T, D], every layer's choice
    of experts [layers, B, T, k]), a few sequences at a time."""
    if lower is not None and lower not in CONTROLS:
        raise ValueError(f"the mellum reference has no control {lower!r}; it has {', '.join(CONTROLS)}")
    static = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v) for k, v in model.items()
                          if k != "layer_types"))
    tokens = jnp.asarray(tokens, jnp.int32)
    xs, routes = [], []
    for b in range(0, tokens.shape[0], SEQUENCES):
        x = params["embed"][tokens[b:b + SEQUENCES]].astype(jnp.float32)
        route = []
        for layer, full in layers_in_order(params, model["layer_types"]):
            x, chosen = _layer(x, layer, model=static, full=full, lower=lower)
            route.append(chosen)
        xs.append(x)
        routes.append(jnp.stack(route).astype(jnp.int32))
    return jnp.concatenate(xs, axis=0), jnp.concatenate(routes, axis=1)


def route(params: dict, model: dict, tokens):
    """[layers, B, T, k] int32: the experts this reference chooses for every
    token of `tokens` [B, T] in every layer (causal: a token's choice hangs
    on nothing after it). The family's cache check hands it to the program
    where it teacher-forces the routing."""
    return _stack(params, model, tokens, None)[1]


def logits(params: dict, model: dict, tokens, rows, lower: str | None = None):
    """Float32 logits [B, R, V] of `tokens` [B, T] at positions `rows`
    [B, R]. `model` holds the configuration file's sizes and `held`."""
    x, _ = _stack(params, model, tokens, lower)
    rows = jnp.asarray(rows, jnp.int32)
    picked = x[jnp.arange(x.shape[0])[:, None], rows]
    return _head(picked, params["norm"], params["lm_head"], eps=model["norm_eps"], lower=lower)


def layer_output(params: dict, model: dict, layer_index: int, x):
    """One layer's expert FF over `x` [B, T, D] float32 (its input already
    normed): what a test adds up over the four shares of the experts."""
    layer, _ = list(layers_in_order(params, model["layer_types"]))[layer_index]
    return _experts(x, layer, model, None)[0]
