"""The plain reference: an LFM2-MoE decoder's forward pass in `jax.numpy`,
float32, matmuls at `highest` precision, no cache, no state carried, no
kernel, no sort. Written from the published description
(https://huggingface.co/LiquidAI/LFM2-24B-A2B, `model_type: lfm2_moe`),
not from the program, of which it imports nothing.

Every layer: `h = x + Op(RMSNorm(x))`, `y = h + FF(RMSNorm(h))`.

- conv layer: `[B, C, u] = split3(x W_in)`, `s = B * u`,
  `c_t = sum_j w[:, j] * s_{t-(taps-1)+j}` (depthwise, causal, `s` zero
  before the sequence), `Op = (C * c) W_out`. No bias, no activation.
- attention layer: q, k, v without bias; RMSNorm with a weight over each
  head of q and of k; rotary over the whole head (half-rotation pairing);
  causal softmax at `head_dim**-0.5`; output projection.
- dense FF (the first `num_dense_layers` layers): SwiGLU.
- expert FF: `s = sigmoid(x W_g)` over all experts; the chosen are the top
  k of `s + b` (`b` enters the choice only); the weights are `s` at the
  chosen over their sum plus 1e-6 (`norm_topk_prob`), times the routed
  scaling factor; `FF = sum_k w_k W2_e(silu(W1_e x) * W3_e x)` over the
  chosen experts that are HELD (`model["held"]`, global ids in the order
  of the weights' leading axis): what absent experts would add is left
  out, as in the program, and that partial sum goes on.

The weights come in the layout they are served in (`lfm2_weights.py`):
`pro` a tuple of whole layer dicts (the leading dense layers), then the
expert layers' weights stacked by what they are: `attn` and `conv` (the
operators, each over its own layers in order) and `ff` (norm, router,
bias and held experts over all expert layers). `model["layer_types"]`, the
source's list, says which operator each layer takes. A layer's kind is
read off its keys (`conv_in` | `wq`; `router` | not).

`lower` names a control: "int8" rounds the input of every matmul (and K
and V) to int8 per row; "bf16" rounds the same to bfloat16, the precision
the configuration states (it must pass: it shows how far rounding alone,
routing decisions that flip on it included, moves the logits); "nobias" leaves the selection bias out; "nonorm"
leaves the chosen scores unnormalised; "capacity" gives each expert room
for `ceil(N k / 2E)` tokens, half its even share, and drops what overflows,
in token order.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HEAD_CHUNKS = 8
CONTROLS = ("int8", "bf16", "nobias", "nonorm", "capacity")
HI = jax.lax.Precision.HIGHEST


def _w(leaf):
    return leaf.astype(jnp.float32)


def _round_int8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-8)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _round_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


ROUND = {"int8": _round_int8, "bf16": _round_bf16}


def _mm(x, w, lower):
    if lower in ROUND:
        x = ROUND[lower](x)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, T, H, d]; HF's rotate_half convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _conv(h, layer, m, lower):
    B, T, D = h.shape
    bcu = _mm(h, _w(layer["conv_in"]), lower)
    b, c, u = bcu[..., :D], bcu[..., D:2 * D], bcu[..., 2 * D:]
    s = b * u
    taps = _w(layer["conv_w"])  # [D, taps]
    n = taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((B, n - 1, D), jnp.float32), s], axis=1)
    conv = sum(padded[:, j:j + T] * taps[:, j] for j in range(n))
    return _mm(c * conv, _w(layer["conv_out"]), lower)


def _attention(h, layer, m, lower):
    B, T, _ = h.shape
    H, Hkv, d = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = _mm(h, _w(layer["wq"]), lower).reshape(B, T, H, d)
    k = _mm(h, _w(layer["wk"]), lower).reshape(B, T, Hkv, d)
    v = _mm(h, _w(layer["wv"]), lower).reshape(B, T, Hkv, d)
    q = _rope(_rms(q, _w(layer["q_norm"]), m["norm_eps"]), m["rope_theta"])
    k = _rope(_rms(k, _w(layer["k_norm"]), m["norm_eps"]), m["rope_theta"])
    k, v = jnp.repeat(k, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2)
    if lower in ROUND:
        k, v = ROUND[lower](k), ROUND[lower](v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision=HI)
    return _mm(a.reshape(B, T, H * d), _w(layer["wo"]), lower)


def _experts(h, layer, m, lower):
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    N, k, held = B * T, m["experts_per_token"], m["held"]
    s = jax.nn.sigmoid(_mm(x, _w(layer["router"]), lower))  # [N, E]
    E = s.shape[-1]
    biased = s + _w(layer["router_bias"]) if m["use_expert_bias"] and lower != "nobias" else s
    _, chosen = jax.lax.top_k(biased, k)  # [N, k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m["norm_topk_prob"] and lower != "nonorm":
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * m["routed_scaling_factor"]
    out = jnp.zeros((N, D), jnp.float32)
    room = -(-N * k // (2 * E))
    for i, e in enumerate(held):
        takes = chosen == e  # [N, k]
        weight = jnp.sum(jnp.where(takes, w, 0.0), axis=-1)  # [N]
        if lower == "capacity":
            mine = jnp.any(takes, axis=-1)
            weight = jnp.where(jnp.cumsum(mine) <= room, weight, 0.0)
        y = jax.nn.silu(_mm(x, _w(layer["w1"][i]), lower)) * _mm(x, _w(layer["w3"][i]), lower)
        out = out + weight[:, None] * _mm(y, _w(layer["w2"][i]), lower)
    return out.reshape(B, T, D), chosen.reshape(B, T, k)


@partial(jax.jit, static_argnames=("model", "lower"))
def _layer(x, layer, *, model, lower):
    m = dict(model)
    h = _rms(x, _w(layer["ln1"]), m["norm_eps"])
    x = x + (_conv(h, layer, m, lower) if "conv_in" in layer else _attention(h, layer, m, lower))
    h = _rms(x, _w(layer["ln2"]), m["norm_eps"])
    if "router" in layer:
        y, chosen = _experts(h, layer, m, lower)
        return x + y, chosen
    y = jax.nn.silu(_mm(h, _w(layer["w1"]), lower)) * _mm(h, _w(layer["w3"]), lower)
    return x + _mm(y, _w(layer["w2"]), lower), None


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, head, *, eps, lower):
    x = _rms(x, _w(norm), eps)
    V = head.shape[-1]
    step = -(-V // HEAD_CHUNKS)
    return jnp.concatenate([_mm(x, _w(head[:, i: i + step]), lower) for i in range(0, V, step)], axis=-1)


def layers_in_order(params: dict, layer_types, num_dense_layers: int):
    """The layer dicts one by one in the model's order: the leading dense
    layers whole (`pro`), then each expert layer's operator from the stack of
    its kind and its FF from `ff`."""
    yield from params["pro"]
    row = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    seen = {"full_attention": 0, "conv": 0}
    for i, kind in enumerate(layer_types[num_dense_layers:]):
        op = row(params["attn" if kind == "full_attention" else "conv"], seen[kind])
        seen[kind] += 1
        yield {**op, **row(params["ff"], i)}


def _stack(params: dict, model: dict, tokens, lower):
    """-> (the stream after the last layer [B, T, D], every expert layer's
    choice of experts [expert layers, B, T, k])."""
    if lower is not None and lower not in CONTROLS:
        raise ValueError(f"the lfm2 reference has no control {lower!r}")
    pattern = {"layer_types", "num_dense_layers"}
    static = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v) for k, v in model.items()
                          if k not in pattern))
    x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    route = []
    for layer in layers_in_order(params, model["layer_types"], model["num_dense_layers"]):
        x, chosen = _layer(x, layer, model=static, lower=lower)
        if chosen is not None:
            route.append(chosen)
    k = model["experts_per_token"]
    return x, jnp.stack(route).astype(jnp.int32) if route else jnp.zeros((0,) + x.shape[:2] + (k,), jnp.int32)


def route(params: dict, model: dict, tokens):
    """[expert layers, B, T, k] int32: the experts this reference chooses for
    every token of `tokens` [B, T] in every expert layer (causal: a token's
    choice hangs on nothing after it). The family's cache check hands it to
    the program where it teacher-forces the routing."""
    return _stack(params, model, tokens, None)[1]


def logits(params: dict, model: dict, tokens, rows, lower: str | None = None):
    """Float32 logits [B, R, V] of `tokens` [B, T] at positions `rows`
    [B, R]. `model` holds the configuration file's sizes and `held`."""
    x, _ = _stack(params, model, tokens, lower)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    rows = jnp.asarray(rows, jnp.int32)
    picked = x[jnp.arange(x.shape[0])[:, None], rows]
    return _head(picked, params["norm"], head, eps=model["norm_eps"], lower=lower)
