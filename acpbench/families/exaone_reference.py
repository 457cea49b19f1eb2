"""The plain reference: a K-EXAONE decoder's forward pass and its
multi-token-prediction module in `jax.numpy`, float32, matmuls at `highest`
precision, whole sequences, no cache, no ring, no kernel, no sort, no draft.
Written from the published configuration
(https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B, `config.json`) and
the equations ISSUE 51 derives from it, not from the program, of which it
imports nothing.

Every layer: `h = x + Attn(RMSNorm(x))`, `y = h + FF(RMSNorm(h))`.

- `Attn`: q, k, v without bias; RMSNorm with a weight over each head of q
  and of k; a `sliding_attention` layer turns q and k by rotary over
  half-split pairs (pair `k` by `theta**(-2k/d)`) and token `i` sees `i -
  window < j <= i`; a `full_attention` layer turns nothing and sees `j <=
  i`; scores at `head_dim**-0.5`, softmax, output projection.
- `FF` of the leading dense layers: `W2(silu(W1 x) * W3 x)`. Of the others:
  `s = sigmoid(x W_r)` over all experts in float32; the chosen are the top k
  of `s + b`; the weights are `s` at the chosen over their sum
  (`norm_topk_prob`) times `routed_scaling_factor`; the sum over the chosen
  experts that are HELD (`model["held"]`), a loop over the held experts;
  plus the shared expert over every token.
- the MTP module: `u_i = W_eh [RMSNorm_h(x_i) ; RMSNorm_e(Emb(t_{i+1}))]`
  with `x_i` the stack's output before its last norm, one block with full
  attention and a dense FF over the `u`, `RMSNorm_m`, the stack's head: row
  `i` is a distribution over `t_{i+2}` (`mtp_logits`).

The weights come in the layout they are served in (`exaone_weights.py`):
`pro` the dense layers whole, `win` and `full` the sparse layers' attention
stacked by kind, `ff` their FF, `mtp` the module.

`lower` names a control: "int8" rounds the input of every matmul (and K and
V) to int8 per row, the precision below the configuration's; "bf16" rounds
the same to bfloat16, the precision it states (it must pass); "bf16_rest"
rounds besides every tensor the program keeps at rest in bfloat16;
"rope_on_full" turns the full layers too; "window_off" lets the window
layers see the whole context; "nonorm" leaves the chosen scores
unnormalised; "bias_off" leaves the selection bias out of the choice;
"route_scale_off" takes `routed_scaling_factor` as 1; "shared_off" leaves
the shared expert out; "mtp_prev_hidden_off" feeds the MTP block zeros in
the hidden state's place (the draft from the next token's embedding alone).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HEAD_CHUNKS = 8
ROW_BLOCK = 512  # rows of the mask computed at a time
SEQUENCES = 2  # sequences a call of a layer takes
CONTROLS = ("int8", "bf16", "bf16_rest", "rope_on_full", "window_off", "nonorm", "bias_off", "route_scale_off",
            "shared_off", "mtp_prev_hidden_off")
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


def _rope(x, theta):
    """x [B, T, H, d], positions 0..T-1; half-split pairs."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(h, layer, m, full, lower):
    B, T, _ = h.shape
    H, Hkv, d = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = _rms(_mm(h, _w(layer["wq"]), lower).reshape(B, T, H, d), _w(layer["q_norm"]), m["norm_eps"], lower)
    k = _rms(_mm(h, _w(layer["wk"]), lower).reshape(B, T, Hkv, d), _w(layer["k_norm"]), m["norm_eps"], lower)
    v = _mm(h, _w(layer["wv"]), lower).reshape(B, T, Hkv, d)
    if not full or lower == "rope_on_full":
        q, k = _rest(_rope(q, m["rope_theta"]), lower), _rest(_rope(k, m["rope_theta"]), lower)
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


def _swiglu(x, w1, w3, w2, lower):
    return _mm(jax.nn.silu(_mm(x, _w(w1), lower)) * _mm(x, _w(w3), lower), _w(w2), lower)


def _experts(h, layer, m, lower):
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    k, held = m["experts_per_token"], m["held"]
    s = jax.nn.sigmoid(_mm(x, _w(layer["router"]), lower, rest=False))  # [N, E]; the router is float32
    biased = s if lower == "bias_off" else s + _w(layer["router_bias"])
    _, chosen = jax.lax.top_k(biased, k)  # [N, k]: the bias in the choice only
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m["norm_topk_prob"] and lower != "nonorm":
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if lower != "route_scale_off":
        w = w * m["routed_scaling_factor"]

    def one(out, expert):  # the held experts one after another: a loop, compiled once
        w1, w3, w2, e = expert
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # [N]
        return out + weight[:, None] * _swiglu(x, w1, w3, w2, lower), None

    out, _ = jax.lax.scan(one, jnp.zeros((B * T, D), jnp.float32),
                          (layer["w1"], layer["w3"], layer["w2"], jnp.asarray(held, jnp.int32)))
    if lower != "shared_off":
        out = out + _swiglu(x, layer["sw1"], layer["sw3"], layer["sw2"], lower)
    return _rest(out, lower).reshape(B, T, D), chosen.reshape(B, T, k)


@partial(jax.jit, static_argnames=("model", "full", "sparse", "lower"))
def _layer(x, layer, *, model, full, sparse, lower):
    m = dict(model)
    x = _rest(x + _attention(_rms(x, _w(layer["ln1"]), m["norm_eps"], lower), layer, m, full, lower), lower)
    h = _rms(x, _w(layer["ln2"]), m["norm_eps"], lower)
    if sparse:
        y, chosen = _experts(h, layer, m, lower)
    else:
        y, chosen = _swiglu(h, layer["w1"], layer["w3"], layer["w2"], lower), None
    return _rest(x + y, lower), chosen


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, head, *, eps, lower):
    x = _rms(x, _w(norm), eps, lower)
    V = head.shape[-1]
    step = -(-V // HEAD_CHUNKS)
    return jnp.concatenate([_mm(x, _w(head[:, i: i + step]), lower) for i in range(0, V, step)], axis=-1)


@partial(jax.jit, static_argnames=("eps", "lower"))
def _mtp_in(x, e, mtp, *, eps, lower):
    if lower == "mtp_prev_hidden_off":
        x = jnp.zeros_like(x)
    u = jnp.concatenate([_rms(x, _w(mtp["hnorm"]), eps, lower), _rms(e, _w(mtp["enorm"]), eps, lower)], axis=-1)
    return _mm(u, _w(mtp["eh_proj"]), lower)


def layers_in_order(params: dict, layer_types):
    """(layer dict, is it a full layer, is its FF sparse) one by one in the
    model's order: a dense layer whole from `pro`; a sparse layer's
    attention from the stack of its kind, its FF from `ff`."""
    row = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    dense = len(params["pro"])
    seen = {"full_attention": 0, "sliding_attention": 0}
    for i, kind in enumerate(layer_types):
        full = kind == "full_attention"
        if i < dense:
            yield params["pro"][i], full, False
            continue
        op = row(params["full" if full else "win"], seen[kind])
        seen[kind] += 1
        yield {**op, **row(params["ff"], i - dense)}, full, True


def _static(model: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v) for k, v in model.items()
                        if k != "layer_types"))


def _stack(params: dict, model: dict, tokens, lower):
    """-> (the stream after the last layer [B, T, D], every sparse layer's
    choice of experts [sparse layers, B, T, k]), a few sequences at a time."""
    if lower is not None and lower not in CONTROLS:
        raise ValueError(f"the exaone reference has no control {lower!r}; it has {', '.join(CONTROLS)}")
    static = _static(model)
    tokens = jnp.asarray(tokens, jnp.int32)
    xs, routes = [], []
    for b in range(0, tokens.shape[0], SEQUENCES):
        x = params["embed"][tokens[b:b + SEQUENCES]].astype(jnp.float32)
        route = []
        for layer, full, sparse in layers_in_order(params, model["layer_types"]):
            x, chosen = _layer(x, layer, model=static, full=full, sparse=sparse, lower=lower)
            if sparse:
                route.append(chosen)
        xs.append(x)
        routes.append(jnp.stack(route).astype(jnp.int32))
    return jnp.concatenate(xs, axis=0), jnp.concatenate(routes, axis=1)


def route(params: dict, model: dict, tokens):
    """[sparse layers, B, T, k] int32: the experts this reference chooses
    for every token of `tokens` [B, T] in every sparse layer (causal). The
    family's cache check hands it to the program where it teacher-forces the
    routing."""
    return _stack(params, model, tokens, None)[1]


def _pick(x, rows):
    rows = jnp.asarray(rows, jnp.int32)
    return x[jnp.arange(x.shape[0])[:, None], rows]


def logits(params: dict, model: dict, tokens, rows, lower: str | None = None):
    """Float32 logits [B, R, V] of `tokens` [B, T] at positions `rows`
    [B, R]. `model` holds the configuration file's sizes and `held`."""
    x, _ = _stack(params, model, tokens, lower)
    return _head(_pick(x, rows), params["norm"], params["lm_head"], eps=model["norm_eps"], lower=lower)


def mtp_logits(params: dict, model: dict, tokens, rows, lower: str | None = None):
    """Float32 drafted logits [B, R, V] at positions `rows` [B, R] (each
    below T - 1): row `i` from the stack's output at `i` and token `i + 1`,
    a distribution over token `i + 2`."""
    x, _ = _stack(params, model, tokens, lower)
    tokens = jnp.asarray(tokens, jnp.int32)
    mtp, eps = params["mtp"], model["norm_eps"]
    static = _static(model)
    out = []
    for b in range(0, tokens.shape[0], SEQUENCES):
        e = params["embed"][tokens[b:b + SEQUENCES, 1:]].astype(jnp.float32)
        u = _mtp_in(x[b:b + SEQUENCES, :-1], e, {k: mtp[k] for k in ("hnorm", "enorm", "eh_proj")}, eps=eps, lower=lower)
        y, _ = _layer(u, mtp["block"], model=static, full=True, sparse=False, lower=lower)
        out.append(y)
    return _head(_pick(jnp.concatenate(out, axis=0), rows), mtp["norm"], params["lm_head"], eps=eps, lower=lower)


def layer_output(params: dict, model: dict, layer_index: int, x):
    """One sparse layer's FF over `x` [B, T, D] float32 (its input already
    normed): what a test adds up over the shares of the experts."""
    layer, _full, sparse = list(layers_in_order(params, model["layer_types"]))[layer_index]
    if not sparse:
        raise ValueError(f"layer {layer_index} is dense")
    return _experts(x, layer, model, None)[0]
