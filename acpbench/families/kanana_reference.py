"""The plain reference: a Kanana-2 (`model_type: deepseek_v3`) decoder's
forward pass in `jax.numpy`, float32, matmuls at `highest` precision, whole
sequences, in the **published, expanded** form: per-head K and V made for
every token, no cache, no kernel, no absorption, no sort. Written from the
published configuration
(https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601,
`config.json`) and the equations ISSUE 44 derives from it, not from the
program, of which it imports nothing.

Every layer: `h = x + Attn(RMSNorm(x))`, `y = h + FFN(RMSNorm(h))`.

- `Attn`: `q = W_q u` -> heads of 128 + 64 (`q_nope`, `q_pe`); `[c ; k_pe] =
  W_kva u` -> 512 + 64; `c <- RMSNorm_kv(c)`; rotary over `q_pe` of every
  head and over the ONE `k_pe` all heads share; `k_nope_h = W_UK_h c`, `v_h =
  W_UV_h c` for every token and head; `k_h = [k_nope_h ; k_pe]`; scores
  `q_h . k_h / sqrt(192)` under a causal mask computed a block of rows at a
  time; softmax; `W_o` over the heads' 128 values.
- rotary: pair `k` of the 64 rope values turns by `pos * theta**(-2k/64)`,
  and the source pairs NEIGHBOURS `(2k, 2k + 1)` (`rope_interleave`). The
  weights come as the program serves them, with the rope columns of `wq`
  and `wkva` de-interleaved (`_as_published` puts the values back in the
  source's order before this file rotates neighbours).
- `FFN` of the first `first_dense` layers: SwiGLU. Of the others: `s =
  sigmoid(u W_r)` over all experts in float32; the chosen are the top k of
  `s + b` (`e_score_correction_bias`, in the choice only; one group); the
  weights are `s` at the chosen over their sum (plus 1e-20) times
  `routed_scaling_factor`; the sum of `w_k W2_e(silu(W1_e u) * W3_e u)` over
  the chosen experts that are HELD (`model["held"]`: a loop over the held
  experts; what absent experts would add is left out, as in the program),
  PLUS the shared expert `S(u)`, a SwiGLU every token passes through.

The weights come in the layout they are served in (`kanana_weights.py`):
`pro` the leading dense layers, `attn` the expert layers' attention stacked,
`ff` their norm, router, bias, held experts and shared expert stacked. The
source's projections come as the parts the program keeps them in, which
this file puts together again (`_published`): `q_proj` as `wq_nope` [H x
128, D] and `wq_pe` [H x 64, D] (outputs first), `kv_a_proj_with_mqa` as
`wkv_c` [D, 512] and `wk_pe` [D, 64], `kv_b_proj` as `wuk` [H, 128, 512] and
`wuv` [H, 512, 128].

`lower` names a control. Precisions: "int8_matmul_inputs" rounds the input
of every matmul (and K and V) to int8 per row, the precision below the
configuration's; "bf16" rounds the same to bfloat16, the precision it states
(it must pass); "bf16_rest" rounds besides every tensor the program keeps at
rest in bfloat16. Faults: "scale_128" (scores over `sqrt(128)`), "rope_all"
(rotary over all 192 of a key's and a query's values), "kv_norm_off" (the
latent not normed), "k_pe_unroped" (the shared key as a row stored before
rotary would hold it), "shared_off", "route_scale_off" (2.448 -> 1),
"bias_off" (the selection bias left out of the choice).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HEAD_CHUNKS = 8
ROW_BLOCK = 512  # rows of the mask computed at a time
SEQUENCES = 1  # sequences a call of a layer takes
PRECISIONS = ("int8_matmul_inputs", "bf16", "bf16_rest")
FAULTS = ("scale_128", "rope_all", "kv_norm_off", "k_pe_unroped", "shared_off", "route_scale_off", "bias_off")
CONTROLS = PRECISIONS + FAULTS
HI = jax.lax.Precision.HIGHEST


def _w(leaf):
    return leaf.astype(jnp.float32)


def _round_int8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-8)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _round_bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


ROUND = {"int8_matmul_inputs": _round_int8, "bf16": _round_bf16, "bf16_rest": _round_bf16}


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


def _as_published(x):
    """Rope values in the source's order: the served layout keeps the even
    ones in the first half and the odd ones in the second."""
    half = x.shape[-1] // 2
    return jnp.stack([x[..., :half], x[..., half:]], axis=-1).reshape(x.shape)


def _published(layer, H: int, nope: int, rope: int, dv: int) -> dict:
    """The source's three projections, inputs first, from the parts they are
    served in (the rope columns stay in the served order: `_as_published`
    reorders the values they give)."""
    D, r = layer["wkv_c"].shape
    q = jnp.concatenate([_w(layer["wq_nope"]).T.reshape(D, H, nope), _w(layer["wq_pe"]).T.reshape(D, H, rope)], axis=-1)
    kvb = jnp.concatenate([jnp.transpose(_w(layer["wuk"]), (2, 0, 1)), jnp.transpose(_w(layer["wuv"]), (1, 0, 2))],
                          axis=-1)  # [512, H, 128 + 128]
    return {"q_proj": q.reshape(D, H * (nope + rope)),
            "kv_a_proj": jnp.concatenate([_w(layer["wkv_c"]), _w(layer["wk_pe"])], axis=-1),
            "kv_b_proj": kvb.reshape(r, H * (nope + dv))}


def _rope(x, theta):
    """x [B, T, H, d], positions 0..T-1; NEIGHBOURS (2k, 2k + 1) are a pair."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _attention(h, layer, m, lower):
    B, T, _ = h.shape
    H, r, nope, rope, dv = m["n_heads"], m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    w = _published(layer, H, nope, rope, dv)
    q = _mm(h, w["q_proj"], lower).reshape(B, T, H, nope + rope)
    q_nope, q_pe = q[..., :nope], _as_published(q[..., nope:])
    down = _mm(h, w["kv_a_proj"], lower)
    c, k_pe = down[..., :r], _as_published(down[..., None, r:])  # [B, T, 512], [B, T, 1, 64]
    if lower != "kv_norm_off":
        c = _rms(c, _w(layer["kv_norm"]), m["norm_eps"], lower)
    kv = _mm(c, w["kv_b_proj"], lower).reshape(B, T, H, nope + dv)  # [k_nope_h ; v_h] a head
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if lower == "rope_all":
        k = _rope(jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, T, H, rope))], axis=-1), m["rope_theta"])
        q = _rope(jnp.concatenate([q_nope, q_pe], axis=-1), m["rope_theta"])
    else:
        if lower != "k_pe_unroped":
            k_pe = _rope(k_pe, m["rope_theta"])
        k = jnp.concatenate([k_nope, jnp.broadcast_to(_rest(k_pe, lower), (B, T, H, rope))], axis=-1)
        q = jnp.concatenate([q_nope, _rest(_rope(q_pe, m["rope_theta"]), lower)], axis=-1)
    if lower in ROUND:
        k, v = ROUND[lower](k), ROUND[lower](v)
    scale = (nope if lower == "scale_128" else nope + rope) ** -0.5
    # the dense [T, T] mask a block of rows at a time (one compiled block,
    # mapped over the blocks; rows past T are padding and are cut off)
    blocks = -(-T // ROW_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, blocks * ROW_BLOCK - T), (0, 0), (0, 0)))
    q = jnp.moveaxis(q.reshape(B, blocks, ROW_BLOCK, H, nope + rope), 1, 0)
    j = jnp.arange(T)[None, :]

    def rows(block):
        qb, r0 = block
        i = jnp.minimum(r0 + jnp.arange(ROW_BLOCK), T - 1)[:, None]  # a padding row repeats the last
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HI) * scale
        s = jnp.where((j <= i)[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision=HI)

    a = jax.lax.map(rows, (q, jnp.arange(blocks) * ROW_BLOCK))
    a = jnp.moveaxis(a, 0, 1).reshape(B, blocks * ROW_BLOCK, H * dv)[:, :T]
    return _mm(a, _w(layer["wo"]), lower)


def _swiglu(x, w1, w3, w2, lower):
    return _mm(jax.nn.silu(_mm(x, _w(w1), lower)) * _mm(x, _w(w3), lower), _w(w2), lower)


def _experts(h, layer, m, lower):
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    k, held = m["experts_per_token"], m["held"]
    s = jax.nn.sigmoid(_mm(x, _w(layer["router"]), lower, rest=False))  # [N, E]; the router is float32
    _, chosen = jax.lax.top_k(s if lower == "bias_off" else s + layer["router_bias"].astype(jnp.float32), k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
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


@partial(jax.jit, static_argnames=("model", "dense", "lower"))
def _layer(x, layer, *, model, dense, lower):
    m = dict(model)
    x = _rest(x + _attention(_rms(x, _w(layer["ln1"]), m["norm_eps"], lower), layer, m, lower), lower)
    u = _rms(x, _w(layer["ln2"]), m["norm_eps"], lower)
    if dense:
        return _rest(x + _rest(_swiglu(u, layer["w1"], layer["w3"], layer["w2"], lower), lower), lower), None
    y, chosen = _experts(u, layer, m, lower)
    return _rest(x + y, lower), chosen


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, head, *, eps, lower):
    x = _rms(x, _w(norm), eps, lower)
    V = head.shape[-1]
    step = -(-V // HEAD_CHUNKS)
    return jnp.concatenate([_mm(x, _w(head[:, i: i + step]), lower) for i in range(0, V, step)], axis=-1)


def layers_in_order(params: dict):
    """(layer dict, is it a dense layer) one by one in the model's order."""
    row = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    for layer in params["pro"]:
        yield layer, True
    for i in range(params["ff"]["ln2"].shape[0]):
        yield {**row(params["attn"], i), **row(params["ff"], i)}, False


_LAST: dict = {}  # the newest pass: a route and the logits of the same tokens are one pass


def _stack(params: dict, model: dict, tokens, lower):
    """-> (the stream after the last layer [B, T, D], every expert layer's
    choice of experts [expert layers, B, T, k]), a sequence at a time."""
    if lower is not None and lower not in CONTROLS:
        raise ValueError(f"the kanana reference has no control {lower!r}; it has {', '.join(CONTROLS)}")
    static = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v) for k, v in model.items()))
    tokens = jnp.asarray(tokens, jnp.int32)
    key = (id(params["embed"]), static, lower, tokens.shape, bytes(memoryview(jax.device_get(tokens))))
    if _LAST.get("key") == key:
        return _LAST["out"]
    xs, routes = [], []
    for b in range(0, tokens.shape[0], SEQUENCES):
        x = params["embed"][tokens[b:b + SEQUENCES]].astype(jnp.float32)
        route = []
        for layer, dense in layers_in_order(params):
            x, chosen = _layer(x, layer, model=static, dense=dense, lower=lower)
            if chosen is not None:
                route.append(chosen)
        xs.append(x)
        routes.append(jnp.stack(route).astype(jnp.int32))
    out = jnp.concatenate(xs, axis=0), jnp.concatenate(routes, axis=1)
    _LAST.update(key=key, out=out)
    return out


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
    rows = jnp.asarray(rows, jnp.int32)
    picked = x[jnp.arange(x.shape[0])[:, None], rows]
    return _head(picked, params["norm"], params["lm_head"], eps=model["norm_eps"], lower=lower)


def layer_output(params: dict, model: dict, layer_index: int, x, shared: bool = True):
    """Expert layer `layer_index`'s FF (of the expert layers) over `x` [B, T,
    D] float32 (its input already normed): what a test adds up over the
    shares of the experts, the shared expert counted once."""
    layer = [layer for layer, dense in layers_in_order(params) if not dense][layer_index]
    return _experts(x, layer, model, None if shared else "shared_off")[0]
