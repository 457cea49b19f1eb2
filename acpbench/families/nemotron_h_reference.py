"""The plain reference: a Nemotron-H (`model_type: nemotron_h`; Nemotron 3
Super) decoder's forward pass in `jax.numpy`, float32, matmuls at `highest`
precision, the Mamba-2 recurrence as a `lax.scan` over time ONE TOKEN at a
time (no chunks), full causal attention, the experts a loop over the held
ones, no cache, no state carried, no kernel, no batching of layers. Written
from the published configuration
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
`config.json`) and the equations ISSUE 54 writes down from it, not from the
program, of which it imports nothing.

Every block is `x + Mixer(RMSNorm(x))` with ONE mixer; the layer list is
`hybrid_override_pattern`, a character a block. A last RMSNorm, an untied
head, no position encoding of any kind.

- `M`, Mamba-2 (H heads of P channels, G groups, state N; d_inner = H P):
  `[z (d_inner), xBC (d_inner + 2 G N), dt (H)] = x W_in`; `xBC' = silu(b +
  sum_j w[j] xBC_{t-3+j})` (depthwise, causal, 4 taps, with bias, `xBC` zero
  before the sequence); `xBC' -> x [H, P], B [G, N], C [G, N]`; `dt =
  softplus(dt + dt_bias)`; `A = -exp(A_log)`, one scalar a head; for head h
  in group g = h // (H / G): `S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h]
  x_t[h] (x) B_t[g]` (S is P x N), `y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]`;
  `y = RMSNorm_groups(y * silu(z)) * w`, the norm over each of the G groups
  of d_inner / G channels, gate first; `Mixer = y W_out`.
- `*`, attention: GQA, causal softmax at `head_dim**-0.5`, no rotary, no bias.
- `E`, LatentMoE: `s = sigmoid(x W_r)` over all experts in float32; the chosen
  are the top k of `s + b` (`e_score_correction_bias`, in the choice only;
  one group); the weights are `s` at the chosen over their sum (plus 1e-20)
  times `routed_scaling_factor`; `u = x W_down`; expert e: `W2_e relu(W1_e
  u)^2` (two matrices, not gated); `routed = (sum over the chosen experts
  that are HELD of w_e expert_e(u)) W_up` (`model["held"]`: what absent
  experts would add is left out, as in the program); the shared expert
  `W2_s relu(W1_s x)^2` at full width; `Mixer = routed + shared`.

Departures from the published model: the multi-token-prediction module is
not computed (it is no part of the next-token pass). The weights come in the
layout they are served in (`nemotron_h_weights.py`): `mamba`, `attn`, `moe`,
each stacked over its own layers in order; `conv_w` is `[taps, channels]`,
the transpose of the published order; `W_in`'s columns in the order `z, xBC,
dt`, the configuration file's `assumed`.

`lower` names a control. Precisions: "int8" rounds the input of every matmul
(and K and V) to int8 per row, the precision below the configuration's in
the matmuls; "bf16" rounds the same to bfloat16, the precision it states (it
must pass); "recurrence_bf16" carries the recurrence in bfloat16 (`x`, `B`,
`C`, `dt`, the decay and `S` after every token: the precision below the one
the file states for it). Faults: "decay_quotient" (the Mamba layers in the
chunked form with a chunk's decays taken as `exp(cum_t) / exp(cum_s)`, the
quotient of two exponentials of the running sum, where the program takes
`exp(cum_t - cum_s)`: the divisor underflows inside a chunk of fast heads);
"latent_skip" (the first expert layer's experts read the first
`latent` channels of `x`, the projection `W_down` skipped).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HEAD_CHUNKS = 8
SEQUENCES = 1  # sequences a call of a layer takes
CHUNK = 128  # "decay_quotient" alone: the published chunk_size
PRECISIONS = ("int8", "bf16", "recurrence_bf16")
FAULTS = ("decay_quotient", "latent_skip")
CONTROLS = PRECISIONS + FAULTS
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
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


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _recurrence(dt, x, b, c, a, lower):
    """The equations a token at a time. dt [B, T, H]; x [B, T, H, P]; b, c
    [B, T, H, N] (a head's group's); a [H] -> y [B, T, H, P]."""
    low = _round_bf16 if lower == "recurrence_bf16" else (lambda v: v)

    def token(state, xs):
        dt_t, x_t, b_t, c_t = xs
        decay = low(jnp.exp(dt_t * a))
        state = low(decay[:, :, None, None] * state + low(dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    B, _, H, P = x.shape
    _, y = jax.lax.scan(token, jnp.zeros((B, H, P, b.shape[-1]), jnp.float32),
                        tuple(jnp.swapaxes(low(v), 0, 1) for v in (dt, x, b, c)))
    return jnp.swapaxes(y, 0, 1)


def _quotient_chunks(dt, x, b, c, a):
    """The "decay_quotient" control: the chunked form with every decay of a
    chunk the QUOTIENT `exp(cum_t) / exp(cum_s)` of two exponentials of the
    running sum `cum` of `dt A`."""
    B, T, H, P = x.shape
    N = b.shape[-1]
    pad = -T % CHUNK
    dt, x, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in (dt, x, b, c))
    n = (T + pad) // CHUNK
    dt, x, b, c = (v.reshape((B, n, CHUNK) + v.shape[2:]) for v in (dt, x, b, c))
    up = jnp.exp(jnp.cumsum(dt * a, axis=2))  # [B, n, L, H]: exp(cum_t)
    down = 1.0 / up  # 1 / exp(cum_s): overflows where exp(cum_s) underflows
    xdt = x * dt[..., None]
    causal = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))

    def chunk(state, xs):
        up_, down_, xdt_, b_, c_ = xs  # [B, L, H], .., [B, L, H, P], [B, L, H, N], ..
        cb = jnp.einsum("bthn,bshn->bhts", c_, b_, precision=HI)
        decay = jnp.where(causal, jnp.einsum("bth,bsh->bhts", up_, down_), 0.0)
        y = jnp.einsum("bhts,bshp->bthp", cb * decay, xdt_, precision=HI)
        y = y + jnp.einsum("bthn,bhpn->bthp", c_ * up_[..., None], state, precision=HI)
        carry = up_[:, -1][:, :, None, None] * state + jnp.einsum(
            "bshp,bshn->bhpn", xdt_ * (up_[:, -1:] * down_)[..., None], b_, precision=HI)
        return carry, y

    _, y = jax.lax.scan(chunk, jnp.zeros((B, H, P, N), jnp.float32),
                        tuple(jnp.swapaxes(v, 0, 1) for v in (up, down, xdt, b, c)))
    return jnp.swapaxes(y, 0, 1).reshape(B, T + pad, H, P)[:, :T]


def _mamba(h, layer, m, lower):
    B, T, _ = h.shape
    H, P, G, N = m["mamba_heads"], m["mamba_head_dim"], m["n_groups"], m["d_state"]
    di = H * P
    zxbcdt = _mm(h, _w(layer["in_proj"]), lower)
    z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:di + di + 2 * G * N], zxbcdt[..., di + di + 2 * G * N:]
    taps = _w(layer["conv_w"])  # [taps, channels]
    k = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((B, k - 1, xbc.shape[-1]), jnp.float32), xbc], axis=1)
    xbc = jax.nn.silu(sum(padded[:, j:j + T] * taps[j] for j in range(k)) + _w(layer["conv_b"]))
    x = xbc[..., :di].reshape(B, T, H, P)
    b = jnp.repeat(xbc[..., di:di + G * N].reshape(B, T, G, N), H // G, axis=2)
    c = jnp.repeat(xbc[..., di + G * N:].reshape(B, T, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + _w(layer["dt_bias"]))
    a = -jnp.exp(_w(layer["A_log"]))
    y = _quotient_chunks(dt, x, b, c, a) if lower == "decay_quotient" else _recurrence(dt, x, b, c, a, lower)
    y = (y + _w(layer["D"])[:, None] * x).reshape(B, T, di) * jax.nn.silu(z)
    groups = y.reshape(B, T, G, di // G)
    groups = groups * jax.lax.rsqrt(jnp.mean(groups * groups, axis=-1, keepdims=True) + m["norm_eps"])
    return _mm(groups.reshape(B, T, di) * _w(layer["gate_norm"]), _w(layer["out_proj"]), lower)


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


def _experts(h, layer, m, lower, skip_latent=False, shared=True):
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    k, held = m["experts_per_token"], m["held"]
    s = jax.nn.sigmoid(jnp.matmul(x, _w(layer["router"]), precision=HI))  # [N, E]; the router is float32 at full width
    _, chosen = jax.lax.top_k(s + layer["router_bias"].astype(jnp.float32), k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * m["routed_scaling_factor"]
    latent = layer["down"].shape[-1]
    u = x[:, :latent] if skip_latent else _mm(x, _w(layer["down"]), lower)

    def one(out, expert):  # the held experts one after another: a loop, compiled once
        w1, w2, e = expert
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # [N]
        return out + weight[:, None] * _mm(_relu2(_mm(u, _w(w1), lower)), _w(w2), lower), None

    out, _ = jax.lax.scan(one, jnp.zeros((B * T, latent), jnp.float32),
                          (layer["w1"], layer["w2"], jnp.asarray(held, jnp.int32)))
    out = _mm(out, _w(layer["up"]), lower)
    if shared:
        out = out + _mm(_relu2(_mm(x, _w(layer["sw1"]), lower)), _w(layer["sw2"]), lower)
    return out.reshape(B, T, D), chosen.reshape(B, T, k)


@partial(jax.jit, static_argnames=("model", "kind", "lower", "skip_latent"))
def _layer(x, layer, *, model, kind, lower, skip_latent=False):
    m = dict(model)
    h = _rms(x, _w(layer["ln"]), m["norm_eps"])
    if kind == "mamba":
        return x + _mamba(h, layer, m, lower), None
    if kind == "attn":
        return x + _attention(h, layer, m, lower), None
    y, chosen = _experts(h, layer, m, lower, skip_latent)
    return x + y, chosen


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, head, *, eps, lower):
    x = _rms(x, _w(norm), eps)
    V = head.shape[-1]
    step = -(-V // HEAD_CHUNKS)
    return jnp.concatenate([_mm(x, _w(head[:, i: i + step]), lower) for i in range(0, V, step)], axis=-1)


def layers_in_order(params: dict, pattern: str):
    """(layer dict, kind) one by one in the model's order, each from the
    stack of its kind."""
    row = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    seen = dict.fromkeys(KINDS.values(), 0)
    for ch in pattern:
        kind = KINDS[ch]
        yield row(params[kind], seen[kind]), kind
        seen[kind] += 1


_LAST: dict = {}  # the newest pass: a route and the logits of the same tokens are one pass


def _stack(params: dict, model: dict, tokens, lower):
    """-> (the stream after the last layer [B, T, D], every expert layer's
    choice of experts [expert layers, B, T, k]), a sequence at a time."""
    if lower is not None and lower not in CONTROLS:
        raise ValueError(f"the nemotron_h reference has no control {lower!r}; it has {', '.join(CONTROLS)}")
    static = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v) for k, v in model.items() if k != "pattern"))
    tokens = jnp.asarray(tokens, jnp.int32)
    key = (id(params["embed"]), static, model["pattern"], lower, tokens.shape, bytes(memoryview(jax.device_get(tokens))))
    if _LAST.get("key") == key:
        return _LAST["out"]
    xs, routes = [], []
    for b in range(0, tokens.shape[0], SEQUENCES):
        x = params["embed"][tokens[b:b + SEQUENCES]].astype(jnp.float32)
        route = []
        for layer, kind in layers_in_order(params, model["pattern"]):
            x, chosen = _layer(x, layer, model=static, kind=kind, lower=lower,
                               skip_latent=lower == "latent_skip" and kind == "moe" and not route)
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
    [B, R]. `model` holds the configuration file's sizes, `pattern` and `held`."""
    x, _ = _stack(params, model, tokens, lower)
    rows = jnp.asarray(rows, jnp.int32)
    picked = x[jnp.arange(x.shape[0])[:, None], rows]
    return _head(picked, params["norm"], params["lm_head"], eps=model["norm_eps"], lower=lower)


def layer_output(params: dict, model: dict, layer_index: int, x, shared: bool = True):
    """Expert layer `layer_index`'s mixer (of the expert layers) over `x` [B,
    T, D] float32 (its input already normed): what a test adds up over the
    shares of the experts, the shared expert counted once."""
    layer = [layer for layer, kind in layers_in_order(params, model["pattern"]) if kind == "moe"][layer_index]
    return _experts(x, layer, dict(model), None, shared=shared)[0]
