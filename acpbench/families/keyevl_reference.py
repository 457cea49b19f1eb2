"""The plain reference: the language model of Keye-VL-2.0-30B-A3B in
`jax.numpy`, float32, matmuls at `highest` precision, whole sequences, no
cache, no kernel, no threshold search. Written from the published
configuration
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json),
the published DeepSeek-V3.2-Exp indexer whose sizes its `sa_config` gives, and
the equations ISSUE 59 derives from them, not from the program, of which it
imports nothing.

Every layer: `h = x + Attn(RMSNorm(x))`, `y = h + MoE(RMSNorm(h))`. With `u`
the normed input of the token at position `t`:

- q (`n_heads`), k, v (`n_kv_heads`) without bias; RMSNorm with a weight over
  each head of q and of k; rotary over three axes: pair `i` of the `d / 2`
  turns by `theta**(-2i/d)` times the position of the axis (time, height,
  width) whose section of `mrope_section` holds `i`, halves rotated; a text
  token's three positions are its index.
- the indexer: `qI = W_iq u` (`index_heads` of `index_head_dim`), `kI =
  LayerNorm(W_ik u)` (one head; weight and bias), `w = W_iw u`; qI and kI
  turned by the plain rope at the temporal position; the FULL `[T, T]` index
  scores `I(t, s) = sum_j w_j relu(qI_j . kI_s)` a block of rows at a time,
  `jax.lax.top_k` a row over `s <= t` (equal scores in index order: ties to
  the earlier row), `min(t + 1, topk)` rows chosen;
- attention: the softmax of `q . k_s / sqrt(d)` over the chosen rows alone
  (a `[T, T]` mask), `n_heads / n_kv_heads` query heads a KV head; `W_o`;
- `MoE`: `s = softmax(u W_r)` over all experts in float32; the top k of `s`,
  their weights over their sum (`norm_topk_prob`); the sum over the chosen
  experts that are HELD (`model["held"]`), a loop over the held experts.

The weights come in the layout they are served in (`keyevl_weights.py`):
`attn` and `ff`, each stacked over the layers.

`select` gives the rows to choose and `route` the experts, in place of this
pass's own (`choices` returns both in the same form): `select` `[layers, B,
T, ceil(T / 8)]` uint8, key `s` of query `t` bit `s % 8` of byte `s // 8`;
`route` `[layers, B, T, k]` int32. The output check compares LOGITS with the
program's choices given, because bfloat16 decides a few of a hundred choices
at the threshold and a row or an expert chosen otherwise moves a logit far
more than rounding does; the choices themselves are compared by `choices(...,
against=)`: a free pass that counts, a query, how many of the rows `against`
chose this pass chose too, and the attention weight this pass gave the rows
`against` missed.

`lower` names a control (free unless `select` / `route` are given with it, as
the family gives them to the controls of precision): "int8" rounds the input of every
matmul, K, V and the indexer's keys to int8 per row, the precision below the
configuration's; "bf16" the same to bfloat16, the stated one (it must pass);
"bf16_rest" besides every tensor the program keeps at rest in bfloat16;
"recent" chooses the `topk` most recent rows (no indexer); "w_one" sets the
indexer's head weights to 1; "topk_half" chooses `topk / 2` rows;
"index_rope_off" leaves qI and kI unturned; "index_norm_off" leaves kI
unnormed; "dense" chooses every row (no selection at all); "one_axis" turns q
and k by the temporal position alone (seen only where the three differ).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HEAD_CHUNKS = 8
ROW_BLOCK = 512  # rows of the [T, T] scores and masks computed at a time
SEQUENCES = 1  # sequences a call of a layer takes
CONTROLS = ("int8", "bf16", "bf16_rest", "recent", "w_one", "topk_half", "index_rope_off", "index_norm_off", "dense",
            "one_axis")
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


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _rope(x, angles):
    """x [B, T, H, d] turned by `angles` [B, T, d / 2]; half-split pairs."""
    d = x.shape[-1]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _angles(positions3, d, theta, sections=None):
    """[B, 3, T] -> [B, T, d / 2]: pair i by its axis' position (`sections`),
    or by the temporal position alone."""
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if sections is None:
        return positions3[:, 0, :, None].astype(jnp.float32) * inv
    axis = jnp.asarray([a for a, n in enumerate(sections) for _ in range(n)])
    by_pair = jnp.moveaxis(positions3, 1, 2)[:, :, axis]  # [B, T, d / 2]
    return by_pair.astype(jnp.float32) * inv


def _attention(h, layer, m, lower, positions3, given, against):
    """-> (output [B, T, D], the rows chosen packed [B, T, ceil(T / 8)], and
    against `against`: (rows of it chosen here too [B, T], this pass's
    attention weight on the rows it missed, a head's mean [B, T]))."""
    B, T, _ = h.shape
    H, Hkv, d = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    Hi, ci, eps = m["index_heads"], m["index_head_dim"], m["norm_eps"]
    topk = m["topk"] // 2 if lower == "topk_half" else m["topk"]
    q = _mm(h, _w(layer["wq"]), lower).reshape(B, T, H, d)
    k = _mm(h, _w(layer["wk"]), lower).reshape(B, T, Hkv, d)
    v = _mm(h, _w(layer["wv"]), lower).reshape(B, T, Hkv, d)
    turn = _angles(positions3, d, m["rope_theta"], None if lower == "one_axis" else m["mrope_section"])
    q = _rest(_rope(_rms(q, _w(layer["q_norm"]), eps, lower), turn), lower)
    k = _rest(_rope(_rms(k, _w(layer["k_norm"]), eps, lower), turn), lower)
    # the indexer
    qi = _mm(h, _w(layer["iq"]), lower).reshape(B, T, Hi, ci)
    ki = _mm(h, _w(layer["ik"]), lower)
    if lower != "index_norm_off":
        ki = _layer_norm(ki, _w(layer["ik_norm"]), _w(layer["ik_bias"]), eps)
    if lower != "index_rope_off":
        turn_i = _angles(positions3, ci, m["rope_theta"])
        qi, ki = _rope(qi, turn_i), _rope(ki[:, :, None, :], turn_i)[:, :, 0, :]
    qi, ki = _rest(qi, lower), _rest(ki, lower)
    wi = jnp.ones((B, T, Hi), jnp.float32) if lower == "w_one" else _mm(h, _w(layer["iw"]), lower, rest=False)
    if lower in ROUND:
        k, v, ki = ROUND[lower](k), ROUND[lower](v), ROUND[lower](ki)

    blocks = -(-T // ROW_BLOCK)
    padded = blocks * ROW_BLOCK
    T8 = -(-T // 8)

    def split(t):
        t = jnp.pad(t, ((0, 0), (0, padded - T)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape((B, blocks, ROW_BLOCK) + t.shape[2:]), 1, 0)

    j = jnp.arange(T)[None, :]

    def rows(block):
        qb, qib, wib, r0, givenb, againstb = block
        i = jnp.minimum(r0 + jnp.arange(ROW_BLOCK), T - 1)[:, None]  # a padding row repeats the last
        causal = j <= i  # [rows, T]
        if givenb is not None:
            chosen = jnp.unpackbits(givenb, axis=-1, count=T, bitorder="little").astype(bool) & causal[None]
        elif lower == "dense":
            chosen = jnp.broadcast_to(causal[None], (B, ROW_BLOCK, T))
        elif lower == "recent":
            chosen = jnp.broadcast_to((causal & (j > i - topk))[None], (B, ROW_BLOCK, T))
        else:
            dots = jnp.einsum("bqhc,bkc->bqhk", qib, ki, precision=HI)
            score = jnp.einsum("bqh,bqhk->bqk", wib, jax.nn.relu(dots), precision=HI)
            score = jnp.where(causal[None], score, -jnp.inf)
            # the k-th largest a row (equal scores come in index order), then the rows over it and, of the rows
            # AT it, the earliest that still fit: the list `top_k` gives, as a mask
            kth = jax.lax.top_k(score, min(topk, T))[0][..., -1:]  # -inf where fewer rows are causal: all are chosen
            above, tied = score > kth, (score == kth) & causal[None]
            room = min(topk, T) - jnp.sum(above, axis=-1, keepdims=True)
            chosen = above | (tied & (jnp.cumsum(tied, axis=-1) <= room))
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k, precision=HI) * d ** -0.5
        p = jax.nn.softmax(jnp.where(chosen[:, None, None], s, -jnp.inf), axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", p, v, precision=HI)
        stats = None
        if againstb is not None:
            theirs = jnp.unpackbits(againstb, axis=-1, count=T, bitorder="little").astype(bool) & causal[None]
            both = jnp.sum(theirs & chosen, axis=-1)
            missed = jnp.sum(p * (chosen & ~theirs)[:, None, None], axis=-1)  # [B, g, r, rows]
            stats = (both, jnp.mean(missed, axis=(1, 2)))
        return out, jnp.packbits(chosen, axis=-1, bitorder="little"), stats

    qs = split(q.reshape(B, T, Hkv, H // Hkv, d))
    a, packed, stats = jax.lax.map(rows, (qs, split(qi), split(wi), jnp.arange(blocks) * ROW_BLOCK,
                                          None if given is None else split(given),
                                          None if against is None else split(against)))
    unsplit = lambda t: jnp.moveaxis(t, 0, 1).reshape((B, padded) + t.shape[3:])[:, :T]  # noqa: E731
    a = unsplit(a).reshape(B, T, H * d)
    stats = None if stats is None else tuple(unsplit(t) for t in stats)
    assert packed.shape[-1] == T8
    return _mm(a, _w(layer["wo"]), lower), unsplit(packed), stats


def _experts(h, layer, m, lower, given=None):
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    k, held = m["experts_per_token"], m["held"]
    s = jax.nn.softmax(_mm(x, _w(layer["router"]), lower, rest=False), axis=-1)  # [N, E]; the router is float32
    chosen = jax.lax.top_k(s, k)[1] if given is None else given.reshape(B * T, k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)

    def one(out, expert):  # the held experts one after another: a loop, compiled once
        w1, w3, w2, e = expert
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # [N]
        y = jax.nn.silu(_mm(x, _w(w1), lower)) * _mm(x, _w(w3), lower)
        return out + weight[:, None] * _mm(y, _w(w2), lower), None

    out, _ = jax.lax.scan(one, jnp.zeros((B * T, D), jnp.float32),
                          (layer["w1"], layer["w3"], layer["w2"], jnp.asarray(held, jnp.int32)))
    return _rest(out, lower).reshape(B, T, D), chosen.reshape(B, T, k)


@partial(jax.jit, static_argnames=("model", "lower"))
def _layer(x, layer, positions3, select, route, against, *, model, lower):
    m = dict(model)
    a, rows, stats = _attention(_rms(x, _w(layer["ln1"]), m["norm_eps"], lower), layer, m, lower, positions3, select,
                                against)
    x = _rest(x + a, lower)
    y, experts = _experts(_rms(x, _w(layer["ln2"]), m["norm_eps"], lower), layer, m, lower, route)
    return _rest(x + y, lower), rows, experts, stats


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, head, *, eps, lower):
    x = _rms(x, _w(norm), eps, lower)
    V = head.shape[-1]
    step = -(-V // HEAD_CHUNKS)
    return jnp.concatenate([_mm(x, _w(head[:, i: i + step]), lower) for i in range(0, V, step)], axis=-1)


def _static(model: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v) for k, v in model.items()))


def _operands(params: dict, tokens, positions3, seqs, i: int, select, route, against) -> tuple:
    """What layer `i` takes for the sequences `seqs` beside the stream: its weights, the positions, and its rows of
    the choices given (each [layers, B, ...] on the host, or None)."""
    row = lambda tree: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    pick = lambda t: None if t is None else jnp.asarray(t[i][seqs])  # noqa: E731
    return {**row(params["attn"]), **row(params["ff"])}, positions3[seqs], pick(select), pick(route), pick(against)


def _embedded(params: dict, tokens, seqs):
    return params["embed"][tokens[seqs]].astype(jnp.float32)


def precompile(params: dict, model: dict, T: int, given: bool) -> None:
    """Compiles a layer of a pass over `T` tokens before the pass asks for it,
    the choices `given` or free and counted against another's: a thread's
    job beside other compiling (a layer at float32 takes the compiler 8 to
    17 s, and a run's clock is short: PERF.md). The operands are made as
    `_stack` makes them, so the pass finds the program compiled."""
    import numpy as np

    tokens, seqs = jnp.zeros((SEQUENCES, T), jnp.int32), slice(0, SEQUENCES)
    bits = np.zeros((1, SEQUENCES, T, -(-T // 8)), np.uint8)
    route = np.zeros((1, SEQUENCES, T, model["experts_per_token"]), np.int32)
    choice = (bits, route, None) if given else (None, None, bits)
    positions3 = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (SEQUENCES, 3, T))
    _layer.lower(_embedded(params, tokens, seqs), *_operands(params, tokens, positions3, seqs, 0, *choice),
                 model=_static(model), lower=None).compile()


def _stack(params: dict, model: dict, tokens, lower, select=None, route=None, against=None, positions3=None,
           tell: bool = False):
    """-> (the stream after the last layer [B, T, D], and with `tell` what
    every layer chose, on the HOST (269 MB a sequence of 16,400 tokens, which
    a device that also holds an engine has no room to stack): the rows packed
    [layers, B, T, ceil(T / 8)] uint8 and the experts [layers, B, T, k]
    int32, else None twice; the counts against `against` or None), a
    sequence at a time."""
    import numpy as np

    if lower is not None and lower not in CONTROLS:
        raise ValueError(f"the keye reference has no control {lower!r}; it has {', '.join(CONTROLS)}")
    static = _static(model)
    tokens = jnp.asarray(tokens, jnp.int32)
    B, T = tokens.shape
    if positions3 is None:
        positions3 = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, 3, T))
    n_layers = params["ff"]["ln2"].shape[0]
    xs, chose, routed, counted = [], [], [], []
    for b in range(0, B, SEQUENCES):
        seqs = slice(b, b + SEQUENCES)
        x = _embedded(params, tokens, seqs)
        per_layer = []
        for i in range(n_layers):
            x, rows, experts, stats = _layer(x, *_operands(params, tokens, positions3, seqs, i, select, route, against),
                                             model=static, lower=lower)
            per_layer.append((np.asarray(rows), np.asarray(experts, np.int32)) if tell else ())
            counted.append(stats)
        xs.append(x)
        if tell:
            rows, experts = zip(*per_layer)
            chose.append(np.stack(rows))
            routed.append(np.stack(experts))
    told = (np.concatenate(chose, axis=1), np.concatenate(routed, axis=1)) if tell else (None, None)
    stats = None
    if against is not None:  # [layers, B, T] each: `counted` runs sequence by sequence, layer by layer
        stats = tuple(jnp.concatenate([jnp.stack([counted[b * n_layers + i][j] for i in range(n_layers)])
                                       for b in range(-(-B // SEQUENCES))], axis=1) for j in range(2))
    return jnp.concatenate(xs, axis=0), *told, stats


def choices(params: dict, model: dict, tokens, against=None, positions3=None) -> dict:
    """What this reference, running free, chooses for every token of `tokens`
    [B, T] in every layer: `select` and `route` in the form `logits` takes
    them. With `against` (a `select` of another's making), besides: `both`
    [layers, B, T], how many of the rows `against` chose for a query this
    pass chose too, and `missed_weight` [layers, B, T], the attention weight
    (a head's mean) this pass gave the rows it chose and `against` did not."""
    _x, select, route, stats = _stack(params, model, tokens, None, against=against, positions3=positions3, tell=True)
    out = {"select": select, "route": route}
    if stats is not None:
        out["both"], out["missed_weight"] = stats
    return out


def logits(params: dict, model: dict, tokens, rows, lower: str | None = None, select=None, route=None,
           positions3=None):
    """Float32 logits [B, R, V] of `tokens` [B, T] at positions `rows`
    [B, R]. `model` holds the configuration file's sizes and `held`; `select`
    and `route` are choices given (module text); `positions3` [B, 3, T] a
    token's three positions (default: its index thrice)."""
    x, *_ = _stack(params, model, tokens, lower, select, route, positions3=positions3)
    rows = jnp.asarray(rows, jnp.int32)
    picked = x[jnp.arange(x.shape[0])[:, None], rows]
    return _head(picked, params["norm"], params["lm_head"], eps=model["norm_eps"], lower=lower)


def layer_output(params: dict, model: dict, layer_index: int, x):
    """One layer's expert FF over `x` [B, T, D] float32 (its input already
    normed): what a test adds up over the eight shares of the experts."""
    layer = jax.tree_util.tree_map(lambda a: a[layer_index], params["ff"])
    return _experts(x, layer, model, None)[0]
