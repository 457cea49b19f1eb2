"""The plain reference: the language model of dots3-note-prev in `jax.numpy`,
float32, matmuls at `highest` precision, whole sequences, no cache, no
kernel, no absorbed form (every row is expanded to heads), no threshold
search. Written from the published configuration
(https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json),
the published DeepSeek-V3.2-Exp indexer whose key names it carries, and the
equations ISSUE 61 derives from them, not from the program, of which it
imports nothing.

Every layer: `h = x + Attn(RMSNorm(x))`, `y = h + FFN(RMSNorm(h))`. With `u`
the normed input of the token at position `t`, and a layer's sizes (`full`
or `swa` of `model`: `n_heads`, `nope`, `rope`, `v`, `q_rank`, `kv_rank`,
`theta`):

- `cQ = a_q RMSNorm(W_qa u)`; `[qN_h ; qR_h] = W_qb cQ`, qR roped; `[c' ; kR]
  = W_kva u`, `c = a_kv RMSNorm(c')`, kR roped, one for all heads; `k_{h,s} =
  [W_UK_h c_s ; kR_s]`, `v_{h,s} = W_UV_h c_s`; scores over `sqrt(nope +
  rope)`; `a_q = sqrt(dim / q_rank)`, `a_kv = sqrt(dim / kv_rank)`
  (`apply_mla_qkv_lora_rescale`); rope by rotated halves.
- a full layer's indexer: `qI = W_iq cQ` (`index_heads` of `index_head_dim`),
  `kI = LayerNorm(W_ik u)` (one head; weight and bias), the first `rope`
  values of both roped, `w = W_iw u`; the FULL index scores `I(t, s) = sum_j
  w_j relu(qI_j . kI_s)` a block of rows at a time, `jax.lax.top_k` a row over
  `s <= t` (ties to the earlier row), `min(t + 1, topk)` rows chosen; the
  softmax over the chosen rows alone (a mask);
- a sliding layer: the softmax over `t - window < s <= t`, a mask by
  positions;
- the gate: `g = sigmoid(W_g u)`, a scalar a head, times the head's output
  before `W_o`;
- the first `first_dense` layers' FFN a SwiGLU; the others': `s = sigmoid(u
  W_r)` in float32, the top k of `s + bias`, their weights `s` over their sum
  (`norm_topk_prob`) times `routed_scaling_factor`, the sum over the chosen
  experts that are HELD (`model["held"]`), a loop over the held experts; plus
  the shared SwiGLU expert over every token.

The weights come in the layout they are served in (`dots_weights.py`):
`dense`, `full`, `swa`, `ff`, each stacked over its layers.

`select` gives the rows to choose and `route` the experts, in place of this
pass's own (`choices` returns both in the same form): `select` `[full-type
layers, B, T, ceil(T / 8)]` uint8, key `s` of query `t` bit `s % 8` of byte
`s // 8`; `route` `[expert layers, B, T, k]` int32. `choices(..., against=)`
is a free pass that counts, a query, how many of the rows `against` chose this
pass chose too, and the attention weight this pass gave the rows `against`
missed (`families/keyevl.py` says why choices are given where logits are
compared).

`lower` names a control: "int8" rounds the input of every matmul, the
expanded K and V and the indexer's keys to int8 per row, the precision below
the configuration's; "bf16" the same to bfloat16, the stated one; "bf16_rest"
besides every tensor the program keeps at rest in bfloat16; "gate_off" leaves
the gate out; "rescale_off" sets `a_q = a_kv = 1`; "index_norm_off" leaves kI
unnormed; "index_rope_off" leaves qI and kI unturned; "recent" chooses the
`topk` most recent rows (no indexer); "dense" chooses every row; "window_off"
lets a sliding layer see every earlier row; "shared_off" leaves the shared
expert out.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HEAD_CHUNKS = 8
ROW_BLOCK = 512  # rows of the [T, T] scores and masks computed at a time
HEAD_BLOCK = 16  # heads whose K, V and scores are made at a time
SEQUENCES = 1  # sequences a call of a layer takes
CONTROLS = ("int8", "bf16", "bf16_rest", "gate_off", "rescale_off", "index_norm_off", "index_rope_off", "recent", "dense",
            "window_off", "shared_off")
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


def _rope(x, theta, n=None):
    """x [B, T, H, d] with its first `n` values (all of them: None) turned by
    the token's index; half-split pairs."""
    d = x.shape[-1] if n is None else n
    T = x.shape[1]
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2: d]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., d:]], axis=-1)


def _blocks(t, size, axis=1):
    """[.., n * size, ..] along `axis` -> [n, .., size, ..]: blocks first, for a `lax.map`."""
    n = t.shape[axis] // size
    return jnp.moveaxis(t.reshape(t.shape[:axis] + (n, size) + t.shape[axis + 1:]), axis, 0)


def _attention(h, layer, m, g, full: bool, lower, given, against):
    """-> (output [B, T, D], of a full layer the rows chosen packed [B, T,
    ceil(T / 8)] else None, and against `against`: (rows of it chosen here
    too [B, T], this pass's attention weight on the rows it missed, a head's
    mean [B, T]))."""
    B, T, _ = h.shape
    H, nope, rope, dv, eps = g["n_heads"], g["nope"], g["rope"], g["v"], m["norm_eps"]
    a_q, a_kv = (1.0, 1.0) if lower == "rescale_off" else ((m["dim"] / g["q_rank"]) ** 0.5, (m["dim"] / g["kv_rank"]) ** 0.5)
    cq = _rest(_rms(_mm(h, _w(layer["wq_a"]), lower), _w(layer["q_norm"]), eps) * a_q, lower)
    q_nope = _mm(cq, _w(layer["wq_nope"]).T, lower).reshape(B, T, H, nope)
    q_pe = _rest(_rope(_mm(cq, _w(layer["wq_pe"]).T, lower).reshape(B, T, H, rope), g["theta"]), lower)
    lat = _rest(_rms(_mm(h, _w(layer["wkv_c"]), lower), _w(layer["kv_norm"]), eps) * a_kv, lower)
    k_pe = _rest(_rope(_mm(h, _w(layer["wk_pe"]), lower)[:, :, None, :], g["theta"])[:, :, 0, :], lower)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    gate = jnp.ones((B, T, H), jnp.float32) if lower == "gate_off" else jax.nn.sigmoid(_mm(h, _w(layer["wg"]), lower, rest=False))

    blocks = -(-T // ROW_BLOCK)
    padded = blocks * ROW_BLOCK
    T8 = -(-T // 8)
    j = jnp.arange(T)[None, :]

    def split(t):
        return _blocks(jnp.pad(t, ((0, 0), (0, padded - T)) + ((0, 0),) * (t.ndim - 2)), ROW_BLOCK)

    unsplit = lambda t: jnp.moveaxis(t, 0, 1).reshape((B, padded) + t.shape[3:])[:, :T]  # noqa: E731
    starts = jnp.arange(blocks) * ROW_BLOCK

    def causal_of(r0):
        i = jnp.minimum(r0 + jnp.arange(ROW_BLOCK), T - 1)[:, None]  # a padding row repeats the last
        return i, j <= i  # [rows, T]

    packed = None
    if full:
        # the indexer, then every query's choice, a block of rows at a time
        Hi, ci, topk = m["index_heads"], m["index_head_dim"], min(m["topk"], T)
        qi = _mm(cq, _w(layer["iq"]), lower).reshape(B, T, Hi, ci)
        ki = _mm(h, _w(layer["ik"]), lower)
        if lower != "index_norm_off":
            ki = _layer_norm(ki, _w(layer["ik_norm"]), _w(layer["ik_bias"]), eps)
        if lower != "index_rope_off":
            qi, ki = _rope(qi, g["theta"], rope), _rope(ki[:, :, None, :], g["theta"], rope)[:, :, 0, :]
        qi, ki = _rest(qi, lower), _rest(ki, lower)
        wi = _mm(h, _w(layer["iw"]), lower, rest=False)
        if lower in ROUND:
            ki = ROUND[lower](ki)

        def choose(block):
            qib, wib, r0, givenb = block
            i, causal = causal_of(r0)
            if givenb is not None:
                chosen = jnp.unpackbits(givenb, axis=-1, count=T, bitorder="little").astype(bool) & causal[None]
            elif lower == "dense":
                chosen = jnp.broadcast_to(causal[None], (B, ROW_BLOCK, T))
            elif lower == "recent":
                chosen = jnp.broadcast_to((causal & (j > i - topk))[None], (B, ROW_BLOCK, T))
            else:
                def heads(score, part):  # the indexer's heads a few at a time: 64 heads' products of a block are 2 GB
                    qh, wh = part
                    dots = jnp.einsum("bqhc,bkc->bqhk", qh, ki, precision=HI)
                    return score + jnp.einsum("bqh,bqhk->bqk", wh, jax.nn.relu(dots), precision=HI), None

                hb = min(HEAD_BLOCK, Hi)
                score, _ = jax.lax.scan(heads, jnp.zeros((B, ROW_BLOCK, T), jnp.float32),
                                        (_blocks(qib, hb, axis=2), _blocks(wib, hb, axis=2)))
                score = jnp.where(causal[None], score, -jnp.inf)
                # the k-th largest a row (equal scores come in index order), then the rows over it and, of the rows
                # AT it, the earliest that still fit: the list `top_k` gives, as a mask
                kth = jax.lax.top_k(score, topk)[0][..., -1:]  # -inf where fewer rows are causal: all are chosen
                above, tied = score > kth, (score == kth) & causal[None]
                room = topk - jnp.sum(above, axis=-1, keepdims=True)
                chosen = above | (tied & (jnp.cumsum(tied, axis=-1) <= room))
            return jnp.packbits(chosen, axis=-1, bitorder="little")

        packed = jax.lax.map(choose, (split(qi), split(wi), starts, None if given is None else split(given)))  # [blocks, B, rows, T8]
    theirs = None if against is None else split(against)
    window = T if lower == "window_off" else m["window"]

    def head_group(part):
        """`HEAD_BLOCK` heads: their K and V expanded from every row's latent, then a block of rows at a time."""
        qh, wuk, wuv, gh = part  # [B, T, hb, nope + rope], [hb, nope, r], [hb, r, v], [B, T, hb]
        k = jnp.concatenate([jnp.einsum("btc,hnc->bthn", lat, _w(wuk), precision=HI),
                             jnp.broadcast_to(k_pe[:, :, None, :], (B, T, qh.shape[2], rope))], axis=-1)
        v = jnp.einsum("btc,hcv->bthv", lat, _w(wuv), precision=HI)
        k, v = _rest(k, lower), _rest(v, lower)
        if lower in ROUND:
            k, v = ROUND[lower](k), ROUND[lower](v)

        def rows(block):
            qb, r0, chosenb, theirsb = block
            i, causal = causal_of(r0)
            if full:
                seen = jnp.unpackbits(chosenb, axis=-1, count=T, bitorder="little").astype(bool)
            else:
                seen = jnp.broadcast_to((causal & (j > i - window))[None], (B, ROW_BLOCK, T))
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HI) * (nope + rope) ** -0.5
            p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)
            missed = None
            if theirsb is not None:
                other = jnp.unpackbits(theirsb, axis=-1, count=T, bitorder="little").astype(bool) & causal[None]
                missed = jnp.sum(p * (seen & ~other)[:, None], axis=(1, 3))  # [B, rows]: summed over this group's heads
            return out, missed

        out, missed = jax.lax.map(rows, (split(qh), starts, packed, theirs if full else None))
        return unsplit(out) * gh[..., None], None if missed is None else unsplit(missed)

    hb = min(HEAD_BLOCK, H)
    a, missed = jax.lax.map(head_group, (_blocks(q, hb, axis=2), _blocks(layer["wuk"], hb, axis=0),
                                         _blocks(layer["wuv"], hb, axis=0), _blocks(gate, hb, axis=2)))
    a = jnp.moveaxis(a, 0, 2).reshape(B, T, H * dv)  # [groups, B, T, hb, v] -> heads in order
    stats = None
    if full:
        packed = unsplit(packed)
        assert packed.shape[-1] == T8
        if against is not None:
            mine = jnp.unpackbits(packed, axis=-1, count=T, bitorder="little").astype(bool)
            other = jnp.unpackbits(against, axis=-1, count=T, bitorder="little").astype(bool) & (j[None] <= jnp.arange(T)[None, :, None])
            stats = (jnp.sum(mine & other, axis=-1), jnp.sum(missed, axis=0) / H)
    return _mm(_rest(a, lower), _w(layer["wo"]), lower), packed, stats


def _swiglu(x, w1, w3, w2, lower):
    return _mm(jax.nn.silu(_mm(x, _w(w1), lower)) * _mm(x, _w(w3), lower), _w(w2), lower)


def _experts(h, layer, m, lower, given=None, shared: bool = True, routed: bool = True):
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    k, held = m["experts_per_token"], m["held"]
    s = jax.nn.sigmoid(_mm(x, _w(layer["router"]), lower, rest=False))  # [N, E]; the router is float32
    chosen = jax.lax.top_k(s + _w(layer["router_bias"]), k)[1] if given is None else given.reshape(B * T, k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * m["routed_scaling_factor"]

    def one(out, expert):  # the held experts one after another: a loop, compiled once
        w1, w3, w2, e = expert
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # [N]
        return out + weight[:, None] * _swiglu(x, w1, w3, w2, lower), None

    out = jnp.zeros((B * T, D), jnp.float32)
    if routed:
        out, _ = jax.lax.scan(one, out, (layer["w1"], layer["w3"], layer["w2"], jnp.asarray(held, jnp.int32)))
    if shared and lower != "shared_off":
        out = out + _swiglu(x, layer["sw1"], layer["sw3"], layer["sw2"], lower)
    return _rest(out, lower).reshape(B, T, D), chosen.reshape(B, T, k)


def _unfrozen(model: tuple) -> dict:
    return {name: (dict(v) if name in ("full", "swa") else v) for name, v in model}


# A layer is two compiled halves, so that the kinds share what they share: the dense and the expert full layers one
# attention (given a choice; free and counted against one), the expert full and sliding layers one FFN. The experts
# are always GIVEN to the compiled FFN: a free pass makes its own choice first (`_route`, a small program)
@partial(jax.jit, static_argnames=("model", "full", "lower"))
def _attention_half(x, layer, select, against, *, model, full, lower):
    m = _unfrozen(model)
    a, rows, stats = _attention(_rms(x, _w(layer["ln1"]), m["norm_eps"], lower), layer, m, m["full" if full else "swa"],
                                full, lower, select, against)
    return _rest(x + a, lower), rows, stats


@partial(jax.jit, static_argnames=("model", "lower"))
def _route(x, layer, *, model, lower):
    m = _unfrozen(model)
    h = _rms(x, _w(layer["ln2"]), m["norm_eps"], lower).reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(_mm(h, _w(layer["router"]), lower, rest=False))
    return jax.lax.top_k(s + _w(layer["router_bias"]), m["experts_per_token"])[1].reshape(x.shape[:2] + (-1,))


@partial(jax.jit, static_argnames=("model", "dense", "lower"))
def _ffn_half(x, layer, route, *, model, dense, lower):
    m = _unfrozen(model)
    h = _rms(x, _w(layer["ln2"]), m["norm_eps"], lower)
    y = _rest(_swiglu(h, layer["w1"], layer["w3"], layer["w2"], lower), lower) if dense else _experts(h, layer, m, lower, route)[0]
    return _rest(x + y, lower)


def _layer(x, layer, select, route, against, *, model, kind, lower):
    """One layer -> (the stream, of a full layer the rows chosen packed, of an expert layer the experts, the counts
    against `against`)."""
    x, rows, stats = _attention_half(x, layer, select, against, model=model, full=kind != "sliding", lower=lower)
    if kind != "dense_full" and route is None:
        route = _route(x, layer, model=model, lower=lower)
    return _ffn_half(x, layer, route, model=model, dense=kind == "dense_full", lower=lower), rows, route, stats


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, head, *, eps, lower):
    x = _rms(x, _w(norm), eps, lower)
    V = head.shape[-1]
    step = -(-V // HEAD_CHUNKS)
    return jnp.concatenate([_mm(x, _w(head[:, i: i + step]), lower) for i in range(0, V, step)], axis=-1)


def _static(model: dict) -> tuple:
    freeze = lambda v: tuple(sorted(v.items())) if isinstance(v, dict) else (tuple(v) if isinstance(v, (list, tuple)) else v)  # noqa: E731
    return tuple(sorted((k, freeze(v)) for k, v in model.items()))


def kinds(model: dict) -> tuple:
    """Every layer's kind: `dense_full` (the first `first_dense`), `full`, `sliding`."""
    return tuple("dense_full" if i < model["first_dense"] else ("full" if t == "full_attention" else "sliding")
                 for i, t in enumerate(model["layer_types"]))


def _plan(model: dict) -> list:
    """(kind, its row in its stack of weights, its row of `select` or None, its row of `route` or None) a layer."""
    seen, out, full_row = {}, [], 0
    for i, kind in enumerate(kinds(model)):
        row = seen.get(kind, 0)
        seen[kind] = row + 1
        out.append((kind, row, full_row if kind != "sliding" else None, i - model["first_dense"] if kind != "dense_full" else None))
        full_row += kind != "sliding"
    return out


def _weights(params: dict, kind: str, row: int, expert_row) -> dict:
    pick = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    layer = pick(params[{"dense_full": "dense", "full": "full", "sliding": "swa"}[kind]], row)
    return layer if expert_row is None else {**layer, **pick(params["ff"], expert_row)}


def precompile(params: dict, model: dict, T: int, given: bool) -> None:
    """Compiles the halves of a pass over `T` tokens before the pass asks for
    them, the rows `given` or free and counted against another's: a thread's
    job beside other compiling (`keyevl_reference.precompile`). The operands
    are made as `_stack` makes them (the stream from the embedding's gather,
    which carries the weights' placement), so the pass finds each compiled."""
    import numpy as np

    static = _static(model)
    x = params["embed"][jnp.zeros((SEQUENCES, T), jnp.int32)].astype(jnp.float32)
    bits = jnp.asarray(np.zeros((SEQUENCES, T, -(-T // 8)), np.uint8))
    route = jnp.asarray(np.zeros((SEQUENCES, T, model["experts_per_token"]), np.int32))
    done = set()
    for kind, row, _full_row, expert_row in _plan(model):
        layer, full, dense = _weights(params, kind, row, expert_row), kind != "sliding", kind == "dense_full"
        if ("attention", full) not in done and (full or given):  # a sliding layer's has no choice: compiled once
            done.add(("attention", full))
            choice = (bits if full and given else None, bits if full and not given else None)
            _attention_half.lower(x, layer, *choice, model=static, full=full, lower=None).compile()
        if ("ffn", dense) not in done and given:
            done.add(("ffn", dense))
            _ffn_half.lower(x, layer, None if dense else route, model=static, dense=dense, lower=None).compile()
            if not dense:
                _route.lower(x, layer, model=static, lower=None).compile()


def _stack(params: dict, model: dict, tokens, lower, select=None, route=None, against=None, tell: bool = False):
    """-> (the stream after the last layer [B, T, D], and with `tell` what
    the layers chose, on the HOST: the rows packed [full-type layers, B, T,
    ceil(T / 8)] uint8 and the experts [expert layers, B, T, k] int32, else
    None twice; the counts against `against` or None), a sequence at a
    time."""
    import numpy as np

    if lower is not None and lower not in CONTROLS:
        raise ValueError(f"the dots reference has no control {lower!r}; it has {', '.join(CONTROLS)}")
    static = _static(model)
    tokens = jnp.asarray(tokens, jnp.int32)
    B = tokens.shape[0]
    plan = _plan(model)
    pick = lambda t, i, seqs: None if t is None or i is None else jnp.asarray(t[i][seqs])  # noqa: E731
    xs, chose, routed, counted = [], [], [], []
    for b in range(0, B, SEQUENCES):
        seqs = slice(b, b + SEQUENCES)
        x = params["embed"][tokens[seqs]].astype(jnp.float32)
        rows_b, experts_b, stats_b = [], [], []
        for kind, row, full_row, expert_row in plan:
            x, rows, experts, stats = _layer(
                x, _weights(params, kind, row, expert_row), pick(select, full_row, seqs), pick(route, expert_row, seqs),
                pick(against, full_row, seqs), model=static, kind=kind, lower=lower)
            if tell and rows is not None:
                rows_b.append(np.asarray(rows))
            if tell and experts is not None:
                experts_b.append(np.asarray(experts, np.int32))
            if stats is not None:
                stats_b.append(stats)
        xs.append(x)
        counted.append(stats_b)
        if tell:
            chose.append(np.stack(rows_b))
            routed.append(np.stack(experts_b))
    told = (np.concatenate(chose, axis=1), np.concatenate(routed, axis=1)) if tell else (None, None)
    stats = None
    if against is not None:  # [full-type layers, B, T] each
        stats = tuple(jnp.concatenate([jnp.stack([layer[j] for layer in per_seq]) for per_seq in counted], axis=1)
                      for j in range(2))
    return jnp.concatenate(xs, axis=0), *told, stats


def choices(params: dict, model: dict, tokens, against=None) -> dict:
    """What this reference, running free, chooses for every token of `tokens`
    [B, T]: `select` and `route` in the form `logits` takes them. With
    `against` (a `select` of another's making), besides: `both` [full-type
    layers, B, T], how many of the rows `against` chose for a query this pass
    chose too, and `missed_weight`, the attention weight (a head's mean) this
    pass gave the rows it chose and `against` did not."""
    _x, select, route, stats = _stack(params, model, tokens, None, against=against, tell=True)
    out = {"select": select, "route": route}
    if stats is not None:
        out["both"], out["missed_weight"] = stats
    return out


def logits(params: dict, model: dict, tokens, rows, lower: str | None = None, select=None, route=None):
    """Float32 logits [B, R, V] of `tokens` [B, T] at positions `rows`
    [B, R]. `model` holds the configuration file's sizes and `held`; `select`
    and `route` are choices given (module text)."""
    x, *_ = _stack(params, model, tokens, lower, select, route)
    rows = jnp.asarray(rows, jnp.int32)
    picked = x[jnp.arange(x.shape[0])[:, None], rows]
    return _head(picked, params["norm"], params["lm_head"], eps=model["norm_eps"], lower=lower)


def layer_output(params: dict, model: dict, expert_layer: int, x, shared: bool = True, routed: bool = True):
    """One expert layer's FF over `x` [B, T, D] float32 (its input already
    normed), its routed part over the held experts and its shared expert,
    either or both: what a test adds up over the sixteen shares of the
    experts, the shared expert counted once."""
    layer = jax.tree_util.tree_map(lambda a: a[expert_layer], params["ff"])
    return _experts(x, layer, model, None, shared=shared, routed=routed)[0]
