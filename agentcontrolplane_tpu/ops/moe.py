"""Mixture-of-Experts FFN: one expert layer, its rows grouped by expert.

The MoE leg of the ``provider: tpu`` data plane: per-layer top-k routed
SwiGLU experts in the dense FFN's place (Mixtral: softmax over the chosen;
LFM2-MoE: sigmoid scores, a selection bias, ``held`` experts of the
router's width).

``routed_experts`` routes over all ``E`` experts the router knows, is told
which of them it HOLDS (``held``, global ids in the order of its weights'
leading axis), and returns the weighted sum over each token's chosen
experts that are held. What absent experts would add is left out: a chip
that holds an eighth of a layer's experts computes its part of the sum and
nothing stands in for the rest. ``held=None`` holds all (Mixtral, the uncut
reference). There is no capacity and no token is dropped.

Compute: the (token, choice) pairs are laid out by held expert, each
expert's group padded to whole row tiles, and one grouped matmul per
projection runs over the rows that landed here (``ops/pallas/moe_gmm.py``
on the chip for one device's experts; ``jax.lax.ragged_dot`` elsewhere, and
wherever GSPMD has to partition the layer: an 'ep' axis over the expert
axis, 'tp' over each expert's hidden width). Static shapes: the row bound is
tokens x k plus a tile of padding per held expert. An expert no token chose
has no tile and costs no weight read.

The layout is the one a stable sort of the pairs by expert gives, and it is
COUNTED, not sorted (``group_rows``, PR 49): within an expert the pairs keep
their own order, so a pair's row is its expert's first row plus the number
of earlier pairs with its key, and sums over a one-hot of the keys give
every integer of the plan. An argsort, two scatters and a ``searchsorted``
loop gave the same integers in 38 ops a layer, which the chip runs an
element at a time: 14-26 us of a layer's 97-338 for a few hundred integers.

The one-hot dispatch/combine formulation with its capacity and dropped
tokens (GShard's; ``moe_ffn``, ``expert_capacity``) went with PR 31: at
decode it read every expert's weights whether or not a token chose it, and
nothing needed it (tests/engine/test_moe.py: the 'ep' x 'tp' forward, the
dp x ep x tp train step, the engine on an 'ep' mesh, int8 expert stacks and
the HF Mixtral logits all hold on the grouped layer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _maybe_dequant(w, dtype):
    from .quant import QuantizedTensor, dequantize

    if isinstance(w, QuantizedTensor):
        return dequantize(w, dtype)  # XLA fuses into the matmul's operand load
    return w


def route_topk(
    logits: jax.Array,  # [N, E] f32
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Top-k expert choice per token -> (indices [N, k], weights [N, k]).
    Weights are the softmax over the SELECTED logits (Mixtral renormalizes
    over the top-k, not over all experts)."""
    top_logits, top_idx = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(top_logits, axis=-1)
    return top_idx, weights


def moe_ffn_reference(
    x: jax.Array,  # [N, D]
    router_w: jax.Array,
    w1: jax.Array,
    w3: jax.Array,
    w2: jax.Array,
    experts_per_token: int,
    act=jax.nn.silu,
) -> jax.Array:
    """Exact per-token reference (a Python loop over each token's choices):
    the semantics ``routed_experts`` must match with Mixtral's flags."""
    N, D = x.shape
    logits = x.astype(jnp.float32) @ _maybe_dequant(router_w, jnp.float32)
    top_idx, top_w = route_topk(logits, experts_per_token)
    w1d = _maybe_dequant(w1, x.dtype)
    w3d = _maybe_dequant(w3, x.dtype)
    w2d = _maybe_dequant(w2, x.dtype)

    def token(xi, idxs, ws):
        out = jnp.zeros((D,), dtype=jnp.float32)
        for j in range(experts_per_token):
            e = idxs[j]
            h = act(xi @ w1d[e]) * (xi @ w3d[e])
            out = out + ws[j] * (h @ w2d[e]).astype(jnp.float32)
        return out

    y = jax.vmap(token)(x, top_idx, top_w)
    return y.astype(x.dtype)


COUNTS_HEAD = 3  # counts vector: pairs, landed, experts_read, then a token count per held expert


def route_scores(
    logits: jax.Array,  # [N, E] f32
    k: int,
    score: str = "softmax",  # "softmax" (Mixtral) | "sigmoid"
    bias: jax.Array | None = None,  # [E] f32: enters the CHOICE only
    renormalize: bool = True,
    scale: float = 1.0,
    chosen: jax.Array | None = None,  # [N, k] int32: the choice GIVEN, not made
) -> tuple[jax.Array, jax.Array]:
    """Top-k choice per token -> (indices [N, k], weights [N, k] f32).

    The score is a softmax or a sigmoid over all experts; the choice is the
    top k of the score plus `bias`; the weights are the scores at the
    chosen, divided by their sum (`renormalize`; the sigmoid's sum gets the
    1e-6 its source adds) and times `scale`. Softmax renormalized over the
    chosen is Mixtral's `route_topk` exactly. With `chosen` no top k is
    taken: a check that teacher-forces the routing as it forces tokens hands
    the reference's choice in; the weights stay this router's own scores."""
    if score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router score {score!r} (softmax|sigmoid)")
    if chosen is None:
        _, idx = jax.lax.top_k(s if bias is None else s + bias.astype(jnp.float32), k)
    else:
        idx = chosen.astype(jnp.int32)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + (1e-6 if score == "sigmoid" else 0.0))
    return idx, w * scale


PREFILL_PAIRS = 2048  # from here on the plan's compares, quadratic in the pairs, cost more than a prefix sum and a scatter
DECODE_TILE = 16  # rows of a decode step's tile: a bf16 sublane tile


def row_tile(pairs: int, experts: int | None = None) -> int:
    """Rows of a tile of the grouped matmul, by what an expert can expect: a
    bf16 sublane tile while a dispatch's pairs over the ``experts`` the
    router knows leave an expert under a tile of rows (a decode step), an
    MXU-height tile once a prefill brings rows by the thousand and a decode
    tile's worth to each. The pairs alone do not say: 128 lanes at top-22 of
    512 are 2,816 pairs and 5.5 rows an expert, a decode step, where 512
    tokens at top-4 of 64 are 2,048 pairs and 32 rows an expert. With 128
    experts or fewer (``experts`` None: not given) the rule is the pairs'."""
    return 128 if pairs >= max(PREFILL_PAIRS, DECODE_TILE * (experts or 0)) else DECODE_TILE


def group_rows(
    key: jax.Array,  # [pairs] int32: a pair's held expert, Eh = its expert is not here
    Eh: int,
    tm: int,
    M: int,  # rows of the grouped matmul: pairs rounded up to tiles, and a tile a held expert
    k: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """The grouped matmul's plan, by counting -> (dest [pairs] the row of
    each pair, M = none; row_token [M] the token of each row, 0 where no
    pair landed; tile_expert [M // tm] the held expert of each tile, a dead
    tile's the last live tile's; n_live [1] the tiles that hold a row;
    counts [Eh] the pairs of each held expert).

    The rows are those a stable sort of the pairs by `key` would give, each
    expert's group then padded to whole tiles: within an expert the pairs
    keep their own order, so a pair's place in its group is the number of
    pairs before it with its key. No sort, no search loop, and no scatter
    at a decode step's rows: the chip runs each of those an element at a
    time, and there are a few hundred integers and eight or sixteen values
    to group them by. Sums over a one-hot of the keys fuse to a dozen ops.
    The two ways to a pair's rank are chosen by what bounds THEM, the pairs
    (compares quadratic in them against a prefix sum and one scatter),
    whatever tile ``row_tile`` gave: a decode step of 2,816 pairs takes the
    16-row tile and the prefix sum (7.9 M compares a layer otherwise)."""
    pairs = key.shape[0]
    experts = jnp.arange(Eh, dtype=jnp.int32)
    pair = jnp.arange(pairs, dtype=jnp.int32)
    hot = (key[:, None] == experts).astype(jnp.int32)  # [pairs, Eh]; all zeros: not here
    counts = jnp.sum(hot, axis=0)
    tiles_of = jax.lax.div(counts + (tm - 1), tm)  # no count is negative: this is the floor
    padded = tiles_of * tm
    # each expert's end row: an inclusive prefix sum over Eh values, as one masked sum
    ends = jnp.sum(jnp.where(experts[:, None] <= experts, padded[:, None], 0), axis=0)
    first = jnp.sum(hot * (ends - padded), axis=1)  # the first row of a pair's expert
    pair_token = jax.lax.div(pair, k)
    if pairs < PREFILL_PAIRS:
        # a decode step's rows: every pair against every pair, every row against every pair,
        # each ONE fused op of under a microsecond
        same_before = (key == key[:, None]) & (pair < pair[:, None])
        dest = jnp.where(key < Eh, first + jnp.sum(same_before.astype(jnp.int32), axis=1), M)
        lands = dest == jnp.arange(M, dtype=jnp.int32)[:, None]
        row_token = jnp.sum(jnp.where(lands, pair_token, 0), axis=1)
    else:
        # a prefill's: those compares are quadratic (the inverse alone 104-208 us a layer for a
        # scatter's 57-76: PERF.md, PR 49), so a prefix sum down the one-hot and ONE scatter
        rank = jnp.sum(hot * (jnp.cumsum(hot, axis=0) - hot), axis=1)
        dest = jnp.where(key < Eh, first + rank, M)
        row_token = jnp.zeros((M,), jnp.int32).at[dest].set(pair_token, mode="drop")
    n_live = jnp.sum(tiles_of, keepdims=True)
    at = jnp.minimum(jnp.arange(M // tm, dtype=jnp.int32), jnp.maximum(n_live - 1, 0)) * tm
    tile_expert = jnp.minimum(jnp.sum((ends <= at[:, None]).astype(jnp.int32), axis=1), Eh - 1)
    return dest, row_token, tile_expert, n_live, counts


def routed_experts(
    x: jax.Array,  # [N, D]
    router_w: jax.Array,  # [D, E]
    w1: jax.Array,  # [E_held, D, F] gate (of an expert with no gate: its one matrix in)
    w3: jax.Array | None,  # [E_held, D, F] up; None: the expert is ``w2 act(w1 .)``, not gated
    w2: jax.Array,  # [E_held, F, D] down
    experts_per_token: int,
    held: tuple[int, ...] | None = None,
    score: str = "softmax",
    bias: jax.Array | None = None,
    renormalize: bool = True,
    scale: float = 1.0,
    valid: jax.Array | None = None,  # [N] bool: False rows route nowhere
    act=jax.nn.silu,
    kernel: bool | None = None,  # None: the Pallas grouped matmul on a TPU
    interpret: bool = False,
    expert_base: jax.Array | int = 0,
    chosen: jax.Array | None = None,  # [N, k]: route_scores' `chosen`
    u: jax.Array | None = None,  # [N, D_u]: what the experts read, where it is not what the router reads
) -> tuple[jax.Array, jax.Array]:
    """-> (y [N, D] in x.dtype, counts [COUNTS_HEAD + E_held] uint32).

    The router always reads ``x``. The experts read ``x`` too, or ``u``
    where one is given (latent experts: ``u = x W_down``, narrower than
    ``x``; ``y`` is then ``[N, w2's columns]`` and the caller projects it
    back up). ``w3`` None: experts of two matrices with ``act`` between.

    ``w1``/``w3``/``w2`` may hold several layers' experts on one leading
    axis (``[layers * E_held, ...]``); ``expert_base`` (traced or not) is
    then the row of this layer's first expert. The grouped matmul indexes
    the stack itself: a slice of it handed to the opaque kernel would be a
    copy of the layer's experts every call."""
    import numpy as np

    N, D = x.shape
    E, k = router_w.shape[-1], experts_per_token
    held = tuple(range(E)) if held is None else tuple(int(e) for e in held)
    Eh = len(held)
    assert w1.shape[0] % Eh == 0, (w1.shape, Eh)
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    pairs = N * k
    tm = row_tile(pairs, E)
    M = -(-pairs // tm) * tm + Eh * tm

    with jax.named_scope("moe_route"):
        logits = x.astype(jnp.float32) @ _maybe_dequant(router_w, jnp.float32).astype(jnp.float32)
        idx, wts = route_scores(logits, k, score, bias, renormalize, scale, chosen)
        local = np.full((E,), Eh, dtype=np.int32)
        local[list(held)] = np.arange(Eh, dtype=np.int32)
        key = jnp.asarray(local)[idx.reshape(-1)]  # [N*k]: held expert, or Eh = not here
        if valid is not None:
            key = jnp.where(jnp.repeat(valid, k), key, Eh)

    with jax.named_scope("moe_sort"):
        n_valid = pairs if valid is None else jnp.sum(valid.astype(jnp.uint32)) * k
        dest, row_token, tile_expert, n_live, counts = group_rows(key, Eh, tm, M, k)
        tile_expert = tile_expert + jnp.asarray(expert_base, jnp.int32)
        xs = (x if u is None else u)[row_token]

    with jax.named_scope("moe_gmm"):
        w1d, w3d, w2d = (None if w is None else _maybe_dequant(w, x.dtype) for w in (w1, w3, w2))
        if kernel or interpret:
            from .pallas.moe_gmm import gmm, gmm_act, gmm_swiglu

            if w3d is None:
                h = gmm_act(xs, w1d, tile_expert, n_live, tm, act=act, interpret=interpret)
            else:
                h = gmm_swiglu(xs, w1d, w3d, tile_expert, n_live, tm, act=act, interpret=interpret)
            # rows past the live tiles are not written by either kernel: the second walks the same live tiles and
            # `dest` below is a live row or the appended zero row
            ys = gmm(h, w2d, tile_expert, n_live, tm, interpret=interpret)
        else:
            if w1d.shape[0] != Eh:
                w1d, w3d, w2d = (None if w is None else jax.lax.dynamic_slice_in_dim(w, expert_base, Eh)
                                 for w in (w1d, w3d, w2d))
            prec = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
            padded = -(-counts // tm) * tm  # the groups' rows, as `group_rows` padded them
            dot = lambda a, w: jax.lax.ragged_dot(  # noqa: E731
                a, w, padded, precision=prec, preferred_element_type=jnp.float32)
            h = (act(dot(xs, w1d)) if w3d is None else act(dot(xs, w1d)) * dot(xs, w3d)).astype(x.dtype)
            ys = dot(h, w2d).astype(x.dtype)
            ys = jnp.where((jnp.arange(M) < n_live * tm)[:, None], ys, 0)

    with jax.named_scope("moe_combine"):
        ys = jnp.concatenate([ys, jnp.zeros((1, w2.shape[-1]), ys.dtype)])
        dest = dest.reshape(N, k)
        w_here = jnp.where(dest < M, wts, 0.0)
        y = jnp.sum(ys[dest].astype(jnp.float32) * w_here[..., None], axis=1)
        stats = jnp.concatenate([
            jnp.stack([jnp.asarray(n_valid, jnp.uint32), jnp.sum(counts).astype(jnp.uint32),
                       jnp.sum(counts > 0).astype(jnp.uint32)]),
            counts.astype(jnp.uint32),
        ])
    return y.astype(x.dtype), stats
