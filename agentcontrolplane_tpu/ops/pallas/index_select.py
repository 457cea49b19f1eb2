"""The indexer's choice of rows as one kernel: no sort.

``ops.attention.topk_rows`` chooses a decode lane's ``topk`` rows of largest
index score with ``jax.lax.top_k``, which the chip lowers to a full sort of
the lane's scores (26,624 a lane and layer at the sparse cells' sizes: a
fifth of a decode step, PERF.md, PR 62) to find a SET whose order nothing
reads. This kernel finds the same set with the scores of a lane held in VMEM:

1. **keys**: a score's float32 bits as an int32 that orders as
   ``jax.lax.top_k`` orders the floats (``-0.0`` under ``+0.0``; a column
   the lane may not choose comes in as ``-inf``);
2. **the threshold**: the ``k``-th largest key (``k`` a lane's own: fewer
   than ``topk`` where it has fewer columns) bit by bit, 32
   compare-and-count passes over the keys (the bisection of
   ``ops.sampling._topk_threshold`` over the bit pattern, so that it ends
   ON a score: nothing to snap);
3. **the tie rule** of ``ops.attention.topk_rows_mask``: every column above
   the threshold, and of the columns AT it the earliest ``room = k -
   above``. The running counts are products on the MXU: a row of 128
   columns against a triangle of ones, the rows before it against the rows'
   totals;
4. **the compaction**: a chosen column ``c`` with ``e`` chosen columns before
   it belongs at slot ``e``, ``d = c - e`` places to its left. ``d`` alone
   travels: a shift by each of its bits in turn, least first (columns never
   collide: two chosen columns' distance never falls under one), over the
   lane's columns as ``[C / 128, 128]`` tiles, a shift a lane roll and a
   sublane roll; slot ``r`` then holds the ``d`` of its column, which is
   column ``r + d``. Ascending position, ``topk`` slots a lane, those past
   ``k`` unread.

A grid step takes ``LANE_GROUP`` lanes: the threshold's 32 passes of each
are one dependent chain (count, compare, next bit), and a group's chains
interleave.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE_GROUP = 8  # lanes a grid step holds (fewer where the lanes are no multiple of it)
COLUMN_TILE = 2048  # a lane's columns are whole tiles of 16 rows of 128 (the bfloat16 operands of the counts' products)
_MIN = np.int32(-(2 ** 31))
_MAX = np.int32(2 ** 31 - 1)


def _total(mask):
    """[T, 128] bool -> [1, 1] int32: how many are set."""
    return jnp.sum(jnp.sum(mask.astype(jnp.int32), axis=0, keepdims=True), axis=1, keepdims=True)


def _kernel(want_ref, scores_ref, pos_ref, tied_ref, key_ref, thr_ref, *, G: int, T: int, R: int):
    g = pl.program_id(0)
    row = jax.lax.broadcasted_iota(jnp.int32, (T, 128), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (T, 128), 1)
    flat = row * 128 + col  # the column of the lane each element is

    bits = jax.lax.bitcast_convert_type(scores_ref[...], jnp.int32)
    key_ref[...] = bits ^ ((bits >> 31) & _MAX)  # orders as `jax.lax.top_k` orders the floats (-0.0 under +0.0)

    # the k-th largest key of each lane, from its highest bit down: `u` is the key with its sign bit turned (unsigned order)
    wanted = [want_ref[g * G + l] for l in range(G)]

    def turn(i, us):
        bit = jnp.left_shift(jnp.int32(1), 31 - i)
        out = []
        for l in range(G):
            tried = us[l] | bit
            enough = _total(key_ref[l] >= (tried ^ _MIN)) >= wanted[l]
            out.append(jnp.where(enough, tried, us[l]))
        return tuple(out)

    us = jax.lax.fori_loop(0, 32, turn, tuple(jnp.zeros((1, 1), jnp.int32) for _ in range(G)))
    for l in range(G):
        thr_ref[l] = jnp.broadcast_to(us[l] ^ _MIN, (1, 128))

    # counts before a column, on the MXU: within its row of 128 (a triangle of ones; beside it all ones: the row's
    # total in every column), and the rows before it (a strict triangle over the rows against the totals)
    T_pad = -(-T // 128) * 128
    tri = jax.lax.broadcasted_iota(jnp.int32, (128, 256), 0) <= jax.lax.broadcasted_iota(jnp.int32, (128, 256), 1)
    upto = jnp.where(tri | (jax.lax.broadcasted_iota(jnp.int32, (128, 256), 1) >= 128), 1.0, 0.0).astype(jnp.bfloat16)
    before = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (T, T_pad), 1) < jax.lax.broadcasted_iota(jnp.int32, (T, T_pad), 0),
                       1.0, 0.0).astype(jnp.bfloat16)

    def counted(mask):
        """[T, 128] bool -> (set columns up to and with each column in its row, its row's total), float32."""
        both = jnp.dot(jnp.where(mask, 1.0, 0.0).astype(jnp.bfloat16), upto, preferred_element_type=jnp.float32)
        return both[:, :128], both[:, 128:]

    def choose(l, _):
        k = want_ref[g * G + l]
        key, thr = key_ref[l], thr_ref[l]
        above, tied = key > thr, key == thr
        (in_a, row_a), (in_t, row_t) = counted(above), counted(tied)
        totals = jnp.concatenate([row_a, row_t], axis=1).astype(jnp.bfloat16)  # [T, 256]: at most 128, exact
        if T_pad != T:
            totals = jnp.concatenate([totals, jnp.zeros((T_pad - T, 256), jnp.bfloat16)], axis=0)
        rows_before = jnp.dot(before, totals, preferred_element_type=jnp.float32)
        upto_a, upto_t = rows_before[:, :128] + in_a, rows_before[:, 128:] + in_t  # counts up to and with a column
        room = (k - _total(above)).astype(jnp.float32)  # [1, 1]: tied columns the lane still takes, the earliest
        chosen = above | (tied & (upto_t <= room))
        earlier = (upto_a - jnp.where(above, 1.0, 0.0)) + jnp.minimum(upto_t - jnp.where(tied, 1.0, 0.0), room)
        tied_ref[l] = jnp.broadcast_to((_total(tied).astype(jnp.float32) > room).astype(jnp.int32), (1, 128))
        d = jnp.where(chosen, flat - earlier.astype(jnp.int32), 0)
        for b in range((T * 128 - 1).bit_length()):
            s = 1 << b
            moving = jnp.where((d & s) != 0, d, 0)
            if s < 128:
                came = pltpu.roll(moving, 128 - s, 1)
                came = jnp.where(col < 128 - s, came, pltpu.roll(came, T - 1, 0))
            else:
                came = pltpu.roll(moving, T - s // 128, 0)
            d = came | (d - moving)
        pos_ref[l] = (flat + d)[:R]
        return 0

    jax.lax.fori_loop(0, G, choose, 0)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def index_select(scores: jax.Array, want: jax.Array, topk: int, interpret: bool = False):
    """scores [S, C] float32 (``-inf`` where a lane may not choose the
    column), want [S] int32 (columns a lane chooses: at most ``topk`` and at
    most those it may) -> (columns [S, topk] int32: each lane's ``want``
    columns of largest score in ascending order, ties at the threshold to the
    earlier column, as ``jax.lax.top_k`` breaks them, the slots past ``want``
    unread; tied [S] bool: lanes whose threshold had more columns at it than
    room, so that the tie rule decided)."""
    S, C = scores.shape
    R = -(-topk // 128)
    C_pad = -(-max(C, R * 128) // COLUMN_TILE) * COLUMN_TILE
    if C_pad != C:
        scores = jnp.pad(scores, ((0, 0), (0, C_pad - C)), constant_values=-jnp.inf)
    T = C_pad // 128
    G = max(g for g in range(1, LANE_GROUP + 1) if S % g == 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S // G,),
        in_specs=[pl.BlockSpec((G, T, 128), lambda g, want: (g, 0, 0))],
        out_specs=[pl.BlockSpec((G, R, 128), lambda g, want: (g, 0, 0)),
                   pl.BlockSpec((G, 1, 128), lambda g, want: (g, 0, 0))],
        scratch_shapes=[pltpu.VMEM((G, T, 128), jnp.int32), pltpu.VMEM((G, 1, 128), jnp.int32)],
    )
    columns, tied = pl.pallas_call(
        functools.partial(_kernel, G=G, T=T, R=R),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, R, 128), jnp.int32), jax.ShapeDtypeStruct((S, 1, 128), jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="index_select",
    )(want.astype(jnp.int32), scores.astype(jnp.float32).reshape(S, T, 128))
    return columns.reshape(S, R * 128)[:, :topk], tied[:, 0, 0] != 0
