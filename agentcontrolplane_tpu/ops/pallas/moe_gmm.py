"""Pallas TPU kernel: grouped matmul over rows laid out by expert (``moe_gmm``).

The routed expert layer (``ops.moe.routed_experts``) counts each (token,
choice) pair's row by expert, groups padded to whole row tiles (no sort:
``ops.moe.group_rows``): a tile has one expert. The kernel walks the row tiles
with that tile -> expert map scalar-prefetched: the weight block of grid
step ``(j, i)`` is expert ``tile_expert[i]``'s column tile ``j``, and
Pallas re-fetches a block only when its index changes, so consecutive tiles
of one expert share one read and an expert no row chose is never read at
all. Tiles past ``n_live`` (the static row bound is ``tokens x k``; what
landed here is usually an eighth of it) repeat the last live tile's block
indices, fetch nothing, skip the product and store zeros.

What the grid reads again: the column tile is the outer axis, so the
weights are read once an expert and column tile (each expert's matrix once
a call, in runs of ``tn`` columns), but the ROWS' block changes at every
step and every live row is read once a column tile, ``N / tn`` times a
call; and every step, dead or live, costs its issue and its output block's
write (~0.15 us), and every sweep over the row tiles ends in its dead tiles
with no fetch in flight (at 128 columns of a 6,144-wide contraction a
decode step's 80 row tiles, 17 of them live, were 1,280 steps in 16 sweeps;
a prefill's live rows were read sixteen times). So the column tile is as
wide as the VMEM a kernel gets unasked holds, and wider under a stated
limit only where that is under 512 columns (``tile_plan``): one tile or
two in three cells, four and four at a hidden width of 6,144.

Two entry points, one kernel each: ``gmm_swiglu`` (``act(x W1_e) * (x
W3_e)``, both weights walked together so the rows are read once a column
tile for both) and ``gmm`` (``x W_e``). Products accumulate in float32 on
the MXU's native bf16 pass; float32 operands (the CPU tests) take the
full-precision contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(x, w):
    precision = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    return jnp.dot(x, w, precision=precision, preferred_element_type=jnp.float32)


def _gmm_kernel(tile_expert_ref, n_live_ref, x_ref, w_ref, o_ref):
    live = pl.program_id(1) < n_live_ref[0]

    @pl.when(live)
    def _():
        o_ref[...] = _dot(x_ref[...], w_ref[0]).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def _gmm_swiglu_kernel(tile_expert_ref, n_live_ref, x_ref, w1_ref, w3_ref, o_ref, *, act):
    live = pl.program_id(1) < n_live_ref[0]

    @pl.when(live)
    def _():
        x = x_ref[...]
        o_ref[...] = (act(_dot(x, w1_ref[0])) * _dot(x, w3_ref[0])).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def _col_tile(n: int, want: int) -> int:
    """The widest column tile <= want that divides n in whole lane tiles
    (n itself where n has no such divisor: the tiny CPU-test widths)."""
    for t in range(min(want, n) // 128 * 128, 0, -128):
        if n % t == 0:
            return t
    return n


# the VMEM a kernel gets unasked. A call that claims no more leaves the compiler the memory plan it always had
_DEFAULT_VMEM_BYTES = 16 << 20
# the most one call may claim of a v5e core's 128 MiB: a quarter. A claim over the default is taken from the
# compiler's own budget for as long as the kernel runs: at 55 MB it sent a decode step's staged q weights (96 MiB at
# a hidden width of 6,144) back to HBM, and at ANY size over the default the compiler's repacker crashed on one
# prefill program of `lfm2` (PERF.md, PR 53). So a call claims more only where the default forces a tile under
# `_MIN_COLS` columns (a weight fetch's runs under 1 KB, sixteen sweeps over the row tiles at a hidden width of 6,144)
_VMEM_LIMIT_BYTES = 32 << 20
_MIN_COLS = 512


def tile_plan(K: int, N: int, weights: int, itemsize: int, tm: int, tn: int | None = None) -> tuple[int, int]:
    """-> (the column tile, the VMEM the call asks for). The tile is the
    widest whole-lane-tile divisor of ``N`` (at most ``tn`` where one is
    given) whose ``weights`` blocks of ``K`` rows, each held twice (the next
    expert's is fetched under this one's products), fit three quarters of
    the VMEM a kernel gets unasked; only where that tile is under
    ``_MIN_COLS`` columns, three quarters of ``_VMEM_LIMIT_BYTES``. The
    limit is what the call then holds: every block twice (Pallas pipelines
    them), a float32 product a weight and one more for their combination,
    and 4 MiB for the compiler's own scratch (it took 0.1-2.2 MB over the
    blocks at every geometry rehearsed), capped at the VMEM it was sized
    for."""
    widest = min(N, tn or N)
    for limit in (_DEFAULT_VMEM_BYTES, _VMEM_LIMIT_BYTES):
        fits = limit * 3 // 4 // (2 * weights * K * itemsize)
        tile = _col_tile(N, max(128, min(fits, widest) // 128 * 128))
        if tile >= min(_MIN_COLS, widest):
            break
    blocks = (weights * K * tile + tm * K + tm * tile) * itemsize
    products = (weights + 1) * tm * tile * 4
    return tile, min(limit, 2 * blocks + products + (4 << 20))


def _call(kernel, x, weights, tile_expert, n_live, tm, tn, interpret):
    M, K = x.shape
    N = weights[0].shape[2]
    tn, vmem_limit = tile_plan(K, N, len(weights), weights[0].dtype.itemsize, tm, tn)
    assert M % tm == 0 and tile_expert.shape == (M // tm,), (M, tm, tile_expert.shape)

    def row(i, nl):  # dead tiles re-use the last live tile's rows: no fetch
        return jnp.maximum(jnp.minimum(i, nl[0] - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // tn, M // tm),
        in_specs=[pl.BlockSpec((tm, K), lambda j, i, te, nl: (row(i, nl), 0))]
        + [pl.BlockSpec((1, K, tn), lambda j, i, te, nl: (te[i], 0, j)) for _ in weights],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, te, nl: (i, j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="moe_gmm",
    )(tile_expert, n_live, x, *weights)


def gmm(
    x: jax.Array,  # [M, K] rows sorted by expert, groups padded to tm
    w: jax.Array,  # [E, K, N]
    tile_expert: jax.Array,  # [M // tm] int32 — the expert of each row tile
    n_live: jax.Array,  # [1] int32 — row tiles that hold any row
    tm: int,
    tn: int | None = None,  # at most this wide a column tile; None: as wide as VMEM holds
    interpret: bool = False,
) -> jax.Array:
    """``x[rows of tile i] @ w[tile_expert[i]]`` -> [M, N]; zeros past n_live."""
    return _call(_gmm_kernel, x, (w,), tile_expert, n_live, tm, tn, interpret)


def gmm_swiglu(
    x: jax.Array,  # [M, K]
    w1: jax.Array,  # [E, K, F] gate
    w3: jax.Array,  # [E, K, F] up
    tile_expert: jax.Array,
    n_live: jax.Array,
    tm: int,
    tn: int | None = None,  # at most this wide a column tile; None: as wide as VMEM holds
    act=jax.nn.silu,
    interpret: bool = False,
) -> jax.Array:
    """``act(x @ w1[e]) * (x @ w3[e])`` per row tile -> [M, F]."""
    kernel = functools.partial(_gmm_swiglu_kernel, act=act)
    return _call(kernel, x, (w1, w3), tile_expert, n_live, tm, tn, interpret)


# -- an expert with no gate (`w2 act(w1 x)`): added below what was here, whose kernels' lines stay where they were
# (a Pallas kernel's program text carries the line of every op: PERF.md, PR 45) --


def _gmm_act_kernel(tile_expert_ref, n_live_ref, x_ref, w_ref, o_ref, *, act):
    live = pl.program_id(1) < n_live_ref[0]

    @pl.when(live)
    def _():
        o_ref[...] = act(_dot(x_ref[...], w_ref[0])).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def gmm_act(
    x: jax.Array,  # [M, K]
    w: jax.Array,  # [E, K, F]
    tile_expert: jax.Array,
    n_live: jax.Array,
    tm: int,
    tn: int | None = None,
    act=jax.nn.silu,
    interpret: bool = False,
) -> jax.Array:
    """``act(x @ w[e])`` per row tile -> [M, F]: the first matrix of an expert
    that has no gate."""
    return _call(functools.partial(_gmm_act_kernel, act=act), x, (w,), tile_expert, n_live, tm, tn, interpret)
