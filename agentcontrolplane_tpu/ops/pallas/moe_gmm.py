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

Two entry points, one kernel each: ``gmm_swiglu`` (``act(x W1_e) * (x
W3_e)``, both weights walked together so the rows are read once) and
``gmm`` (``x W_e``). Products accumulate in float32 on the MXU's native
bf16 pass; float32 operands (the CPU tests) take the full-precision
contract.
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


# what a grid step's weight blocks may take of the 16 MiB a kernel gets, each
# block twice (pipelined): at a hidden width of 6,144 two blocks of 512
# columns are 24 MB and the chip's compiler refuses the kernel
# (tests/engine/test_chip_compile.py, the exaone cases); every narrower
# hidden width the benchmark has keeps its 512 columns
_WEIGHT_BLOCKS_BYTES = 10 << 20


def _call(kernel, x, weights, tile_expert, n_live, tm, tn, interpret):
    M, K = x.shape
    N = weights[0].shape[2]
    fits = _WEIGHT_BLOCKS_BYTES // (2 * len(weights) * K * weights[0].dtype.itemsize)
    tn = _col_tile(N, min(tn, max(128, fits // 128 * 128)))
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
        interpret=interpret,
        name="moe_gmm",
    )(tile_expert, n_live, x, *weights)


def gmm(
    x: jax.Array,  # [M, K] rows sorted by expert, groups padded to tm
    w: jax.Array,  # [E, K, N]
    tile_expert: jax.Array,  # [M // tm] int32 — the expert of each row tile
    n_live: jax.Array,  # [1] int32 — row tiles that hold any row
    tm: int,
    tn: int = 512,
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
    tn: int = 512,
    act=jax.nn.silu,
    interpret: bool = False,
) -> jax.Array:
    """``act(x @ w1[e]) * (x @ w3[e])`` per row tile -> [M, F]."""
    kernel = functools.partial(_gmm_swiglu_kernel, act=act)
    return _call(kernel, x, (w1, w3), tile_expert, n_live, tm, tn, interpret)
