"""Pallas TPU kernel: grouped matmul over rows laid out by expert (``moe_gmm``).

The routed expert layer (``ops.moe.routed_experts``) counts each (token,
choice) pair's row by expert, groups padded to whole row tiles (no sort:
``ops.moe.group_rows``): a tile has one expert, and the tile -> expert map
and the number of live tiles are scalar-prefetched. The static row bound is
``tokens x k`` plus a tile a held expert; what lands here is the chip's
share of it (17 of 80 tiles at ``kexaone``'s decode step, ~64 of 240 at
``nemotron3s``'s).

The kernel walks the LIVE tiles only, as ONE stream of units whose weight
fetches it issues itself (rows, weights and output stay in HBM; there is no
grid). A RUN is a stretch of live tiles of one expert, of at most as many
tiles as the call keeps rows for; a UNIT is one run under one COLUMN CHUNK
of the expert's weights, ``W[e, :, c0:c1]`` of every weight of the call with
``K`` whole. The order is run outside, chunk inside, the run's tiles
innermost, so each expert's matrix is read once a run and an expert no row
chose is never read. A unit's chunks land in a slot of a small ring and
signal the slot's one semaphore; after the products that read a slot the
unit ``depth`` ahead is fetched into it, so the queue runs from an expert's
last chunk into the next expert's first and from the last live tile to the
call's end without draining, and a dead tile is not a step. A run's rows
are fetched ONCE, kept for all of the expert's chunks, and under the run's
last chunk each tile's place is refilled with the next run's rows as soon as
its product has read it. Each product is ``[tm, K] x [K, chunk]`` with
float32 accumulation over the whole ``K`` (the bits are those of any other
column tiling: PR 53), written to one of two output tiles and copied out
while the next is computed. Rows past the live tiles are NOT written:
nothing reads them (the second kernel walks the same live tiles, and
``moe_combine`` gathers a pair's live row or the appended zero row).

Until PR 55 this was a ``pallas_call`` grid (column tile, every row tile of
the bound) over Pallas's block pipeline: a dead tile was a step, every sweep
ended with no fetch in flight, and a prefill's rows were read once a column
tile (PERF.md, Findings PR 53 and PR 55).

Sizing (``chunk_plan``, ``held_tiles``, ``vmem_bytes``), from ``K``, ``N``, the weights'
count and itemsize and the row tile alone. A decode step is bound by the
weight fetches (measured, PR 55: with the products taken out the calls take
the same time), so the chunk is as wide as two units fit and as contiguous
as that makes the fetch (HBM arrays are tiled ``(8,128)(2,1)``: a 128-column
chunk of bf16 is runs of 4 KB, a whole width one run an expert). A call
states what it holds and 2 MiB as its VMEM limit (``vmem_bytes``), never more than the 16 MiB
a kernel gets unasked: a claim over the default is taken from the compiler's
own plan for as long as the kernel runs (PR 53: it evicted a neighbour's
staged weights in one program and crashed the compiler in another), and a
call that states nothing is given the whole default, which sent `mellum2`'s
staged stack of window-layer k/v weights (49.5 MB in VMEM under the decode
block) to HBM under kernels that hold 4.7 and 12.8 MB (PR 55).

A geometry's call is built ONCE a process and jitted (``_stream``): the
kernel's body is five times the grid's to trace and lower, and a process
holds it at up to fifty sites.

Three entry points over the one kernel: ``gmm_swiglu`` (``act(x W1_e) * (x
W3_e)``, both weights' chunks a unit), ``gmm_act`` (``act(x W_e)``) and
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


def _stream_kernel(tile_expert_ref, n_live_ref, x_hbm, *refs, weights, chunks, depth, combine):
    w_hbm, o_hbm = refs[:weights], refs[weights]
    run_ref, x_buf, w_buf, o_buf, x_sem, w_sem, o_sem = refs[weights + 1:]
    held, tm = x_buf.shape[:2]
    tc = o_buf.shape[2]
    n_live = n_live_ref[0]

    # the runs: run_ref[r] is the first tile of the r-th stretch of live tiles with one expert (of at most `held`
    # tiles: what stays in VMEM for all of the expert's chunks); run_ref[n_runs] and the one after are n_live
    def mark(i, c):
        n, first = c
        new = (i == 0) | (tile_expert_ref[i] != tile_expert_ref[jnp.maximum(i - 1, 0)]) | (i - first == held)

        @pl.when(new)
        def _():
            run_ref[n] = i

        return n + new.astype(jnp.int32), jnp.where(new, i, first)

    n_runs, _ = jax.lax.fori_loop(0, n_live, mark, (jnp.int32(0), jnp.int32(0)))
    run_ref[n_runs] = n_live
    run_ref[n_runs + 1] = n_live
    units = n_runs * chunks

    def at(u):  # unit u -> (its run, its column chunk)
        return (u, 0) if chunks == 1 else (jax.lax.div(u, chunks), jax.lax.rem(u, chunks))

    def cols(c):
        return pl.ds(c * tc if chunks == 1 else pl.multiple_of(c * tc, tc), tc)

    def rows(i):
        return pl.ds(pl.multiple_of(i * tm, tm), tm)

    def fetch(u):  # a unit's chunk of every weight into ring slot u % depth, all signalling the slot's one semaphore
        r, c = at(u)
        expert, slot = tile_expert_ref[run_ref[r]], jax.lax.rem(u, depth)
        for j, w in enumerate(w_hbm):
            pltpu.make_async_copy(w.at[expert, :, cols(c)], w_buf.at[slot, j], w_sem.at[slot]).start()

    def fetch_rows(i, t):  # tile i's rows into the t-th place of the run's rows
        pltpu.make_async_copy(x_hbm.at[rows(i)], x_buf.at[t], x_sem.at[t]).start()

    def wait(buf, sem, slot):  # every copy into (or out of) the slot: one wait of its size
        pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()

    jax.lax.fori_loop(0, run_ref[1], lambda t, _: fetch_rows(t, t), None)  # the first run's rows
    jax.lax.fori_loop(0, jnp.minimum(depth, units), lambda u, _: fetch(u), None)

    def unit(u, done):  # done: the tiles written so far
        r, c = at(u)
        first, then = run_ref[r], run_ref[r + 1]
        n = then - first
        # under a run's last chunk each tile's place is refilled with the next run's rows as soon as it was read
        n_next = jnp.where(c + 1 == chunks, run_ref[r + 2] - then, 0)
        slot = jax.lax.rem(u, depth)
        wait(w_buf, w_sem, slot)

        def tile(t, done):
            @pl.when(c == 0)
            def _():
                wait(x_buf, x_sem, t)

            os = jax.lax.rem(done, 2)

            @pl.when(done >= 2)
            def _():
                wait(o_buf, o_sem, os)

            x = x_buf[t]
            o_buf[os] = combine(*(_dot(x, w_buf[slot, j]) for j in range(weights))).astype(o_buf.dtype)
            pltpu.make_async_copy(o_buf.at[os], o_hbm.at[rows(first + t), cols(c)], o_sem.at[os]).start()

            @pl.when(t < n_next)
            def _():
                fetch_rows(then + t, t)

            return done + 1

        done = jax.lax.fori_loop(0, n, tile, done)
        jax.lax.fori_loop(n, n_next, lambda t, _: fetch_rows(then + t, t), None)  # a longer run's other tiles

        @pl.when(u + depth < units)  # after the products that read the slot, never before
        def _():
            fetch(u + depth)

        return done

    done = jax.lax.fori_loop(0, units, unit, jnp.int32(0))
    for back in (1, 2):

        @pl.when(done >= back)
        def _():
            wait(o_buf, o_sem, jax.lax.rem(done - back, 2))


# the VMEM a kernel gets unasked: no call states more, so the compiler keeps the memory plan it always had
_DEFAULT_VMEM_BYTES = 16 << 20
# the compiler's own scratch beside what a call holds: it took 0.1-1.3 MB at every geometry rehearsed
# (tests/engine/test_chip_compile.py holds the sum inside what the call states)
_COMPILER_BYTES = 2 << 20
# a run's rows: sixteen tiles of a decode step, two of a prefill (3 MiB at a hidden width of 6,144, where rows read
# again at every chunk cost as many bytes as the weights: 805 MB beside 805 MB a call, PR 55). A longer run is two
_RUN_ROWS = 256


def held_tiles(tm: int) -> int:
    """Tiles of rows a call keeps in VMEM: the longest run."""
    return max(1, _RUN_ROWS // tm)


def vmem_bytes(K: int, weights: int, itemsize: int, tm: int, chunk: int, depth: int) -> int:
    """The VMEM limit a call states: what it holds (the ring, a run's rows,
    two tiles of output, a float32 product a weight and one for their
    combination) and the compiler's own scratch."""
    ring = depth * weights * K * chunk * itemsize
    rows = held_tiles(tm) * tm * K * itemsize
    return ring + rows + 2 * tm * chunk * itemsize + (weights + 1) * tm * chunk * 4 + _COMPILER_BYTES


def chunk_plan(K: int, N: int, weights: int, itemsize: int, tm: int, tn: int | None = None) -> tuple[int, int]:
    """-> (the column chunk, the ring's depth in units). The chunk is the
    widest whole-lane-tile divisor of ``N`` (at most ``tn`` where one is
    given; ``N`` itself where it has none) with which a ring of two units
    and everything else a call states fit the VMEM a kernel gets unasked.
    The ring is three units deep where that fits too: a ring of two ran dry
    under units of under a megabyte (PR 55: 767 us for 549 at
    ``nemotron3s``'s first matrix in 128-column chunks), and from three on
    nothing measured moved."""
    fits = lambda chunk, depth: vmem_bytes(K, weights, itemsize, tm, chunk, depth) <= _DEFAULT_VMEM_BYTES  # noqa: E731
    chunk = N if N % 128 else 128
    for wider in range(256, min(N, tn or N) + 1, 128):
        if N % wider == 0 and fits(wider, 2):
            chunk = wider
    return chunk, 3 if fits(chunk, 3) else 2


_COMBINE = {
    "gmm": lambda act: lambda p: p,
    "gmm_act": lambda act: act,
    "gmm_swiglu": lambda act: lambda g, u: act(g) * u,
}


@functools.lru_cache(maxsize=256)
def _stream(entry, act, M, K, N, W, dtype, w_dtype, tm, tn, interpret):
    """The call of one geometry, jitted: a program that holds it at several
    sites (layers written out, a prefill's chunks) and a process that holds
    it in several programs (up to thirty) trace the kernel ONCE and lower it
    once a program. A layer's two kernels cost 150 ms to trace and lower
    where the grid's cost 40 (PR 55), and `setup_s` pays that a site."""
    itemsize = jnp.dtype(w_dtype).itemsize
    chunk, depth = chunk_plan(K, N, W, itemsize, tm, tn)
    held = held_tiles(tm)
    # never over the default (a width whose narrowest ring does not fit is the compiler's to refuse); where it is under
    # it, the compiler has the rest for what it stages around the kernels
    limit = min(_DEFAULT_VMEM_BYTES, vmem_bytes(K, W, itemsize, tm, chunk, depth))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + W),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SMEM((M // tm + 2,), jnp.int32),
            pltpu.VMEM((held, tm, K), dtype),
            pltpu.VMEM((depth, W, K, chunk), w_dtype),
            pltpu.VMEM((2, tm, chunk), dtype),
            pltpu.SemaphoreType.DMA((held,)),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_stream_kernel, weights=W, chunks=N // chunk, depth=depth, combine=_COMBINE[entry](act))
    return jax.jit(pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        interpret=interpret,
        name="moe_gmm",
    ))


def _call(entry, act, x, weights, tile_expert, n_live, tm, tn, interpret):
    M, K = x.shape
    assert M % tm == 0 and tile_expert.shape == (M // tm,), (M, tm, tile_expert.shape)
    call = _stream(entry, act, M, K, weights[0].shape[2], len(weights), x.dtype, weights[0].dtype, tm, tn, interpret)
    return call(tile_expert, n_live, x, *weights)


def gmm(
    x: jax.Array,  # [M, K] rows sorted by expert, groups padded to tm
    w: jax.Array,  # [E, K, N]
    tile_expert: jax.Array,  # [M // tm] int32 — the expert of each row tile
    n_live: jax.Array,  # [1] int32 — row tiles that hold any row
    tm: int,
    tn: int | None = None,  # at most this wide a column chunk; None: as the plan gives
    interpret: bool = False,
) -> jax.Array:
    """``x[rows of tile i] @ w[tile_expert[i]]`` -> [M, N]; rows past the
    live tiles are not written."""
    return _call("gmm", None, x, (w,), tile_expert, n_live, tm, tn, interpret)


def gmm_swiglu(
    x: jax.Array,  # [M, K]
    w1: jax.Array,  # [E, K, F] gate
    w3: jax.Array,  # [E, K, F] up
    tile_expert: jax.Array,
    n_live: jax.Array,
    tm: int,
    tn: int | None = None,
    act=jax.nn.silu,
    interpret: bool = False,
) -> jax.Array:
    """``act(x @ w1[e]) * (x @ w3[e])`` per row tile -> [M, F]."""
    return _call("gmm_swiglu", act, x, (w1, w3), tile_expert, n_live, tm, tn, interpret)


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
    return _call("gmm_act", act, x, (w,), tile_expert, n_live, tm, tn, interpret)
