"""The recurrence of a Mamba-2 layer (state-space duality), as two kernels.

For each head ``h`` of ``H`` (``P`` channels a head), in group ``g = h //
(H / G)``, with ONE decay a head and token and ``B``, ``C`` a group::

    S_t[h] = exp(dt_t[h] * A[h]) * S_{t-1}[h] + dt_t[h] * x_t[h] (x) B_t[g]     (S: P x N)
    y_t[h] = S_t[h] C_t[g]

Everything here is float32. ``ops/pallas/ssm_scan.py`` is Mamba-1's: a state
``[N=16, D]`` with an exponential for every element; run through it this
state (``H x P x N`` = 128 x 64 x 128, 4 MiB a slot and layer) would take a
million exponentials a slot and layer where the recurrence has 128.

**The stored order** is ``[.., J, N, LW]``: ``N`` on the sublanes, and on the
lanes the ``P`` channels of ``hp = 128 // P`` neighbouring heads (``LW = hp *
P``; ``J = H / hp`` such blocks, two heads each at ``P`` = 64), not the
equations' ``[H, P, N]``. Why: the update needs ``x`` and the decay as a ROW
over a block's lanes (``[1, LW]``: the order ``x`` has in ``[S, H * P]``, a
plain slice) and ``B``, ``C`` as a COLUMN over its sublanes (``[N, 1]``), and
``y = sum_n S C`` then falls out as a row too, summed down the sublanes. With
``P`` on the sublanes it would be ``x`` and ``y`` that are columns, one a
head; here the columns are a group's, shared by its ``H / G`` heads. A
block is whole float32 tiles with no padding at ``N`` = ``LW`` = 128.
``stored`` / ``logical`` turn one order into the other (tests, the harness).

A token that must leave the state as it is (padding past a row's length, an
idle decode lane) is given ``dt = 0``: the decay is ``exp(0) = 1`` and the
input term 0, so ``S`` passes through exactly. The callers mask ``dt``.

``ssd_update`` (decode): ONE kernel from the rows the conv leaves to the row
the output projection reads. In: the whole stacked state ``[layers, slots, J,
N, LW]`` (read and written IN PLACE, aliased to the output, the layer a
prefetched scalar), ``dt [S, H]``, ``x [S, H, P]``, ``B``, ``C [S, G, N]``,
the gate ``z [S, H P]`` and the layer's ``A``, ``D [H]`` and the grouped
norm's weight ``[H P]``. Out: lanes ``0..S-1`` of layer ``row`` one step on,
and ``RMSNorm_groups((y + D x) silu(z)) w`` as a row ``[S, H P]`` in the
model's dtype, float32 until its last cast: the norm's groups are the
recurrence's (``H P / G`` channels of one group's heads), so nothing of the
epilogue leaves a lane. A step moves each live lane's 4 MiB once in and once
out and nothing else of the stack, the contract ``ssm_update`` keeps.

A grid step is ``LANES`` lanes WHOLE (4 MiB each, one piece of HBM), and the
state's DMAs are issued by the kernel itself and take TURNS: one direction
in flight at a time. What a step costs on a v5e, as measured with the
kernel's parts taken out (PERF.md, PR 57; ms a call of 128 lanes, 1.074 GB
of state moved, 1.311 ms at 819 GB/s): **the stream alone sets the time, the
body none of it.** Under the BlockSpec pipeline (a fetch and a write-back
always in flight together, blocks of 4 lanes of one group: the kernel until
PR 57) a plain copy took 1.667, the same as the whole body, at 1, 2 and 4 MiB
a block alike; with ``B`` and ``C`` constant 1.664, with ``y`` not reduced
1.664. The same bytes moved by hand, a fetch to its end and then a store to
its end: ``bytes / 702 GB/s + 0.28 us`` a transfer (1.815, 1.672, 1.601,
1.567 at 1, 2, 4 and 8 MiB a transfer): a stream of ONE direction reaches 86%
of the published rate, both directions at once 77-79%, and a transfer alone
in flight pays its start's latency once. So the blocks are large (the
latency) and whole lanes (one descriptor each way), and the directions
alternate: the next step's fetch runs under this step's arithmetic, then this
step's store runs alone, and the fetch after that is issued at the step's END
so that the grid's own step-to-step work (0.1 us) falls under it: 1.626 at
one lane a step, **1.591 at two** (16 MiB of buffers, a stated limit of 22),
1.583 at four (38 MiB, which the decode block's other residents do not leave
free). The arithmetic all fits under the fetch: a group of one lane is a
tile ``[blocks, LW]`` of ``x``, ``z`` and the row out; ``dt`` is read as SMEM
scalars and spread over a head's channels by selects (one exponential a
head), ``A`` and ``D`` likewise; the columns of ``B`` and ``C`` are made once a
lane and group by spreading a row over the sublanes and turning it, and kept
in VMEM scratch; a block is two multiplies and an add an element, stored
where it was read, then a multiply and a sum down the sublanes for ``y``; the
skip, the gate and the norm run on the group's one tile. The loop over the
groups is a ``fori_loop`` written out by the compiler (``unroll=True``), the
lanes and blocks in Python: a body written out tile by tile in Python read
the same on the device and took 80 s to trace (a decode program's set-up); a
block loop left as a loop took 2.00.

``ssd_scan`` (prefill, continuation): the chunked form. Inside a chunk of
``CHUNK`` tokens, with ``cum`` the float32 running sum of ``dt A`` over the
chunk's tokens (inclusive)::

    y_t  = sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s  +  exp(cum_t) S_in C_t
    S_out = exp(cum_L) S_in + sum_s exp(cum_L - cum_s) dt_s x_s (x) B_s

``C B^T`` (a group's, shared by its heads), the masked product with ``x``,
``S_in C`` and ``x^T B`` are matrix products on the MXU at float32 contract
precision; only the chunk-to-chunk state is a recurrence, carried in VMEM
along the last grid axis. Every decay is the exponential of a DIFFERENCE of
the running sum (at most 0), never a quotient of two exponentials: ``exp(-
cum_s)`` alone overflows float32 within a chunk of fast heads. A snapshot
due ``snap_rel`` tokens into the row (a multiple of ``GROUP``) is the same
sum cut at that token. Chunks wholly past a row's length are skipped
(``n_chunks``): they write zeros for ``y`` and leave ``S`` alone.

``*_reference`` are the same functions in plain XLA, a ``lax.scan`` over
time a token at a time: what the programs run where there is no TPU and
what the tests hold the kernels to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128  # tokens a grid step of the scan: the published `chunk_size`
GROUP = 16  # the snapshot falls on a multiple of this (the page size divides into it or it into the page)
LANES = 2  # decode lanes a grid step of the update: their whole state of one layer, 4 MiB a lane in one piece of HBM


def heads_per_tile(head_dim: int, heads_per_group: int) -> int:
    """Heads whose channels share a block's lanes: as many as fill a lane
    tile of 128, of one group."""
    for hp in range(min(max(1, 128 // head_dim), heads_per_group), 0, -1):
        if heads_per_group % hp == 0:
            return hp
    return 1


def stored(s: jax.Array, hp: int) -> jax.Array:
    """``[.., H, P, N]`` (the equations' order) -> ``[.., H / hp, N, hp * P]``."""
    *lead, H, P, N = s.shape
    s = jnp.moveaxis(s.reshape(*lead, H // hp, hp, P, N), -1, -3)
    return s.reshape(*lead, H // hp, N, hp * P)


def logical(s: jax.Array, head_dim: int) -> jax.Array:
    """``stored``'s inverse: ``[.., J, N, LW]`` -> ``[.., H, P, N]``."""
    *lead, J, N, LW = s.shape
    hp = LW // head_dim
    s = jnp.moveaxis(s.reshape(*lead, J, N, hp, head_dim), -3, -1)
    return s.reshape(*lead, J * hp, head_dim, N)


_dot = functools.partial(jax.lax.dot_general, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _rows(dt, x):
    """dt [.., H], x [.., H, P] -> dt * x as a row over ``H * P`` lanes."""
    return (dt[..., None] * x).reshape(*dt.shape[:-1], -1)


# ---------------------------------------------------------------------------
# prefill: the chunked scan
# ---------------------------------------------------------------------------


def _scan_kernel(snap_ref, nchunk_ref, xdt_ref, cumx_ref, cumt_ref, b_ref, c_ref, h0_ref,
                 y_ref, end_ref, snapped_ref, h_ref, *, chunk, head_dim):
    r, t = pl.program_id(0), pl.program_id(2)
    blocks, _, lw = h_ref.shape
    f32 = jnp.float32

    @pl.when(t == 0)
    def _():
        h_ref[...] = h0_ref[0]
        snapped_ref[0] = h0_ref[0]

    @pl.when(t < nchunk_ref[r])
    def _():
        m = snap_ref[r] - t * chunk  # tokens of this chunk before the snapshot

        @pl.when(m == 0)
        def _():
            snapped_ref[0] = h_ref[...]

        bm, cm = b_ref[0], c_ref[0]  # [L, N]
        cb = _dot(cm, bm, _NT)  # [L, L]: C_t . B_s
        at_or_before = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
                        >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
        for head in range(blocks * lw // head_dim):  # inside the chunk: a head's own decays over its group's C B^T
            lanes = slice(head * head_dim, (head + 1) * head_dim)
            cum_t = cumx_ref[0, :, head * head_dim:head * head_dim + 1]  # [L, 1]
            cum_s = cumt_ref[0, 0, 0, head:head + 1, :]  # [1, L]
            decayed = jnp.exp(jnp.where(at_or_before, cum_t - cum_s, -jnp.inf)) * cb
            y_ref[0, :, lanes] = _dot(decayed, xdt_ref[0, :, lanes], _NN)
        token = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        before = token < m
        for j in range(blocks):  # across chunks: the state in, and the state out
            lanes = slice(j * lw, (j + 1) * lw)
            s_in, cum, xdt = h_ref[j], cumx_ref[0, :, lanes], xdt_ref[0, :, lanes]
            y_ref[0, :, lanes] += _dot(cm, s_in, _NN) * jnp.exp(cum)
            last = cumx_ref[0, chunk - 1:chunk, lanes]  # [1, LW]
            h_ref[j] = jnp.exp(last) * s_in + _dot(bm, xdt * jnp.exp(last - cum), _TN)

            @pl.when((m > 0) & (m < chunk))
            def _(j=j, lanes=lanes, s_in=s_in, cum=cum, xdt=xdt):
                # the running sum at the last token kept, as a masked sum: a load at a traced row must be tile-aligned
                cut = jnp.sum(jnp.where(token == m - 1, cum, 0.0), axis=0, keepdims=True)
                kept = xdt * jnp.exp(jnp.where(before, cut - cum, -jnp.inf))
                snapped_ref[0, j] = jnp.exp(cut) * s_in + _dot(bm, kept, _TN)

        @pl.when(m == chunk)
        def _():
            snapped_ref[0] = h_ref[...]

    @pl.when(t >= nchunk_ref[r])
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, f32)

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        end_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_scan(dt, x, b, c, a, h0, snap_rel, n_chunks, interpret: bool = False):
    """dt [R, T, H] (0 where a token is padding); x [R, T, H, P]; b, c [R, T,
    G, N]; a [H]; h0 [R, J, N, LW] (stored order); snap_rel, n_chunks [R]
    int32 -> (y [R, T, H, P], h_end, h_snap as h0). T is padded here to whole
    chunks (dt 0: the state passes through)."""
    f32 = jnp.float32
    R, T, H, P = x.shape
    G, N = b.shape[2:]
    J, _, LW = h0.shape[1:]
    L = CHUNK
    pad = -T % L
    dt, x, b, c = (jnp.pad(v.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in (dt, x, b, c))
    Tp, nC, width = T + pad, (T + pad) // L, H // G * P
    cum = jnp.cumsum((dt * a.astype(f32)).reshape(R, nC, L, H), axis=2)  # the float32 running sum, a chunk at a time
    cumx = jnp.repeat(cum.reshape(R, Tp, H), P, axis=2)
    cum_t = jnp.swapaxes(cum, 2, 3).reshape(R, nC, G, H // G, L)
    seq = pl.BlockSpec((1, L, width), lambda r, g, t, *_: (r, t, g))
    coef = pl.BlockSpec((1, L, N), lambda r, g, t, *_: (r, t, g))
    state = pl.BlockSpec((1, J // G, N, LW), lambda r, g, t, *_: (r, g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, G, nC),
        in_specs=[seq, seq, pl.BlockSpec((1, 1, 1, H // G, L), lambda r, g, t, *_: (r, t, g, 0, 0)), coef, coef, state],
        out_specs=[seq, state, state],
        scratch_shapes=[pltpu.VMEM((J // G, N, LW), f32)],
    )
    y, h_end, h_snap = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=L, head_dim=P),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, Tp, H * P), f32), jax.ShapeDtypeStruct(h0.shape, f32),
                   jax.ShapeDtypeStruct(h0.shape, f32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",  # the name `acpbench/device_scopes.py` files under mixer/ssm_scan
    )(snap_rel.astype(jnp.int32), n_chunks.astype(jnp.int32), _rows(dt, x), cumx, cum_t,
      b.reshape(R, Tp, G * N), c.reshape(R, Tp, G * N), h0.astype(f32))
    return y[:, :T].reshape(R, T, H, P), h_end, h_snap


def ssd_scan_reference(dt, x, b, c, a, h0, snap_rel, n_chunks=None):
    """``ssd_scan`` as the recurrence itself, a ``lax.scan`` a token at a time."""
    f32 = jnp.float32
    dt, x, b, c, a = (v.astype(f32) for v in (dt, x, b, c, a))
    H, P = x.shape[2:]
    per_group = H // b.shape[2]

    def token(carry, xs):
        h, snapped, i = carry  # h [R, H, P, N]
        snapped = jnp.where((snap_rel == i)[:, None, None, None], h, snapped)
        dt_t, x_t, b_t, c_t = xs  # [R, H], [R, H, P], [R, G, N], [R, G, N]
        b_t, c_t = jnp.repeat(b_t, per_group, axis=1), jnp.repeat(c_t, per_group, axis=1)
        h = jnp.exp(dt_t * a)[:, :, None, None] * h + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :]
        return (h, snapped, i + 1), jnp.sum(h * c_t[:, :, None, :], axis=-1)

    hp = h0.shape[-1] // P
    h0 = logical(h0.astype(f32), P)
    (h, snapped, n), y = jax.lax.scan(
        token, (h0, h0, jnp.int32(0)), tuple(jnp.swapaxes(v, 0, 1) for v in (dt, x, b, c)))
    snapped = jnp.where((snap_rel == n)[:, None, None, None], h, snapped)
    return jnp.swapaxes(y, 0, 1), stored(h, hp), stored(snapped, hp)


def scan(dt, x, b, c, a, h0, snap_rel, n_chunks, kernel: bool | None = None):
    """The prefill's recurrence: the kernel on a TPU (``kernel`` None), the
    ``lax.scan`` elsewhere, as ``ssm_scan.scan`` chooses."""
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    if kernel:
        return ssd_scan(dt, x, b, c, a, h0, snap_rel, n_chunks)
    return ssd_scan_reference(dt, x, b, c, a, h0, snap_rel)


# ---------------------------------------------------------------------------
# decode: one step, the stacked state in place
# ---------------------------------------------------------------------------


def _update_kernel(row_ref, dt_ref, a_ref, d_ref, state_hbm, x_ref, z_ref, b_ref, c_ref, w_ref, y_ref, out_hbm,
                   buf, sem, bcol_ref, ccol_ref, sums_ref, *, lanes, head_dim, eps):
    f32 = jnp.float32
    k, last = pl.program_id(0), pl.num_programs(0) - 1
    groups, blocks, lw = w_ref.shape
    n = buf.shape[3]
    hp = lw // head_dim
    # a group's `blocks x lw` channels as one tile, a block a row: the head a channel is of, counted from the group's first
    head_of = (jax.lax.broadcasted_iota(jnp.int32, (blocks, lw), 0) * hp
               + jax.lax.broadcasted_iota(jnp.int32, (blocks, lw), 1) // head_dim)
    slot = k % 2

    def fetch(step, into):  # the whole state of a step's lanes in layer `row`: `lanes` x 4 MiB in one piece
        return pltpu.make_async_copy(state_hbm.at[row_ref[0], pl.ds(step * lanes, lanes)], buf.at[into], sem.at[0, into])

    def store(step, out_of):
        return pltpu.make_async_copy(buf.at[out_of], out_hbm.at[row_ref[0], pl.ds(step * lanes, lanes)], sem.at[1, out_of])

    def per_head(scalar, first):
        """A group's tile from its heads' scalars (SMEM), head ``first`` on, each spread over its channels."""
        tile = jnp.full((blocks, lw), scalar(first), f32)
        for h in range(1, blocks * hp):
            tile = jnp.where(head_of == h, scalar(first + h), tile)
        return tile

    # The state's DMAs are issued here and take TURNS: one direction in flight at a time, a fetch of the next step's
    # lanes under this step's arithmetic, then this step's store alone. The next fetch but one is issued at the
    # step's end, so that the grid's own step-to-step work falls under it.
    @pl.when(k == 0)
    def _():
        fetch(0, 0).start()
        fetch(0, 0).wait()

        @pl.when(last > 0)
        def _():
            fetch(1, 1).start()

    def group(g, carry):
        first = g * (blocks * hp)
        a, d = per_head(lambda h: a_ref[h], first), per_head(lambda h: d_ref[h], first)
        for s in range(lanes):
            lane = k * lanes + s
            dt, x = per_head(lambda h: dt_ref[lane, h], first), x_ref[s, g]
            decay, xdt = jnp.exp(dt * a), dt * x  # one exponential a head, spread over its channels
            # this group's B and C down the sublanes and across the lanes, once a lane: a row spread over the
            # sublanes and turned, kept in VMEM where a block's arithmetic reads it beside the block
            bcol_ref[...] = jnp.broadcast_to(b_ref[s, pl.ds(g, 1), :], (lw, n)).T
            ccol_ref[...] = jnp.broadcast_to(c_ref[s, pl.ds(g, 1), :], (lw, n)).T
            for j in range(blocks):  # an element of state: two multiplies and an add, then a multiply and an add for y
                at = g * blocks + j
                h = decay[j:j + 1, :] * buf[slot, s, at] + bcol_ref[...] * xdt[j:j + 1, :]
                buf[slot, s, at] = h
                sums_ref[j:j + 1, :] = jnp.sum(h * ccol_ref[...], axis=0, keepdims=True)
            # the skip, the gate and the grouped norm: the norm's group is this group's heads, `blocks * lw` channels
            y = (sums_ref[...] + d * x) * jax.nn.silu(z_ref[s, g])
            mean = jnp.sum(jnp.sum(y * y, axis=1, keepdims=True), axis=0, keepdims=True) / (blocks * lw)
            y_ref[s, g] = (y * jax.lax.rsqrt(mean + eps) * w_ref[g]).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, groups, group, 0, unroll=True)  # traced once, written out: the scheduler sees a lane whole

    @pl.when(k < last)
    def _():
        fetch(k + 1, 1 - slot).wait()

    store(k, slot).start()
    store(k, slot).wait()

    @pl.when(k + 1 < last)
    def _():
        fetch(k + 2, slot).start()


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "interpret", "lanes"))
def ssd_update(state, row, dt, x, b, c, a, *, z, d, norm, eps, dtype, interpret: bool = False, lanes: int = LANES):
    """state [layers, slots, J, N, LW] float32 (stored order); row () int32,
    the layer; dt [S, H] (0: the lane's state passes through); x [S, H, P];
    b, c [S, G, N]; a [H]; z [S, H * P] the gate; d [H] the skip; norm [H * P]
    the grouped norm's weight -> (the gated, normed row [S, H * P] in
    ``dtype``, state with ``state[row, :S]`` one step on). S <= slots."""
    f32 = jnp.float32
    S, H, P = x.shape
    G, N = b.shape[1:]
    J, _, LW = state.shape[2:]
    assert J % G == 0, (J, G)
    lanes = lanes if S % lanes == 0 else 1
    tiles = (G, J // G, LW)  # a lane's `H * P` channels a group, a block and a block's lanes: a group is one tile
    row_of = pl.BlockSpec((lanes,) + tiles, lambda k, *_: (k, 0, 0, 0))
    columns = pl.BlockSpec((lanes, G, N), lambda k, *_: (k, 0, 0))
    held = 2 * lanes * J * N * LW * 4  # two buffers of a step's lanes; the limit states them and 6 MiB for the rows, the columns and the compiler
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # the layer; dt, A and D a head, read as scalars and spread over a head's channels
        grid=(S // lanes,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY), row_of, row_of, columns, columns,
                  pl.BlockSpec(tiles, lambda k, *_: (0, 0, 0))],
        out_specs=[row_of, pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((2, lanes, J, N, LW), f32), pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((N, LW), f32), pltpu.VMEM((N, LW), f32), pltpu.VMEM((J // G, LW), f32)],
    )
    y, state = pl.pallas_call(
        functools.partial(_update_kernel, lanes=lanes, head_dim=P, eps=eps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S,) + tiles, dtype), jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={4: 1},  # operands 0 to 3 are the prefetched scalars
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=max(held + (6 << 20), 16 << 20)),
        interpret=interpret,
        name="ssm_update",  # the name `acpbench/device_scopes.py` files under mixer/ssm_update
    )(jnp.reshape(row, (1,)).astype(jnp.int32), dt.astype(f32), a.astype(f32), d.astype(f32), state,
      x.astype(f32).reshape((S,) + tiles), z.astype(f32).reshape((S,) + tiles), b.astype(f32), c.astype(f32),
      norm.astype(f32).reshape(tiles))
    return y.reshape(S, H * P), state


def gate_norm(y, x, z, d, norm, n_groups: int, eps: float):
    """What follows the recurrence in a Mamba-2 layer, float32: the skip ``y +
    D x`` (y, x [.., H, P]; d [H]), the gate ``silu(z)`` (z [.., H * P]) and
    the RMS norm over each of ``n_groups`` groups of channels, times ``norm``
    [H * P] -> [.., H * P]."""
    f32 = jnp.float32
    y = (y + d.astype(f32)[:, None] * x).reshape(z.shape) * jax.nn.silu(z.astype(f32))
    groups = y.reshape(*z.shape[:-1], n_groups, -1)
    groups = groups * jax.lax.rsqrt(jnp.mean(jnp.square(groups), axis=-1, keepdims=True) + eps)
    return groups.reshape(z.shape) * norm.astype(f32)


def ssd_update_reference(state, row, dt, x, b, c, a, *, z, d, norm, eps, dtype):
    """``ssd_update`` in plain XLA, in the stored order."""
    f32 = jnp.float32
    S, H, P = x.shape
    G = b.shape[1]
    J, N, LW = state.shape[2:]
    dt, x, b, c, a = (v.astype(f32) for v in (dt, x, b, c, a))
    per_block = J // G  # blocks of lanes a group
    h = jax.lax.dynamic_slice(state, (row, 0, 0, 0, 0), (1, S) + state.shape[2:])[0]  # [S, J, N, LW]
    decay = jnp.repeat(jnp.exp(dt * a), P, axis=1).reshape(S, J, 1, LW)
    b_col = jnp.repeat(b, per_block, axis=1)[..., None]  # [S, J, N, 1]
    c_col = jnp.repeat(c, per_block, axis=1)[..., None]
    h = decay * h + b_col * _rows(dt, x).reshape(S, J, 1, LW)
    y = jnp.sum(h * c_col, axis=2).reshape(S, H, P)
    return (gate_norm(y, x, z, d, norm, G, eps).astype(dtype),
            jax.lax.dynamic_update_slice(state, h[None], (row, 0, 0, 0, 0)))


def update(state, row, dt, x, b, c, a, kernel: bool | None = None, **epilogue):
    """The decode step's recurrence and what follows it (``z``, ``d``, ``norm``,
    ``eps``, ``dtype``: ``ssd_update``), chosen as ``scan`` is."""
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    return (ssd_update if kernel else ssd_update_reference)(state, row, dt, x, b, c, a, **epilogue)
