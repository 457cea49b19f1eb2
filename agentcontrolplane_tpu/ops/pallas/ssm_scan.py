"""The selective state-space recurrence of a Mamba-1 layer, as two kernels.

For each channel ``c`` of ``D`` and state ``n`` of ``N``::

    h_t[n, c] = exp(delta_t[c] * A[n, c]) * h_{t-1}[n, c] + delta_t[c] * B_t[n] * u_t[c]
    y_t[c]    = sum_n h_t[n, c] * C_t[n]

Everything here is float32. The state is laid out ``[.., N, D]``: the ``N``
(16) states on the sublanes and the channels on the lanes, so a slot's state
of one layer is ``N * D / 1024`` whole float32 tiles with no padding (the
published ``[D, N]`` order would pad 16 lanes to 128, eight times the
memory). ``A`` comes in the same order, ``[N, D]``.

A token that must leave the state as it is (padding past a row's length, an
inactive decode lane) is given ``delta = 0``: ``exp(0) = 1`` and the input
term is 0, so ``h`` passes through exactly. The callers mask ``delta``; the
kernels know no lengths but the one thing that cannot be had that way, the
snapshot.

``ssm_scan`` (prefill and continuation): time in chunks of ``CHUNK`` tokens
on the last ("arbitrary") grid axis, ``h`` of one row and one block of
channels held in VMEM across the chunks, never an ``[T, N, D]`` array in HBM.
The state after the row's last chunk is written once (``h_end``), and a
second copy where the row's snapshot is due (``snap_rel`` tokens into the
row: a multiple of the page size, so it falls on the edge of a group of
``GROUP`` tokens; at 0 it is the state the row started from). Chunks that lie
wholly past a row's length are skipped (``n_chunks``): they write zeros for
``y`` and leave ``h`` alone.

``ssm_update`` (decode): one step of the same recurrence over the lanes
``0..S-1`` of layer ``row`` of the whole stacked state
``[layers, slots, N, D]``, read and written IN PLACE (the state is aliased to
the output; the layer comes as a prefetched scalar into the index maps), so a
step moves each live lane's state once in and once out and nothing else of
the stack. The programs call it in their Mamba layers' loop bodies with the
stack as the loops' carry; the stack never passes through a conditional,
whose pass-through branch the compiler answered with a copy of the whole
stack a layer (PERF.md, PR 37).

``*_reference`` are the same functions in plain XLA (a ``lax.scan`` over
time), what the programs run where there is no TPU and what the tests hold
the kernels to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128  # tokens a grid step: one lane tile of B and C
GROUP = 16  # the snapshot falls on a multiple of this (the page size divides into it or it into the page)
LANES = 8  # decode lanes a grid step of the update


def _col_tile(n: int, want: int) -> int:
    """The widest channel tile <= want that divides n in whole lane tiles
    (n itself where there is none: the CPU tests' widths)."""
    for t in range(min(want, n) // 128 * 128, 0, -128):
        if n % t == 0:
            return t
    return n


def _step(h, a, delta_t, du_t, b_t, c_t):
    """One token: h [N, tc]; a [N, tc]; delta_t, du_t [1, tc]; b_t, c_t [N, 1]."""
    h = jnp.exp(delta_t * a) * h + du_t * b_t
    return h, jnp.sum(h * c_t, axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# prefill: the chunked scan
# ---------------------------------------------------------------------------


def _scan_kernel(snap_ref, nchunk_ref, delta_ref, u_ref, b_ref, c_ref, a_ref, h0_ref,
                 y_ref, end_ref, snapped_ref, h_ref, *, chunk):
    r, t = pl.program_id(0), pl.program_id(2)
    snap_rel = snap_ref[r]

    @pl.when(t == 0)
    def _():
        h_ref[...] = h0_ref[0]
        snapped_ref[0] = h0_ref[0]

    @pl.when(t < nchunk_ref[r])
    def _():
        a = a_ref[...]
        h = h_ref[...]
        for i in range(chunk):
            if i % GROUP == 0:
                @pl.when(t * chunk + i == snap_rel)
                def _(h=h):
                    snapped_ref[0] = h
            delta_t = delta_ref[0, i:i + 1, :]
            h, y = _step(h, a, delta_t, delta_t * u_ref[0, i:i + 1, :], b_ref[0, :, i:i + 1], c_ref[0, :, i:i + 1])
            y_ref[0, i:i + 1, :] = y
        h_ref[...] = h

        @pl.when((t + 1) * chunk == snap_rel)
        def _():
            snapped_ref[0] = h

    @pl.when(t >= nchunk_ref[r])
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        end_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret", "col_tile"))
def ssm_scan(delta, u, b, c, a, h0, snap_rel, n_chunks, interpret: bool = False, col_tile: int = 512):
    """delta, u [R, T, D]; b, c [R, T, N]; a [N, D]; h0 [R, N, D]; snap_rel,
    n_chunks [R] int32 -> (y [R, T, D], h_end [R, N, D], h_snap [R, N, D]).
    T is padded here to whole chunks (delta 0: the state passes through)."""
    R, T, D = delta.shape
    N = a.shape[0]
    chunk = CHUNK
    pad = -T % chunk
    if pad:
        delta, u, b, c = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (delta, u, b, c))
    Tp = T + pad
    tc = _col_tile(D, col_tile)
    f32 = jnp.float32
    seq = pl.BlockSpec((1, chunk, tc), lambda r, j, t, *_: (r, t, j))
    coef = pl.BlockSpec((1, N, chunk), lambda r, j, t, *_: (r, 0, t))
    state = pl.BlockSpec((1, N, tc), lambda r, j, t, *_: (r, 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, D // tc, Tp // chunk),
        in_specs=[seq, seq, coef, coef, pl.BlockSpec((N, tc), lambda r, j, t, *_: (0, j)), state],
        out_specs=[seq, state, state],
        scratch_shapes=[pltpu.VMEM((N, tc), f32)],
    )
    y, h_end, h_snap = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, Tp, D), f32), jax.ShapeDtypeStruct((R, N, D), f32),
                   jax.ShapeDtypeStruct((R, N, D), f32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(snap_rel.astype(jnp.int32), n_chunks.astype(jnp.int32), delta.astype(f32), u.astype(f32),
      jnp.swapaxes(b.astype(f32), 1, 2), jnp.swapaxes(c.astype(f32), 1, 2), a.astype(f32), h0.astype(f32))
    return y[:, :T], h_end, h_snap


def ssm_scan_reference(delta, u, b, c, a, h0, snap_rel, n_chunks=None):
    """``ssm_scan`` as a ``lax.scan`` over time, a token at a time."""
    f32 = jnp.float32
    delta, u, b, c, a = (x.astype(f32) for x in (delta, u, b, c, a))

    def token(carry, xs):
        h, snapped, i = carry
        snapped = jnp.where((snap_rel == i)[:, None, None], h, snapped)
        d_t, u_t, b_t, c_t = xs  # [R, D], [R, D], [R, N], [R, N]
        h = jnp.exp(d_t[:, None, :] * a[None]) * h + (d_t * u_t)[:, None, :] * b_t[:, :, None]
        return (h, snapped, i + 1), jnp.sum(h * c_t[:, :, None], axis=1)

    h0 = h0.astype(f32)
    (h, snapped, n), y = jax.lax.scan(
        token, (h0, h0, jnp.int32(0)),
        tuple(jnp.swapaxes(x, 0, 1) for x in (delta, u, b, c)))
    snapped = jnp.where((snap_rel == n)[:, None, None], h, snapped)
    return jnp.swapaxes(y, 0, 1), h, snapped


def scan(delta, u, b, c, a, h0, snap_rel, n_chunks, kernel: bool | None = None):
    """The prefill's recurrence: the kernel on a TPU (``kernel`` None), the
    ``lax.scan`` elsewhere, as ``ops/moe.py`` chooses its grouped matmul."""
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    if kernel:
        return ssm_scan(delta, u, b, c, a, h0, snap_rel, n_chunks)
    return ssm_scan_reference(delta, u, b, c, a, h0, snap_rel)


# ---------------------------------------------------------------------------
# decode: one step, the stacked state in place
# ---------------------------------------------------------------------------


def _update_kernel(row_ref, state_ref, delta_ref, u_ref, b_ref, c_ref, a_ref, y_ref, out_ref, *, lanes):
    del row_ref
    a = a_ref[...]
    for s in range(lanes):
        delta_t = delta_ref[s:s + 1, :]
        h, y = _step(state_ref[0, s], a, delta_t, delta_t * u_ref[s:s + 1, :], b_ref[s], c_ref[s])
        out_ref[0, s] = h
        y_ref[s:s + 1, :] = y


@functools.partial(jax.jit, static_argnames=("interpret", "col_tile"))
def ssm_update(state, row, delta, u, b, c, a, interpret: bool = False, col_tile: int = 2560):
    """state [layers, slots, N, D] float32; row () int32, the layer; delta,
    u [S, D]; b, c [S, N]; a [N, D] -> (y [S, D], state with
    ``state[row, :S]`` one step on). S <= slots, a multiple of LANES or S
    itself."""
    S, D = delta.shape
    N = a.shape[0]
    lanes = LANES if S % LANES == 0 else S
    tc = _col_tile(D, col_tile)
    f32 = jnp.float32
    per_lane = pl.BlockSpec((lanes, tc), lambda i, j, row: (i, j))
    column = pl.BlockSpec((lanes, N, 1), lambda i, j, row: (i, 0, 0))
    block = pl.BlockSpec((1, lanes, N, tc), lambda i, j, row: (row[0], i, 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S // lanes, D // tc),
        in_specs=[block, per_lane, per_lane, column, column, pl.BlockSpec((N, tc), lambda i, j, row: (0, j))],
        out_specs=[per_lane, block],
    )
    return pl.pallas_call(
        functools.partial(_update_kernel, lanes=lanes),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, D), f32), jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={1: 1},  # operand 0 is the prefetched scalar
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_update",
    )(jnp.reshape(row, (1,)).astype(jnp.int32), state,
      delta.astype(f32), u.astype(f32), b.astype(f32)[:, :, None], c.astype(f32)[:, :, None], a.astype(f32))


def ssm_update_reference(state, row, delta, u, b, c, a):
    """``ssm_update`` in plain XLA."""
    f32 = jnp.float32
    S = delta.shape[0]
    delta, u, b, c, a = (x.astype(f32) for x in (delta, u, b, c, a))
    h = jax.lax.dynamic_slice(state, (row, 0, 0, 0), (1, S) + state.shape[2:])[0]
    h = jnp.exp(delta[:, None, :] * a[None]) * h + (delta * u)[:, None, :] * b[:, :, None]
    return jnp.sum(h * c[:, :, None], axis=1), jax.lax.dynamic_update_slice(state, h[None], (row, 0, 0, 0))


def update(state, row, delta, u, b, c, a, kernel: bool | None = None):
    """The decode step's recurrence, chosen as ``scan`` is."""
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    return (ssm_update if kernel else ssm_update_reference)(state, row, delta, u, b, c, a)
