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

``ssd_update`` (decode): one step over lanes ``0..S-1`` of layer ``row`` of
the whole stacked state ``[layers, slots, J, N, LW]``, read and written IN
PLACE (aliased to the output, the layer a prefetched scalar in the index
maps): a step moves each live lane's 4 MiB once in and once out and nothing
else of the stack, the contract ``ssm_update`` keeps. The grid is (lanes,
groups): a block is ``lanes`` lanes of one group's heads.

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
LANES = 4  # decode lanes a grid step of the update: 2 MiB of one group's state in and as much out


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


def _update_kernel(row_ref, state_ref, decay_ref, xdt_ref, bt_ref, ct_ref, y_ref, out_ref, *, lanes):
    del row_ref
    blocks, _, lw = state_ref.shape[2:]
    group = jax.lax.broadcasted_iota(jnp.int32, (1, bt_ref.shape[2]), 1) == pl.program_id(1)
    for s in range(lanes):
        # this group's B and C as columns down the sublanes, shared by its heads
        b_col = jnp.sum(jnp.where(group, bt_ref[s], 0.0), axis=1, keepdims=True)
        c_col = jnp.sum(jnp.where(group, ct_ref[s], 0.0), axis=1, keepdims=True)
        for j in range(blocks):
            at = slice(j * lw, (j + 1) * lw)
            h = decay_ref[s, :, at] * state_ref[0, s, j] + b_col * xdt_ref[s, :, at]
            out_ref[0, s, j] = h
            y_ref[s, :, at] = jnp.sum(h * c_col, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret", "lanes"))
def ssd_update(state, row, dt, x, b, c, a, interpret: bool = False, lanes: int = LANES):
    """state [layers, slots, J, N, LW] float32 (stored order); row () int32,
    the layer; dt [S, H] (0: the lane's state passes through); x [S, H, P];
    b, c [S, G, N]; a [H] -> (y [S, H, P], state with ``state[row, :S]`` one
    step on). S <= slots."""
    f32 = jnp.float32
    S, H, P = x.shape
    G, N = b.shape[1:]
    J, _, LW = state.shape[2:]
    dt, x = dt.astype(f32), x.astype(f32)
    width = H // G * P
    lanes = lanes if S % lanes == 0 else S
    decay = jnp.repeat(jnp.exp(dt * a.astype(f32)), P, axis=1)[:, None, :]  # one exponential a head, a row over its lanes
    per_lane = pl.BlockSpec((lanes, 1, width), lambda i, g, row: (i, 0, g))
    columns = pl.BlockSpec((lanes, N, G), lambda i, g, row: (i, 0, 0))
    block = pl.BlockSpec((1, lanes, J // G, N, LW), lambda i, g, row: (row[0], i, g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S // lanes, G),
        in_specs=[block, per_lane, per_lane, columns, columns],
        out_specs=[per_lane, block],
    )
    y, state = pl.pallas_call(
        functools.partial(_update_kernel, lanes=lanes),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, 1, H * P), f32), jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={1: 1},  # operand 0 is the prefetched scalar
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_update",  # the name `acpbench/device_scopes.py` files under mixer/ssm_update
    )(jnp.reshape(row, (1,)).astype(jnp.int32), state, decay, _rows(dt, x)[:, None, :],
      jnp.swapaxes(b.astype(f32), 1, 2), jnp.swapaxes(c.astype(f32), 1, 2))
    return y.reshape(S, H, P), state


def ssd_update_reference(state, row, dt, x, b, c, a):
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
    return y, jax.lax.dynamic_update_slice(state, h[None], (row, 0, 0, 0, 0))


def update(state, row, dt, x, b, c, a, kernel: bool | None = None):
    """The decode step's recurrence, chosen as ``scan`` is."""
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    return (ssd_update if kernel else ssd_update_reference)(state, row, dt, x, b, c, a)
