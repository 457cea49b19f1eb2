"""Attention of a whole prompt under a mask given key by key, as one kernel.

``ops.attention.blocked_causal_attention`` does a prefill's attention as XLA
ops: a block of queries against a block of keys at a time, the block's
``[heads, queries, keys]`` float32 scores written to HBM, read back for the
running maximum, again for the exponentials, and again as the second
product's operand. The scores of a 24,576-token prompt are 77 GB a layer, so
that prefill is bound by the traffic of its own scores: 8 layers took most of
2.7 s where their products are 0.2 s of the MXU (PERF.md, PR 59). This kernel
keeps a block's scores in VMEM: the flash form (one pass over the keys, a
running maximum, sum and weighted values a query block) with the mask read a
tile at a time beside K and V.

The mask is DATA (``[T, T]`` int8, 1 where query ``t`` may see key ``s``,
the causal rule already in it): sparse attention's choice of rows
(``models/keye.py``) is made per query from scores, so no rule of positions
can stand for it. What the kernel knows of causality is which key blocks lie
wholly after a query block: those it neither fetches nor computes (their
index maps repeat the diagonal's block, which the pipeline does not fetch
twice). One grid step takes a block of queries of ALL the query heads of one
KV head against one block of that head's keys: K, V and the mask tile are
fetched once for the group, not once a head.

Keys (with the queries) and values each have a width of their own, both
whole lane tiles: ``models/keye.py`` passes 128 and 128, ``models/dots.py``
its expanded latent rows, keys of 192 padded to 256 beside values of 128,
and the scale of the 192.

A query none of whose keys is unmasked (a bucket's padding row) gets the mean
of the values it visited: finite, and read by nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 256  # query rows a grid step holds (of each of a KV head's query heads)
BLOCK_K = 512  # keys a grid step folds in
_MASKED = -1e30  # finite: a row masked so far keeps numbers, and the first real key's correction wipes them


def serves(T: int, key_width: int, value_width: int) -> bool:
    """Whether the kernel takes a prompt of ``T`` rows whose heads' keys (and
    queries) are ``key_width`` wide and values ``value_width``: whole blocks
    of queries and keys, and each width whole lane tiles. The two widths need
    not be equal (latent attention expanded: keys of 192 padded with zeros
    to 256, which change no product, beside values of 128; the caller then
    states the scale of the unpadded width)."""
    return T % BLOCK_K == 0 and T % BLOCK_Q == 0 and key_width % 128 == 0 and value_width % 128 == 0


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float, n_rep: int):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(ki * BLOCK_K <= qi * BLOCK_Q + BLOCK_Q - 1)  # a key block wholly after the queries: nothing to see
    def _():
        k, v = k_ref[0], v_ref[0]
        seen = mask_ref[...].astype(jnp.int32) != 0
        for r in range(n_rep):  # the query heads of this KV head, one after another over the same K, V and mask
            s = jax.lax.dot_general(q_ref[r], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, _MASKED)
            m_prev = m_ref[r]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[r] = alpha * l_ref[r] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[r] = alpha * acc_ref[r] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_ref[r] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def masked_attention(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array, scale: float | None = None,
                     interpret: bool = False) -> jax.Array:
    """q [T, H, d], k [T, H_kv, d], v [T, H_kv, dv], mask [T, T] int8 (1:
    query t sees key s; nothing after a query is ever set) -> [T, H, dv] in
    q's dtype: ``softmax(scale q . k)`` over the unmasked keys times v,
    grouped ``H / H_kv`` query heads to a KV head. ``scale`` None is ``d **
    -0.5``. ``serves(T, d, dv)`` must hold."""
    T, H, d = q.shape
    H_kv, dv = k.shape[1], v.shape[2]
    n_rep = H // H_kv
    if not serves(T, d, dv):
        raise ValueError(f"masked_attention takes whole blocks of {BLOCK_Q} queries and {BLOCK_K} keys and widths of "
                         f"whole lane tiles, not {T} rows of keys {d} and values {dv} wide")
    # heads first: a block is rows of one head, whole lane tiles wide
    qh = jnp.moveaxis(q, 1, 0).reshape(H_kv, n_rep, T, d)
    kh, vh = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)

    def last_seen(qi, ki):  # the key block to fetch: past the diagonal, the diagonal's again (no fetch)
        return jnp.minimum(ki, (qi * BLOCK_Q + BLOCK_Q - 1) // BLOCK_K)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(H_kv, T // BLOCK_Q, T // BLOCK_K),
        in_specs=[
            pl.BlockSpec((None, n_rep, BLOCK_Q, d), lambda g, qi, ki: (g, 0, qi, 0)),
            pl.BlockSpec((1, BLOCK_K, d), lambda g, qi, ki: (g, last_seen(qi, ki), 0)),
            pl.BlockSpec((1, BLOCK_K, dv), lambda g, qi, ki: (g, last_seen(qi, ki), 0)),
            pl.BlockSpec((BLOCK_Q, BLOCK_K), lambda g, qi, ki: (qi, last_seen(qi, ki))),
        ],
        out_specs=pl.BlockSpec((None, n_rep, BLOCK_Q, dv), lambda g, qi, ki: (g, 0, qi, 0)),
        scratch_shapes=[pltpu.VMEM((n_rep, BLOCK_Q, 1), jnp.float32), pltpu.VMEM((n_rep, BLOCK_Q, 1), jnp.float32),
                        pltpu.VMEM((n_rep, BLOCK_Q, dv), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=d ** -0.5 if scale is None else scale, n_rep=n_rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H_kv, n_rep, T, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="masked_prefill_attention",
    )(qh, kh, vh, mask.astype(jnp.int8))
    return jnp.moveaxis(out.reshape(H, T, dv), 0, 1)
