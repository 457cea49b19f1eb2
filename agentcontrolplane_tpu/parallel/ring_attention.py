"""Ring attention: sequence-parallel exact attention over an ``sp`` mesh axis.

Long-context design (build brief: "ring attention or all-to-all
sequence/context parallelism for long sequences"): the sequence dimension is
sharded across devices; each device keeps its Q chunk resident while K/V
chunks rotate around the ring via ``lax.ppermute`` (one hop per step, riding
ICI), accumulating an online-softmax (flash-style m/l/acc running state) so
the result is EXACT full attention — memory per device stays O(T/sp).

Used through ``shard_map`` (see ``ring_causal_attention``); the inner
function is written per-device (local arrays, explicit collectives).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import repeat_kv

NEG_INF = -1e30


def _ring_attention_local(
    q: jax.Array,  # [B, Tq, H, d] local chunk
    k: jax.Array,  # [B, Tk, H_kv, d] local chunk
    v: jax.Array,  # [B, Tk, H_kv, d]
    q_pos: jax.Array,  # [B, Tq] global positions (-1 = padding)
    kv_pos: jax.Array,  # [B, Tk]
    axis_name: str,
) -> jax.Array:
    sp = jax.lax.psum(1, axis_name)
    B, Tq, H, d = q.shape
    n_rep = H // k.shape[-2]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    qf = q.astype(jnp.float32)

    m = jnp.full((B, H, Tq), -jnp.inf, dtype=jnp.float32)
    l = jnp.zeros((B, H, Tq), dtype=jnp.float32)
    acc = jnp.zeros((B, H, Tq, d), dtype=jnp.float32)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    from ..ops.attention import online_softmax_finalize, online_softmax_step

    def step(carry, _):
        k, v, kv_pos, m, l, acc = carry
        kf = repeat_kv(k, n_rep).astype(jnp.float32)
        vf = repeat_kv(v, n_rep).astype(jnp.float32)
        mask = (
            (kv_pos[:, None, None, :] <= q_pos[:, None, :, None])
            & (q_pos[:, None, :, None] >= 0)
            & (kv_pos[:, None, None, :] >= 0)
        )
        m, l, acc = online_softmax_step(qf, kf, vf, mask, m, l, acc, scale)
        # rotate k/v/kv_pos one hop around the ring
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        kv_pos = jax.lax.ppermute(kv_pos, axis_name, perm)
        return (k, v, kv_pos, m, l, acc), None

    (k, v, kv_pos, m, l, acc), _ = jax.lax.scan(
        step, (k, v, kv_pos, m, l, acc), None, length=sp
    )
    return online_softmax_finalize(l, acc, q.dtype)  # [B,Tq,H,d]


def ring_causal_attention(
    mesh: Mesh,
    q: jax.Array,  # [B, T, H, d] — T sharded over 'sp'
    k: jax.Array,
    v: jax.Array,
    positions: jax.Array,  # [B, T] global positions, sharded over 'sp'
    batch_axes: tuple[str, ...] = ("dp",),
    seq_axis: str = "sp",
    head_axis: str = "tp",
) -> jax.Array:
    """shard_map wrapper: exact causal attention with the sequence dimension
    sharded over ``seq_axis`` and heads over ``head_axis``."""
    batch_spec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    qkv_spec = P(batch_spec, seq_axis, head_axis, None)
    pos_spec = P(batch_spec, seq_axis)
    return jax.shard_map(
        partial(_ring_attention_local, axis_name=seq_axis),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, pos_spec, pos_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v, positions, positions)
