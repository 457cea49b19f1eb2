"""Sharding correctness on the virtual 8-device CPU mesh: ring attention vs
dense reference, TP-sharded forward vs single-device forward, and the full
dp/sp/tp train step."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from agentcontrolplane_tpu.models.llama import PRESETS, forward, init_params
from agentcontrolplane_tpu.ops.attention import causal_attention
from agentcontrolplane_tpu.parallel.mesh import make_mesh, param_shardings
from agentcontrolplane_tpu.parallel.ring_attention import ring_causal_attention
from agentcontrolplane_tpu.train.trainer import Trainer

TINY = PRESETS["tiny"]

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)


def test_ring_attention_matches_dense():
    mesh = make_mesh({"dp": 1, "sp": 4, "tp": 2})
    B, T, H, Hkv, d = 2, 32, 4, 2, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, T, H, d)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, d)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, d)), dtype=jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    dense = causal_attention(q, k, v, positions)
    ring = ring_causal_attention(mesh, q, k, v, positions)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), rtol=1e-5, atol=1e-5)


def test_ring_attention_with_padding_positions():
    mesh = make_mesh({"dp": 1, "sp": 4, "tp": 2})
    B, T, H, Hkv, d = 1, 16, 4, 2, 8
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, T, H, d)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, d)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, d)), dtype=jnp.float32)
    # last 6 positions are padding (-1)
    positions = jnp.asarray(
        [[0, 1, 2, 3, 4, 5, 6, 7, 8, 9, -1, -1, -1, -1, -1, -1]], dtype=jnp.int32
    )
    dense = causal_attention(q, k, v, positions)
    ring = ring_causal_attention(mesh, q, k, v, positions)
    # compare only valid positions (padding rows are garbage in both)
    np.testing.assert_allclose(
        np.asarray(ring)[:, :10], np.asarray(dense)[:, :10], rtol=1e-5, atol=1e-5
    )


def test_tp_sharded_forward_matches_single_device():
    """The same logits must come out of the TP=8-sharded forward as from an
    unsharded one — XLA's inserted collectives are semantics-preserving."""
    mesh = make_mesh({"tp": 8})
    cfg = dataclasses.replace(TINY, n_kv_heads=8 if TINY.n_heads >= 8 else TINY.n_kv_heads)
    # tiny has 4 heads / 2 kv heads; tp=8 can't divide heads -> use tp=2 mesh
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    params = init_params(TINY, jax.random.key(0))
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], dtype=jnp.int32)
    base = forward(params, tokens, TINY)

    shardings = param_shardings(mesh, TINY, params)
    sharded_params = jax.tree_util.tree_map(jax.device_put, params, shardings)
    sharded_logits = jax.jit(lambda p, t: forward(p, t, TINY))(sharded_params, tokens)
    np.testing.assert_allclose(
        np.asarray(sharded_logits), np.asarray(base), rtol=2e-4, atol=2e-4
    )


def test_train_step_dp_tp_loss_decreases():
    mesh = make_mesh({"dp": 2, "sp": 1, "tp": 2}, devices=jax.devices()[:4])
    trainer = Trainer(
        config=TINY, mesh=mesh, optimizer=optax.adam(1e-3), sequence_parallel=False
    )
    params, opt_state = trainer.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    tokens, mask = trainer.shard_batch(rng.integers(0, TINY.vocab_size, size=(4, 32)))
    losses = []
    for _ in range(5):
        params, opt_state, loss = trainer.train_step(params, opt_state, tokens, mask)
        losses.append(float(loss))
    assert losses[-1] < losses[0]  # memorizing one batch
    assert np.isfinite(losses).all()


def test_train_step_sequence_parallel_matches_dense():
    """One train step with ring-attention sp=2 must produce the same loss as
    the dense dp-only step (exact attention, just distributed)."""
    mesh_sp = make_mesh({"dp": 1, "sp": 2, "tp": 2}, devices=jax.devices()[:4])
    mesh_dense = make_mesh({"dp": 1, "sp": 1, "tp": 2}, devices=jax.devices()[:4][:2])
    rng = np.random.default_rng(0)
    batch = rng.integers(0, TINY.vocab_size, size=(2, 32))

    t_sp = Trainer(config=TINY, mesh=mesh_sp, optimizer=optax.sgd(1e-2), sequence_parallel=True)
    t_dn = Trainer(config=TINY, mesh=mesh_dense, optimizer=optax.sgd(1e-2))
    p_sp, o_sp = t_sp.init(jax.random.key(7))
    p_dn, o_dn = t_dn.init(jax.random.key(7))

    tok_sp, m_sp = t_sp.shard_batch(batch)
    tok_dn, m_dn = t_dn.shard_batch(batch)
    p_sp, o_sp, loss_sp = t_sp.train_step(p_sp, o_sp, tok_sp, m_sp)
    p_dn, o_dn, loss_dn = t_dn.train_step(p_dn, o_dn, tok_dn, m_dn)
    np.testing.assert_allclose(float(loss_sp), float(loss_dn), rtol=1e-4)
    # params after the step agree too
    np.testing.assert_allclose(
        np.asarray(p_sp["norm"]), np.asarray(p_dn["norm"]), rtol=1e-4, atol=1e-5
    )


def test_ring_attention_gradients_match_dense():
    """Backward through the ring (ppermute + online softmax) must produce
    the same input gradients as dense attention — sp fine-tuning is exact."""
    mesh = make_mesh({"dp": 1, "sp": 4, "tp": 2})
    B, T, H, Hkv, d = 1, 16, 4, 2, 8
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(B, T, H, d)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, d)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, d)), dtype=jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    cotangent = jnp.asarray(rng.normal(size=(B, T, H, d)), dtype=jnp.float32)

    def loss_dense(q, k, v):
        return jnp.sum(causal_attention(q, k, v, positions) * cotangent)

    def loss_ring(q, k, v):
        return jnp.sum(ring_causal_attention(mesh, q, k, v, positions) * cotangent)

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gr, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4, err_msg=name
        )
