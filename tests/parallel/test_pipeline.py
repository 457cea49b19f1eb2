"""Pipeline parallelism (parallel/pipeline.py): the GPipe schedule over a
'pp' mesh axis must be numerically transparent — logits AND gradients equal
the plain forward — and must communicate only neighbor-sized activations
(no layer-stack gather)."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from agentcontrolplane_tpu.models.llama import PRESETS, forward, init_params
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.parallel.pipeline import (
    pipeline_forward,
    pipeline_loss_fn,
    pipeline_shardings,
)

TINY = dataclasses.replace(PRESETS["tiny"], n_layers=4)


def _setup(mesh):
    params = init_params(TINY, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, TINY.vocab_size, size=(4, 16)),
        dtype=jnp.int32,
    )
    p_sh = pipeline_shardings(mesh, TINY, params)
    return jax.device_put(params, p_sh), tokens, p_sh


def test_pipeline_forward_matches_plain_forward():
    params = init_params(TINY, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, TINY.vocab_size, size=(4, 16)),
        dtype=jnp.int32,
    )
    ref = forward(params, tokens, TINY)
    for axes in ({"pp": 4}, {"pp": 2}, {"dp": 2, "pp": 2}):
        n = int(np.prod(list(axes.values())))
        mesh = make_mesh(axes, devices=jax.devices()[:n])
        params_pp, tokens_j, _ = _setup(mesh)
        out = jax.jit(
            lambda p, t, mesh=mesh: pipeline_forward(p, t, TINY, mesh)
        )(params_pp, tokens_j)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4,
            err_msg=str(axes),
        )


def test_pipeline_gradients_match_plain_gradients():
    """jax.grad through the schedule (ppermute transpose = reverse
    rotation) must equal the unpipelined gradients — the GPipe backward
    emerges from autodiff, not hand-written code."""
    from agentcontrolplane_tpu.train.trainer import lm_loss

    def plain_loss(params, tokens, mask):
        return lm_loss(params, tokens, mask, TINY)

    params = init_params(TINY, jax.random.key(0))
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(1, TINY.vocab_size, size=(4, 12)), dtype=jnp.int32)
    mask = jnp.ones_like(tokens)
    ref_loss, ref_grads = jax.value_and_grad(plain_loss)(params, tokens, mask)

    mesh = make_mesh({"pp": 4}, devices=jax.devices()[:4])
    p_sh = pipeline_shardings(mesh, TINY, params)
    params_pp = jax.device_put(params, p_sh)
    loss, grads = jax.jit(
        jax.value_and_grad(
            lambda p, t, m: pipeline_loss_fn(p, t, m, TINY, mesh)
        )
    )(params_pp, tokens, mask)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
    flat_ref = jax.tree_util.tree_leaves(ref_grads)
    flat_pp = jax.tree_util.tree_leaves(grads)
    assert len(flat_ref) == len(flat_pp)
    for a, b in zip(flat_ref, flat_pp):
        np.testing.assert_allclose(
            np.asarray(b, dtype=np.float32), np.asarray(a, dtype=np.float32),
            rtol=5e-3, atol=1e-5,
        )


def test_pipeline_no_layer_stack_gather():
    """The compiled HLO must not all-gather the layer stack: stages
    exchange only [mb, T, D] activations (collective-permute)."""
    import re

    mesh = make_mesh({"pp": 4}, devices=jax.devices()[:4])
    params_pp, tokens, _ = _setup(mesh)
    compiled = (
        jax.jit(lambda p, t: pipeline_forward(p, t, TINY, mesh))
        .lower(params_pp, tokens)
        .compile()
    )
    text = compiled.as_text()
    assert "collective-permute" in text  # the rotation is really there
    stack_elems = TINY.n_layers * TINY.dim * TINY.ffn_dim  # largest stacked leaf
    for line in text.splitlines():
        if "all-gather" not in line:
            continue
        dims = re.search(r"\[([0-9,]+)\]", line)
        assert dims is not None, line
        elems = int(np.prod([int(x) for x in dims.group(1).split(",")]))
        assert elems < stack_elems // 2, f"layer-stack all-gather: {line.strip()[:160]}"


def test_pipeline_validates_divisibility():
    mesh = make_mesh({"pp": 4}, devices=jax.devices()[:4])
    cfg = dataclasses.replace(TINY, n_layers=3)
    params = init_params(cfg, jax.random.key(0))
    tokens = jnp.zeros((4, 8), dtype=jnp.int32)
    with pytest.raises(ValueError, match="n_layers"):
        pipeline_forward(params, tokens, cfg, mesh)


def test_trainer_pipeline_parallel_step_matches_plain():
    """Trainer(pipeline_parallel=True) over dp2 x pp2: one train step's
    loss equals the unsharded trainer's (same objective, same init)."""
    import optax

    from agentcontrolplane_tpu.train.trainer import Trainer

    batch = np.random.default_rng(3).integers(1, TINY.vocab_size, size=(4, 16))

    def one_step(mesh_axes, **kw):
        n = int(np.prod(list(mesh_axes.values())))
        mesh = make_mesh(mesh_axes, devices=jax.devices()[:n])
        tr = Trainer(config=TINY, mesh=mesh, optimizer=optax.adamw(1e-3), **kw)
        params, opt = tr.init(jax.random.key(0))
        tokens, mask = tr.shard_batch(batch)
        _, _, loss = tr.train_step(params, opt, tokens, mask)
        return float(loss)

    pp_loss = one_step({"dp": 2, "pp": 2}, pipeline_parallel=True)
    ref_loss = one_step({"dp": 1, "tp": 1})
    assert np.isfinite(pp_loss)
    np.testing.assert_allclose(pp_loss, ref_loss, rtol=2e-3)


# -- gemma-2 soft-caps through the pipelined training path ------------------
# (pure per-stage math — no shard_map, so these run on any device count)

G2ISH = dataclasses.replace(
    PRESETS["tiny"], n_layers=4, attn_logit_softcap=5.0, final_logit_softcap=3.0
)


def test_stage_apply_and_head_match_plain_forward_with_softcaps():
    """The pipeline's per-stage body must thread the attention-logit
    soft-cap and its head must apply the final-logit soft-cap: one stage
    holding ALL layers, composed with the shared embed/norm/head, must
    reproduce the plain forward exactly. Before the fix, _stage_apply
    dropped the attention cap and pipeline_forward skipped the final cap —
    silently training a different model than configured."""
    from agentcontrolplane_tpu.models.llama import embed, final_norm_w, head_logits
    from agentcontrolplane_tpu.ops.norms import rms_norm
    from agentcontrolplane_tpu.parallel.pipeline import _stage_apply

    c = G2ISH
    params = init_params(c, jax.random.key(1))
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(1, c.vocab_size, size=(2, 16)),
        dtype=jnp.int32,
    )
    positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    x = embed(params, tokens, c)
    x = _stage_apply(params["layers"], x, positions, c)
    x = rms_norm(x, final_norm_w(params, c), c.norm_eps)
    logits = head_logits(x, params, c)
    ref = forward(params, tokens, c)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), rtol=2e-4, atol=2e-4)
    # and the caps genuinely bite on this config (the comparison above is
    # not vacuously equal to the uncapped model)
    uncapped = forward(
        params, tokens, dataclasses.replace(c, attn_logit_softcap=0.0, final_logit_softcap=0.0)
    )
    assert not np.allclose(np.asarray(ref), np.asarray(uncapped), rtol=2e-4, atol=2e-4)


def test_forward_refuses_custom_attn_impl_with_softcap():
    """refuse-don't-mis-serve: a swapped-in attention op can't apply the
    configured attention soft-cap, so forward must raise instead of
    silently computing the uncapped model."""
    params = init_params(G2ISH, jax.random.key(0))
    tokens = jnp.zeros((1, 8), dtype=jnp.int32)
    with pytest.raises(ValueError, match="softcap"):
        forward(params, tokens, G2ISH, attn_impl=lambda q, k, v, positions: q)


def test_trainer_refuses_ring_attention_with_softcap():
    import optax

    from agentcontrolplane_tpu.train.trainer import Trainer

    mesh = make_mesh({"sp": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="soft"):
        Trainer(
            config=G2ISH, mesh=mesh, optimizer=optax.sgd(1e-3),
            sequence_parallel=True,
        )
