"""The barrier between the q / k projections and their split to heads
(`models/llama.py attn_mlp`) is the identity on values, and it is in the
programs of the families that run `attn_mlp` and in no other family's.

What it does to the chip's program (no layer's `wq` / `wk` staged in fast
memory, no stack copied) is held by `test_chip_compile.py`; here, on the
CPU at a tiny size: bit-equal results over every flag that reaches the
products, a gradient through `forward`, and the other families' lowered
decode steps untouched.
"""

from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentcontrolplane_tpu.models import llama
from agentcontrolplane_tpu.ops.attention import causal_attention
from agentcontrolplane_tpu.ops.quant import quantize_params

BASE = dataclasses.replace(llama.PRESETS["tiny"], dtype=jnp.bfloat16)
LLAMA31_ROPE = dict(rope_scaling_factor=8.0, rope_low_freq_factor=1.0, rope_high_freq_factor=4.0,
                    rope_original_max_seq=32)

# (id, what the config changes, int8 weights): each flag that reaches the products
CASES = [
    ("grouped-bf16", {}, False),
    ("plain-multi-head", {"n_kv_heads": 4}, False),
    ("qkv-bias", {"qkv_bias": True}, False),
    ("int8-weights", {}, True),
    ("int8-weights-qkv-bias", {"qkv_bias": True}, True),
    ("rope-scaling", LLAMA31_ROPE, False),
    ("query-pre-attn-scalar", {"query_pre_attn_scalar": 24.0, "head_dim_override": 32}, False),
    ("post-norms", {"post_norms": True, "norm_plus_one": True}, False),
    ("float32", {"dtype": jnp.float32, "qkv_bias": True}, False),
]


def _without_barrier(monkeypatch):
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)


def _block(c, int8: bool):
    """One layer's `attn_mlp` jitted afresh -> (out, q, k, v), and its operands."""
    params = llama.init_params(c, jax.random.key(3))
    keys = iter(jax.random.split(jax.random.key(4), 8))
    for name in ("bq", "bk", "bv"):  # drawn, not the zeros a fresh model starts with
        if name in params["layers"]:
            shape = params["layers"][name].shape
            params["layers"][name] = (jax.random.normal(next(keys), shape) * 0.5).astype(c.dtype)
    if int8:
        params = quantize_params(params)
    layer = jax.tree_util.tree_map(lambda a: a[1], params["layers"])
    B, T = 2, 5
    x = jax.random.normal(next(keys), (B, T, c.dim)).astype(c.dtype)
    positions = jnp.broadcast_to(jnp.arange(7, 7 + T, dtype=jnp.int32), (B, T))

    def block(x, layer, positions):
        seen = {}

        def attn(q, k, v):
            seen["q"] = q
            return causal_attention(q, k, v, positions)

        out, k, v = llama.attn_mlp(x, layer, c, positions, attn)
        return out, seen["q"], k, v

    return block, (x, layer, positions)


@pytest.mark.parametrize("case", CASES, ids=lambda c: c[0])
def test_the_barrier_is_the_identity_on_q_k_v_and_the_blocks_output(case, monkeypatch):
    _, changes, int8 = case
    c = dataclasses.replace(BASE, **changes)
    block, args = _block(c, int8)
    assert "optimization_barrier" in str(jax.make_jaxpr(block)(*args)), "the block holds no barrier to compare"
    with_barrier = jax.jit(block)(*args)
    _without_barrier(monkeypatch)
    block, args = _block(c, int8)  # traced anew: nothing of the first trace is reused
    assert "optimization_barrier" not in str(jax.make_jaxpr(block)(*args))
    plain = jax.jit(block)(*args)
    for name, a, b in zip(("out", "q", "k", "v"), with_barrier, plain):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    heads = (c.n_heads, c.n_kv_heads, c.n_kv_heads)
    assert [a.shape[2:] for a in with_barrier[1:]] == [(h, c.head_dim) for h in heads]


def test_a_gradient_runs_through_forward_and_is_the_barrier_free_one(monkeypatch):
    """`forward` (training, ring attention, `remat`) shares the block: the
    barrier differentiates and batches, and the gradient is the plain one."""
    c = dataclasses.replace(llama.PRESETS["tiny"], qkv_bias=True)
    params = llama.init_params(c, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 9), 0, c.vocab_size)

    def loss(p, t):
        logits = llama.forward(p, t, c, remat=True)
        return jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0])

    grads = jax.jit(jax.grad(loss))(params, tokens)
    batched = jax.vmap(lambda t: loss(params, t[None]))(tokens)
    assert batched.shape == (2,) and bool(jnp.isfinite(batched).all())
    _without_barrier(monkeypatch)
    plain = jax.jit(jax.grad(loss))(params, tokens)  # a new transform object: traced anew
    for (path, g), (_, h) in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves_with_path(plain)):
        assert bool(jnp.isfinite(g).all()), path
        np.testing.assert_allclose(np.asarray(g), np.asarray(h), rtol=1e-6, atol=1e-7, err_msg=str(path))
    assert float(jnp.abs(grads["layers"]["wq"]).max()) > 0 and float(jnp.abs(grads["layers"]["bk"]).max()) > 0


FAMILIES = [("lfm2", "lfm2-tiny"), ("jamba", "jamba-tiny"), ("mellum", "mellum-tiny"), ("kanana", "kanana-tiny")]
PAGE, SLOTS, TABLE = 16, 2, 4


def _lowered_decode_step(name: str, preset: str) -> str:
    model = importlib.import_module(f"agentcontrolplane_tpu.models.{name}")
    c = model.PRESETS[preset]
    params = jax.eval_shape(lambda: model.init_params(c, jax.random.key(0)))
    cache = jax.eval_shape(lambda: model.init_paged_cache(c, 1 + SLOTS * TABLE, PAGE, max_slots=SLOTS))
    vec = lambda *shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
    step = lambda p, ca, tok, n, tables, active: model.decode_step_paged(p, ca, tok, n, tables, active, c)  # noqa: E731
    return jax.jit(step).lower(params, cache, vec(SLOTS), vec(SLOTS), vec(SLOTS, TABLE), vec(SLOTS, dt=jnp.bool_)).as_text()


@pytest.mark.parametrize("family", FAMILIES, ids=lambda f: f[0])
def test_a_family_with_its_own_attn_qkv_lowers_to_the_same_decode_step(family, monkeypatch):
    """`lfm2`, `jamba`, `mellum` and `kanana` project q and k in bodies of
    their own: they name nothing of `attn_mlp`, their decode step holds no
    barrier, and its lowered text is the same with `optimization_barrier`
    taken out of jax altogether."""
    name, preset = family
    model = importlib.import_module(f"agentcontrolplane_tpu.models.{name}")
    assert not hasattr(model, "attn_mlp") and not hasattr(model, "llama")
    text = _lowered_decode_step(name, preset)
    assert "optimization_barrier" not in text
    _without_barrier(monkeypatch)
    assert _lowered_decode_step(name, preset) == text


def test_the_families_that_run_attn_mlp_do_hold_the_barrier():
    """The control of the case above: `ouro`'s decode step, which runs
    `attn_mlp`, lowers WITH the barrier, so that comparison can see one."""
    assert "optimization_barrier" in _lowered_decode_step("ouro", "ouro-tiny")
