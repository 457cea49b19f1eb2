"""int8 Pallas page walk (interpreter mode) vs the XLA dequant reference.

The kernel DMAs int8 pages plus their f32 scale rows and applies the
scales in VMEM (factored out of both products: ``(q . k_int8) * s``) — so
against the reference (which dequantizes after the per-slot gather) the
two paths compute the same f32 math up to rounding order and the pin is
the usual 1e-5, not a loose quantization tolerance. Covers both entry
forms, both sharded wrappers, scale-row
alignment edges (mid-page seq_lens, exact page boundaries, single-token
rows) and TRASH_PAGE / tail-row masking with poisoned scales.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from agentcontrolplane_tpu.ops.paged import (
    TRASH_PAGE,
    paged_decode_attention_reference,
    paged_decode_attention_reference_cache_plus_new,
)
from agentcontrolplane_tpu.ops.pallas.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_cache_plus_new,
    paged_decode_attention_cache_plus_new_sharded,
    paged_decode_attention_sharded,
    walk_scale_rows,
)
from agentcontrolplane_tpu.ops.quant import kv_quantize

from .test_paged import _setup


def _quantize_pages(k_pages, v_pages):
    """Per-row-per-head int8 pages + f32 scale twins (the allocator's
    storage layout: scales are pages-shaped, indexed by the same ids)."""
    kq, ks = kv_quantize(k_pages)
    vq, vs = kv_quantize(v_pages)
    return kq, vq, ks, vs


def merged(pages):
    """One layer's pages as a pool stores them: a row its KV heads side by
    side, ``[num_pages, P, H_kv * d]`` (what the sharded wrappers take)."""
    return pages.reshape(*pages.shape[:2], -1)


def _setup_int8(**kw):
    q, k_pages, v_pages, tables, seq_lens, _ = _setup(**kw)
    kq, vq, ks, vs = _quantize_pages(k_pages, v_pages)
    return q, kq, vq, ks, vs, tables, seq_lens


def test_int8_walk_matches_reference_interpret():
    q, kq, vq, ks, vs, tables, seq_lens = _setup_int8()
    ref = paged_decode_attention_reference(
        q, kq, vq, tables, seq_lens, k_scales=ks, v_scales=vs
    )
    out = paged_decode_attention(
        q, kq, vq, tables, seq_lens, interpret=True, k_scales=ks, v_scales=vs
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_int8_walk_gqa_and_bigger_shapes():
    q, kq, vq, ks, vs, tables, seq_lens = _setup_int8(
        seed=1, S=3, H=8, Hkv=2, d=16, P=8, max_pages=4, num_pages=16
    )
    ref = paged_decode_attention_reference(
        q, kq, vq, tables, seq_lens, k_scales=ks, v_scales=vs
    )
    out = paged_decode_attention(
        q, kq, vq, tables, seq_lens, interpret=True, k_scales=ks, v_scales=vs
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_int8_cache_plus_new_matches_reference_interpret():
    """The serving hot-path form: int8 pages + a full-precision new token
    (not yet written, so no scale applies to the self term)."""
    for seed, kw in ((3, {}), (4, dict(S=3, H=8, Hkv=2, d=16, P=8, max_pages=4, num_pages=16))):
        q, kq, vq, ks, vs, tables, seq_lens = _setup_int8(seed=seed, **kw)
        rng = np.random.default_rng(seed + 20)
        S, Hkv, d = q.shape[0], kq.shape[2], kq.shape[3]
        k_new = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype=jnp.float32)
        v_new = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype=jnp.float32)
        ref = paged_decode_attention_reference_cache_plus_new(
            q, kq, vq, tables, seq_lens, k_new, v_new, k_scales=ks, v_scales=vs
        )
        out = paged_decode_attention_cache_plus_new(
            q, kq, vq, tables, seq_lens, k_new, v_new, interpret=True,
            k_scales=ks, v_scales=vs,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
        )


def test_int8_walk_scale_row_alignment_edges():
    """Each page's scale row is fetched whole with the page, so the edge
    cases are sequence lengths that end mid-page, exactly on a page
    boundary, and a single-token row (the first fetch is also the last)."""
    base = _setup(seed=7, S=3, H=4, Hkv=2, d=8, P=4, max_pages=6, num_pages=32)
    q, k_pages, v_pages, tables, _, _ = base
    kq, vq, ks, vs = _quantize_pages(k_pages, v_pages)
    for lens in ([8, 4, 16], [1, 4, 17], [9, 1, 12], [4, 3, 1]):
        seq_lens = jnp.asarray(lens, dtype=jnp.int32)
        ref = paged_decode_attention_reference(
            q, kq, vq, tables, seq_lens, k_scales=ks, v_scales=vs
        )
        out = paged_decode_attention(
            q, kq, vq, tables, seq_lens, interpret=True, k_scales=ks, v_scales=vs
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5,
            err_msg=f"seq_lens={lens}",
        )


def test_int8_walk_masks_trash_page_and_poisoned_tail_scales():
    """Garbage in the masked region must not reach the output: poison the
    TRASH_PAGE and every row past each slot's seq_len (values AND scales)
    with large finite junk, and pin the result against the reference over
    the CLEAN pages — if the kernel read a poisoned scale row the outputs
    would diverge wildly, not within 1e-5."""
    q, kq, vq, ks, vs, tables, seq_lens = _setup_int8(seed=8)
    clean = paged_decode_attention_reference(
        q, kq, vq, tables, seq_lens, k_scales=ks, v_scales=vs
    )
    P = kq.shape[1]
    kq_p, vq_p = kq, vq
    ks_p = ks.at[TRASH_PAGE].set(1e30)
    vs_p = vs.at[TRASH_PAGE].set(1e30)
    kq_p = kq_p.at[TRASH_PAGE].set(127)
    vq_p = vq_p.at[TRASH_PAGE].set(127)
    for s in range(q.shape[0]):
        ln = int(seq_lens[s])
        last = (ln - 1) // P  # last walked page; poison its tail rows
        page = int(tables[s, last])
        off = ln - last * P
        if off < P:
            ks_p = ks_p.at[page, off:].set(1e30)
            vs_p = vs_p.at[page, off:].set(1e30)
            kq_p = kq_p.at[page, off:].set(127)
            vq_p = vq_p.at[page, off:].set(127)
    out = paged_decode_attention(
        q, kq_p, vq_p, tables, seq_lens, interpret=True,
        k_scales=ks_p, v_scales=vs_p,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(clean), rtol=1e-5, atol=1e-5
    )


def test_int8_walk_sharded_tp2_interpret():
    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    q, kq, vq, ks, vs, tables, seq_lens = _setup_int8(
        seed=2, S=3, H=8, Hkv=2, d=16, P=8, max_pages=4, num_pages=16
    )
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    ref = paged_decode_attention_reference(
        q, kq, vq, tables, seq_lens, k_scales=ks, v_scales=vs
    )
    out = paged_decode_attention_sharded(
        mesh, q, merged(kq), merged(vq), tables, seq_lens, interpret=True,
        kv_heads=kq.shape[2], k_scales=ks, v_scales=vs,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_int8_cache_plus_new_sharded_tp_and_sp_interpret():
    """All sharded int8 forms: tp-only (shard_map over head-sharded pages
    and scale twins) and sp>1 (context-parallel slices with the cross-rank
    (acc, m, l) merge; scales shard with the pages' row axis)."""
    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    q, kq, vq, ks, vs, tables, seq_lens = _setup_int8(
        seed=6, S=3, H=8, Hkv=2, d=16, P=8, max_pages=4, num_pages=16
    )
    rng = np.random.default_rng(26)
    S, Hkv, d = q.shape[0], kq.shape[2], kq.shape[3]
    k_new = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype=jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype=jnp.float32)
    ref = paged_decode_attention_reference_cache_plus_new(
        q, kq, vq, tables, seq_lens, k_new, v_new, k_scales=ks, v_scales=vs
    )
    for axes in ({"tp": 2}, {"sp": 4, "tp": 2}, {"sp": 2, "tp": 1}):
        n = axes.get("sp", 1) * axes.get("tp", 1)
        if len(jax.devices()) < n:
            pytest.skip(f"needs {n} devices")
        mesh = make_mesh(axes, devices=jax.devices()[:n])
        out = paged_decode_attention_cache_plus_new_sharded(
            mesh, q, merged(kq), merged(vq), tables, seq_lens, k_new, v_new, interpret=True,
            k_scales=ks, v_scales=vs,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5,
            err_msg=str(axes),
        )
        # the scales laid out for the kernel once, outside the caller's
        # layer scan (what decode_step_paged does), read the same
        laid = paged_decode_attention_cache_plus_new_sharded(
            mesh, q, merged(kq), merged(vq), tables, seq_lens, k_new, v_new, interpret=True,
            k_scales=walk_scale_rows(ks, mesh), v_scales=walk_scale_rows(vs, mesh), scales_laid=True,
        )
        np.testing.assert_array_equal(np.asarray(laid), np.asarray(out), err_msg=str(axes))
