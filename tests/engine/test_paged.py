"""Paged KV cache: reference ops, page allocator, and the Pallas kernel
(interpreter mode) against dense attention."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from agentcontrolplane_tpu.ops.attention import decode_attention
from agentcontrolplane_tpu.ops.paged import (
    PageAllocator,
    TRASH_PAGE,
    init_kv_pages,
    paged_decode_attention_reference,
    write_prompt_to_pages,
    write_token_to_pages,
)
from agentcontrolplane_tpu.ops.pallas.paged_attention import paged_decode_attention


def _setup(seed=0, S=3, H=4, Hkv=2, d=8, P=4, max_pages=6, num_pages=32):
    """Build a paged cache and an equivalent slot cache with random KV."""
    rng = np.random.default_rng(seed)
    seq_lens = np.asarray([9, 4, 17][:S], dtype=np.int32)
    q = jnp.asarray(rng.normal(size=(S, H, d)), dtype=jnp.float32)

    k_pages = jnp.zeros((num_pages, P, Hkv, d), dtype=jnp.float32)
    v_pages = jnp.zeros((num_pages, P, Hkv, d), dtype=jnp.float32)
    C = max_pages * P
    k_slot = np.zeros((S, C, Hkv, d), dtype=np.float32)
    v_slot = np.zeros((S, C, Hkv, d), dtype=np.float32)

    alloc = PageAllocator(num_pages)
    tables = np.full((S, max_pages), TRASH_PAGE, dtype=np.int32)
    for s in range(S):
        n = -(-int(seq_lens[s]) // P)
        pages = alloc.alloc(n)
        tables[s, :n] = pages
        kv = rng.normal(size=(2, int(seq_lens[s]), Hkv, d)).astype(np.float32)
        k_slot[s, : seq_lens[s]] = kv[0]
        v_slot[s, : seq_lens[s]] = kv[1]
        for j, page in enumerate(pages):
            lo, hi = j * P, min((j + 1) * P, int(seq_lens[s]))
            k_pages = k_pages.at[page, : hi - lo].set(kv[0][lo:hi])
            v_pages = v_pages.at[page, : hi - lo].set(kv[1][lo:hi])
    return q, k_pages, v_pages, jnp.asarray(tables), jnp.asarray(seq_lens), (
        jnp.asarray(k_slot), jnp.asarray(v_slot),
    )


def test_reference_paged_matches_slot_attention():
    q, k_pages, v_pages, tables, seq_lens, (k_slot, v_slot) = _setup()
    dense = decode_attention(q, k_slot, v_slot, seq_lens)
    paged = paged_decode_attention_reference(q, k_pages, v_pages, tables, seq_lens)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense), rtol=1e-5, atol=1e-5)


def test_pallas_kernel_matches_reference_interpret():
    q, k_pages, v_pages, tables, seq_lens, _ = _setup()
    ref = paged_decode_attention_reference(q, k_pages, v_pages, tables, seq_lens)
    out = paged_decode_attention(q, k_pages, v_pages, tables, seq_lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_pallas_kernel_gqa_and_bigger_shapes():
    q, k_pages, v_pages, tables, seq_lens, _ = _setup(
        seed=1, S=3, H=8, Hkv=2, d=16, P=8, max_pages=4, num_pages=16
    )
    ref = paged_decode_attention_reference(q, k_pages, v_pages, tables, seq_lens)
    out = paged_decode_attention(q, k_pages, v_pages, tables, seq_lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_write_token_and_prompt_roundtrip():
    P, Hkv, d = 4, 2, 8
    pages = init_kv_pages(1, 16, P, Hkv, d, jnp.float32)
    k_pages, v_pages = pages["k"][0], pages["v"][0]
    rng = np.random.default_rng(0)

    # prompt of 6 tokens -> pages [3, 5] (2 pages, second half-filled)
    prompt_k = jnp.asarray(rng.normal(size=(8, Hkv, d)), dtype=jnp.float32)
    prompt_v = jnp.asarray(rng.normal(size=(8, Hkv, d)), dtype=jnp.float32)
    page_ids = jnp.asarray([3, 5], dtype=jnp.int32)
    k_pages, v_pages = write_prompt_to_pages(k_pages, v_pages, page_ids, prompt_k, prompt_v)
    np.testing.assert_array_equal(np.asarray(k_pages[3]), np.asarray(prompt_k[:4]))
    np.testing.assert_array_equal(np.asarray(k_pages[5]), np.asarray(prompt_k[4:8]))

    # decode token at position 6 for slot with table [3,5] -> page 5 offset 2
    tables = jnp.asarray([[3, 5, 0]], dtype=jnp.int32)
    tok_k = jnp.asarray(rng.normal(size=(1, Hkv, d)), dtype=jnp.float32)
    tok_v = jnp.asarray(rng.normal(size=(1, Hkv, d)), dtype=jnp.float32)
    k_pages, v_pages = write_token_to_pages(
        k_pages, v_pages, tables, jnp.asarray([6]), jnp.asarray([True]), tok_k, tok_v
    )
    np.testing.assert_array_equal(np.asarray(k_pages[5, 2]), np.asarray(tok_k[0]))

    # inactive slot writes land in the trash page
    k_before = np.asarray(k_pages[5])
    k_pages, v_pages = write_token_to_pages(
        k_pages, v_pages, tables, jnp.asarray([7]), jnp.asarray([False]), tok_k, tok_v
    )
    np.testing.assert_array_equal(np.asarray(k_pages[5]), k_before)
    np.testing.assert_array_equal(np.asarray(k_pages[TRASH_PAGE, 3]), np.asarray(tok_k[0]))


def test_page_allocator():
    a = PageAllocator(8)
    assert a.free_count == 7  # page 0 reserved
    p1 = a.alloc(3)
    assert TRASH_PAGE not in p1
    a.free(p1)
    assert a.free_count == 7
    with pytest.raises(MemoryError):
        a.alloc(8)


def test_pallas_cache_plus_new_matches_reference_interpret():
    """The serving hot-path form (read-only pages + self term, merged from
    the kernel's unnormalized (acc, m, l)) == the exact XLA reference."""
    from agentcontrolplane_tpu.ops.paged import (
        paged_decode_attention_reference_cache_plus_new,
    )
    from agentcontrolplane_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_cache_plus_new,
    )

    for seed, kw in ((3, {}), (4, dict(S=3, H=8, Hkv=2, d=16, P=8, max_pages=4, num_pages=16))):
        q, k_pages, v_pages, tables, seq_lens, _ = _setup(seed=seed, **kw)
        rng = np.random.default_rng(seed + 10)
        Hkv, d = k_pages.shape[2], k_pages.shape[3]
        S = q.shape[0]
        k_new = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype=jnp.float32)
        v_new = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype=jnp.float32)
        ref = paged_decode_attention_reference_cache_plus_new(
            q, k_pages, v_pages, tables, seq_lens, k_new, v_new
        )
        out = paged_decode_attention_cache_plus_new(
            q, k_pages, v_pages, tables, seq_lens, k_new, v_new, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_reference_cache_plus_new_equals_write_then_attend():
    """The self-term form must equal writing the token then attending —
    the two decode formulations are semantically identical."""
    from agentcontrolplane_tpu.ops.paged import (
        paged_decode_attention_reference_cache_plus_new,
    )

    q, k_pages, v_pages, tables, seq_lens, _ = _setup(seed=5)
    rng = np.random.default_rng(15)
    S, (Hkv, d) = q.shape[0], k_pages.shape[2:]
    k_new = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype=jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype=jnp.float32)
    active = jnp.ones(S, dtype=bool)
    with_self = paged_decode_attention_reference_cache_plus_new(
        q, k_pages, v_pages, tables, seq_lens, k_new, v_new
    )
    kw, vw = write_token_to_pages(
        k_pages, v_pages, tables, seq_lens, active, k_new, v_new
    )
    written = paged_decode_attention_reference(q, kw, vw, tables, seq_lens + 1)
    np.testing.assert_allclose(
        np.asarray(with_self), np.asarray(written), rtol=1e-5, atol=1e-5
    )


def test_pallas_cache_plus_new_sharded_tp2_interpret():
    from agentcontrolplane_tpu.ops.paged import (
        paged_decode_attention_reference_cache_plus_new,
    )
    from agentcontrolplane_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_cache_plus_new_sharded,
    )
    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    q, k_pages, v_pages, tables, seq_lens, _ = _setup(
        seed=6, S=3, H=8, Hkv=2, d=16, P=8, max_pages=4, num_pages=16
    )
    rng = np.random.default_rng(16)
    S, (Hkv, d) = q.shape[0], k_pages.shape[2:]
    k_new = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype=jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype=jnp.float32)
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    ref = paged_decode_attention_reference_cache_plus_new(
        q, k_pages, v_pages, tables, seq_lens, k_new, v_new
    )
    out = paged_decode_attention_cache_plus_new_sharded(
        mesh, q, k_pages, v_pages, tables, seq_lens, k_new, v_new, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_pallas_kernel_sharded_tp2_interpret():
    """shard_map wrapper over head-sharded pages (tp=2) == reference."""
    import jax

    from agentcontrolplane_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_sharded,
    )
    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    q, k_pages, v_pages, tables, seq_lens, _ = _setup(
        seed=2, S=3, H=8, Hkv=2, d=16, P=8, max_pages=4, num_pages=16
    )
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    ref = paged_decode_attention_reference(q, k_pages, v_pages, tables, seq_lens)
    out = paged_decode_attention_sharded(
        mesh, q, k_pages, v_pages, tables, seq_lens, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_pallas_cache_plus_new_sp_sharded_interpret():
    """Context-parallel kernel wrapper (sp=4 x tp=2): each rank runs the
    kernel over its within-page slice and the unnormalized (acc, m, l)
    states merge across sp with pmax + psum — result == exact reference."""
    from agentcontrolplane_tpu.ops.paged import (
        paged_decode_attention_reference_cache_plus_new,
    )
    from agentcontrolplane_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_cache_plus_new_sharded,
    )
    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    q, k_pages, v_pages, tables, seq_lens, _ = _setup(
        seed=9, S=3, H=8, Hkv=2, d=16, P=8, max_pages=4, num_pages=16
    )
    rng = np.random.default_rng(19)
    S, (Hkv, d) = q.shape[0], k_pages.shape[2:]
    k_new = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype=jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(S, Hkv, d)), dtype=jnp.float32)
    ref = paged_decode_attention_reference_cache_plus_new(
        q, k_pages, v_pages, tables, seq_lens, k_new, v_new
    )
    for axes in ({"sp": 4, "tp": 2}, {"sp": 2, "tp": 1}):
        n = axes["sp"] * axes["tp"]
        mesh = make_mesh(axes, devices=jax.devices()[:n])
        out = paged_decode_attention_cache_plus_new_sharded(
            mesh, q, k_pages, v_pages, tables, seq_lens, k_new, v_new,
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5,
            err_msg=str(axes),
        )


@pytest.mark.parametrize(
    "dtype,int8,plus_new",
    [
        ("float32", False, False),
        ("float32", True, True),
        ("bfloat16", False, True),
        ("bfloat16", True, True),
    ],
)
def test_shared_parity_helper_interpret(dtype, int8, plus_new):
    """The helper chip_smoke.py and test_tpu_hardware.py run compiled on
    the chip, here in interpret mode: seeded ragged pages, kernel vs
    reference, judged against the dtype's tolerance."""
    from agentcontrolplane_tpu.engine.kernel_parity import (
        make_paged_case,
        page_walk_parity,
    )

    case = make_paged_case(
        11, S=3, H=4, H_kv=2, d=8, P=4, max_pages=4, num_pages=32,
        dtype=jnp.dtype(dtype), int8=int8,
    )
    got = page_walk_parity(case, plus_new=plus_new, interpret=True)
    assert got["ok"], got
    assert got["shape"] == (3, 4, 8) and len(got["seq_lens"]) == 3
