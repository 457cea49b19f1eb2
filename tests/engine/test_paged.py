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


def _merged(pages):
    """One layer's pages as a pool stores them: a row's KV heads side by
    side, ``[num_pages, P, H_kv * d]`` (what the sharded wrappers take)."""
    return pages.reshape(*pages.shape[:2], -1)


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

    assert k_pages.shape == (16, P, Hkv * d)  # a row holds its KV heads side by side

    # prompt of 6 tokens -> pages [3, 5] (2 pages, second half-filled)
    prompt_k = jnp.asarray(rng.normal(size=(8, Hkv * d)), dtype=jnp.float32)
    prompt_v = jnp.asarray(rng.normal(size=(8, Hkv * d)), dtype=jnp.float32)
    page_ids = jnp.asarray([3, 5], dtype=jnp.int32)
    k_pages, v_pages = write_prompt_to_pages(k_pages, v_pages, page_ids, prompt_k, prompt_v)
    np.testing.assert_array_equal(np.asarray(k_pages[3]), np.asarray(prompt_k[:4]))
    np.testing.assert_array_equal(np.asarray(k_pages[5]), np.asarray(prompt_k[4:8]))

    # decode token at position 6 for slot with table [3,5] -> page 5 offset 2
    tables = jnp.asarray([[3, 5, 0]], dtype=jnp.int32)
    tok_k = jnp.asarray(rng.normal(size=(1, Hkv * d)), dtype=jnp.float32)
    tok_v = jnp.asarray(rng.normal(size=(1, Hkv * d)), dtype=jnp.float32)
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
        mesh, q, _merged(k_pages), _merged(v_pages), tables, seq_lens, k_new, v_new, interpret=True
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
        mesh, q, _merged(k_pages), _merged(v_pages), tables, seq_lens, interpret=True,
        kv_heads=k_pages.shape[2],
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
            mesh, q, _merged(k_pages), _merged(v_pages), tables, seq_lens, k_new, v_new,
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


def _straddle_case(dtype, seed=21, P=16, H=4, Hkv=2, d=8):
    """One batch of ragged contexts that straddle a turn of the walk:
    lengths 0 (an inactive slot), 1, P-1, P, G*P-1, G*P, G*P+1, 3*G*P+5.
    Every pool page that no block table names — the trash page the tables'
    padding names is the one exception — is NaN, so a read of a wrong page
    fails loudly; rows of a turn's buffer that no DMA wrote are NaN in
    interpret mode (uninitialized scratch), so an unfetched row does too."""
    from agentcontrolplane_tpu.ops.pallas.paged_attention import pages_per_turn

    G = pages_per_turn(P, dtype, Hkv, d)
    T = G * P
    seq_lens = np.asarray([0, 1, P - 1, P, T - 1, T, T + 1, 3 * T + 5], dtype=np.int32)
    S, max_pages = len(seq_lens), 3 * G + 2
    num_pages = int(sum(-(-int(n) // P) for n in seq_lens)) + 9
    rng = np.random.default_rng(seed)
    k_pages = np.full((num_pages, P, Hkv, d), np.nan, dtype=np.float32)
    v_pages = np.full((num_pages, P, Hkv, d), np.nan, dtype=np.float32)
    k_pages[TRASH_PAGE] = v_pages[TRASH_PAGE] = 0.0
    alloc = PageAllocator(num_pages)
    tables = np.full((S, max_pages), TRASH_PAGE, dtype=np.int32)
    # scatter: interleave the slots' pages so no walk reads a contiguous run
    order = [(s, j) for s in range(S) for j in range(-(-int(seq_lens[s]) // P))]
    rng.shuffle(order)
    for s, j in order:
        (page,) = alloc.alloc(1)
        tables[s, j] = page
        # whole pages are written (rows past seq_len hold finite stale data,
        # as a recycled page does in the engine)
        k_pages[page] = rng.normal(size=(P, Hkv, d))
        v_pages[page] = rng.normal(size=(P, Hkv, d))
    as_dt = lambda x: jnp.asarray(x, dtype=dtype)  # noqa: E731
    return dict(
        G=G,
        q=as_dt(rng.normal(size=(S, H, d))),
        k_pages=as_dt(k_pages), v_pages=as_dt(v_pages),
        tables=jnp.asarray(tables), seq_lens=jnp.asarray(seq_lens),
        k_new=as_dt(rng.normal(size=(S, Hkv, d))),
        v_new=as_dt(rng.normal(size=(S, Hkv, d))),
    )


@pytest.mark.parametrize("plus_new", [False, True], ids=["plain", "cache-plus-new"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_walk_parity_contexts_straddling_a_turn(dtype, plus_new):
    """A turn of the walk covers G pages (one lane tile of tokens): contexts
    on both sides of every turn edge, in one batch, against a reference fed
    only the rows the block tables name."""
    from agentcontrolplane_tpu.engine.kernel_parity import TOLERANCE
    from agentcontrolplane_tpu.ops.paged import (
        paged_decode_attention_reference_cache_plus_new,
    )
    from agentcontrolplane_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_cache_plus_new,
    )

    c = _straddle_case(jnp.dtype(dtype))
    assert c["G"] == 128 // 16
    # the reference gathers whole tables: give it the same pool with the
    # unnamed pages zeroed (its mask then drops them exactly)
    clean = lambda x: jnp.nan_to_num(x.astype(jnp.float32)).astype(x.dtype)  # noqa: E731
    args = [c["q"], c["k_pages"], c["v_pages"], c["tables"], c["seq_lens"]]
    ref_args = [c["q"], clean(c["k_pages"]), clean(c["v_pages"]), c["tables"], c["seq_lens"]]
    if plus_new:
        kernel, reference = (
            paged_decode_attention_cache_plus_new,
            paged_decode_attention_reference_cache_plus_new,
        )
        args += [c["k_new"], c["v_new"]]
        ref_args += [c["k_new"], c["v_new"]]
    else:
        kernel, reference = paged_decode_attention, paged_decode_attention_reference
    out = np.asarray(kernel(*args, interpret=True).astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference(*ref_args).astype(jnp.float32))
    live = np.asarray(c["seq_lens"]) > 0
    if plus_new:
        live[:] = True  # the self term gives an empty slot its one token
    assert np.isfinite(out[live]).all(), "a walk read an unnamed page or an unfetched row"
    atol = {"float32": 1e-5, "bfloat16": TOLERANCE["bfloat16"]}[dtype]
    np.testing.assert_allclose(out[live], ref[live], rtol=0, atol=atol)
    if not plus_new:
        # an inactive slot walks nothing: acc 0 over the floor of l
        np.testing.assert_array_equal(out[~live], 0.0)


# -- the walk as one stream of turns over every slot ---------------------------
#
# The fetches run RING - 1 turns ahead of the fold from the kernel's first turn
# to its last, across slot boundaries: a slot's last turns are folded while the
# next slots' first are in flight, and slots with nothing to walk are stepped
# over. `_DEPTH` turns in flight; a turn is `_G` pages of 16 rows.

_DEPTH, _G = 3, 8
# pages a slot, by case: turn counts 0, 1, depth - 1, depth, depth + 1 and
# 3 x depth + 1 with the empty slot first, last and between two long ones
_TURNS = {
    "empty-first": [0, 10, 1, 2, 3, 4],
    "empty-last": [10, 4, 3, 2, 1, 0],
    "empty-between-long": [10, 0, 10, 1, 0, 0, 4, 2, 0, 3],
    "every-slot-empty": [0, 0, 0],
    "one-slot-alone": [4],
}
_PAGES = {k: [t * _G for t in v] for k, v in _TURNS.items()} | {
    "last-turns-of-one-page": [9 * _G + 1, 1, 0, 3 * _G + 1, _G + 1],
    "last-turns-of-G-1-pages": [4 * _G - 1, _G - 1, 0, 10 * _G - 1],
}


def _stream_case(pages, dtype, seed=43, P=16, H=4, Hkv=2, d=8, int8=False):
    """One batch whose slot ``s`` walks ``pages[s]`` pages (its last one
    part-filled), scattered over a pool in which every page no block table
    names is NaN (int8 pools: its scales are): a fetch of any page that is
    not the walk's own fails loudly, and so does a row no fetch wrote
    (uninitialised scratch is NaN in interpret mode). The tables' padding
    names page 0, which is NaN too: nothing may read it."""
    from agentcontrolplane_tpu.ops.quant import kv_quantize

    rng = np.random.default_rng(seed)
    S, max_pages = len(pages), max(max(pages), 1) + 3
    seq_lens = np.asarray(
        [0 if n == 0 else (n - 1) * P + 1 + (5 * s + 3) % P for s, n in enumerate(pages)], np.int32)
    num_pages = sum(pages) + 7
    k_pages = np.full((num_pages, P, Hkv, d), np.nan, np.float32)
    v_pages = np.full((num_pages, P, Hkv, d), np.nan, np.float32)
    named = np.zeros(num_pages, bool)
    tables = np.full((S, max_pages), TRASH_PAGE, np.int32)
    free = list(rng.permutation(np.arange(1, num_pages)))
    order = [(s, j) for s in range(S) for j in range(pages[s])]
    rng.shuffle(order)  # no walk reads a contiguous run
    for s, j in order:
        page = int(free.pop())
        tables[s, j], named[page] = page, True
        # whole pages are written: rows past seq_len hold finite stale data
        k_pages[page] = rng.normal(size=(P, Hkv, d))
        v_pages[page] = rng.normal(size=(P, Hkv, d))
    as_dt = lambda x: jnp.asarray(x, dtype=dtype)  # noqa: E731
    case = dict(
        q=as_dt(rng.normal(size=(S, H, d))), k_pages=as_dt(k_pages), v_pages=as_dt(v_pages),
        tables=jnp.asarray(tables), seq_lens=jnp.asarray(seq_lens),
        k_new=as_dt(rng.normal(size=(S, Hkv, d))), v_new=as_dt(rng.normal(size=(S, Hkv, d))),
        scales={}, clean_scales={},
    )
    # the reference gathers whole tables: it gets the pool with the unnamed
    # pages zeroed (its mask then drops them exactly)
    clean = lambda x: jnp.nan_to_num(x.astype(jnp.float32)).astype(x.dtype)  # noqa: E731
    case["clean_k"], case["clean_v"] = clean(case["k_pages"]), clean(case["v_pages"])
    if int8:
        poison = jnp.where(jnp.asarray(named)[:, None, None], 1.0, jnp.nan)
        case["k_pages"], ks = kv_quantize(case["clean_k"])
        case["v_pages"], vs = kv_quantize(case["clean_v"])
        case["clean_k"], case["clean_v"] = case["k_pages"], case["v_pages"]
        case["scales"] = {"k_scales": ks * poison, "v_scales": vs * poison}
        case["clean_scales"] = {"k_scales": ks, "v_scales": vs}
    return case


def _stream_parity(c, plus_new, atol, interpret=True, **kernel_kw):
    from agentcontrolplane_tpu.ops.paged import paged_decode_attention_reference_cache_plus_new
    from agentcontrolplane_tpu.ops.pallas.paged_attention import paged_decode_attention_cache_plus_new

    args = [c["q"], c["k_pages"], c["v_pages"], c["tables"], c["seq_lens"]]
    ref_args = [c["q"], c["clean_k"], c["clean_v"], c["tables"], c["seq_lens"]]
    if plus_new:
        kernel, reference = paged_decode_attention_cache_plus_new, paged_decode_attention_reference_cache_plus_new
        args += [c["k_new"], c["v_new"]]
        ref_args += [c["k_new"], c["v_new"]]
    else:
        kernel, reference = paged_decode_attention, paged_decode_attention_reference
    out = np.asarray(kernel(*args, interpret=interpret, **c["scales"], **kernel_kw).astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference(*ref_args, **c["clean_scales"]).astype(jnp.float32))
    live = np.asarray(c["seq_lens"]) > 0
    if plus_new:
        live[:] = True  # the self term gives an empty slot its one token
    assert np.isfinite(out[live]).all(), "a walk read a page that is not its own, or a row no fetch wrote"
    np.testing.assert_allclose(out[live], ref[live], rtol=0, atol=atol)
    if not plus_new:
        # a slot with nothing to walk keeps the start state: acc 0 over the floor of l
        np.testing.assert_array_equal(out[~live], 0.0)


@pytest.mark.parametrize("plus_new", [False, True], ids=["plain", "cache-plus-new"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("order", list(_PAGES))
def test_the_stream_of_turns_across_slots_matches_the_reference(order, dtype, plus_new):
    from agentcontrolplane_tpu.engine.kernel_parity import TOLERANCE
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    assert (pa.RING - 1, pa.pages_per_turn(16, jnp.dtype(dtype), 2, 8)) == (_DEPTH, _G)
    c = _stream_case(_PAGES[order], jnp.dtype(dtype))
    _stream_parity(c, plus_new, {"float32": 1e-5, "bfloat16": TOLERANCE["bfloat16"]}[dtype])


@pytest.mark.parametrize("order", ["empty-between-long", "empty-first", "every-slot-empty"])
def test_a_batch_too_large_for_one_program_streams_in_several(order, monkeypatch):
    """Where the slots' q and outputs do not fit VMEM together a program
    takes a divisor of them (`slots_per_program`) and the next program the
    next: each drains its own stream, tables and lengths read at its offset."""
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    c = _stream_case(_PAGES[order], jnp.float32)
    S = len(_PAGES[order])
    assert pa.slots_per_program(S, 2, 2, 8, jnp.float32) == S
    monkeypatch.setattr(pa, "_SLOTS_BUDGET", 40 << 10)
    assert pa.slots_per_program(S, 2, 2, 8, jnp.float32) == {10: 2, 6: 2, 3: 1}[S]
    _stream_parity(c, True, 1e-5)
    _stream_parity(c, False, 1e-5)


def test_the_stream_walks_int8_pages_a_page_a_turn():
    """`G = 1`: every page a turn of its own, its scale rows fetched beside
    it; the turn counts of the empty-between-long order in pages."""
    c = _stream_case([10, 0, 13, 1, 0, 0, 4, 2, 0, 3], jnp.bfloat16, int8=True)
    _stream_parity(c, True, 2e-2)


def test_the_stream_walks_packed_heads_at_width_64():
    """Two KV heads to a lane window (the wrapper's layout): the stream
    underneath is the same."""
    from agentcontrolplane_tpu.ops.pallas.paged_attention import heads_per_window

    assert heads_per_window(64, 2) == 2
    c = _stream_case(_PAGES["empty-between-long"], jnp.float32, H=4, Hkv=2, d=64)
    _stream_parity(c, True, 1e-5)
    _stream_parity(c, False, 1e-5)


def test_the_stream_walks_sp2_slices():
    """Each rank walks its half of every page (f32, 8 rows a rank: 16 pages
    a turn) and the ranks' states merge; empty slots first, between, last."""
    from agentcontrolplane_tpu.ops.paged import paged_decode_attention_reference_cache_plus_new
    from agentcontrolplane_tpu.ops.pallas.paged_attention import paged_decode_attention_cache_plus_new_sharded
    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    c = _stream_case([0, 65, 1, 0, 16, 17, 33, 0], jnp.float32)
    mesh = make_mesh({"sp": 2, "tp": 1}, devices=jax.devices()[:2])
    out = paged_decode_attention_cache_plus_new_sharded(
        mesh, c["q"], _merged(c["k_pages"]), _merged(c["v_pages"]), c["tables"], c["seq_lens"],
        c["k_new"], c["v_new"], interpret=True)
    ref = paged_decode_attention_reference_cache_plus_new(
        c["q"], c["clean_k"], c["clean_v"], c["tables"], c["seq_lens"], c["k_new"], c["v_new"])
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_the_stream_walks_windows_whose_ring_wraps_inside_a_turn():
    """The window walk (`starts`, `ring`): a ring of 17 pages a slot, walks
    of 0 to 3 turns that begin anywhere in the ring and wrap inside a turn;
    slots that have not reached a row yet walk nothing. Every page of the
    pool that is not a slot's ring is NaN."""
    from agentcontrolplane_tpu.ops import paged
    from agentcontrolplane_tpu.ops.pallas.paged_attention import paged_decode_attention_cache_plus_new

    S, H, H_kv, d, P, W = 8, 4, 2, 8, 16, 256
    ring = paged.ring_size(W, P)
    lens = np.asarray([0, 1000, 15, 0, 256 + 130, 17 * 16 * 3 + 5, 0, 255], np.int32)
    rng = np.random.default_rng(7)
    NW = (S + 2) * ring
    kp, vp = (rng.normal(size=(NW, P, H_kv * d)).astype(np.float32) for _ in range(2))
    kp[S * ring:] = vp[S * ring:] = np.nan  # the pad slot's ring and beyond: nobody's
    q = jnp.asarray(rng.normal(size=(S, H, d)), jnp.float32)
    kn, vn = (jnp.asarray(rng.normal(size=(S, H_kv, d)), jnp.float32) for _ in range(2))
    n = jnp.asarray(lens)
    first = jnp.maximum(n + 1 - W, 0)
    tables = paged.ring_tables(jnp.arange(S, dtype=jnp.int32), ring)
    want = paged.paged_decode_attention_reference_cache_plus_new(
        q, jnp.nan_to_num(kp), jnp.nan_to_num(vp), tables, n, kn, vn,
        row_positions=paged.ring_positions(n, ring, P), starts=first)
    got = paged_decode_attention_cache_plus_new(
        q, jnp.asarray(kp), jnp.asarray(vp), tables, n, kn, vn, interpret=True, starts=first, ring=ring)
    np.testing.assert_allclose(got, want, atol=5e-6)


def _spy_on_fetches(monkeypatch):
    """Every start (0) and wait (1) of the walk's async copies as they run,
    `(kind, buffer, semaphore column)`: a `jax.debug.callback` beside each."""
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    events = []
    real = pa.pltpu.make_async_copy

    class Spy:
        def __init__(self, src, dst, sem):
            self.copy, self.at = real(src, dst, sem), sem.transforms[-1].indices

        def _note(self, kind):
            jax.debug.callback(lambda k, b, i: events.append((int(k), int(b), int(i))), kind, *self.at)

        def start(self):
            self._note(0)
            self.copy.start()

        def wait(self):
            self._note(1)
            self.copy.wait()

    monkeypatch.setattr(pa.pltpu, "make_async_copy", Spy)
    return events


@pytest.mark.parametrize("order", ["empty-first", "empty-between-long", "last-turns-of-G-1-pages", "every-slot-empty"])
def test_every_fetch_is_started_once_and_waited_for_once(order, monkeypatch):
    """A wait on a fetch never started hangs the chip, and the interpreter
    does not hang: so the starts and waits are recorded as they run (a
    callback beside each) and held to the ring's discipline, semaphore by
    semaphore: a turn's 2 x G starts, then its one wait, then the buffer's
    next turn; as many waits as the batch has turns; nothing left started.
    (Callbacks of one loop turn may run in any order, those of different
    turns run in theirs: a buffer's events are never of one turn.)"""
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    events = _spy_on_fetches(monkeypatch)
    c = _stream_case(_PAGES[order], jnp.float32)
    out = paged_decode_attention(c["q"], c["k_pages"], c["v_pages"], c["tables"], c["seq_lens"], interpret=True)
    jax.block_until_ready(out)
    jax.effects_barrier()
    turns = sum(-(-n // _G) for n in _PAGES[order])
    assert sum(k for k, _, _ in events) == turns
    for buf in range(pa.RING):
        mine = [k for k, b, _ in events if b == buf]
        assert mine == ([0] * (2 * _G) + [1]) * (len(mine) // (2 * _G + 1)), (buf, mine)
    assert len(events) == turns * (2 * _G + 1)


@pytest.mark.parametrize(
    "P_local,dtype,H_kv,d,quantized,leaves,want",
    [
        (16, "bfloat16", 4, 128, False, 2, 8),   # the engine's page: a lane tile a turn
        (16, "float32", 8, 128, False, 2, 8),
        (128, "bfloat16", 4, 128, False, 2, 1),  # a page is a tile already
        (8, "bfloat16", 4, 128, False, 2, 1),    # sp=2 slice of page 16: under a bf16 tile
        (8, "float32", 4, 128, False, 2, 16),    # the same slice in f32: on its tile
        (16, "int8", 4, 128, True, 2, 1),        # int8 tile is 32 rows; scale rows per page
        (32, "int8", 4, 128, True, 2, 1),
        (16, "float32", 32, 128, False, 2, 4),   # MHA 32 heads f32: scratch over budget, halved
        (16, "float32", 64, 128, False, 2, 2),   # and halved again
        # the cells: the 7B and mellum2, a chip of the 32B at tp=4, lfm2's
        # four lane windows of two heads of 64, jamba2's one KV head
        (16, "bfloat16", 2, 128, False, 2, 8),
        (16, "bfloat16", 4, 128, False, 2, 8),   # lfm2: 8 KV heads of 64 walk as 4 windows of 128
        (16, "bfloat16", 1, 128, False, 2, 8),
        # a pool of one leaf (a latent row, key and value at once): four lane tiles a turn
        (16, "bfloat16", 1, 640, False, 1, 32),  # kanana2: 512 rows a turn
        (16, "float32", 1, 640, False, 1, 32),
        (128, "bfloat16", 1, 640, False, 1, 4),
        (8, "bfloat16", 1, 640, False, 1, 1),    # under a bf16 tile: a page a turn, as K and V pages
        (8, "float32", 1, 640, False, 1, 64),
        (16, "float32", 1, 8192, False, 1, 4),   # a row of 32 KB: halved to the scratch budget
    ],
)
def test_pages_per_turn_rule(P_local, dtype, H_kv, d, quantized, leaves, want):
    """G by geometry, and beside it what the walk keeps in flight: RING - 1
    turns of G pages, K and V or a pool's one leaf, whatever a turn's bytes
    (deeper rings measured slower on the chip: PERF.md, PR 43; no faster
    over one leaf: PR 45), in a ring that holds to the scratch budget."""
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    assert pa.pages_per_turn(P_local, jnp.dtype(dtype), H_kv, d, quantized, leaves) == want
    turn = leaves * want * P_local * H_kv * d * jnp.dtype(dtype).itemsize
    assert pa.fetches_in_flight(P_local, jnp.dtype(dtype), H_kv, d, quantized, leaves) == (pa.RING - 1, (pa.RING - 1) * turn)
    assert pa.RING * turn <= pa._SCRATCH_BUDGET
    if leaves == 2:  # the default is K and V pages
        assert pa.pages_per_turn(P_local, jnp.dtype(dtype), H_kv, d, quantized) == want


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("turns", [(0, 3, 1, 0, 2, 0), (2, 0), (0, 0)], ids=["empty-around", "empty-last", "every-slot-empty"])
def test_the_latent_parity_case_the_chip_runs_holds_interpreted(turns, dtype):
    """`kernel_parity.latent_walk_parity` is what `chip_smoke.py` and
    `test_tpu_hardware.py` run compiled: the same case (fewer turns, 8
    heads) through the interpreter, unnamed pages NaN."""
    from agentcontrolplane_tpu.engine.kernel_parity import latent_walk_parity, make_latent_case

    case = make_latent_case(3, H=8, turns=turns, dtype=jnp.dtype(dtype))
    assert case["pages_per_turn"] == 32 and bool(jnp.isnan(case["pages"]).any())
    got = latent_walk_parity(case, interpret=True)
    assert got["ok"] and got["shape"] == (len(turns), 8, 512), got


def test_excluded_geometry_walks_one_page_a_turn():
    """int8 pages stay at G = 1 — the scratch the walk allocates is one
    page deep — and still match the reference."""
    from agentcontrolplane_tpu.engine.kernel_parity import make_paged_case, page_walk_parity
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    case = make_paged_case(
        5, S=4, H=4, H_kv=2, d=8, P=16, max_pages=10, num_pages=64,
        dtype=jnp.bfloat16, int8=True,
    )
    jaxpr = jax.make_jaxpr(
        lambda c: pa.paged_decode_attention(
            c["q"], c["k_pages"], c["v_pages"], c["block_tables"], c["seq_lens"],
            interpret=True, **c["scales"],
        )
    )(case)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    kv_buf = call.params["jaxpr"].invars[-5].aval  # kv_buf, sc_buf, sems, turns, next
    assert kv_buf.shape == (pa.RING, 2, 16, 2 * 8), kv_buf.shape
    assert pa.fetches_in_flight(16, jnp.int8, 2, 8, True) == (pa.RING - 1, (pa.RING - 1) * 2 * 16 * 16)
    assert page_walk_parity(case, plus_new=True, interpret=True)["ok"]


def test_sp_slices_walk_a_tile_a_turn_interpret():
    """Context-parallel slices that land on their dtype's tile (f32, page
    16 over sp=2: 8 rows a rank, 16 pages a turn): a turn's columns lie
    page_size tokens apart page to page, and the ranks' (acc, m, l) merge
    to the exact reference."""
    from agentcontrolplane_tpu.ops.paged import (
        paged_decode_attention_reference_cache_plus_new,
    )
    from agentcontrolplane_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_cache_plus_new_sharded,
        pages_per_turn,
    )
    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    assert pages_per_turn(16 // 2, jnp.float32, 2, 8) == 16
    S, H, Hkv, d, P, G = 3, 4, 2, 8, 16, 16
    # under one turn, past one, past two; pages handed out in table order
    seq_lens = np.asarray([G * P + 3, 7, 2 * G * P + 2 * P + 1], dtype=np.int32)
    tables = np.full((S, 2 * G + 3), TRASH_PAGE, dtype=np.int32)
    nxt = 1
    for s in range(S):
        n = -(-int(seq_lens[s]) // P)
        tables[s, :n] = np.arange(nxt, nxt + n)
        nxt += n
    rng = np.random.default_rng(13)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype=jnp.float32)  # noqa: E731
    q, k_pages, v_pages = f32(S, H, d), f32(nxt, P, Hkv, d), f32(nxt, P, Hkv, d)
    k_new, v_new = f32(S, Hkv, d), f32(S, Hkv, d)
    tables, seq_lens = jnp.asarray(tables), jnp.asarray(seq_lens)
    ref = paged_decode_attention_reference_cache_plus_new(
        q, k_pages, v_pages, tables, seq_lens, k_new, v_new
    )
    mesh = make_mesh({"sp": 2, "tp": 1}, devices=jax.devices()[:2])
    out = paged_decode_attention_cache_plus_new_sharded(
        mesh, q, _merged(k_pages), _merged(v_pages), tables, seq_lens, k_new, v_new, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# -- the programs over the pool stored as the walk reads it --------------------
#
# `[L, pages, P, H_kv * d]`, read and written through the pool flattened over
# its layers with page ids offset by the layer (ops/paged.py). Every page no
# block table names is NaN in every layer (int8 pages: its scales are), so a
# read through a wrong layer offset or a wrong page fails loudly, and a write
# that lands anywhere else is seen where the NaNs are counted afterwards. The
# reference is the model's plain causal forward over the whole sequence: it
# knows no pool.

_P, _M, _LAYERS, _POOL_PAGES = 8, 4, 3, 24
_MESHES = {"one-device": None, "tp2": {"tp": 2}, "sp2": {"sp": 2, "tp": 1}}


def _pool_case(mesh_axes, int8_pages):
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as Spec

    from agentcontrolplane_tpu.models import llama
    from agentcontrolplane_tpu.parallel.mesh import make_mesh, param_shardings

    c = dataclasses.replace(llama.PRESETS["tiny"], n_layers=_LAYERS)
    params = llama.init_params(c, jax.random.key(7))
    rng = np.random.default_rng(3)
    tokens = rng.integers(1, c.vocab_size, size=(2, _M * _P)).astype(np.int32)
    # scattered pages in table order; the second sequence never needs its fourth
    ids = rng.permutation(np.arange(1, _POOL_PAGES))[:7]
    tables = np.asarray([ids[:4], list(ids[4:7]) + [TRASH_PAGE]], dtype=np.int32)
    named = np.zeros(_POOL_PAGES, bool)
    named[tables.reshape(-1)] = True
    pool = llama.init_paged_cache(c, _POOL_PAGES, _P, quantize_kv=int8_pages)
    assert pool["k"].shape == (_LAYERS, _POOL_PAGES, _P, c.n_kv_heads * c.head_dim)
    poisoned = ("ks", "vs") if int8_pages else ("k", "v")
    for name in poisoned:
        pool[name] = pool[name].at[:, ~named].set(jnp.nan)
    mesh = None
    if mesh_axes is not None:
        n = int(np.prod(list(mesh_axes.values())))
        mesh = make_mesh(mesh_axes, devices=jax.devices()[:n])
        page_sh = NamedSharding(mesh, Spec(None, None, "sp" if "sp" in mesh_axes else None, "tp"))
        pool = {name: jax.device_put(a, page_sh) for name, a in pool.items()}
        params = jax.device_put(params, param_shardings(mesh, c, params))
    want = np.asarray(llama.forward(llama.init_params(c, jax.random.key(7)), jnp.asarray(tokens), c))
    return c, params, pool, tokens, tables, named, poisoned, mesh, want


def _rows(tokens, starts, lengths, T):
    out = np.zeros((len(starts), T), np.int32)
    for b, (s, n) in enumerate(zip(starts, lengths)):
        out[b, :n] = tokens[b, s:s + n]
    return jnp.asarray(out)


@pytest.mark.parametrize("walk", ["xla-gather", "pallas-interpret"])
@pytest.mark.parametrize("int8_pages", [False, True], ids=["f32-pages", "int8-pages"])
@pytest.mark.parametrize("mesh_axes", list(_MESHES.values()), ids=list(_MESHES))
def test_programs_through_the_merged_pool_match_the_plain_forward(mesh_axes, int8_pages, walk, monkeypatch):
    """Prefill, continuation, verify and two decode steps of two sequences,
    each program's logits against the plain forward at the same positions,
    on one device, tp=2 and sp=2, the decode walk by the XLA gather and by
    the kernel (interpret mode)."""
    import functools

    from agentcontrolplane_tpu.models import llama
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    name = "paged_decode_attention_cache_plus_new_sharded"  # the one entry the model calls, on any mesh
    monkeypatch.setattr(pa, name, functools.partial(getattr(pa, name), interpret=True))
    c, params, pool, tokens, tables, named, poisoned, mesh, want = _pool_case(mesh_axes, int8_pages)
    tb = jnp.asarray(tables)
    i32 = lambda *a: jnp.asarray(a, jnp.int32)  # noqa: E731
    # int8 pages round every K and V a row and head: the gate's tolerance, not the exact one
    close = functools.partial(np.testing.assert_allclose, rtol=0, atol=0.12 if int8_pages else 2e-4)

    # whole prompts of 16 and 8 tokens (rows padded to 16), two pages and one
    n0 = np.asarray([16, 8])
    page_ids = np.where(np.arange(2)[None, :] * _P < n0[:, None], tables[:, :2], TRASH_PAGE)
    pool, logits = jax.jit(lambda p, kv, *a: llama.prefill_paged_batch(p, kv, *a, c))(
        params, pool, _rows(tokens, [0, 0], n0, 16), i32(*n0), jnp.asarray(page_ids))
    close(np.asarray(logits), want[np.arange(2), n0 - 1], err_msg="prefill")

    # continuations from the page-aligned ends: 7 and 5 tokens, one page each
    n1 = np.asarray([7, 5])
    pool, logits = jax.jit(lambda p, kv, *a: llama.prefill_paged_continue(p, kv, *a, c))(
        params, pool, _rows(tokens, n0, n1, _P), i32(*n1), i32(*n0),
        jnp.asarray(tables[np.arange(2), n0 // _P][:, None]), tb)
    close(np.asarray(logits), want[np.arange(2), n0 + n1 - 1], err_msg="continuation")

    # a verify pass from mid-page (23 and 13): 3 and 2 tokens, a token-row commit
    s2, n2 = n0 + n1, np.asarray([3, 2])
    pool, logits = jax.jit(lambda p, kv, *a: llama.verify_paged_continue(p, kv, *a, c))(
        params, pool, _rows(tokens, s2, n2, 4), i32(*n2), i32(*s2), tb)
    for b in range(2):
        close(np.asarray(logits)[b, :n2[b]], want[b, s2[b]:s2[b] + n2[b]], err_msg=f"verify row {b}")

    # two decode steps; seq 0 crosses into its fourth page at 26 -> 24..31 is page 3
    seq = s2 + n2
    step = jax.jit(lambda p, kv, t, n, a: llama.decode_step_paged(
        p, kv, t, n, tb, a, c, use_pallas=walk == "pallas-interpret", mesh=mesh))
    for j in range(2):
        pool, logits = step(params, pool, jnp.asarray(tokens[np.arange(2), seq + j]), i32(*(seq + j)),
                            jnp.ones((2,), bool))
        close(np.asarray(logits), want[np.arange(2), seq + j], err_msg=f"decode step {j}")
    # an inactive lane writes the trash page and nothing else
    before = {name: np.asarray(a) for name, a in pool.items()}
    pool, _ = step(params, pool, jnp.asarray(tokens[np.arange(2), seq + 2]), i32(*(seq + 2)),
                   jnp.asarray([True, False]))
    for name, a in pool.items():
        a = np.asarray(a)
        lane1 = tables[1][tables[1] != TRASH_PAGE]
        np.testing.assert_array_equal(a[:, lane1], before[name][:, lane1], err_msg=f"{name}: an inactive lane's pages")
    # nothing was written to a page no table names, in any layer
    for name in poisoned:
        a = np.asarray(pool[name])
        assert np.isnan(a[:, ~named]).all(), f"{name}: a write landed on an unnamed page"
        assert np.isfinite(a[:, named & (np.arange(_POOL_PAGES) != TRASH_PAGE)]).all(), name


# -- the pool as a tree of page-shaped leaves: every helper takes them as they come ----------


def _pools():
    """A `k` / `v` pool, an int8 one with its scale twins, and a latent
    pool's one leaf: fresh rows for each, [L, B, T, heads, d]."""
    from agentcontrolplane_tpu.ops import paged

    L, NP, P, B, T = 3, 9, 4, 2, 8
    key = jax.random.key(5)
    rows = lambda i, heads, d: jax.random.normal(jax.random.fold_in(key, i), (L, B, T, heads, d), jnp.float32)  # noqa: E731
    return {
        "k_and_v": (paged.init_kv_pages(L, NP, P, 2, 8, jnp.float32), {"k": rows(1, 2, 8), "v": rows(2, 2, 8)}),
        "int8": (paged.init_kv_pages(L, NP, P, 2, 8, jnp.float32, quantize=True), {"k": rows(1, 2, 8), "v": rows(2, 2, 8)}),
        "one_leaf": (paged.init_latent_pages(L, NP, P, 24, jnp.float32), {"kv": rows(3, 1, 24)}),
    }, (L, NP, P, B, T)


@pytest.mark.parametrize("kind", ["k_and_v", "int8", "one_leaf"])
def test_the_pool_helpers_take_a_pools_leaves_as_they_come(kind):
    """`commit_whole_pages`, `commit_tokens`, `gather_pages` and `set_pages`
    over each kind of pool: what is committed is what is gathered (int8: to
    its rounding), no leaf is named by the helpers, and pages not written
    stay as they were."""
    from agentcontrolplane_tpu.ops import paged

    pools, (L, NP, P, B, T) = _pools()
    pool, new = pools[kind]
    ids = jnp.asarray([[1, 2], [5, 6]], jnp.int32)
    done = paged.commit_whole_pages(pool, new, ids)
    assert set(done) == set(pool) and all(done[name].shape == pool[name].shape for name in pool)
    tol = 0.05 if kind == "int8" else 0.0
    for name, rows in new.items():
        heads = rows.shape[-2]
        for layer in range(L):
            got = paged.gather_pages(done, name, paged.layer_tables(ids, layer, NP), jnp.float32, heads)
            np.testing.assert_allclose(got.reshape(B, T, heads, -1), rows[layer], atol=tol)
        untouched = np.asarray(done[name])[:, [0, 3, 4, 7, 8]]
        assert float(np.abs(untouched).max()) == 0.0
    # a token at a time: row 1 of pages 3 and 7, and nothing else moves
    one = {name: rows[:, :, 0] for name, rows in new.items()}
    after = paged.commit_tokens(done, one, jnp.asarray([3, 7], jnp.int32), jnp.asarray([1, 1], jnp.int32))
    for name, rows in one.items():
        heads = rows.shape[-2]
        got = paged.gather_pages(after, name, paged.layer_tables(jnp.asarray([[3], [7]]), 2, NP), jnp.float32, heads)
        np.testing.assert_allclose(got[:, 0, 1], rows[2], atol=tol)
        assert float(np.abs(np.asarray(got)[:, 0, [0, 2, 3]]).max()) == 0.0
        np.testing.assert_array_equal(np.asarray(after[name])[:, [1, 2, 5, 6]], np.asarray(done[name])[:, [1, 2, 5, 6]])
    # whole pages set leaf by leaf, as the engine's swap-in scatters a host entry's blocks
    blocks = {name: np.asarray(after[name])[:, [1, 2]] for name in after}
    moved = {name: paged.set_pages(after[name], jnp.asarray([7, 8]), jnp.asarray(blocks[name])) for name in after}
    for name in after:
        np.testing.assert_array_equal(np.asarray(moved[name])[:, [7, 8]], blocks[name])
    assert set(paged.pool_leaves({**pool, "state": {"x": 1}})) == set(pool)


@pytest.mark.parametrize("turns", [[0, 3, 1, 0, 2, 1], [0, 0, 5], [1, 0, 0], [0, 0]],
                         ids=["empty-between", "empty-first", "empty-last", "every-slot-empty"])
def test_every_fetch_of_the_latent_walk_is_started_once_and_waited_for_once(turns, monkeypatch):
    """The latent walk on the same stream of turns at its own geometry, four
    lane tiles of rows a turn: ONE fetch a page (a turn's G = 32 starts,
    then its one wait), as many waits as the batch has turns, nothing left
    started; a slot's last turn ends a page short of whole or on one page."""
    from agentcontrolplane_tpu.engine.kernel_parity import make_latent_case
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    events = _spy_on_fetches(monkeypatch)
    c = make_latent_case(2, H=4, width=256, value_width=128, turns=tuple(turns), dtype=jnp.float32)
    G = c["pages_per_turn"]
    assert G == pa.LATENT_TILES * pa.LANES // 16 == 32
    out = pa.paged_latent_state(c["q"], c["pages"], c["block_tables"], c["seq_lens"], 128, 192, interpret=True)
    jax.block_until_ready(out)
    jax.effects_barrier()
    pages_of = [-(-int(n) // 16) for n in c["seq_lens"]]
    total = sum(-(-n // G) for n in pages_of)
    assert total == sum(turns) and sum(k for k, _, _ in events) == total
    for buf in range(pa.RING):
        mine = [k for k, b, _ in events if b == buf]
        assert mine == ([0] * G + [1]) * (len(mine) // (G + 1)), (buf, mine)
    assert len(events) == total * (G + 1)
