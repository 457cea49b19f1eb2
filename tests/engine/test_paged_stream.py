"""The page walk as one stream of turns over every slot (interpreter mode):
contexts that straddle a turn, every order of empty, short and long slots,
a batch over one program's slots, int8 pages, packed heads, sp slices and a
window's ring, each against the reference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from agentcontrolplane_tpu.ops.paged import PageAllocator, TRASH_PAGE, paged_decode_attention_reference
from agentcontrolplane_tpu.ops.pallas.paged_attention import paged_decode_attention

from ._paged_cases import DEPTH, G, PAGES, merged, stream_case, stream_parity


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


@pytest.mark.parametrize("plus_new", [False, True], ids=["plain", "cache-plus-new"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("order", list(PAGES))
def test_the_stream_of_turns_across_slots_matches_the_reference(order, dtype, plus_new):
    from agentcontrolplane_tpu.engine.kernel_parity import TOLERANCE
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    assert (pa.RING - 1, pa.pages_per_turn(16, jnp.dtype(dtype), 2, 8)) == (DEPTH, G)
    c = stream_case(PAGES[order], jnp.dtype(dtype))
    stream_parity(c, plus_new, {"float32": 1e-5, "bfloat16": TOLERANCE["bfloat16"]}[dtype])


@pytest.mark.parametrize("order", ["empty-between-long", "empty-first", "every-slot-empty"])
def test_a_batch_too_large_for_one_program_streams_in_several(order, monkeypatch):
    """Where the slots' q and outputs do not fit VMEM together a program
    takes a divisor of them (`slots_per_program`) and the next program the
    next: each drains its own stream, tables and lengths read at its offset."""
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    c = stream_case(PAGES[order], jnp.float32)
    S = len(PAGES[order])
    assert pa.slots_per_program(S, 2, 2, 8, jnp.float32) == S
    monkeypatch.setattr(pa, "_SLOTS_BUDGET", 40 << 10)
    assert pa.slots_per_program(S, 2, 2, 8, jnp.float32) == {10: 2, 6: 2, 3: 1}[S]
    stream_parity(c, True, 1e-5)
    stream_parity(c, False, 1e-5)


def test_the_stream_walks_int8_pages_a_page_a_turn():
    """`G = 1`: every page a turn of its own, its scale rows fetched beside
    it; the turn counts of the empty-between-long order in pages."""
    c = stream_case([10, 0, 13, 1, 0, 0, 4, 2, 0, 3], jnp.bfloat16, int8=True)
    stream_parity(c, True, 2e-2)


def test_the_stream_walks_packed_heads_at_width_64():
    """Two KV heads to a lane window (the wrapper's layout): the stream
    underneath is the same."""
    from agentcontrolplane_tpu.ops.pallas.paged_attention import heads_per_window

    assert heads_per_window(64, 2) == 2
    c = stream_case(PAGES["empty-between-long"], jnp.float32, H=4, Hkv=2, d=64)
    stream_parity(c, True, 1e-5)
    stream_parity(c, False, 1e-5)


def test_the_stream_walks_sp2_slices():
    """Each rank walks its half of every page (f32, 8 rows a rank: 16 pages
    a turn) and the ranks' states merge; empty slots first, between, last."""
    from agentcontrolplane_tpu.ops.paged import paged_decode_attention_reference_cache_plus_new
    from agentcontrolplane_tpu.ops.pallas.paged_attention import paged_decode_attention_cache_plus_new_sharded
    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    c = stream_case([0, 65, 1, 0, 16, 17, 33, 0], jnp.float32)
    mesh = make_mesh({"sp": 2, "tp": 1}, devices=jax.devices()[:2])
    out = paged_decode_attention_cache_plus_new_sharded(
        mesh, c["q"], merged(c["k_pages"]), merged(c["v_pages"]), c["tables"], c["seq_lens"],
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
