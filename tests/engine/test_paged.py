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

from ._paged_cases import G, PAGES, merged, stream_case


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
        mesh, q, merged(k_pages), merged(v_pages), tables, seq_lens, k_new, v_new, interpret=True
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
        mesh, q, merged(k_pages), merged(v_pages), tables, seq_lens, interpret=True,
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
            mesh, q, merged(k_pages), merged(v_pages), tables, seq_lens, k_new, v_new,
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
    c = stream_case(PAGES[order], jnp.float32)
    out = paged_decode_attention(c["q"], c["k_pages"], c["v_pages"], c["tables"], c["seq_lens"], interpret=True)
    jax.block_until_ready(out)
    jax.effects_barrier()
    turns = sum(-(-n // G) for n in PAGES[order])
    assert sum(k for k, _, _ in events) == turns
    for buf in range(pa.RING):
        mine = [k for k, b, _ in events if b == buf]
        assert mine == ([0] * (2 * G) + [1]) * (len(mine) // (2 * G + 1)), (buf, mine)
    assert len(events) == turns * (2 * G + 1)


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
        mesh, q, merged(k_pages), merged(v_pages), tables, seq_lens, k_new, v_new, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


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
