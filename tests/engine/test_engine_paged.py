"""Engine in paged-KV mode must behave identically to slot mode (same greedy
tokens), handle page exhaustion by preemption/backpressure, and recycle pages."""

import dataclasses

import pytest

import jax
import jax.numpy as jnp

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.models.llama import PRESETS
from agentcontrolplane_tpu.parallel.mesh import make_mesh

TOK = ByteTokenizer()
CFG = dataclasses.replace(PRESETS["tiny"], vocab_size=512, max_seq_len=256, n_kv_heads=2)


def make_engine(kv_layout, **kw):
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    eng = Engine(
        config=CFG,
        tokenizer=TOK,
        mesh=mesh,
        max_slots=4,
        max_ctx=64,
        prefill_buckets=(32, 64),
        decode_block_size=4,
        kv_layout=kv_layout,
        page_size=8,
        **kw,
    )
    eng.start()
    return eng


@pytest.fixture(scope="module")
def engines():
    slot = make_engine("slot")
    paged = make_engine("paged")
    yield slot, paged
    slot.stop()
    paged.stop()


def test_paged_matches_slot_greedy(engines):
    slot, paged = engines
    for prompt in ["hello world", "a", "xyz" * 7]:
        r_slot = slot.generate(prompt, SamplingParams(temperature=0.0, max_tokens=10))
        r_paged = paged.generate(prompt, SamplingParams(temperature=0.0, max_tokens=10))
        assert r_paged.tokens == r_slot.tokens, prompt


def test_paged_concurrent_matches_solo(engines):
    _, paged = engines
    prompts = ["aaaa", "bb", "cccccc", "d"]
    solo = [
        paged.generate(p, SamplingParams(temperature=0.0, max_tokens=6)).tokens
        for p in prompts
    ]
    futs = [paged.submit(p, SamplingParams(temperature=0.0, max_tokens=6)) for p in prompts]
    assert [f.result(timeout=120).tokens for f in futs] == solo


def test_pages_recycled_after_completion(engines):
    _, paged = engines
    free0 = paged._allocator.free_count
    futs = [paged.submit(f"req {i}", SamplingParams(temperature=0.0, max_tokens=5)) for i in range(8)]
    for f in futs:
        f.result(timeout=120)
    # allocator drains back to the initial level once everything finishes
    deadline = 100
    while paged._allocator.free_count != free0 and deadline:
        import time

        time.sleep(0.05)
        deadline -= 1
    assert paged._allocator.free_count == free0


def test_page_exhaustion_backpressure():
    # tiny pool: 9 usable pages of size 8 -> at most ~2 concurrent 32-token
    # sequences; 6 requests must still ALL complete via backpressure
    eng = make_engine("paged", kv_pages=10)
    try:
        futs = [
            eng.submit("w" * 20, SamplingParams(temperature=0.0, max_tokens=12))
            for _ in range(6)
        ]
        results = [f.result(timeout=180) for f in futs]
        assert len(results) == 6
        assert all(r.finish_reason in ("stop", "length") for r in results)
    finally:
        eng.stop()


def test_lookahead_reservation_bounds_table_uploads():
    """Page reservation runs several decode blocks ahead so the block table
    is NOT re-uploaded every dispatch (each upload is a serialized
    host->device RTT in the decode hot loop). With block K == 4 and
    lookahead 8, a 48-token generation must dirty the table ~ once per 8
    blocks, not once per block."""
    eng = make_engine("paged")
    assert eng.page_lookahead_blocks == 8
    try:
        r = eng.generate("q" * 16, SamplingParams(temperature=0.0, max_tokens=48))
        assert len(r.tokens) >= 1
        blocks = eng.decode_steps / eng.decode_block_size
        # strictly fewer uploads than dispatched blocks; the exact count
        # depends on prefill/admission, so assert the order of magnitude
        assert eng.table_uploads <= max(3, blocks / 2), (
            f"{eng.table_uploads} uploads over ~{blocks:.0f} blocks"
        )
    finally:
        eng.stop()


def test_lookahead_one_matches_legacy_per_block_behavior():
    """page_lookahead_blocks=1 degenerates to the strict per-block
    allocation; output must be identical to the default lookahead."""
    a, b = make_engine("paged"), make_engine("paged")
    a.page_lookahead_blocks, b.page_lookahead_blocks = 1, 8
    try:
        ra = a.generate("lookahead", SamplingParams(temperature=0.0, max_tokens=24))
        rb = b.generate("lookahead", SamplingParams(temperature=0.0, max_tokens=24))
        assert ra.tokens == rb.tokens
    finally:
        a.stop()
        b.stop()


def test_pass1_reclaims_other_slots_lookahead_pages():
    """ADVICE r3: lookahead top-ups must never starve a strictly-fitting
    slot in a LATER round — on pass-1 exhaustion, unused lookahead pages
    (beyond other slots' strict next-block need) are clawed back before
    preempting. White-box: drain the allocator into slot 0's table as
    lookahead excess, then ask for a strict allocation for slot 1."""
    # pool sized so slot 0's max table (max_pages_per_seq) drains it exactly
    # (page 0 is the reserved trash page)
    eng = make_engine("paged", kv_pages=9)
    try:
        K = eng.decode_block_size
        strict0 = -(-(16 + K) // eng.page_size)  # slot 0's strict need
        import types

        eng._slots[0] = types.SimpleNamespace(  # white-box stub
            parked=False, prefilling=False
        )
        eng._seq_lens[0] = 16
        # hand slot 0 its strict pages plus the rest of the pool as lookahead
        table = eng._allocator.alloc(strict0)
        table += eng._allocator.alloc(
            min(eng._allocator.free_count, eng.max_pages_per_seq - strict0)
        )
        eng._slot_pages[0] = list(table)
        eng._block_tables[0, : len(table)] = table
        assert eng._allocator.free_count == 0

        got = eng._alloc_reclaiming_lookahead(2, requester=1)
        assert got is not None and len(got) == 2
        # slot 0 kept exactly its strict need; the excess was reclaimed
        assert len(eng._slot_pages[0]) == strict0
        assert eng._tables_dirty

        # nothing left to reclaim below strict need -> honest failure
        assert eng._alloc_reclaiming_lookahead(10_000, requester=1) is None
        assert len(eng._slot_pages[0]) == strict0
    finally:
        eng._slots.clear()
        eng.stop()


def test_stats_name_the_walks_pages_per_turn(engines):
    """`kv_pages.pages_per_turn` is the G the compiled page walk runs with;
    on the CPU no walk is compiled (the XLA reference serves): 0."""
    slot, paged = engines
    assert not paged._use_pallas
    assert paged.stats()["kv_pages"]["pages_per_turn"] == 0
    assert "kv_pages" not in slot.stats()


def test_stats_name_what_the_walk_keeps_in_flight(engines):
    """`kv_pages.turns_in_flight` / `bytes_in_flight`: what the compiled walk
    keeps started ahead of the turn it folds, from the rule that sizes its
    scratch; an engine with no kernel reads 0 and 0. The rule itself at the
    7B's geometry: three turns of eight 16 KB pages, K and V."""
    from agentcontrolplane_tpu.ops.pallas.paged_attention import fetches_in_flight

    slot, paged = engines
    pages = paged.stats()["kv_pages"]
    assert (pages["turns_in_flight"], pages["bytes_in_flight"]) == (0, 0)
    assert list(pages)[:6] == ["total", "free", "page_size", "pages_per_turn", "turns_in_flight", "bytes_in_flight"]
    assert fetches_in_flight(16, jnp.bfloat16, 4, 128) == (3, 3 * 2 * 8 * 16 * 512 * 2)
