"""Mutation harness for the armed runtime invariant checker
(engine/invariants.py).

A checker that never fires proves nothing: each test seeds one HISTORICAL
bug class into a healthy engine's state and asserts the audit catches it —

- **mirror drift** (the PR 6 class: stats counters diverging from the slot
  dict) via direct corruption AND end-to-end via the
  ``engine.invariant_break`` fault site (armed engine crashes with
  ``InvariantViolation``, callers fail loudly, ``ensure_running`` recovers);
- **refcount leak / conservation break** (the PR 5 class: reclaim stripping
  pages an in-flight dispatch was granted);
- **parked-KV coverage break** (the PR 7 garbage-lane class in its
  host-observable form: a parked slot no longer holding exactly its
  prompt-covering pages means adoption would resume over corrupt KV);
- **quantized scale-row corruption** (ISSUE 14: int8 KV pages whose
  per-page scale ownership leaks past a free, vanishes under a live
  allocation, or shears off the cache structurally — each means later
  reads dequantize through wrong/unowned scale storage).

Every corruption is reverted so the module-scoped engine stays healthy
between tests; the audit itself is read-only.
"""

import dataclasses
import time

import pytest

import jax

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.invariants import (
    InvariantViolation,
    check_engine_invariants,
    verify_engine,
)
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.models.llama import PRESETS
from agentcontrolplane_tpu.observability.metrics import REGISTRY
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import FAULTS

TOK = ByteTokenizer()
CFG = dataclasses.replace(PRESETS["tiny"], vocab_size=512, max_seq_len=256, n_kv_heads=2)


def make_engine(**kw):
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    kw.setdefault("check_invariants", True)
    eng = Engine(
        config=CFG,
        tokenizer=TOK,
        mesh=mesh,
        max_slots=4,
        max_ctx=64,
        prefill_buckets=(32, 64),
        decode_block_size=4,
        kv_layout="paged",
        page_size=8,
        **kw,
    )
    eng.start()
    return eng


def counter(name: str) -> float:
    m = REGISTRY._metrics.get(name)
    return 0.0 if m is None else m.values.get((), 0.0)


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    FAULTS.reset()


@pytest.fixture(scope="module")
def eng():
    """One armed paged engine, warmed with real traffic that leaves a
    parked slot and live prefix-cache entries behind — the audit must hold
    on the REAL state shapes, not an empty engine. The violations counter
    is process-global, and earlier suites deliberately trip it (the flight
    recorder's crash-dump test arms engine.invariant_break) — snapshot it
    so this module asserts on ITS engine's delta, not absolutes."""
    e = make_engine(spec_len=4, prefill_chunk=16)
    e.violations0 = counter("acp_engine_invariant_violations_total")
    sp = SamplingParams(temperature=0.0, max_tokens=10)
    futs = [
        e.submit(f"hello world {i} " * 3, sp, park=(i == 0)) for i in range(4)
    ]
    for f in futs:
        assert f.result(timeout=600).finish_reason in ("stop", "length")
    yield e
    e.stop()


def _settle(e: Engine) -> None:
    """Let the engine loop drain to idle so test-thread reads don't race a
    dispatch in flight."""
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and (e._has_work() or len(e._waiting)):
        time.sleep(0.01)
    time.sleep(0.05)


def test_clean_engine_audits_clean_and_counts_checks(eng):
    _settle(eng)
    assert eng._parked_count == 1  # the fixture's parked conversation
    assert verify_engine(eng) == []
    # the engine ran armed through the fixture's traffic: every dispatch
    # cycle audited, none tripped
    assert counter("acp_engine_invariant_checks_total") > 0
    assert counter("acp_engine_invariant_violations_total") == eng.violations0


def test_mirror_drift_is_detected(eng):
    _settle(eng)
    eng._parked_count += 1
    try:
        problems = verify_engine(eng)
    finally:
        eng._parked_count -= 1
    assert any("mirror drift" in p and "_parked_count" in p for p in problems)

    eng._prefilling_count += 1
    try:
        problems = verify_engine(eng)
    finally:
        eng._prefilling_count -= 1
    assert any("_prefilling_count" in p for p in problems)
    assert verify_engine(eng) == []


def test_refcount_leak_and_conservation_break_are_detected(eng):
    _settle(eng)
    refs = eng._allocator._refs
    page = next(iter(refs))
    refs[page] += 1  # a reference nothing owns: the page can never pool
    try:
        problems = verify_engine(eng)
    finally:
        refs[page] -= 1
    assert any("refcount leak" in p for p in problems)

    stolen = eng._allocator._free.pop()  # page vanishes from accounting
    try:
        problems = verify_engine(eng)
    finally:
        eng._allocator._free.append(stolen)
    assert any("vanished from accounting" in p for p in problems)
    assert verify_engine(eng) == []


def test_parked_kv_coverage_break_is_detected(eng):
    _settle(eng)
    slot = next(s for s, sl in eng._slots.items() if sl.parked)

    # page list no longer covers the prompt cut (the host-observable shape
    # of the PR 7 garbage-lane corruption of parked prompt KV)
    page = eng._slot_pages[slot].pop()
    try:
        problems = verify_engine(eng)
    finally:
        eng._slot_pages[slot].append(page)
    assert any("parked slot" in p for p in problems)

    # seq_len mirror diverging from the adoption cut
    cut = int(eng._seq_lens[slot])
    eng._seq_lens[slot] = cut + 1
    try:
        problems = verify_engine(eng)
    finally:
        eng._seq_lens[slot] = cut
    assert any("park_cut" in p for p in problems)
    assert verify_engine(eng) == []


def test_check_raises_and_counts(eng):
    _settle(eng)
    check_engine_invariants(eng)  # healthy: no raise
    before = counter("acp_engine_invariant_violations_total")
    eng._parked_count += 1
    try:
        with pytest.raises(InvariantViolation, match="mirror drift"):
            check_engine_invariants(eng)
    finally:
        eng._parked_count -= 1
    assert counter("acp_engine_invariant_violations_total") > before


def test_host_resident_page_leak_is_detected():
    """PR 11 corruption class 1: KV swapped out to the host tier whose
    bytes drift from the pool's entry accounting — RAM that can never be
    restored or reclaimed. Seeded both ways: counter drift and an entry
    vanishing behind the counter's back."""
    e = make_engine(kv_pages=10, host_kv_bytes=1 << 22)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=12)
        with e.hold_admission():  # oversubscribe -> preempt -> swap out
            futs = [e.submit(ch * 20, sp) for ch in "abcdef"]
        for f in futs:
            f.result(timeout=180)
        assert e.kv_swap_outs >= 1
        _settle(e)
        # a park-expiry swap may land an entry; make one deterministically
        if not len(e._host_pool):
            from agentcontrolplane_tpu.ops.paged import HostKVEntry
            import numpy as np

            e._host_pool.put(HostKVEntry(
                rid="seed", tokens=tuple(range(16)),
                rows={"k": np.zeros((2, 16, 2, 8), dtype=np.float32),
                      "v": np.zeros((2, 16, 2, 8), dtype=np.float32)},
            ))
            e._publish_memory_state()
        assert verify_engine(e) == []

        e._host_pool.used_bytes += 123  # bytes with no entry: the leak
        try:
            problems = verify_engine(e)
        finally:
            e._host_pool.used_bytes -= 123
        assert any("host KV pool leak" in p for p in problems)
        # the engine mirror must also be flagged (stats() serves it)
        assert any("_host_kv_used" in p for p in problems)

        rid, entry = next(iter(e._host_pool._entries.items()))
        del e._host_pool._entries[rid]  # entry gone, bytes still counted
        try:
            problems = verify_engine(e)
        finally:
            e._host_pool._entries[rid] = entry
        assert any("host KV pool leak" in p for p in problems)
        assert verify_engine(e) == []
    finally:
        e.stop()


def test_quantized_scale_row_corruption_classes_are_detected():
    """The quantized-page accounting class (ISSUE 14): an engine serving
    int8 KV must own exactly one set of scale rows per allocated page.
    Both corruption directions — a scale row leaking past its page's
    free, and an allocated page whose scale ownership vanished — plus the
    structural cache coupling (scale twins sheared off, scale storage on
    a knobs-off engine) must all trip the audit."""
    e = make_engine(quantize_kv=True)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        assert e.generate("warm quantized pages", sp).finish_reason in (
            "stop", "length",
        )
        _settle(e)
        assert verify_engine(e) == []

        scale_pages = e._allocator._scale_pages
        # direction 1: scale rows owned for a page that was freed
        stale = max(set(range(1, e.num_pages)) - set(e._allocator._refs))
        scale_pages.add(stale)
        try:
            problems = verify_engine(e)
        finally:
            scale_pages.discard(stale)
        assert any("scale-row leak" in p for p in problems)

        # direction 2: an allocated page without owned scale rows — seed a
        # live allocation first (the idle engine may hold none)
        pages = e._allocator.alloc(1)
        try:
            scale_pages.discard(pages[0])
            problems = verify_engine(e)
            scale_pages.add(pages[0])
        finally:
            e._allocator.free(pages)
        assert any("without owned scale rows" in p for p in problems)

        # structural coupling: scale twin sheared off its values
        ks = e.cache.pop("ks")
        try:
            problems = verify_engine(e)
        finally:
            e.cache["ks"] = ks
        assert any("cache carries keys" in p for p in problems)
        assert verify_engine(e) == []
    finally:
        e.stop()


def test_off_knob_engine_with_scale_storage_is_detected(eng):
    """The purity direction: a knobs-off engine carrying scale storage is
    itself a violation (the bit-identical plain path must have none)."""
    _settle(eng)
    import jax.numpy as jnp

    eng.cache["ks"] = jnp.zeros((1,), dtype=jnp.float32)
    try:
        problems = verify_engine(eng)
    finally:
        del eng.cache["ks"]
    assert any("quantize_kv off" in p for p in problems)
    assert verify_engine(eng) == []


def test_shared_page_refcount_drift_is_detected(eng):
    """PR 11 corruption class 2: a dedup'd/shared page freed while a
    second owner still holds it — the next free would pool a live page and
    hand it to two sequences. The fixture's parked slot + its prefix-cache
    entry share pages (refcount 2), so dropping one ref leaves unshared
    multi-ownership plus shared-counter drift."""
    _settle(eng)
    _, refs = eng._allocator.audit()
    shared_pg = next(pg for pg, r in refs.items() if r > 1)
    eng._allocator.free([shared_pg])  # one owner's ref silently dropped
    try:
        problems = verify_engine(eng)
    finally:
        eng._allocator.share([shared_pg])  # restore the dropped reference
    assert any("owners but refcount" in p for p in problems)
    assert verify_engine(eng) == []

    # incremental shared-counter drift is caught independently
    eng._allocator._shared += 1
    try:
        problems = verify_engine(eng)
    finally:
        eng._allocator._shared -= 1
    assert any("shared_count" in p for p in problems)
    # and the stats() mirror drift class
    eng._prefix_shared_pages += 1
    try:
        problems = verify_engine(eng)
    finally:
        eng._prefix_shared_pages -= 1
    assert any("_prefix_shared_pages" in p for p in problems)
    assert verify_engine(eng) == []


def test_goodput_ledger_conservation_break_is_detected(eng):
    """ISSUE 12 corruption class: a dispatch site adding compute without
    classifying it (or a non-zero-sum reclassify) breaks the goodput
    ledger the scheduler autopilot will steer by. Seeded three ways:
    unclassified compute, a negative waste counter, and negative
    goodput."""
    _settle(eng)
    assert verify_engine(eng) == []
    prof = eng.profiler

    prof._computed += 7  # compute nothing classified
    try:
        problems = verify_engine(eng)
    finally:
        prof._computed -= 7
    assert any("goodput ledger conservation broken" in p for p in problems)

    pad0, comp0 = prof._waste["pad_bucket"], prof._computed
    prof._waste["pad_bucket"] = -2
    prof._computed = comp0 - pad0 - 2  # keep the sum balanced: only negativity trips
    try:
        problems = verify_engine(eng)
    finally:
        prof._waste["pad_bucket"], prof._computed = pad0, comp0
    assert any("negative waste-cause counters" in p for p in problems)

    good0, comp0 = prof._goodput, prof._computed
    prof._goodput = -1
    prof._computed = -1 + sum(prof._waste.values())  # balanced but negative
    try:
        problems = verify_engine(eng)
    finally:
        prof._goodput, prof._computed = good0, comp0
    assert any("goodput ledger negative" in p for p in problems)
    assert verify_engine(eng) == []


def test_invariant_break_fault_trips_end_to_end():
    """The deterministic fault site corrupts a mirror inside the engine
    loop; the armed checker must crash the engine, fail the in-flight
    caller loudly, and leave the engine recoverable."""
    eng = make_engine()
    try:
        # healthy round trip first (also compiles the programs)
        assert eng.generate("ab", SamplingParams(max_tokens=2)).tokens
        FAULTS.arm("engine.invariant_break")
        fut = eng.submit("hello there", SamplingParams(temperature=0.0, max_tokens=48))
        with pytest.raises(RuntimeError, match="invariant"):
            fut.result(timeout=600)
        assert eng._crashed
        # phase-machine posture: rebuild serving state and carry on
        assert eng.ensure_running()
        out = eng.generate("hello again", SamplingParams(max_tokens=4))
        assert out.finish_reason in ("stop", "length")
        assert verify_engine(eng) == []
    finally:
        eng.stop()


def test_disarmed_fault_site_is_inert():
    """Arming engine.invariant_break against a DISARMED engine must not
    corrupt anything: the site is gated on check_invariants."""
    eng = make_engine(check_invariants=False)
    try:
        FAULTS.arm("engine.invariant_break")
        out = eng.generate("hello", SamplingParams(temperature=0.0, max_tokens=8))
        assert out.finish_reason in ("stop", "length")
        assert verify_engine(eng) == []  # mirrors untouched
    finally:
        eng.stop()
