"""Compute efficiency observatory, engine-integrated (ISSUE 12):

- profiler on vs off is byte-identical on greedy outputs (both KV layouts,
  spec + chunking on) — the observatory measures, never steers;
- per-program dispatch telemetry populates for the real program zoo;
- the cold-compile observatory: a deliberately un-prewarmed shape after
  prewarm-complete fires the event + counter, and a fully-prewarmed run
  reports zero serving-time cold compiles;
- the goodput/waste ledger conserves (computed == goodput + Σ waste) under
  the stress/fault matrix (preempt + spec_mismatch + host_swap_error) with
  the armed invariant checker auditing every cycle;
- the prewarm coverage gap is data, not a log line (satellite: a provoked
  "batch never formed" records a flight event + counter).
"""

import dataclasses
import time

import pytest

import jax

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.invariants import verify_engine
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.models.llama import PRESETS
from agentcontrolplane_tpu.observability.metrics import REGISTRY
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import FAULTS

TOK = ByteTokenizer()
CFG = dataclasses.replace(PRESETS["tiny"], vocab_size=512, max_seq_len=256, n_kv_heads=2)


def make_engine(kv_layout="paged", **kw):
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
        kv_layout=kv_layout,
        page_size=8,
        **kw,
    )
    eng.start()
    return eng


def counter(name: str, **labels) -> float:
    m = REGISTRY._metrics.get(name)
    if m is None:
        return 0.0
    return m.values.get(tuple(sorted(labels.items())), 0.0)


def _settle(e: Engine) -> None:
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline and (e._has_work() or len(e._waiting)):
        time.sleep(0.01)
    time.sleep(0.05)


def _conserved(e: Engine) -> dict:
    led = e.profiler.ledger()
    assert led["computed"] == led["goodput"] + sum(led["waste"].values()), led
    return led


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    FAULTS.reset()


# -- byte identity: the observatory measures, never steers --------------------


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_profiler_on_off_greedy_byte_identity(kv_layout):
    """Same seed, same prompts, spec + chunked prefill on: the engine with
    the profiler enabled must emit bit-for-bit the tokens of the engine
    with it disabled — the hooks never touch dispatch inputs/outputs."""
    prompts = ["hello profiler " + c * 9 for c in "abc"]
    sp = SamplingParams(temperature=0.0, max_tokens=12)
    outs = []
    for enabled in (True, False):
        eng = make_engine(kv_layout=kv_layout, spec_len=4, prefill_chunk=16)
        eng.profiler.enabled = enabled
        try:
            futs = [eng.submit(p, sp) for p in prompts]
            outs.append([f.result(timeout=600).tokens for f in futs])
        finally:
            eng.stop()
    assert outs[0] == outs[1]


# -- per-program telemetry + ledger -------------------------------------------


def test_program_stats_and_ledger_populate():
    # megastep OFF: this test pins the SPLIT dispatch zoo (chunk + decode
    # program keys), which remains the fused path's shape-bound fallback;
    # the fused zoo is pinned by tests/engine/test_megastep.py
    eng = make_engine(kv_layout="paged", spec_len=4, prefill_chunk=16, megastep=False)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=10)
        futs = [eng.submit(f"telemetry {i} " * 3, sp) for i in range(4)]
        for f in futs:
            f.result(timeout=600)
        _settle(eng)
        perf = eng.stats()["perf"]
        assert perf["enabled"] is True
        programs = perf["programs"]
        # the chunked paged engine's zoo: chunk dispatches + final-chunk
        # continuations + decode blocks (spec verify fires only when the
        # drafter proposes — not asserted, scheduling-dependent)
        assert any(k.startswith("chunk[paged,") for k in programs)
        assert any(k.startswith("decode[paged,") for k in programs)
        for p in programs.values():
            assert p["dispatches"] > 0
            assert p["host_ms_mean"] >= 0.0
            assert p["device_samples"] >= 1  # first dispatch always samples
            assert p["real_tokens"] + p["padded_tokens"] >= 0
        led = _conserved(eng)
        assert led["computed"] > 0 and led["goodput"] > 0
        g = perf["goodput"]
        assert 0.0 < g["ratio"] <= 1.0
        # program keys ride the flight dispatch events too
        blocks = eng.flight.events(kind="decode_block")
        assert blocks and all(
            e["detail"]["program"].startswith("decode[paged,")
            for e in blocks
        )
    finally:
        eng.stop()


def test_dispatch_seconds_histogram_exported():
    eng = make_engine(kv_layout="slot")
    try:
        eng.generate("histogram", SamplingParams(temperature=0.0, max_tokens=6))
        _settle(eng)
        keys = [k for k in eng.profiler.stats()["programs"] if k.startswith("decode[")]
        assert keys
        count, window = REGISTRY.series_window(
            "acp_engine_dispatch_seconds", {"program": keys[0]}
        )
        assert count > 0 and window
    finally:
        eng.stop()


# -- cold-compile observatory -------------------------------------------------


def test_unprewarmed_shape_fires_cold_compile_event_and_counter():
    """Dispatching a shape never seen before prewarm-complete must surface
    as a cold_compile flight event + acp_engine_cold_compiles_total."""
    eng = make_engine(kv_layout="slot", prefix_cache_entries=0)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=5)
        eng.generate("x" * 10, sp)  # compiles prefill[32x1] + decode widths
        _settle(eng)
        eng.profiler.mark_prewarmed()
        before = counter("acp_engine_cold_compiles_total")
        assert eng.profiler.stats()["cold_compiles"]["serving"] == 0
        # bucket 64 was never dispatched: a deliberately un-prewarmed shape
        eng.generate("y" * 40, sp)
        _settle(eng)
        cold = eng.profiler.stats()["cold_compiles"]
        assert cold["serving"] >= 1
        assert any(
            ev["program"].startswith("prefill[slot,64x1") and ev["wall_s"] > 0
            for ev in cold["events"]
        )
        assert counter("acp_engine_cold_compiles_total") > before
        evs = eng.flight.events(kind="cold_compile")
        assert evs and any(
            e["detail"]["program"].startswith("prefill[slot,64x1") for e in evs
        )
    finally:
        eng.stop()


def test_fully_prewarmed_engine_reports_zero_cold_compiles():
    """After Engine.prewarm() the documented coverage holds: serving
    requests whose shapes prewarm compiled must record NO serving-time
    cold compiles."""
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    eng = Engine(
        config=CFG,
        tokenizer=TOK,
        mesh=mesh,
        max_slots=2,
        max_ctx=64,
        prefill_buckets=(16, 32),
        decode_block_size=4,
        kv_layout="slot",
        prefix_cache_entries=0,  # prefix extract programs compile per cut
        check_invariants=True,
    )
    eng.start()
    try:
        eng.prewarm(constrained=False)
        assert eng.profiler.stats()["prewarmed"] is True
        assert eng.profiler.stats()["cold_compiles"]["serving"] == 0
        sp = SamplingParams(temperature=0.0, max_tokens=9)
        futs = [eng.submit("c" * 10, sp), eng.submit("d" * 20, sp)]
        for f in futs:
            f.result(timeout=600)
        _settle(eng)
        cold = eng.profiler.stats()["cold_compiles"]
        assert cold["serving"] == 0, cold["events"]
        assert counter("acp_engine_prewarm_gaps_total", phase="plain") == 0.0
    finally:
        eng.stop()


# -- satellite: the prewarm coverage gap is data ------------------------------


class _DropSet(set):
    """A dispatch record that 'loses' one batch size — the deterministic
    provocation of the 'batch never formed' retry exhaustion."""

    def __init__(self, drop):
        super().__init__()
        self._drop = drop

    def add(self, item):
        if item != self._drop:
            super().add(item)


def test_prewarm_gap_records_flight_event_and_counter():
    eng = make_engine(kv_layout="slot", prefill_chunk=16, prefix_cache_entries=0)
    try:
        eng._chunk_batch_sizes = _DropSet(2)  # B=2 can never verify
        before = counter("acp_engine_prewarm_gaps_total", phase="chunked")
        eng._prewarm_chunked(constrained=False)
        assert counter("acp_engine_prewarm_gaps_total", phase="chunked") == before + 1
        evs = eng.flight.events(kind="prewarm_gap")
        assert evs
        assert evs[-1]["detail"] == {"phase": "chunked", "B": 2}
    finally:
        eng.stop()


# -- conservation under the stress/fault matrix -------------------------------


def test_token_conservation_under_fault_matrix():
    """preempt + spec_mismatch + host_swap_error, armed invariants (the
    audit now includes the profiler ledger): every request completes, the
    audit stays clean, conservation holds, and the waste the faults
    manufactured is attributed to real causes."""
    eng = make_engine(
        kv_layout="paged", kv_pages=24, spec_len=4, prefill_chunk=16,
        host_kv_bytes=1 << 22, check_invariants=True,
    )
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=12)
        # warm pass compiles the zoo so the fault legs measure scheduling
        for f in [eng.submit("warm " + c * 16, sp) for c in "ab"]:
            f.result(timeout=600)
        _settle(eng)
        FAULTS.arm("engine.force_preempt", after_steps=2)
        FAULTS.arm("engine.spec_mismatch", times=1)
        FAULTS.arm("engine.host_swap_error", times=2)
        with eng.hold_admission():  # oversubscribe the tiny pool
            futs = [eng.submit(ch * 24, sp) for ch in "cdefgh"]
        for f in futs:
            assert f.result(timeout=600).finish_reason in ("stop", "length")
        _settle(eng)
        assert verify_engine(eng) == []
        led = _conserved(eng)
        assert led["computed"] > 0
        waste = led["waste"]
        # pool pressure + the armed faults must have manufactured real
        # attributed waste (which bucket depends on where the fault popped)
        assert eng.preemptions > 0
        assert (
            waste["preempt_discard"] + waste["swap_recompute"]
            + waste["spec_rejected"]
        ) > 0
        # the perf payload reports the same ledger the audit verified
        g = eng.stats()["perf"]["goodput"]
        assert g["computed"] == led["computed"]
        assert g["waste"] == waste
        assert g["ratio"] == pytest.approx(
            led["goodput"] / led["computed"], abs=1e-4
        )
    finally:
        eng.stop()


# -- engine-cycle phases, queue wait, spans on the profiler's trace -----------


PHASE_NAMES = {"admit", "park", "launch", "fetch", "commit", "publish", "cycle"}


def _serve(eng: Engine, n: int, max_tokens: int = 10) -> list:
    sp = SamplingParams(temperature=0.0, max_tokens=max_tokens)
    futs = [eng.submit(f"phase {i} " * 3, sp) for i in range(n)]
    return [f.result(timeout=600) for f in futs]


@pytest.mark.parametrize("kw", [
    pytest.param({}, id="plain"),
    pytest.param({"prefill_chunk": 16, "spec_len": 4}, id="chunked-spec-megastep"),
])
def test_phases_partition_the_engine_loop(kw):
    """Every busy cycle launches, fetches and commits at least once, and
    the phases' self times partition the loop's wall time."""
    t0 = time.monotonic()
    eng = make_engine(kv_layout="paged", **kw)
    try:
        _serve(eng, 6)
        _settle(eng)
        perf = eng.stats()["perf"]
        wall = time.monotonic() - t0
    finally:
        eng.stop()
    phases = perf["phases"]
    assert set(phases) <= PHASE_NAMES and {"admit", "launch", "fetch", "commit", "publish"} <= set(phases)
    assert perf["cycles"] >= 3
    for name in ("launch", "fetch", "commit", "publish"):
        assert phases[name]["n"] >= perf["cycles"], (name, phases, perf["cycles"])
    assert phases["cycle"]["n"] == perf["cycles"]
    total = sum(row["s"] for row in phases.values())
    # the loop thread started after t0 and an open park is not yet counted
    assert 0.9 * (wall - 0.5) <= total <= wall
    if not kw:
        assert perf["blocks"] == len(eng.flight.events(kind="decode_block")) > 0
    # the loop's glue outside every phase is next to nothing
    assert phases["cycle"]["s"] < 0.05 * total


def test_queue_wait_counts_first_admissions_only():
    eng = make_engine(kv_layout="paged")
    try:
        _serve(eng, 2)
        _settle(eng)
        before = eng.stats()["scheduler"]["queue_wait"]
        assert before["n"] == 2 and before["s"] > 0
        # a forced preemption re-admits its victim: a resume, not an arrival
        FAULTS.arm("engine.force_preempt", times=1)
        results = _serve(eng, 7, max_tokens=16)  # 7 over 4 slots: some wait a generation out
        _settle(eng)
        after = eng.stats()
    finally:
        eng.stop()
    assert len(results) == 7
    assert after["preemptions"] >= 1
    qw = after["scheduler"]["queue_wait"]
    assert qw["n"] == before["n"] + 7
    admits = [e for e in eng.flight.events(kind="admit")]
    assert sum(1 for e in admits if not e["detail"]["resumed"]) == qw["n"]
    assert sum(1 for e in admits if e["detail"]["resumed"]) >= 1
    # the counter is the flight recorder's submit -> admit, summed
    assert qw["s"] > before["s"]


def test_flight_events_name_the_cycle_they_were_served_in():
    eng = make_engine(kv_layout="paged")
    try:
        res = _serve(eng, 1, max_tokens=12)[0]
        _settle(eng)
        cycles = eng.stats()["perf"]["cycles"]
        rid = eng.flight.request_ids()[-1]
        timeline = eng.flight.timeline(rid)
        blocks = eng.flight.events(kind="decode_block")
    finally:
        eng.stop()
    assert len(res.tokens) == 12
    tagged = [(e["kind"], e["detail"]["cycle"]) for e in timeline if "cycle" in (e.get("detail") or {})]
    assert [k for k, _ in tagged] == ["admit", "prefill_done", "finish"]
    assert 1 <= tagged[0][1] == tagged[1][1] <= tagged[2][1] <= cycles
    nums = [e["detail"]["cycle"] for e in blocks]
    assert nums == sorted(nums) and len(set(nums)) == len(nums)  # one decode block a cycle
    assert nums[-1] == tagged[2][1]  # the request finished in the cycle of its last block


def test_disabled_profiler_serves_the_same_tokens_and_records_no_phase(monkeypatch):
    outs = []
    for flag in ("1", "0"):
        monkeypatch.setenv("ACP_PROF", flag)
        eng = make_engine(kv_layout="paged")
        try:
            outs.append([r.tokens for r in _serve(eng, 3)])
            _settle(eng)
            st = eng.stats()
        finally:
            eng.stop()
        if flag == "1":
            assert {"init", "init.pool"} <= set(st["perf"]["setup"]["phases"])
        if flag == "0":
            assert st["perf"]["phases"] == {} and st["perf"]["cycles"] == 0
            assert "setup" not in st["perf"]  # no set-up phase, no split, no wait on the pool
            # the counter of the scheduler does not hang on the profiler
            assert st["scheduler"]["queue_wait"]["n"] == 3
            assert st["cycle_s"] > 0  # the planner's clock falls back to its own reads
    assert outs[0] == outs[1]


def test_spans_land_on_the_host_plane_of_a_profiler_trace(tmp_path):
    """`jax.profiler` around a serving engine: `acp.cycle` and its children
    are on the host plane, with increasing cycle numbers, the launches named
    for their programs."""
    import glob

    eng = make_engine(kv_layout="paged")
    try:
        _serve(eng, 3)  # compile outside the trace
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            _serve(eng, 3)
            _settle(eng)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    path = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("acp."):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats)))
    cycles = sorted(s for s in spans if s[2] == "acp.cycle")
    assert len(cycles) >= 3
    steps = [s[3]["step_num"] for s in cycles]
    assert steps == sorted(steps) and len(set(steps)) == len(steps)
    kids = {"acp.admit", "acp.launch", "acp.fetch", "acp.commit", "acp.publish"}
    for start, end, _, stats in cycles:
        inside = [s for s in spans if s[2] != "acp.cycle" and start <= s[0] and s[1] <= end]
        assert {s[2] for s in inside} >= kids - {"acp.admit"} or not inside
        assert all(s[3]["cycle"] == stats["step_num"] for s in inside)
    assert kids <= {s[2] for s in spans}
    launches = [s for s in spans if s[2] == "acp.launch" and "program" in s[3]]
    # the jitted call began inside the span: what ties the device trace's clock to this one
    assert launches and all(0 <= s[3]["call_us"] * 1000 <= s[1] - s[0] for s in launches)
    programs = {s[3].get("program") for s in spans if s[2] == "acp.launch"}
    assert any(p and p.startswith("decode[paged,") for p in programs)
    assert any(p and p.startswith("prefill[paged,") for p in programs)
