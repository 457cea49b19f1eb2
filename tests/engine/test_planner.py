"""Admission-time chunk-rate planner + scheduler autopilot (ISSUE 13):

- quota arithmetic (engine/planner.py project_quota): tokens-remaining /
  cycles-until-deadline, clamped sane at every edge;
- the engine integration: a tight-deadline long prompt gets a quota-sized
  per-cycle chunk and FINISHES where the flat one-chunk cadence would
  expire mid-prefill — deadlines met by arithmetic, not EDF luck;
- reprojection: preempt→resume re-enters admission and re-plans (flight
  ``quota`` events carry reason=resume; the counter rises);
- quota-vs-actual surfaces in the request timeline (``rate_plan`` block);
- the autopilot's recommend() policy: each bounded step moves the right
  knob in the right direction, never past its limits.
"""

import dataclasses
import time

import pytest

import jax

from agentcontrolplane_tpu.engine import engine as engine_module
from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.planner import (
    Autopilot,
    AutopilotLimits,
    CycleClock,
    project_quota,
    recommend,
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
    kw.setdefault("prefix_cache_entries", 0)
    eng = Engine(
        config=CFG,
        tokenizer=TOK,
        mesh=mesh,
        max_slots=4,
        max_ctx=256,
        prefill_buckets=(32, 64, 128, 256),
        width_buckets=(1, 2, 4),
        decode_block_size=4,
        kv_layout="paged",
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


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    FAULTS.reset()


# -- quota arithmetic ---------------------------------------------------------


def test_project_quota_arithmetic():
    # 200 tokens, 16-token chunks = 13 chunks; 0.5s left at 50ms cycles =
    # 10 cycles - 2 slack = 8 -> ceil(13/8) = 2 chunks per cycle
    assert project_quota(200, 16, 0.5, 0.05) == 2
    # plenty of time: the flat PR 7 cadence
    assert project_quota(200, 16, 60.0, 0.05) == 1
    # desperately tight: capped at max_quota, never unbounded
    assert project_quota(4096, 16, 0.01, 0.05, max_quota=8) == 8
    # edges: no deadline / already expired / nothing left / bad chunk
    assert project_quota(200, 16, None, 0.05) == 1
    assert project_quota(200, 16, -1.0, 0.05) == 1
    assert project_quota(0, 16, 0.5, 0.05) == 1
    assert project_quota(200, 0, 0.5, 0.05) == 1
    # degenerate clock seeds never divide by zero
    assert project_quota(200, 16, 0.5, 0.0) >= 1


def test_cycle_clock_ewma_seeds_and_decays():
    clk = CycleClock(alpha=0.5)
    assert clk.cycle_s == 0.0
    clk.observe(0.1)
    assert clk.cycle_s == pytest.approx(0.1)
    clk.observe(0.3)
    assert clk.cycle_s == pytest.approx(0.2)
    clk.observe(-1.0)  # ignored
    assert clk.cycle_s == pytest.approx(0.2)


# -- deadlines met by arithmetic ----------------------------------------------


CYCLE_S = 0.02


class _CycleTicks:
    """``time`` as engine/engine.py sees it, with ``monotonic()`` a clock the
    test advances: one CYCLE_S a scheduler cycle that runs prefill chunks.
    A deadline then allows a COUNT of cycles, whatever the machine's load
    (a wall-clock deadline judged the six test workers' contention, not the
    plan). Everything else is the real module."""

    def __init__(self):
        self.now = time.monotonic()

    def monotonic(self):
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


def test_planner_meets_deadline_flat_cadence_would_miss(monkeypatch):
    """One long prompt, chunk=8, 20 ms a cycle on the engine's clock,
    deadline 0.45 s = 22 cycles: the flat cadence needs 25 cycles (200
    tokens / 8) and expires mid-prefill; the planner's quota-sized chunks
    (ceil(25 / (22 - 2 slack)) = 2 a cycle) finish in 13. Same engine, same
    clock — only the planner knob differs."""
    prompt = [1 + (i % 250) for i in range(200)]
    sp = SamplingParams(temperature=0.0, max_tokens=4)

    def run(planner: bool):
        clock = _CycleTicks()
        monkeypatch.setattr(engine_module, "time", clock)
        eng = make_engine(prefill_chunk=8, rate_planner=planner)
        real = eng._prefill_chunks
        cycles = []

        def ticking_chunks(budget):
            clock.now += CYCLE_S
            cycles.append(budget)
            return real(budget)

        eng._prefill_chunks = ticking_chunks
        # seed the cycle clock so admission projects against the ticked
        # cadence instead of the cold-start default
        eng._cycle_clock.observe(CYCLE_S)
        try:
            fut = eng.submit(prompt, sp, timeout_s=0.45)
            try:
                return ("ok", fut.result(timeout=120).tokens, len(cycles))
            except Exception as e:
                return ("expired", type(e).__name__, len(cycles))
        finally:
            eng.stop()

    flat = run(False)
    planned = run(True)
    assert flat[0] == "expired", flat
    assert 22 <= flat[2] < 25, flat  # every cycle the deadline allows, not enough
    assert planned[0] == "ok", planned
    assert planned[2] <= 22, planned


def test_quota_projection_event_and_chunk_sizing(monkeypatch):
    """Admission records a ``quota`` flight event and the scheduler sizes
    the slot's per-cycle chunk as quota x chunk (capped at the largest
    bucket, page-aligned). On a clock that stands still: the plan is the
    subject, and a loaded machine must not expire the request under it."""
    monkeypatch.setattr(engine_module, "time", _CycleTicks())
    eng = make_engine(prefill_chunk=8)
    try:
        eng._cycle_clock.observe(0.05)
        fut = eng.submit(
            [1 + (i % 250) for i in range(200)],
            SamplingParams(temperature=0.0, max_tokens=4),
            timeout_s=0.6,
        )
        fut.result(timeout=120)
        quotas = [e for e in eng.flight.events(kind="quota")]
        assert quotas, "no quota projection recorded"
        q = quotas[-1]["detail"]
        assert q["reason"] == "admit"
        assert q["quota"] >= 2
        # the chunk sizing followed the quota: at least one chunk bigger
        # than the base grain dispatched
        chunks = [e["detail"]["n"] for e in eng.flight.events(kind="prefill_chunk")]
        assert max(chunks) >= 2 * 8, chunks
    finally:
        eng.stop()


def test_preempt_resume_reprojects_quota():
    """A deadline request preempted mid-prefill re-enters admission and
    REPROJECTS its plan: reason=resume quota event + the reprojection
    counter. Output still completes (resume is byte-identical; pinned
    elsewhere — here the plan bookkeeping is the subject)."""
    eng = make_engine(prefill_chunk=8)
    try:
        eng._cycle_clock.observe(0.01)
        re0 = counter("acp_engine_quota_reprojections_total")
        FAULTS.arm(
            "engine.preempt_mid_prefill", times=1,
            after_steps=eng.prefill_chunks + 2,
        )
        fut = eng.submit(
            [1 + (i % 250) for i in range(200)],
            SamplingParams(temperature=0.0, max_tokens=4),
            timeout_s=30.0,
        )
        fut.result(timeout=180)
        reasons = [
            e["detail"]["reason"] for e in eng.flight.events(kind="quota")
        ]
        assert "resume" in reasons, reasons
        assert counter("acp_engine_quota_reprojections_total") > re0
    finally:
        eng.stop()


def test_timeline_surfaces_rate_plan():
    """The request timeline carries quota-vs-actual (the acp-tpu timeline
    CLI prints this block)."""
    eng = make_engine(prefill_chunk=8)
    try:
        eng._cycle_clock.observe(0.05)
        fut = eng.submit(
            [1 + (i % 250) for i in range(120)],
            SamplingParams(temperature=0.0, max_tokens=4),
            timeout_s=5.0,
        )
        fut.result(timeout=120)
        rid = fut.rid
        doc = eng.flight.timeline_doc(rid)
        assert doc is not None
        rp = doc.get("rate_plan")
        assert rp is not None, "timeline missing the rate_plan block"
        assert rp["quota"] >= 1
        assert rp["chunks_dispatched"] >= 1
        assert rp["chunk_tokens"] >= 120
        assert rp["projections"][0]["reason"] == "admit"
    finally:
        eng.stop()


def test_no_deadline_keeps_flat_cadence():
    """Deadline-free requests keep quota 1 — the planner is inert for them
    (exactly the PR 7 cadence, no quota events beyond the projection)."""
    eng = make_engine(prefill_chunk=8)
    try:
        fut = eng.submit(
            [1 + (i % 250) for i in range(100)],
            SamplingParams(temperature=0.0, max_tokens=4),
        )
        fut.result(timeout=120)
        chunks = [e["detail"]["n"] for e in eng.flight.events(kind="prefill_chunk")]
        assert chunks and max(chunks) <= 8
    finally:
        eng.stop()


# -- autopilot policy ---------------------------------------------------------

LIMITS = AutopilotLimits(chunk_min=8, chunk_max=256, budget_max=2048, spec_len_max=16)
KNOBS = {"prefill_chunk": 32, "token_budget": 128, "spec_len": 4}


def test_autopilot_raises_budget_when_prefill_bound_and_saturated():
    out = recommend(
        {"prefill": 2.0, "queue_wait": 0.1, "decode": 0.5, "preempt_stall": 0.0},
        utilization_avg=0.99, spec_acceptance=0.5, knobs=KNOBS, limits=LIMITS,
    )
    assert out.get("token_budget", 0) > KNOBS["token_budget"]
    assert out["token_budget"] <= LIMITS.budget_max


def test_autopilot_grows_chunk_when_queue_bound():
    out = recommend(
        {"prefill": 0.1, "queue_wait": 2.0, "decode": 0.5, "preempt_stall": 0.0},
        utilization_avg=0.5, spec_acceptance=None, knobs=KNOBS, limits=LIMITS,
    )
    assert out.get("prefill_chunk") == 64


def test_autopilot_shrinks_chunk_under_preempt_thrash():
    out = recommend(
        {"prefill": 0.1, "queue_wait": 0.1, "decode": 0.5, "preempt_stall": 0.4},
        utilization_avg=0.5, spec_acceptance=None, knobs=KNOBS, limits=LIMITS,
    )
    assert out.get("prefill_chunk") == 16


def test_autopilot_steers_spec_len_by_acceptance():
    low = recommend({}, 0.5, 0.1, KNOBS, LIMITS)
    assert low.get("spec_len") == 3
    high = recommend({}, 0.5, 0.9, KNOBS, LIMITS)
    assert high.get("spec_len") == 5
    mid = recommend({}, 0.5, 0.5, KNOBS, LIMITS)
    assert "spec_len" not in mid
    # bounded: never below 1, never past the cap
    floor = recommend({}, 0.5, 0.0, {**KNOBS, "spec_len": 1}, LIMITS)
    assert "spec_len" not in floor
    cap = recommend({}, 0.5, 1.0, {**KNOBS, "spec_len": 16}, LIMITS)
    assert "spec_len" not in cap


def test_autopilot_holds_when_nothing_dominates():
    out = recommend(
        {"prefill": 0.2, "queue_wait": 0.2, "decode": 0.5, "preempt_stall": 0.0},
        utilization_avg=0.5, spec_acceptance=0.5, knobs=KNOBS, limits=LIMITS,
    )
    assert out == {}


def test_autopilot_due_interval_and_adjustment_count():
    ap = Autopilot(LIMITS, interval=4)
    fires = [ap.due() for _ in range(8)]
    assert fires == [False, False, False, True] * 2
    assert ap.step({}, 0.5, 0.1, KNOBS)  # low acceptance -> a change
    assert ap.adjustments == 1
    assert ap.step({}, 0.5, 0.5, {"prefill_chunk": 0, "token_budget": 0, "spec_len": 0}) == {}
    assert ap.adjustments == 1


def test_autopilot_engine_applies_and_flight_records():
    """Engine integration: with the autopilot armed at a tiny interval and
    spec acceptance forced low, the engine applies a spec_len step and
    flight-records it."""
    eng = make_engine(prefill_chunk=8, spec_len=6, autopilot=True)
    eng._autopilot.interval = 2
    try:
        a0 = counter("acp_engine_autopilot_adjustments_total")
        # force terrible acceptance so the policy must shrink spec_len
        eng.spec_proposed, eng.spec_accepted = 1000, 10
        futs = [
            eng.submit("steer me " * 4, SamplingParams(temperature=0.0, max_tokens=8))
            for _ in range(3)
        ]
        for f in futs:
            f.result(timeout=120)
        deadline = time.monotonic() + 30
        while eng.spec_len == 6 and time.monotonic() < deadline:
            eng.generate("tick", SamplingParams(temperature=0.0, max_tokens=4))
        assert eng.spec_len < 6
        assert counter("acp_engine_autopilot_adjustments_total") > a0
        assert eng.flight.events(kind="autopilot")
    finally:
        eng.stop()
