"""Deterministic trace replay (scenarios/replay.py + library.py): the
byte-identity contract (same trace + same seed -> same tokens, per KV
layout, with speculation and chunked prefill on), scenario outcome shapes
(cancel churn, tool swarms, fault cocktails), and fleet replay with
stitched cross-replica phase attribution."""

from __future__ import annotations

import dataclasses

import jax
import pytest

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.fleet import FleetRouter
from agentcontrolplane_tpu.kernel import Store
from agentcontrolplane_tpu.models.llama import PRESETS
from agentcontrolplane_tpu.observability.flight import attribute_phases
from agentcontrolplane_tpu.observability.trace_export import (
    export_fleet_trace,
    export_trace,
    stitched_fleet_timelines,
    validate_trace,
)
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.scenarios import (
    SCENARIOS,
    build,
    byte_identical,
    replay,
    synth_prompt,
)
from agentcontrolplane_tpu.testing import FAULTS

TOK = ByteTokenizer()
CFG = dataclasses.replace(
    PRESETS["tiny"], vocab_size=512, max_seq_len=256, n_kv_heads=2
)


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    FAULTS.reset()


def make_engine(kv_layout="paged", **kw):
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    kw.setdefault("check_invariants", True)
    eng = Engine(
        config=CFG, tokenizer=TOK, mesh=mesh, max_slots=4, max_ctx=64,
        prefill_buckets=(32, 64), decode_block_size=4, kv_layout=kv_layout,
        page_size=8, **kw,
    )
    eng.start()
    return eng


def teardown(router, *engines):
    router.stop()
    for eng in engines:
        try:
            eng.stop()
        except Exception:
            pass


# -- pure: synthetic content + the library ---------------------------------


def test_synth_prompt_is_deterministic_and_persona_shared():
    a = synth_prompt(7, "abcd", 16, 40, 3)
    b = synth_prompt(7, "abcd", 16, 40, 3)
    assert a == b and len(a) == 40
    other_index = synth_prompt(7, "abcd", 16, 40, 4)
    assert other_index[:16] == a[:16]      # persona prefix shared
    assert other_index[16:] != a[16:]      # per-request body differs
    assert synth_prompt(8, "abcd", 16, 40, 3) != a   # seed is load-bearing
    # replay prompts must not accidentally open tool-call or tag syntax
    assert "{" not in a and "<" not in a


def test_every_library_scenario_emits_a_valid_trace():
    for name, gen in SCENARIOS.items():
        doc = gen()
        assert validate_trace(doc) == [], name
        assert doc["source"] == f"scenario:{name}"
        assert doc["requests"], name
        offsets = [r["offset_s"] for r in doc["requests"]]
        assert offsets == sorted(offsets), name


def test_cancel_churn_trace_carries_doom_and_throttle():
    doc = build("cancel_churn", n=6)
    cancels = [r for r in doc["requests"] if "cancel_after_s" in r]
    deadlines = [r for r in doc["requests"] if "deadline_s" in r]
    assert cancels and deadlines
    for r in cancels + deadlines:
        assert r["output_tokens"] > doc["requests"][0]["output_tokens"]
    assert any(f["site"] == "engine.slow_cycle" for f in doc["faults"])


# -- byte-identity: the replay determinism contract ------------------------


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_live_trace_replays_byte_identical(kv_layout):
    """Acceptance: record a trace off live traffic, replay it (twice) at
    1x on the warmed engine — with speculation and chunked prefill on —
    and the two replays' greedy outputs are byte-identical per request."""
    eng = make_engine(kv_layout, spec_len=6, prefill_chunk=16)
    try:
        eng.prewarm(constrained=True)
        # The trace starts at the live traffic. The prewarm's bursts go
        # through submit() and are recorded too (eight one-token requests,
        # one, two and four at a time), and each 1x replay then slept
        # through the compiles between them and the live requests: most of
        # this case's time. Four live requests fill the four slots as the
        # widest burst did.
        with eng.flight._lock:
            eng.flight._done.clear()
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        live = [
            "persona alpha shares this long prefix // req one",
            "persona alpha shares this long prefix // req two",
            "persona beta is its own prompt shape",
            "persona gamma asks for something else again",
        ]
        for f in [eng.submit(p, sp) for p in live]:
            f.result(timeout=120)
        trace = export_trace(eng.flight)
        assert validate_trace(trace) == []
        assert len(trace["requests"]) >= 3
        a = replay(trace, eng, speed=1.0, seed=5, record_metrics=False)
        b = replay(trace, eng, speed=1.0, seed=5, record_metrics=False)
        assert a.count("completed") == len(trace["requests"])
        assert byte_identical(a, b)
        # a different seed is a different workload (same shape)
        c = replay(trace, eng, speed=1.0, seed=6, record_metrics=False)
        assert not byte_identical(a, c)
    finally:
        eng.stop()


# -- scenario outcome shapes ----------------------------------------------


def test_cancel_churn_replay_exercises_cleanup_paths():
    """On a cold engine the first prefill compiles while the rest queue:
    cancel timers land on queued/running requests and tight deadlines
    expire in the admission queue — and none of it surfaces as an error."""
    eng = make_engine()  # no prewarm, deliberately cold
    try:
        trace = build(
            "cancel_churn", n=8, prompt_tokens=16, output_tokens=4,
            doomed_output_tokens=40, slow_cycles=80,
        )
        report = replay(trace, eng, scenario="cancel_churn")
        doc = report.slo_doc()
        assert doc["errors"] == 0
        assert doc["cancelled"] >= 1
        assert doc["expired"] >= 1
        total = (
            doc["completed"] + doc["cancelled"] + doc["expired"]
            + doc["shed"] + doc["errors"]
        )
        assert total == doc["requests"] == 8
    finally:
        eng.stop()


def test_tool_swarm_replay_fires_tool_callbacks():
    eng = make_engine()
    try:
        eng.prewarm(constrained=True)
        trace = build(
            "tool_swarm", n=3, tools_per_request=1, prompt_tokens=16,
            output_tokens=8, slow_tools=2, tool_delay_s=0.01,
        )
        report = replay(trace, eng, scenario="tool_swarm")
        doc = report.slo_doc()
        assert doc["completed"] == 3
        assert doc["tool_calls"] == 3  # one forced envelope per request
    finally:
        eng.stop()


def test_fault_cocktail_replay_arms_the_switchboard():
    eng = make_engine()
    try:
        eng.prewarm(constrained=True)
        trace = build(
            "fault_cocktail", n=6, prompt_tokens=16, output_tokens=4,
            preempts=1, queue_fulls=1,
        )
        report = replay(trace, eng, scenario="fault_cocktail")
        doc = report.slo_doc()
        assert doc["shed"] == 1       # engine.queue_full surfaced as a shed
        assert doc["errors"] == 0
        assert doc["completed"] + doc["shed"] == 6
    finally:
        eng.stop()


def test_scenario_metrics_are_emitted():
    from agentcontrolplane_tpu.observability.metrics import REGISTRY

    eng = make_engine()
    try:
        eng.prewarm(constrained=True)
        trace = build("persona_storm", n=4, prompt_tokens=24,
                      prefix_tokens=16, output_tokens=4)
        replay(trace, eng, scenario="persona_storm")
        text = REGISTRY.render()
        assert 'acp_scenario_requests_total{outcome="completed",scenario="persona_storm"}' in text or \
               'acp_scenario_requests_total{scenario="persona_storm",outcome="completed"}' in text
        assert "acp_scenario_ttft_seconds" in text
        assert "acp_scenario_decode_stall_seconds" in text
    finally:
        eng.stop()


# -- fleet replay + stitched phase attribution -----------------------------


def test_fleet_replay_stitched_phases_sum_once():
    """Replay against a disaggregated pool, then stitch each request's
    router + prefill + decode legs: attributed phases must sum to the
    caller-visible end-to-end once — the per-leg naive sum double-counts
    queue_wait (each replica re-queues the request), the stitched
    timeline must not."""
    router = FleetRouter(store=Store(), handoff_min_tokens=8,
                         heartbeat_interval=60.0)
    prefill = make_engine()
    decode = make_engine()
    router.add_replica("pf", prefill, role="prefill")
    router.add_replica("dc", decode, role="decode")
    try:
        trace = build("persona_storm", n=6, prompt_tokens=24,
                      prefix_tokens=16, output_tokens=4)
        report = replay(trace, router, scenario="persona_storm")
        assert report.count("completed") == 6
        stitched, missing = stitched_fleet_timelines(router)
        assert stitched and missing == 0
        checked = 0
        for rid, events in stitched.items():
            kinds = [e["kind"] for e in events]
            if "handoff_submit" not in kinds:
                continue  # degraded to a local prefill — nothing to stitch
            durations, spans = attribute_phases(events)
            submit_t = next(e["t"] for e in events if e["kind"] == "submit")
            end_t = max(e["t"] for e in events)
            e2e = end_t - submit_t
            phase_sum = sum(durations.values())
            assert phase_sum == pytest.approx(e2e, rel=0.05, abs=0.005), rid
            # the stitched view keeps exactly one admission edge
            assert kinds.count("admit") == 1
            checked += 1
        assert checked >= 1
        fleet_doc = export_fleet_trace(router)
        assert validate_trace(fleet_doc) == []
        assert len(fleet_doc["requests"]) == 6
    finally:
        teardown(router, prefill, decode)


# -- compressed-time replays (slow tier) -----------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("speed", [10.0, 100.0])
def test_replay_speed_compression_stays_deterministic(speed):
    eng = make_engine(spec_len=6, prefill_chunk=16)
    try:
        eng.prewarm(constrained=True)
        trace = build("persona_storm", n=8, prompt_tokens=24,
                      prefix_tokens=16, output_tokens=6)
        a = replay(trace, eng, speed=speed, seed=3, record_metrics=False)
        b = replay(trace, eng, speed=speed, seed=3, record_metrics=False)
        assert a.count("completed") == 8
        assert byte_identical(a, b)
    finally:
        eng.stop()


@pytest.mark.slow
def test_replay_100x_compresses_wall_clock():
    eng = make_engine()
    try:
        eng.prewarm(constrained=True)
        trace = build("long_tail", n=8, long_tokens=40, interval_s=0.5)
        fast = replay(trace, eng, speed=100.0, record_metrics=False)
        assert fast.count("completed") == 8
        # a 3.5s arrival span compressed 100x: the run is dominated by
        # decode, not by sleeping out the schedule
        assert fast.wall_s < 2.0
    finally:
        eng.stop()
