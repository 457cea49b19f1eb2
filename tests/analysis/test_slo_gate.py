"""SLO envelope gate (analysis/slo_gate.py): structural per-scenario
judgement of a scenario run's SLO summary — request conservation, outcome
floors, percentile sanity. Stdlib-only."""

from __future__ import annotations

from agentcontrolplane_tpu.analysis.slo_gate import (
    ENVELOPES,
    check_block,
)


def good_block(**over):
    block = {
        "requests": 10, "completed": 10, "shed": 0, "cancelled": 0,
        "expired": 0, "errors": 0, "tool_calls": 0,
        "ttft_p50_ms": 12.0, "ttft_p99_ms": 30.0, "e2e_p50_ms": 40.0,
        "e2e_p99_ms": 90.0, "decode_stall_p99_ms": 8.0, "preempt_p99": 0.0,
        "wall_s": 1.0, "goodput_ratio": 0.8,
    }
    block.update(over)
    return block


def checks(scenario, block, arm="single"):
    return {v.check for v in check_block(scenario, arm, block)}


def test_healthy_storm_passes():
    assert check_block("persona_storm", "single", good_block()) == []


def test_conservation_violation_trips():
    assert "conservation" in checks(
        "persona_storm", good_block(completed=8)  # 2 requests vanished
    )


def test_errors_always_trip():
    got = checks("long_tail", good_block(completed=9, errors=1))
    assert "errors" in got and "conservation" not in got


def test_completed_ratio_floor():
    # persona_storm demands 100%; one shed request breaks its envelope
    # but would be fine for the long tail (floor 0.7)
    shedding = good_block(completed=9, shed=1)
    assert "completed_ratio" in checks("persona_storm", shedding)
    assert check_block("long_tail", "single", shedding) == []


def test_churn_must_churn():
    placid = good_block()
    got = checks("cancel_churn", placid)
    assert "cancelled" in got and "expired" in got
    churned = good_block(completed=5, cancelled=3, expired=2)
    assert check_block("cancel_churn", "single", churned) == []


def test_tool_swarm_requires_tool_calls():
    assert "tool_calls" in checks("tool_swarm", good_block())
    assert check_block(
        "tool_swarm", "single", good_block(tool_calls=10)
    ) == []


def test_percentile_and_goodput_sanity():
    assert "percentiles" in checks(
        "persona_storm", good_block(ttft_p99_ms=5.0)  # p99 < p50
    )
    assert "ttft" in checks(
        "persona_storm", good_block(ttft_p50_ms=0.0, ttft_p99_ms=0.0)
    )
    assert "goodput" in checks(
        "persona_storm", good_block(goodput_ratio=1.7)
    )


def test_unknown_scenario_uses_default_envelope():
    assert "completed_ratio" in checks(
        "brand_new_scenario", good_block(completed=4, shed=6)
    )


def test_every_shipped_scenario_has_an_envelope():
    from agentcontrolplane_tpu.scenarios import SCENARIOS

    assert set(ENVELOPES) == set(SCENARIOS)
