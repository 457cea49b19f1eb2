"""slo-gate: per-scenario SLO envelopes over a scenario run's summary.

``acp-tpu replay`` (cli.py) and the chaos drill (scenarios/chaos.py)
replay the scenario library (scenarios/library.py) and hand each run's
SLO summary (``ReplayReport.slo_doc()``) to ``check_block``, which judges
it against the scenario's envelope.

Envelope philosophy: CPU-fixture latency numbers are noise, so absolute
latency ceilings are deliberately loose (they catch order-of-magnitude
cliffs, not percent drift). What the gate holds TIGHT is structure, which
is platform-independent:

- request conservation — every replayed request accounted for exactly once
  across completed/shed/cancelled/expired/error
- no unexplained errors — scheduler cleanup paths (cancel, deadline,
  shed, failover) must resolve requests, not leak exceptions
- percentile sanity — p50 <= p99, TTFT present whenever something
  completed, goodput in (0, 1]
- scenario intent — a persona storm completes everything; cancel churn
  actually cancelled and expired; a tool swarm surfaced tool calls; a
  fault cocktail still completed the healthy majority

Stdlib-only, like the rest of ``analysis/``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional


@dataclass(frozen=True)
class Envelope:
    """Per-scenario acceptance envelope for one SLO summary block."""

    # structural floors/ceilings (counts are exact, platform-independent)
    min_completed_ratio: float = 0.0  # completed / requests
    max_errors: int = 0
    min_cancelled: int = 0
    min_expired: int = 0
    min_tool_calls_per_request: float = 0.0
    # loose physics: order-of-magnitude cliffs only (CPU fixtures are noisy)
    max_ttft_p99_ms: Optional[float] = 120_000.0
    max_decode_stall_p99_ms: Optional[float] = 120_000.0
    min_goodput_ratio: Optional[float] = None


ENVELOPES: dict[str, Envelope] = {
    # a dedup storm is the engine's best case: everything completes
    "persona_storm": Envelope(min_completed_ratio=1.0),
    # the long tail may shed under pressure but the majority completes
    "long_tail": Envelope(min_completed_ratio=0.7),
    # every request decodes forced tool envelopes -> at least one call each
    "tool_swarm": Envelope(
        min_completed_ratio=0.9, min_tool_calls_per_request=1.0,
    ),
    # churn must actually churn — and cleanup must not leak errors
    "cancel_churn": Envelope(
        min_completed_ratio=0.3, min_cancelled=1, min_expired=1,
    ),
    # faults drop requests by design; the healthy majority still lands
    "fault_cocktail": Envelope(min_completed_ratio=0.5),
}

_DEFAULT = Envelope(min_completed_ratio=0.5)


@dataclass
class SLOViolation:
    scenario: str
    arm: str  # single | fleet
    check: str
    detail: str

    def __str__(self) -> str:
        return f"{self.scenario}/{self.arm}: {self.check} — {self.detail}"


def check_block(
    scenario: str, arm: str, block: dict[str, Any]
) -> list[SLOViolation]:
    """Judge one scenario run's SLO summary against its envelope."""
    env = ENVELOPES.get(scenario, _DEFAULT)
    out: list[SLOViolation] = []

    def trip(check: str, detail: str) -> None:
        out.append(SLOViolation(scenario, arm, check, detail))

    requests = int(block.get("requests") or 0)
    if requests <= 0:
        trip("requests", "scenario ran zero requests")
        return out
    parts = {
        k: int(block.get(k) or 0)
        for k in ("completed", "shed", "cancelled", "expired", "errors")
    }
    if sum(parts.values()) != requests:
        trip(
            "conservation",
            f"outcomes {parts} sum to {sum(parts.values())}, "
            f"not {requests} requests — a request leaked or double-counted",
        )
    if parts["errors"] > env.max_errors:
        trip(
            "errors",
            f"{parts['errors']} unexplained errors > allowed {env.max_errors}",
        )
    ratio = parts["completed"] / requests
    if ratio < env.min_completed_ratio:
        trip(
            "completed_ratio",
            f"{parts['completed']}/{requests} completed "
            f"({ratio:.0%}) < floor {env.min_completed_ratio:.0%}",
        )
    if parts["cancelled"] < env.min_cancelled:
        trip(
            "cancelled",
            f"{parts['cancelled']} cancels < expected {env.min_cancelled} "
            "(the churn never churned)",
        )
    if parts["expired"] < env.min_expired:
        trip(
            "expired",
            f"{parts['expired']} deadline expiries < expected "
            f"{env.min_expired}",
        )
    tool_calls = float(block.get("tool_calls") or 0)
    if tool_calls < env.min_tool_calls_per_request * requests:
        trip(
            "tool_calls",
            f"{tool_calls:.0f} tool calls < "
            f"{env.min_tool_calls_per_request:.1f}/request floor "
            f"(forced envelopes never surfaced as events)",
        )
    p50 = float(block.get("ttft_p50_ms") or 0.0)
    p99 = float(block.get("ttft_p99_ms") or 0.0)
    if parts["completed"] > 0 and p50 <= 0.0:
        trip("ttft", "requests completed but TTFT p50 is zero/absent")
    if p99 < p50:
        trip("percentiles", f"ttft p99 {p99:.1f}ms < p50 {p50:.1f}ms")
    if env.max_ttft_p99_ms is not None and p99 > env.max_ttft_p99_ms:
        trip(
            "ttft_ceiling",
            f"ttft p99 {p99:.0f}ms > cliff ceiling {env.max_ttft_p99_ms:.0f}ms",
        )
    stall = float(block.get("decode_stall_p99_ms") or 0.0)
    if (
        env.max_decode_stall_p99_ms is not None
        and stall > env.max_decode_stall_p99_ms
    ):
        trip(
            "decode_stall",
            f"decode-stall p99 {stall:.0f}ms > cliff ceiling "
            f"{env.max_decode_stall_p99_ms:.0f}ms",
        )
    goodput = block.get("goodput_ratio")
    if goodput is not None:
        g = float(goodput)
        if not (0.0 < g <= 1.0):
            trip("goodput", f"goodput_ratio {g} outside (0, 1]")
        elif env.min_goodput_ratio is not None and g < env.min_goodput_ratio:
            trip(
                "goodput_floor",
                f"goodput {g:.3f} < floor {env.min_goodput_ratio:.3f}",
            )
    return out


__all__ = ["Envelope", "ENVELOPES", "SLOViolation", "check_block"]
