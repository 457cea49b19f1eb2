"""Deterministic trace replayer: play a workload trace back against a
single Engine or the fleet router, at 1x/10x/100x, with seeded synthetic
content.

The SCHEDULE is pure data: arrival order and virtual arrival times come
only from the trace (``offset_s``, already monotone — validate_trace pins
it), never from the wall clock. The wall clock is used for exactly one
thing — SLEEPING until the next virtual arrival (``t0 + offset/speed``) —
so two replays of one trace submit the same prompts in the same order with
the same sampling, and a warmed greedy engine answers byte-identically
(the engine's own layout/spec/chunking byte-identity contracts carry the
rest).

Prompt content is regenerated, not replayed: traces are anonymized
(lengths + persona fingerprints only — observability/trace_export.py), so
``synth_prompt`` derives each prompt from ``(seed, persona, index)`` via
SHA-256 over a 64-character alphabet with no JSON/special-token characters.
Requests sharing a persona share a prefix of ``personas[key].prefix_tokens``
characters — one char per token under the byte tokenizer — which is what
exercises prefix-cache dedup and cache-affinity routing. Tool-call patterns
replay through ``forced_prefix``: a teacher-forced tool-call envelope makes
the decode stream emit real ``tool_call`` events at deterministic positions
regardless of what the (random tiny) model would have sampled.

Fault cocktails ride the trace: a ``faults`` list is armed on the global
``FAULTS`` switchboard before the first submission, so scenario docs fully
describe the run — including ``fleet.replica_crash`` legs.

Client-side SLO measurement (what the gate consumes): TTFT and the max
inter-batch decode gap per request from ``on_tokens`` timestamps, end-to-end
latency, preempt counts from results, goodput from the target's declared
stats surface — exported as ``acp_scenario_*`` series and summarized by
:meth:`ReplayReport.slo_doc`.
"""

from __future__ import annotations

import hashlib
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from ..faults import FAULTS
from ..observability.metrics import REGISTRY
from ..observability.trace_export import validate_trace

# no '<' (special-token opener), no '{' (tool-call JSON opener): synthetic
# prompts must never alias the wire conventions the engine parses
_ALPHABET = (
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789 _"
)
assert len(_ALPHABET) == 64

# the teacher-forced tool-call envelope (one per replayed tool call):
# matches engine/toolparse.py's wire convention so the stream parser emits
# real tool_call flight events mid-decode
TOOL_ENVELOPE = '{"name": "replay_tool", "arguments": {"i": %d}} '


def synth_text(key: str, n: int) -> str:
    """``n`` deterministic alphabet characters for ``key`` — one token per
    character under the byte tokenizer."""
    if n <= 0:
        return ""
    out: list[str] = []
    block = 0
    while len(out) < n:
        digest = hashlib.sha256(f"{key}#{block}".encode()).digest()
        out.extend(_ALPHABET[b & 63] for b in digest)
        block += 1
    return "".join(out[:n])


def synth_prompt(
    seed: int, persona: str, prefix_tokens: int, prompt_tokens: int, index: int
) -> str:
    """The request's regenerated prompt: a persona-shared prefix (same for
    every request of that persona — the prefix-cache/dedup surface) plus a
    per-request body."""
    prompt_tokens = max(1, int(prompt_tokens))
    prefix = max(0, min(int(prefix_tokens), prompt_tokens))
    head = synth_text(f"{seed}:{persona}:prefix", prefix)
    body = synth_text(f"{seed}:{persona}:{index}:body", prompt_tokens - prefix)
    return head + body


def _percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile; 0.0 on empty input."""
    if not values:
        return 0.0
    ordered = sorted(values)
    rank = max(0, min(len(ordered) - 1, int(round(q * (len(ordered) - 1)))))
    return ordered[rank]


@dataclass
class _RequestProbe:
    """Client-side timing for one replayed request (fed by on_tokens)."""

    index: int
    t_submit: float
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    max_gap_s: float = 0.0
    tool_calls: int = 0
    # every token batch as delivered: the exactly-once evidence — under
    # faults (failover, hedging) this must still equal the final result
    streamed: list = field(default_factory=list)

    def on_tokens(self, tokens) -> None:
        now = time.monotonic()
        if self.t_first is None:
            self.t_first = now
        elif self.t_last is not None:
            self.max_gap_s = max(self.max_gap_s, now - self.t_last)
        self.t_last = now
        self.streamed.extend(tokens)


@dataclass
class ReplayRow:
    """Outcome of one replayed request."""

    index: int
    persona: str
    outcome: str = "error"  # completed | shed | cancelled | expired | error
    text: str = ""
    tokens: tuple = ()
    streamed: tuple = ()  # what on_tokens actually delivered, in order
    finish_reason: str = ""
    ttft_ms: Optional[float] = None
    e2e_ms: Optional[float] = None
    decode_stall_ms: float = 0.0
    preempts: int = 0
    tool_calls: int = 0
    error: str = ""


@dataclass
class ReplayReport:
    """Everything a scenario run produced, plus the SLO summary the gate
    consumes."""

    scenario: str
    speed: float
    seed: int
    rows: list[ReplayRow] = field(default_factory=list)
    goodput_ratio: Optional[float] = None
    wall_s: float = 0.0

    def outputs(self) -> dict[int, tuple]:
        """index -> generated token tuple, completed requests only — the
        byte-identity comparison surface."""
        return {
            r.index: tuple(r.tokens)
            for r in self.rows if r.outcome == "completed"
        }

    def count(self, outcome: str) -> int:
        return sum(1 for r in self.rows if r.outcome == outcome)

    def stream_violations(self) -> list[int]:
        """Indices of completed requests whose delivered stream differs
        from the final result — the exactly-once check. Empty under the
        router's dedupe contract no matter how many failovers or hedges
        the request survived."""
        return [
            r.index for r in self.rows
            if r.outcome == "completed" and r.streamed != r.tokens
        ]

    def slo_doc(self) -> dict[str, Any]:
        ttft = [r.ttft_ms for r in self.rows if r.ttft_ms is not None]
        e2e = [r.e2e_ms for r in self.rows if r.e2e_ms is not None]
        stalls = [r.decode_stall_ms for r in self.rows if r.ttft_ms is not None]
        preempts = [float(r.preempts) for r in self.rows]
        doc: dict[str, Any] = {
            "scenario": self.scenario,
            "speed": self.speed,
            "requests": len(self.rows),
            "completed": self.count("completed"),
            "shed": self.count("shed"),
            "cancelled": self.count("cancelled"),
            "expired": self.count("expired"),
            "errors": self.count("error"),
            "tool_calls": sum(r.tool_calls for r in self.rows),
            "ttft_p50_ms": round(_percentile(ttft, 0.50), 3),
            "ttft_p99_ms": round(_percentile(ttft, 0.99), 3),
            "e2e_p50_ms": round(_percentile(e2e, 0.50), 3),
            "e2e_p99_ms": round(_percentile(e2e, 0.99), 3),
            "decode_stall_p99_ms": round(_percentile(stalls, 0.99), 3),
            "preempt_p99": _percentile(preempts, 0.99),
            "wall_s": round(self.wall_s, 3),
        }
        if self.goodput_ratio is not None:
            doc["goodput_ratio"] = round(float(self.goodput_ratio), 4)
        return doc


def _target_goodput(target) -> Optional[float]:
    """Goodput ratio from the target's declared stats surface: the engine
    publishes it under ``perf.goodput.ratio``; the fleet router aggregates
    per-replica ratios (mean over replicas that report one)."""
    try:
        stats = target.stats()
    except Exception:
        return None
    perf = stats.get("perf")
    if isinstance(perf, dict):
        ratio = (perf.get("goodput") or {}).get("ratio")
        return float(ratio) if ratio is not None else None
    rows = stats.get("replicas")
    if isinstance(rows, list):
        ratios = [
            float(r["goodput_ratio"]) for r in rows
            if isinstance(r, dict) and r.get("goodput_ratio") is not None
        ]
        if ratios:
            return sum(ratios) / len(ratios)
    return None


class TraceReplayer:
    """Replay one trace document against one target (Engine or
    FleetRouter — anything with the Engine submit/cancel duck type).

    ``speed`` divides every virtual offset: 10x replays a 30s trace in 3s
    of arrivals. ``seed`` keys the synthetic content; a different seed is a
    different (but equally shaped) workload, the same seed is byte-for-byte
    the same workload."""

    def __init__(
        self,
        trace: dict,
        *,
        speed: float = 1.0,
        seed: int = 0,
        scenario: Optional[str] = None,
        request_timeout_s: float = 120.0,
        record_metrics: bool = True,
        sampling_factory: Optional[Callable[[dict], Any]] = None,
    ):
        problems = validate_trace(trace)
        if problems:
            raise ValueError(
                "unreplayable trace: " + "; ".join(problems[:5])
            )
        self.trace = trace
        self.speed = max(1e-6, float(speed))
        self.seed = int(seed)
        self.scenario = scenario or str(trace.get("source") or "replay")
        self.request_timeout_s = float(request_timeout_s)
        self.record_metrics = bool(record_metrics)
        self._sampling_factory = sampling_factory

    # -- content regeneration -------------------------------------------

    def _prefix_tokens(self, persona: str) -> int:
        meta = (self.trace.get("personas") or {}).get(persona) or {}
        return int(meta.get("prefix_tokens") or 0)

    def prompt_for(self, row: dict) -> str:
        persona = str(row.get("persona") or f"solo{row.get('i', 0)}")
        return synth_prompt(
            self.seed, persona, self._prefix_tokens(persona),
            int(row.get("prompt_tokens") or 1), int(row.get("i") or 0),
        )

    def _sampling_for(self, row: dict, target):
        from ..engine.engine import SamplingParams

        if self._sampling_factory is not None:
            return self._sampling_factory(row)
        forced: tuple = ()
        n_tools = len(row.get("tool_calls") or ())
        if n_tools:
            text = "".join(TOOL_ENVELOPE % i for i in range(n_tools))
            forced = tuple(target.tokenizer.encode(text))
        # output_tokens is a CAP, not a promise: greedy decode on the
        # target model stops wherever EOS lands, and exported traces record
        # the actual produced length — so replaying an export reproduces
        # real lengths while synthetic scenarios treat theirs as budgets.
        max_tokens = max(1, int(row.get("output_tokens") or 1), len(forced) + 1)
        return SamplingParams(
            temperature=0.0, max_tokens=max_tokens, forced_prefix=forced,
        )

    # -- the run ---------------------------------------------------------

    def run(self, target) -> ReplayReport:
        rows = list(self.trace.get("requests") or [])
        rows.sort(key=lambda r: (float(r.get("offset_s") or 0.0), r.get("i", 0)))
        for spec in self.trace.get("faults") or ():
            spec = dict(spec)
            site = spec.pop("site", "")
            if site:
                FAULTS.arm(
                    site,
                    times=int(spec.pop("times", 1)),
                    after_steps=int(spec.pop("after_steps", 0)),
                    **spec,
                )
        supports_affinity = bool(getattr(target, "supports_affinity", False))
        report = ReplayReport(self.scenario, self.speed, self.seed)
        probes: list[tuple[dict, _RequestProbe, Any]] = []
        timers: list[threading.Timer] = []
        t0 = time.monotonic()
        try:
            for row in rows:
                due = t0 + float(row.get("offset_s") or 0.0) / self.speed
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                probe = _RequestProbe(int(row.get("i") or 0), time.monotonic())
                sampling = self._sampling_for(row, target)
                kwargs: dict[str, Any] = {
                    "sampling": sampling,
                    "on_tokens": probe.on_tokens,
                    "timeout_s": row.get("deadline_s"),
                }
                if row.get("tool_calls"):
                    def _on_tool(idx, call, _p=probe):
                        _p.tool_calls += 1
                        if FAULTS.enabled:
                            slow = FAULTS.pop("tool.slow")
                            if slow:
                                time.sleep(float(slow.get("delay_s", 0.02)))

                    kwargs["on_tool_call"] = _on_tool
                if supports_affinity and row.get("persona"):
                    kwargs["affinity_key"] = str(row["persona"])
                fut = target.submit(self.prompt_for(row), **kwargs)
                cancel_after = row.get("cancel_after_s")
                if cancel_after is not None:
                    timer = threading.Timer(
                        float(cancel_after) / self.speed,
                        lambda f=fut: target.cancel(f),
                    )
                    timer.daemon = True
                    timer.start()
                    timers.append(timer)
                probes.append((row, probe, fut))
            report.rows = [
                self._collect(row, probe, fut) for row, probe, fut in probes
            ]
        finally:
            for timer in timers:
                timer.cancel()
        report.wall_s = time.monotonic() - t0
        report.goodput_ratio = _target_goodput(target)
        if self.record_metrics:
            self._record_metrics(report)
        return report

    def _collect(self, row: dict, probe: _RequestProbe, fut) -> ReplayRow:
        out = ReplayRow(
            index=probe.index, persona=str(row.get("persona") or ""),
            tool_calls=probe.tool_calls,
        )
        try:
            result = fut.result(timeout=self.request_timeout_s)
        except Exception as exc:
            name = type(exc).__name__
            if fut.cancelled() or name == "CancelledError":
                out.outcome = "cancelled"
            elif "Overloaded" in name:
                out.outcome = "shed"
            elif "Deadline" in name or "Timeout" in name or "timeout" in str(exc):
                out.outcome = "expired"
            else:
                out.outcome = "error"
                out.error = f"{name}: {exc}"
            return out
        # a mid-decode cancel resolves the future with the partial result
        # and finish_reason "cancelled" (only queued cancels raise)
        out.outcome = (
            "cancelled" if result.finish_reason == "cancelled" else "completed"
        )
        out.text = result.text
        out.tokens = tuple(result.tokens)
        out.streamed = tuple(probe.streamed)
        out.finish_reason = result.finish_reason
        out.preempts = int(getattr(result, "preempt_count", 0) or 0)
        if probe.t_first is not None:
            out.ttft_ms = (probe.t_first - probe.t_submit) * 1e3
            out.decode_stall_ms = probe.max_gap_s * 1e3
            t_done = probe.t_last if probe.t_last is not None else probe.t_first
            out.e2e_ms = (t_done - probe.t_submit) * 1e3
        return out

    def _record_metrics(self, report: ReplayReport) -> None:
        labels = {"scenario": report.scenario}
        for row in report.rows:
            REGISTRY.counter_add(
                "acp_scenario_requests_total", 1.0,
                labels={**labels, "outcome": row.outcome},
                help="requests replayed by the scenario harness "
                "(scenarios/replay.py), by scenario and outcome "
                "(completed | shed | cancelled | expired | error)",
            )
            if row.ttft_ms is not None:
                REGISTRY.observe(
                    "acp_scenario_ttft_seconds", row.ttft_ms / 1e3,
                    labels=labels,
                    help="client-observed time to first token during "
                    "scenario replay, per scenario",
                )
                REGISTRY.observe(
                    "acp_scenario_decode_stall_seconds",
                    row.decode_stall_ms / 1e3, labels=labels,
                    help="client-observed max inter-batch gap inside one "
                    "request's decode stream during scenario replay "
                    "(preemption/requeue stalls surface here)",
                )


def replay(
    trace: dict, target, *, speed: float = 1.0, seed: int = 0, **kw
) -> ReplayReport:
    """One-call convenience: ``TraceReplayer(trace, ...).run(target)``."""
    return TraceReplayer(trace, speed=speed, seed=seed, **kw).run(target)


def byte_identical(a: ReplayReport, b: ReplayReport) -> bool:
    """Same completed indices, same token stream per index — the replay
    determinism contract between two runs of one trace."""
    oa, ob = a.outputs(), b.outputs()
    return bool(oa) and oa == ob


__all__ = [
    "TraceReplayer",
    "ReplayReport",
    "ReplayRow",
    "replay",
    "byte_identical",
    "synth_prompt",
    "synth_text",
    "TOOL_ENVELOPE",
]
