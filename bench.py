"""Headline benchmark: continuous-batching decode throughput per chip.

Runs the serving engine (the ``provider: tpu`` data plane) on the real
device(s): concurrent requests continuously batched into one decode stream,
Llama-3-family architecture sized to the available HBM (``bench-1b``
~1.1B params bf16 on a single v5e chip; the 8B flagship needs the full
v5e-8 — or one chip with ``ACP_BENCH_QUANTIZE=int8``).

Prints ONE JSON line:
  {"metric": "decode_tok_s_per_chip", "value": N, "unit": "tok/s/chip",
   "vs_baseline": N/1000, "ttft_first_toolcall_ms": {...}, ...}
vs_baseline is against BASELINE.md's >1,000 tok/s/chip north-star target.

Wedge-resistant architecture (round-3 rework): the PARENT process NEVER
initializes PJRT — not even ``jax.devices()``. Every accelerator-touching
phase runs in a watchdogged CHILD process:

  parent ──probe child──▶ ``python -c "import jax; jax.devices()"`` (disposable)
         ──main child───▶ ``bench.py --phase main``  (attach → engine → burst → TTFT)
         ──ab child─────▶ ``bench.py --phase ab``    (the other KV layout)

``ACP_BENCH_SPEC_LEN`` (default 0 = off) opts the burst into n-gram
prompt-lookup speculative decoding (``ACP_BENCH_SPEC_NGRAM`` tunes the
drafter); the emitted payloads then carry an additive ``spec`` block —
acceptance counters plus ``spec_accepted_tokens_per_block`` and a spec-
on/off delta note — without changing what the headline metric measures.

Children report progress via ``MARK <name>`` / ``RESULT <key> <json>`` lines
on stdout; the parent enforces a per-mark deadline schedule and SIGKILLs a
child that misses one (a hung PJRT attach leaves threads alive, so
heartbeats prove nothing — only forward progress counts). A killed phase is
retried after a fresh probe while budget remains; partial results that
already arrived are kept.

Device contract:
  (a) the required backend is ``tpu``: probe AND child refuse anything else.
      With no chip the parent exits non-zero and prints no number, old or
      new (``ACP_BENCH_ALLOW_CPU=1`` admits the CPU backend for the harness
      tests and dev boxes — such a run is never a device measurement);
  (b) the total budget default is 1500 s — inside any plausible driver
      timeout — and the parent RE-PRINTS the JSON line the instant each
      result lands, so a late SIGKILL cannot erase a captured headline (the
      last parseable line on stdout is always the freshest state);
  (c) the probed backend + device kind are recorded under ``platform``.

Knobs (env): ACP_BENCH_PRESET, ACP_BENCH_REQUESTS, ACP_BENCH_MAX_TOKENS,
ACP_BENCH_PROMPT_LEN, ACP_BENCH_MAX_CTX, ACP_BENCH_BLOCK,
ACP_BENCH_KV_LAYOUT (slot|paged), ACP_BENCH_QUANTIZE (int8),
ACP_BENCH_DEADLINE_S (per-burst wall-clock cap),
ACP_BENCH_DEVICE_TIMEOUT_S (attach watchdog),
ACP_BENCH_BUILD_TIMEOUT_S, ACP_BENCH_WARM_TIMEOUT_S,
ACP_BENCH_TTFT=0 / ACP_BENCH_TTFT_TASKS / ACP_BENCH_TTFT_DEADLINE_S /
ACP_BENCH_TTFT_TIMEOUT_S, ACP_BENCH_AB=0 / ACP_BENCH_AB_BUDGET_S,
ACP_BENCH_TOTAL_BUDGET_S, ACP_BENCH_RETRIES,
ACP_BENCH_FLIGHT=1 / ACP_BENCH_FLIGHT_LEGS (flight-recorder on/off
overhead guard on the headline burst — the <2% contract, emitted as the
doc's additive ``flight`` block),
ACP_BENCH_PROF=1 / ACP_BENCH_PROF_LEGS (dispatch-profiler on/off overhead
guard on the headline burst — the compute efficiency observatory's <2%
contract, emitted as the doc's additive ``prof`` block with the burst's
goodput ratio),
ACP_BENCH_MEGASTEP=1 (fused-megastep dispatches-per-cycle A/B; knobs
ACP_BENCH_MEGASTEP_DECODERS/_PROMPT/_LONGS/_CHUNK/_TAIL_TOKENS/_KV_LAYOUT),
ACP_BENCH_METAL=1 / ACP_BENCH_METAL_TASKS / ACP_BENCH_METAL_TAIL_TOKENS /
ACP_BENCH_METAL_KV_PAGES / ACP_BENCH_METAL_CHUNK (down-to-the-metal
fixture: swap-in stall p99 with async host-KV prefetch off vs on, and
dispatches-per-busy-cycle with the PR 20 absorbed swap/plain megastep
phases vs split — both byte-identical, emitted as the doc's additive
``metal`` block),
ACP_BENCH_MEM=1 / ACP_BENCH_MEM_PROMPT / ACP_BENCH_MEM_TASKS /
ACP_BENCH_MEM_PERSONA / ACP_BENCH_MEM_HOST_BYTES (KV memory-tier
fixture: preempt->resume swap-in vs recompute-prefill latency, and
effective concurrent slots with shared-prefix dedup on/off at a fixed
page budget — emitted as the doc's additive ``mem`` block),
ACP_BENCH_QUANT=1 / ACP_BENCH_QUANT_PROMPT / ACP_BENCH_QUANT_TASKS /
ACP_BENCH_QUANT_BASE_TASKS (quantized-serving fixture: effective
concurrent slots bf16 vs int8 KV at a fixed HBM byte budget, bar >=
1.5x, plus the byte-identity-relaxed accuracy-gate numbers — emitted as
the doc's additive ``quant`` block),
ACP_BENCH_SCENARIOS=1 / ACP_BENCH_SCENARIO_SPEED / ACP_BENCH_SCENARIO_N
(scenario factory: replay the scenario library — persona storm, long
tail, tool swarm, cancel churn, fault cocktail — against a single engine
and a 2-replica fleet pool; per-scenario SLO percentiles land under
``scenarios.<name>.<single|fleet>`` for --slo-envelopes / --bench-trend),
ACP_BENCH_FLEET=1 / ACP_BENCH_FLEET_PERSONAS / ACP_BENCH_FLEET_TURNS /
ACP_BENCH_FLEET_PERSONA / ACP_BENCH_FLEET_PROMPT /
ACP_BENCH_FLEET_MAX_TOKENS (fleet-tier fixture: affinity vs round-robin
routing on a same-persona burst — pool-wide prefix-cache hit rate and
TTFT p99 — plus disaggregated prefill->decode handoff TTFT vs a full
local prefill and the KV bytes moved — emitted as the doc's additive
``fleet`` block),
ACP_BENCH_CHAOS=1 / ACP_BENCH_CHAOS_SPEED / ACP_BENCH_CHAOS_N /
ACP_BENCH_CHAOS_DELAY_S / ACP_BENCH_CHAOS_TIMES /
ACP_BENCH_CHAOS_HEDGE_S / ACP_BENCH_CHAOS_SEED (gray-failure fixture:
persona storm on a 3-replica fleet with ``engine.slow_cycle`` pinned to
one replica, hedging OFF vs ON — stuck-request e2e p99 both ways plus
the byte-identical verdict — and one seeded chaos-conductor run's
invariant verdict, emitted as the doc's additive ``chaos`` block).

``ACP_INVARIANTS=1`` additionally arms the engine's runtime invariant
checker (engine/invariants.py) for every bench engine — per-dispatch state
audits ride the measured burst without changing the headline contract
(slower, for soak/debug runs; leave unset for comparable numbers). The
flag is registered explicitly on each Engine below so child processes and
future refactors can't silently drop it.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

TARGET_TOK_S = 1000.0
_THIS = os.path.abspath(__file__)


def _log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# parent side
# ---------------------------------------------------------------------------


_PROBE_SNIPPET = (
    "import jax, json; d = jax.devices(); print(json.dumps("
    "{'backend': jax.default_backend(), 'n': len(d), "
    "'device_kind': d[0].device_kind if d else ''}))"
)


def _backend_ok(backend: object) -> bool:
    """The required backend is ``tpu`` — by name, so a fallback to any
    other platform reads as no chip. ``ACP_BENCH_ALLOW_CPU=1`` admits cpu."""
    return backend == "tpu" or (
        backend == "cpu" and os.environ.get("ACP_BENCH_ALLOW_CPU", "0") == "1"
    )


def _probe_once(timeout_s: float) -> dict | None:
    """One DISPOSABLE probe subprocess. Returns {backend, n, device_kind} or
    None. The parent's own PJRT state stays virgin no matter what happens
    here. The required backend is ``tpu``: when JAX finds no chip it
    silently reports CPU devices, and that is a probe FAILURE, never a
    successful attach."""
    try:
        out = subprocess.run(
            [sys.executable, "-c", _PROBE_SNIPPET],
            capture_output=True,
            timeout=timeout_s,
            text=True,
        )
    except subprocess.TimeoutExpired:
        return None
    if out.returncode == 0 and out.stdout.strip():
        try:
            info = json.loads(out.stdout.strip().splitlines()[-1])
        except (ValueError, json.JSONDecodeError):
            return None
        if not isinstance(info, dict) or not info.get("n"):
            return None
        if not _backend_ok(info.get("backend")):
            _log(
                f"probe reached backend={info.get('backend')!r} "
                f"({info.get('n')} device(s)) — not a tpu; no chip"
            )
            return None
        return info
    return None


_ACTIVE_RUN: "_PhaseRun | None" = None


def _parent_signal_cleanup(signum, frame):  # pragma: no cover - signal path
    """A driver-killed parent must not orphan a TPU-holding child: the child
    lives in its own session (start_new_session), so a group-kill of the
    parent misses it and it would hold the single chip for minutes."""
    if _ACTIVE_RUN is not None:
        _ACTIVE_RUN.kill()
    sys.exit(128 + signum)


class _PhaseRun:
    """One child process + the MARK/RESULT reader + deadline enforcement.

    ``on_result`` (if given) fires from the reader thread the INSTANT a
    RESULT line parses — the parent uses it to flush the JSON doc while
    ``run_schedule`` is still blocked on a later mark, so a driver SIGKILL
    during a hung TTFT leg cannot erase an already-captured headline."""

    def __init__(self, argv: list[str], on_result=None):
        global _ACTIVE_RUN
        _ACTIVE_RUN = self
        self.on_result = on_result
        self.results: dict[str, object] = {}
        self.marks: list[str] = []
        self._cond = threading.Condition()
        self.proc = subprocess.Popen(
            [sys.executable, _THIS, *argv],
            stdout=subprocess.PIPE,
            stderr=None,  # child diagnostics flow to the parent's stderr
            text=True,
            errors="replace",
            start_new_session=True,
        )
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        assert self.proc.stdout is not None
        try:
            self._read_lines()
        except Exception as e:  # a dead reader must never strand the child
            _log(f"reader thread error: {e!r}")
        finally:
            with self._cond:
                self._cond.notify_all()

    def _read_lines(self) -> None:
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            with self._cond:
                if line.startswith("MARK ") and line.split(None, 1)[1:]:
                    self.marks.append(line.split(None, 1)[1])
                elif line.startswith("RESULT "):
                    parts = line.split(None, 2)
                    if len(parts) == 3:
                        try:
                            self.results[parts[1]] = json.loads(parts[2])
                        except json.JSONDecodeError:
                            _log(f"unparseable RESULT {parts[1]}: {parts[2][:200]}")
                        else:
                            if self.on_result is not None:
                                try:
                                    self.on_result(parts[1], self.results[parts[1]])
                                except Exception as e:
                                    _log(f"on_result callback error: {e!r}")
                    else:
                        _log(f"malformed protocol line: {line[:200]}")
                else:
                    _log(f"child: {line}")
                self._cond.notify_all()

    def _satisfied(self, want: str) -> bool:
        if want.startswith("RESULT "):
            return want.split(None, 1)[1] in self.results
        return want in self.marks or any(m.split()[0] == want for m in self.marks)

    def wait_for(self, want: str, timeout: float) -> bool:
        """Block until the mark/result arrives, the child exits, or the
        deadline passes. True only if the mark arrived."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while not self._satisfied(want):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                if self.proc.poll() is not None and not self._reader.is_alive():
                    return self._satisfied(want)
                self._cond.wait(min(remaining, 1.0))
            return True

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(os.getpgid(self.proc.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                self.proc.kill()
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass

    def run_schedule(self, schedule: list[tuple[str, float]], hard_deadline: float) -> str:
        """Walk the (mark, timeout)-schedule. Returns 'ok' or the name of the
        first mark that never arrived. Always reaps the child."""
        for want, timeout in schedule:
            timeout = min(timeout, max(5.0, hard_deadline - time.monotonic()))
            if not self.wait_for(want, timeout):
                _log(f"phase overdue waiting for '{want}' ({timeout:.0f}s) — killing child")
                self.kill()
                return want
        # schedule satisfied; give the child a moment to exit cleanly
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            self.kill()
        return "ok"


_FLUSH_LOCK = threading.Lock()  # doc is mutated from reader threads too


def _flush_doc(doc: dict) -> None:
    """Print the one JSON line NOW, flushed. Called the moment any result
    lands (r3 failure (b): the driver SIGKILLed before the final ``finally``
    fired, erasing everything). If the driver takes the LAST parseable line,
    later flushes with more fields win; if it kills us mid-run, the most
    recent flush stands."""
    print(json.dumps(doc), flush=True)


def _write_pr_doc(doc: dict) -> None:
    """Per-PR perf doc: persist the final bench doc to $ACP_BENCH_PR_DOC
    (e.g. BENCH_PR6.json) so the repo accumulates a perf trajectory the
    ROADMAP re-anchors can read. Additive — the stdout one-JSON-line
    headline contract is untouched, and the doc carries its platform
    provenance so a CPU run can never masquerade as hardware."""
    path = os.environ.get("ACP_BENCH_PR_DOC", "")
    if not path:
        return
    try:
        with open(path, "w") as f:
            json.dump(
                {**doc, "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())},
                f, indent=2,
            )
            f.write("\n")
    except OSError as e:
        _log(f"could not write PR perf doc {path}: {e}")


def _bench_lint() -> dict:
    """acplint self-measure (PR 15): rule/suppression counts + wall time,
    recorded into the per-PR doc so the bench-trend sentinel can watch the
    pass pack's size and the suppression-debt trajectory. Parent-side and
    stdlib-only — the analysis package never imports jax, so this runs even
    when the accelerator probe later fails."""
    from agentcontrolplane_tpu.analysis.core import analyze, collect_suppressions
    from agentcontrolplane_tpu.analysis.passes import RULES

    root = os.path.dirname(os.path.abspath(__file__))
    targets = [
        os.path.join(root, "agentcontrolplane_tpu"),
        os.path.join(root, "tests"),
        os.path.join(root, "bench.py"),
    ]
    per_rule: dict[str, float] = {}
    t0 = time.perf_counter()
    violations = analyze(targets, timings=per_rule)
    wall = time.perf_counter() - t0
    return {
        "rules_total": len(RULES),
        "suppressions_total": len(collect_suppressions(targets)),
        "violations": len(violations),
        "wall_s": round(wall, 3),
        "per_rule_s": {k: round(v, 4) for k, v in sorted(per_rule.items())},
    }


class _NoChip(Exception):
    """The probe found no tpu backend: there is nothing to measure."""


def _parent() -> int:
    """Orchestrates the phases; returns the process exit code. Once a chip
    is attached the one JSON line is emitted no matter what — a parent-side
    exception must never eat an already-captured headline. With NO chip
    nothing is printed to stdout (no number, old or new) and the code is
    non-zero."""
    doc: dict = {
        "metric": "decode_tok_s_per_chip",
        "value": 0.0,
        "unit": "tok/s/chip",
        "vs_baseline": 0.0,
    }
    notes: list[str] = []
    try:
        _parent_run(doc, notes)
    except _NoChip as e:
        _log(f"FAILED: {e}")
        return 3
    except Exception as e:
        notes.append(f"parent error: {e!r}")
    with _FLUSH_LOCK:
        doc["notes"] = [n for n in notes if n]
        _flush_doc(doc)
        _write_pr_doc(doc)
    for n in notes:
        _log(n)
    return 0


def _parent_run(doc: dict, notes: list[str]) -> None:
    for sig in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
        try:
            signal.signal(sig, _parent_signal_cleanup)
        except (ValueError, OSError):  # non-main thread (tests) / unsupported
            pass
    if os.environ.get("ACP_BENCH_LINT", "0") == "1":
        # before the device probe: the lint series must land in the doc
        # even when the accelerator is unreachable
        try:
            with _FLUSH_LOCK:
                doc["lint"] = _bench_lint()
                _flush_doc(doc)
        except Exception as e:
            notes.append(f"lint section failed: {e!r}")
    # r3 failure (b): 4500s default exceeded the driver's own timeout, so the
    # driver SIGKILLed the parent before anything flushed. 1500s leaves
    # comfortable headroom inside any plausible driver budget (VERDICT r3
    # "next round" #1 demands ≤1800).
    total_budget = float(os.environ.get("ACP_BENCH_TOTAL_BUDGET_S", "1500"))
    t0 = time.monotonic()
    hard_deadline = t0 + total_budget
    probe_timeout = float(os.environ.get("ACP_BENCH_DEVICE_TIMEOUT_S", "120"))
    build_timeout = float(os.environ.get("ACP_BENCH_BUILD_TIMEOUT_S", "600"))
    warm_timeout = float(os.environ.get("ACP_BENCH_WARM_TIMEOUT_S", "600"))
    deadline_s = float(os.environ.get("ACP_BENCH_DEADLINE_S", "240"))
    ttft_on = os.environ.get("ACP_BENCH_TTFT", "1") != "0"
    ttft_timeout = float(os.environ.get("ACP_BENCH_TTFT_TIMEOUT_S", "600"))
    ab_on = os.environ.get("ACP_BENCH_AB", "1") != "0"
    ab_budget = float(os.environ.get("ACP_BENCH_AB_BUDGET_S", "600"))
    retries = int(os.environ.get("ACP_BENCH_RETRIES", "2"))
    kv_layout = os.environ.get("ACP_BENCH_KV_LAYOUT", "slot")

    info = _probe_once(probe_timeout)
    if info is None:
        raise _NoChip(
            "no tpu backend (a CPU fallback counts as no chip); nothing measured"
        )
    with _FLUSH_LOCK:
        doc["platform"] = {
            "backend": info["backend"],
            "devices": info["n"],
            "device_kind": info.get("device_kind", ""),
        }
        _flush_doc(doc)

    # captured results live here; `capture` fires FROM THE READER THREAD the
    # instant a RESULT line parses, so the doc is flushed while run_schedule
    # is still blocked on a later mark (a driver SIGKILL during a hung TTFT
    # leg must not erase an already-captured headline — the r3 failure).
    got: dict[str, dict | None] = {"headline": None, "ttft": None}

    def capture(key: str, val: object) -> None:
        if not isinstance(val, dict):
            return
        with _FLUSH_LOCK:
            if key == "platform":
                doc["platform"] = val  # child-observed; fresher than the probe
            elif key == "headline" and got["headline"] is None:
                got["headline"] = val
                doc["value"] = val.get("tok_s_per_chip", 0.0)
                doc["vs_baseline"] = round(doc["value"] / TARGET_TOK_S, 3)
                doc["headline_note"] = str(val.get("note", ""))
                if "mfu" in val:
                    doc["mfu"] = val["mfu"]
                    # record the denominator so the MFU stays re-derivable
                    # if the peak table is ever corrected
                    if "peak_flops_per_chip" in val:
                        doc["peak_flops_per_chip"] = val["peak_flops_per_chip"]
                if "spec" in val:  # additive; absent unless ACP_BENCH_SPEC_LEN
                    doc["spec"] = val["spec"]
            elif key == "ttft" and got["ttft"] is None:
                got["ttft"] = val
                doc["ttft_first_toolcall_ms"] = val
            elif key == "tool_turn" and "tool_turn" not in doc:
                doc["tool_turn"] = val
            elif key == "hol" and "hol" not in doc:
                doc["hol"] = val
            elif key == "mem" and "mem" not in doc:
                doc["mem"] = val
            elif key == "quant" and "quant" not in doc:
                doc["quant"] = val
            elif key == "fleet" and "fleet" not in doc:
                doc["fleet"] = val
            elif key == "scenarios" and "scenarios" not in doc:
                doc["scenarios"] = val
            elif key == "flight" and "flight" not in doc:
                doc["flight"] = val
            elif key == "prof" and "prof" not in doc:
                doc["prof"] = val
            elif key == "megastep" and "megastep" not in doc:
                doc["megastep"] = val
            elif key == "metal" and "metal" not in doc:
                doc["metal"] = val
            else:
                return
            _flush_doc(doc)

    main_schedule: list[tuple[str, float]] = [
        ("attach_ok", probe_timeout),
        ("engine_built", build_timeout),
        ("warm_done", warm_timeout),
        ("RESULT headline", deadline_s + 240),
    ]
    if os.environ.get("ACP_BENCH_TOOL_TURN", "0") == "1":
        main_schedule.append(("RESULT tool_turn", 600))
    if os.environ.get("ACP_BENCH_HOL", "0") == "1":
        main_schedule.append(("RESULT hol", 900))
    if os.environ.get("ACP_BENCH_MEM", "0") == "1":
        main_schedule.append(("RESULT mem", 900))
    if os.environ.get("ACP_BENCH_QUANT", "0") == "1":
        main_schedule.append(("RESULT quant", 900))
    if os.environ.get("ACP_BENCH_FLEET", "0") == "1":
        main_schedule.append(("RESULT fleet", 900))
    if os.environ.get("ACP_BENCH_SCENARIOS", "0") == "1":
        main_schedule.append(("RESULT scenarios", 1200))
    if os.environ.get("ACP_BENCH_CHAOS", "0") == "1":
        main_schedule.append(("RESULT chaos", 1200))
    if os.environ.get("ACP_BENCH_FLIGHT", "0") == "1":
        main_schedule.append(("RESULT flight", 900))
    if os.environ.get("ACP_BENCH_PROF", "0") == "1":
        main_schedule.append(("RESULT prof", 900))
    if os.environ.get("ACP_BENCH_MEGASTEP", "0") == "1":
        main_schedule.append(("RESULT megastep", 900))
    if os.environ.get("ACP_BENCH_METAL", "0") == "1":
        main_schedule.append(("RESULT metal", 900))
    if ttft_on:
        main_schedule.append(("RESULT ttft", ttft_timeout))

    for attempt in range(1, retries + 1):
        if time.monotonic() > hard_deadline - 120:
            notes.append("total budget exhausted before main phase completed")
            break
        only_ttft = got["headline"] is not None
        argv = ["--phase", "main"]
        if only_ttft:
            argv.append("--only-ttft")
        elif not ttft_on:
            argv.append("--no-ttft")
        schedule = (
            [("attach_ok", probe_timeout), ("engine_built", build_timeout),
             ("RESULT ttft", ttft_timeout)]
            if only_ttft
            else main_schedule
        )
        _log(f"main phase attempt {attempt} ({'ttft-only' if only_ttft else 'full'})")
        run = _PhaseRun(argv, on_result=capture)
        status = run.run_schedule(schedule, hard_deadline)
        if status == "ok":
            break
        notes.append(f"main attempt {attempt} stalled at '{status}'")
        if got["headline"] is not None and (not ttft_on or got["ttft"] is not None):
            break
        if attempt < retries and _probe_once(probe_timeout) is None:
            notes.append("chip did not answer the probe for a retry")
            break

    headline = got["headline"]
    if not headline:
        notes.append("FAILED: no headline result captured from any child attempt")
    if ttft_on and got["ttft"] is None:
        doc["ttft_first_toolcall_ms"] = {"error": "ttft phase did not complete"}

    remaining = hard_deadline - time.monotonic()
    if ab_on and headline and remaining > 300:
        other = "paged" if kv_layout == "slot" else "slot"
        budget = min(ab_budget, remaining - 60)
        _log(f"A/B phase ({other}) with {budget:.0f}s budget")
        run = _PhaseRun(
            ["--phase", "ab", "--layout", other, "--budget", str(budget)],
            on_result=capture,
        )
        status = run.run_schedule(
            [("attach_ok", probe_timeout),
             ("engine_built", min(build_timeout, budget)),
             ("RESULT ab", budget)],
            hard_deadline,
        )
        ab = run.results.get("ab")
        if isinstance(ab, dict) and "tok_s_per_chip" in ab:
            with _FLUSH_LOCK:
                doc[f"{other}_tok_s_per_chip"] = ab["tok_s_per_chip"]
                if "mfu" in ab:
                    doc[f"{other}_mfu"] = ab["mfu"]
                if "spec" in ab:
                    doc[f"{other}_spec"] = ab["spec"]
                doc["kv_layout_winner"] = (
                    kv_layout if doc["value"] >= ab["tok_s_per_chip"] else other
                )
                _flush_doc(doc)
            notes.append(f"A/B {other}: {ab.get('note', '')}")
        else:
            doc["ab_error"] = f"ab phase stalled at '{status}'"
    elif ab_on and headline:
        doc["ab_skipped"] = f"only {remaining:.0f}s of total budget left"


# ---------------------------------------------------------------------------
# FLOPs model (VERDICT r4 #3: MFU next to tok/s — throughput alone can't
# show distance from roofline)
# ---------------------------------------------------------------------------

_PEAK_BF16_FLOPS = {
    # dense bf16 MXU peak per chip, FLOP/s, keyed by substring of the PJRT
    # device_kind. Weight-only int8 serving still multiplies in bf16 (the
    # int8->bf16 convert fuses into the operand load — ops/quant.py), so
    # bf16 peak is the denominator in both quant modes. Ordered most-specific
    # first: matching iterates in insertion order, and "v4" would otherwise
    # swallow the half-peak "v4 lite" (v4i).
    "v4 lite": 138e12,
    "v5 lite": 197e12,
    "v6 lite": 918e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
    "v4": 275e12,
}


def _peak_flops_per_chip(device_kind: str) -> float | None:
    dk = (device_kind or "").lower()
    for key, peak in _PEAK_BF16_FLOPS.items():
        if key in dk:
            return peak
    return None


def _matmul_params(c) -> float:
    """Weights that participate in matmuls per decoded token: attention
    projections + FFN (active experts only for MoE, plus the router) +
    lm_head. The embedding gather is not a matmul; tied embeddings still pay
    the lm_head matmul."""
    hd = c.head_dim
    attn = (
        c.dim * c.n_heads * hd          # Wq
        + 2 * c.dim * c.n_kv_heads * hd  # Wk, Wv
        + c.n_heads * hd * c.dim         # Wo
    )
    if c.n_experts:
        mlp = 3 * c.dim * c.ffn_dim * c.experts_per_token + c.dim * c.n_experts
    else:
        mlp = 3 * c.dim * c.ffn_dim  # gate, up, down
    return float(c.n_layers * (attn + mlp) + c.dim * c.vocab_size)


def _flops_per_token(c, ctx: float) -> float:
    """2 FLOPs (mul+add) per matmul weight, plus the QK^T and AV score
    matmuls against ``ctx`` cached positions (GQA shrinks the KV *cache*,
    not these two matmuls — queries still use all n_heads)."""
    attn_scores = 4.0 * c.n_layers * c.n_heads * c.head_dim * ctx
    return 2.0 * _matmul_params(c) + attn_scores


def _burst_model_flops(
    c, prompt_len: int, prefills: int, gen_tokens: int, mean_ctx: float
) -> float:
    """Model FLOPs for one measured burst. The headline window includes the
    prefill work (elapsed spans submit -> last token), so MFU must count it:
    each prefill processes prompt_len tokens at mean attention context
    prompt_len/2; each generated token is one decode step at mean_ctx.

    The lm_head matmul is counted ONCE per prefill, not per prefill token:
    the engine's prefill computes logits only at the LAST position
    (prefill_batch returns [B, V]), so charging every prompt token with the
    2*dim*vocab head FLOPs overstates prefill work — and thus MFU — by up
    to the head's share of the model (large for small-dim/big-vocab
    configs)."""
    head = 2.0 * c.dim * c.vocab_size
    prefill = prefills * (
        prompt_len * (_flops_per_token(c, prompt_len / 2.0) - head) + head
    )
    decode = gen_tokens * _flops_per_token(c, mean_ctx)
    return prefill + decode


# ---------------------------------------------------------------------------
# child side — the only code that may touch PJRT
# ---------------------------------------------------------------------------


def _mark(name: str) -> None:
    print(f"MARK {name}", flush=True)


def _result(key: str, payload: dict) -> None:
    print(f"RESULT {key} {json.dumps(payload)}", flush=True)


def _child(args: argparse.Namespace) -> None:
    import jax

    preset = os.environ.get("ACP_BENCH_PRESET", "bench-1b")
    n_requests = int(os.environ.get("ACP_BENCH_REQUESTS", "64"))
    max_tokens = int(os.environ.get("ACP_BENCH_MAX_TOKENS", "64"))
    prompt_len = int(os.environ.get("ACP_BENCH_PROMPT_LEN", "128"))
    max_ctx = int(os.environ.get("ACP_BENCH_MAX_CTX", "512"))
    block = int(os.environ.get("ACP_BENCH_BLOCK", "16"))
    quantize = os.environ.get("ACP_BENCH_QUANTIZE") or None
    deadline_s = float(os.environ.get("ACP_BENCH_DEADLINE_S", "420"))
    kv_layout = args.layout or os.environ.get("ACP_BENCH_KV_LAYOUT", "slot")
    # speculative decoding knobs (off by default so the headline's meaning
    # is unchanged unless the operator opts in, like ACP_BENCH_QUANTIZE)
    spec_len = int(os.environ.get("ACP_BENCH_SPEC_LEN", "0"))
    spec_ngram = int(os.environ.get("ACP_BENCH_SPEC_NGRAM", "3"))
    if args.budget:
        deadline_s = min(deadline_s, args.budget / 3)

    devices = jax.devices()  # the parent watchdogs this line
    n_chips = len(devices)
    backend = jax.default_backend()
    if not _backend_ok(backend):
        # the chip went away between probe and attach and JAX fell back to
        # another platform. NEVER mark attach_ok here — exit so the parent's
        # watchdog treats this as a failed attempt.
        _log(f"attach reached backend={backend!r}, not tpu — aborting child")
        sys.exit(3)
    _mark(f"attach_ok {n_chips}")
    _result("platform", {
        "backend": backend,
        "devices": n_chips,
        "device_kind": devices[0].device_kind if devices else "",
    })

    import dataclasses

    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
    from agentcontrolplane_tpu.models.llama import PRESETS
    from agentcontrolplane_tpu.parallel.mesh import serving_mesh

    config = PRESETS[preset]
    if config.max_seq_len < max_ctx:  # small presets (tiny) honor the knob
        config = dataclasses.replace(config, max_seq_len=max_ctx)
    ttft_on = args.phase == "main" and not args.no_ttft

    engine = Engine(
        config=config,
        tokenizer=ByteTokenizer(),
        mesh=serving_mesh(),
        max_slots=n_requests,
        max_ctx=max_ctx,
        prefill_buckets=(prompt_len, max_ctx),
        decode_block_size=block,
        kv_layout=kv_layout,
        quantize=quantize,
        spec_len=spec_len,
        spec_ngram=spec_ngram,
        seed=0,
        # opt-in per-dispatch state audits (see module docstring)
        check_invariants=os.environ.get("ACP_INVARIANTS", "") not in ("", "0"),
    )
    if ttft_on or (args.phase == "ab" and os.environ.get("ACP_BENCH_TTFT", "1") != "0"):
        # build the constraint token table up front so EVERY program in this
        # process (headline warm included) traces against the real table
        # shape — otherwise the TTFT phase's table build would orphan the
        # dummy-shaped compiles the headline phase paid for. The ab child
        # mirrors the headline child's condition so the two layouts are
        # measured under identical HBM/compiled-program conditions.
        engine._get_token_table()
    engine.start()
    _mark("engine_built")

    prompt = [1 + (i % 250) for i in range(prompt_len - 1)]
    sampling = SamplingParams(temperature=0.8, top_p=0.95, max_tokens=max_tokens)
    # measured-burst window of the speculative-decoding counters (zeros and
    # absent from payloads unless ACP_BENCH_SPEC_LEN opted in)
    spec_window: dict = {"d0": 0, "p0": 0, "a0": 0, "dispatches": 0, "proposed": 0, "accepted": 0}

    def spec_fields() -> dict:
        """Additive spec block for the result payloads — the headline
        decode_tok_s_per_chip contract is untouched (same metric, same
        burst); this only documents how much of it speculation carried."""
        if not engine.spec_len:
            return {}
        d = spec_window["dispatches"]
        acc = spec_window["accepted"]
        prop = spec_window["proposed"]
        per_block = round(acc / d, 3) if d else 0.0
        return {"spec": {
            "spec_len": engine.spec_len,
            "ngram": engine.spec_ngram,
            "proposed": prop,
            "accepted": acc,
            "acceptance_rate": round(acc / prop, 4) if prop else 0.0,
            "verify_dispatches": d,
            "spec_accepted_tokens_per_block": per_block,
            "note": (
                f"speculation on (len={engine.spec_len}, ngram={engine.spec_ngram}): "
                f"{1 + per_block:.2f} tokens/verify dispatch vs 1.00/model-step "
                "with speculation off — headline metric unchanged"
            ),
        }}

    def measure(
        warm_timeout: float = float(os.environ.get("ACP_BENCH_WARM_TIMEOUT_S", "1200")),
        drain: bool = True,
    ) -> tuple[float, int, float, int]:
        """Warmup (compiles every jit entry the burst hits: batched prefill
        chunks, max-width decode, the narrow decay widths) then the measured
        full-width burst. Returns (tok/s/chip, tokens, elapsed, done)."""
        warm = [
            engine.submit(list(prompt), SamplingParams(temperature=0.0, max_tokens=block + 1))
            for _ in range(n_requests)
        ]
        warm_deadline = time.monotonic() + warm_timeout
        for f in warm:
            f.result(timeout=max(1.0, warm_deadline - time.monotonic()))
        _mark("warm_done")
        t0 = time.monotonic()
        toks0 = engine.tokens_generated
        spec_window.update(
            d0=engine.spec_dispatches, p0=engine.spec_proposed,
            a0=engine.spec_accepted, dispatches=0, proposed=0, accepted=0,
        )
        futures = [engine.submit(list(prompt), sampling) for _ in range(n_requests)]
        deadline = t0 + deadline_s
        done = 0
        for f in futures:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                f.result(timeout=remaining)
                done += 1
            except Exception:
                break
        elapsed = time.monotonic() - t0
        total = engine.tokens_generated - toks0
        spec_window["dispatches"] = engine.spec_dispatches - spec_window["d0"]
        spec_window["proposed"] = engine.spec_proposed - spec_window["p0"]
        spec_window["accepted"] = engine.spec_accepted - spec_window["a0"]
        # drain leftovers so any next phase in THIS process measures an idle
        # engine; skipped when the result is about to be emitted and the
        # process exits (the parent's mark deadline must not eat the drain)
        for f in futures:
            engine.cancel(f)
        if drain:
            drain_deadline = time.monotonic() + 120
            while time.monotonic() < drain_deadline:
                s = engine.stats()
                if (
                    s["active_slots"] == 0
                    and s["waiting"] == 0
                    and s.get("prefilling_slots", 0) == 0
                ):
                    break
                time.sleep(0.2)
        return (total / elapsed) / max(n_chips, 1), total, elapsed, done

    def mfu_fields(total: int, elapsed: float, done: int) -> dict:
        """MFU for the measured burst, against the chip's dense bf16 peak.
        Prefills counted at ``done`` when the deadline truncated the burst
        (conservative: under-, never over-states utilization)."""
        peak = _peak_flops_per_chip(devices[0].device_kind if devices else "")
        if peak is None or elapsed <= 0:
            return {}
        # count one prefill per COMPLETED request even though the engine
        # prefills every submission — on a truncated burst this undercounts
        # work done, which understates (never overstates) MFU
        prefills = done
        mean_ctx = prompt_len + max_tokens / 2.0
        flops = _burst_model_flops(config, prompt_len, prefills, total, mean_ctx)
        return {
            "mfu": round(flops / elapsed / max(n_chips, 1) / peak, 4),
            "peak_flops_per_chip": peak,
        }

    if args.phase == "ab":
        tok_s, total, elapsed, done = measure(
            warm_timeout=max(60.0, (args.budget or 900) / 3), drain=False
        )
        _result("ab", {
            "tok_s_per_chip": round(tok_s, 1),
            **mfu_fields(total, elapsed, done),
            **spec_fields(),
            "note": (
                f"{total} tokens in {elapsed:.2f}s on {n_chips} chip(s); kv={kv_layout} "
                f"quant={quantize or 'bf16'}; {done}/{n_requests} done"
            ),
        })
        engine.stop()
        return

    if not args.only_ttft:
        tok_s, total, elapsed, done = measure(
            drain=ttft_on
            or os.environ.get("ACP_BENCH_FLIGHT", "0") == "1"
            or os.environ.get("ACP_BENCH_PROF", "0") == "1"
        )
        _result("headline", {
            "tok_s_per_chip": round(tok_s, 1),
            **mfu_fields(total, elapsed, done),
            **spec_fields(),
            "note": (
                f"{total} tokens in {elapsed:.2f}s on {n_chips} chip(s); preset={preset} "
                f"kv={kv_layout} quant={quantize or 'bf16'} block={block}; "
                f"{done}/{n_requests} requests completed"
                + ("" if done == n_requests else " (deadline hit; partial but honest)")
            ),
        })
    else:
        _mark("warm_done")

    if (
        not args.only_ttft
        and os.environ.get("ACP_BENCH_TOOL_TURN", "0") == "1"
    ):
        try:
            _result("tool_turn", _bench_tool_turn(engine))
        except Exception as e:  # the fixture must not lose the headline
            _result("tool_turn", {"error": str(e)})

    if (
        not args.only_ttft
        and os.environ.get("ACP_BENCH_HOL", "0") == "1"
    ):
        try:
            _result("hol", _bench_hol())
        except Exception as e:  # the fixture must not lose the headline
            _result("hol", {"error": str(e)})

    if (
        not args.only_ttft
        and os.environ.get("ACP_BENCH_MEM", "0") == "1"
    ):
        try:
            _result("mem", _bench_mem())
        except Exception as e:  # the fixture must not lose the headline
            _result("mem", {"error": str(e)})

    if (
        not args.only_ttft
        and os.environ.get("ACP_BENCH_QUANT", "0") == "1"
    ):
        try:
            _result("quant", _bench_quant())
        except Exception as e:  # the fixture must not lose the headline
            _result("quant", {"error": str(e)})

    if (
        not args.only_ttft
        and os.environ.get("ACP_BENCH_FLEET", "0") == "1"
    ):
        try:
            _result("fleet", _bench_fleet())
        except Exception as e:  # the fixture must not lose the headline
            _result("fleet", {"error": str(e)})

    if (
        not args.only_ttft
        and os.environ.get("ACP_BENCH_SCENARIOS", "0") == "1"
    ):
        try:
            _result("scenarios", _bench_scenarios())
        except Exception as e:  # the fixture must not lose the headline
            _result("scenarios", {"error": str(e)})

    if (
        not args.only_ttft
        and os.environ.get("ACP_BENCH_CHAOS", "0") == "1"
    ):
        try:
            _result("chaos", _bench_chaos())
        except Exception as e:  # the fixture must not lose the headline
            _result("chaos", {"error": str(e)})

    if (
        not args.only_ttft
        and os.environ.get("ACP_BENCH_FLIGHT", "0") == "1"
    ):
        try:
            _result("flight", _bench_flight(engine, measure))
        except Exception as e:  # the fixture must not lose the headline
            _result("flight", {"error": str(e)})

    if (
        not args.only_ttft
        and os.environ.get("ACP_BENCH_PROF", "0") == "1"
    ):
        try:
            _result("prof", _bench_prof(engine, measure))
        except Exception as e:  # the fixture must not lose the headline
            _result("prof", {"error": str(e)})

    if (
        not args.only_ttft
        and os.environ.get("ACP_BENCH_MEGASTEP", "0") == "1"
    ):
        try:
            _result("megastep", _bench_megastep())
        except Exception as e:  # the fixture must not lose the headline
            _result("megastep", {"error": str(e)})

    if (
        not args.only_ttft
        and os.environ.get("ACP_BENCH_METAL", "0") == "1"
    ):
        try:
            _result("metal", _bench_metal())
        except Exception as e:  # the fixture must not lose the headline
            _result("metal", {"error": str(e)})

    if ttft_on or args.only_ttft:
        try:
            _result("ttft", _bench_ttft(engine))
        except Exception as e:  # TTFT failure must not lose the headline
            _result("ttft", {"error": str(e)})
    engine.stop()


def _bench_megastep() -> dict:
    """Fused-megastep fixture (ACP_BENCH_MEGASTEP=1): a busy chunked
    engine — N short decoders streaming while L long prompts chunk
    through them — run twice against the same warmed engine, megastep OFF
    (the PR 7 split per-phase dispatches) then ON (one fused program per
    busy cycle). Reported per leg: model-program dispatches per
    chunk-carrying scheduler cycle (the headline this PR exists to cut,
    measured from the PR 12 profiler's program keys against the flight
    recorder's per-cycle prefill_round events), decoder throughput, and
    serving-time cold compiles (the engine is mark_prewarmed() after the
    warm pass, so every first-of-shape in a measured leg is counted — the
    fused shape zoo's real startup cost, not hidden). Generated tokens
    must be byte-identical between the legs.

    Knobs: ACP_BENCH_MEGASTEP_DECODERS (default 6),
    ACP_BENCH_MEGASTEP_PROMPT (1024), ACP_BENCH_MEGASTEP_LONGS (4),
    ACP_BENCH_MEGASTEP_CHUNK (128), ACP_BENCH_MEGASTEP_TAIL_TOKENS (96),
    ACP_BENCH_MEGASTEP_KV_LAYOUT (paged)."""
    import dataclasses

    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
    from agentcontrolplane_tpu.models.llama import PRESETS

    n_dec = int(os.environ.get("ACP_BENCH_MEGASTEP_DECODERS", "6"))
    plen = int(os.environ.get("ACP_BENCH_MEGASTEP_PROMPT", "1024"))
    n_long = int(os.environ.get("ACP_BENCH_MEGASTEP_LONGS", "4"))
    chunk = int(os.environ.get("ACP_BENCH_MEGASTEP_CHUNK", "128"))
    dec_budget = int(os.environ.get("ACP_BENCH_MEGASTEP_TAIL_TOKENS", "96"))
    kv_layout = os.environ.get("ACP_BENCH_MEGASTEP_KV_LAYOUT", "paged")
    max_ctx = plen + 2 * chunk
    cfg = dataclasses.replace(PRESETS["tiny"], max_seq_len=max_ctx, vocab_size=512)
    engine = Engine(
        config=cfg,
        tokenizer=ByteTokenizer(),
        max_slots=n_dec + 2,
        max_ctx=max_ctx,
        prefill_buckets=(64, chunk, plen),
        decode_block_size=4,
        kv_layout=kv_layout,
        page_size=16,
        prefill_chunk=chunk,
        prefix_cache_entries=0,  # leg 2 must not skip leg 1's prefills
        check_invariants=os.environ.get("ACP_INVARIANTS", "") not in ("", "0"),
    )
    engine.start()
    CYCLE_KINDS = (
        "megastep", "chunk", "decode", "spec_verify", "prefill_cont",
        "prefill", "spill",
    )

    def model_dispatches() -> int:
        return sum(
            v["dispatches"]
            for k, v in engine.profiler.stats()["programs"].items()
            if k.split("[")[0] in CYCLE_KINDS
        )

    def chunk_cycles() -> int:
        # prefill_round fires once per scheduler cycle that carried chunk
        # work — the busy-cycle denominator
        return sum(1 for _ in engine.flight.events(kind="prefill_round", last=4096))

    try:
        shorts = [[2 + ((i + j) % 200) for j in range(48)] for i in range(n_dec)]
        longs = [
            [1 + ((i + j) % 250) for j in range(plen - 8 * i)]
            for i in range(n_long)
        ]
        dec_sp = SamplingParams(temperature=0.0, max_tokens=dec_budget)
        one = SamplingParams(temperature=0.0, max_tokens=4)

        def leg(mega_on: bool) -> dict:
            engine.megastep = mega_on
            d0, c0 = model_dispatches(), chunk_cycles()
            cold0 = engine.profiler.stats()["cold_compiles"]["serving"]
            t0 = time.monotonic()
            futs = [engine.submit(list(s), dec_sp) for s in shorts]
            for f in futs:
                f.admitted.result(timeout=1800)
            long_futs = [engine.submit(list(p), one) for p in longs]
            results = [f.result(timeout=1800) for f in futs + long_futs]
            elapsed = time.monotonic() - t0
            toks = sum(len(r.tokens) for r in results)
            cycles = max(1, chunk_cycles() - c0)
            stats = engine.profiler.stats()
            return {
                "dispatches_per_chunk_cycle": round(
                    (model_dispatches() - d0) / cycles, 2
                ),
                "chunk_cycles": cycles,
                "tok_s": round(toks / elapsed, 1),
                "serving_cold_compiles": (
                    stats["cold_compiles"]["serving"] - cold0
                ),
                "tokens": [r.tokens for r in results],
            }

        # warm BOTH paths with the full leg-shaped workload (compiles
        # land outside the measured legs — on CPU a single fused compile
        # would otherwise dominate a leg), then declare prewarm so any
        # REMAINING first-of-shape dispatch in a measured leg is honestly
        # counted as a serving-time cold compile
        for mega_on in (False, True):
            leg(mega_on)
        engine.profiler.mark_prewarmed()

        off = leg(mega_on=False)
        on = leg(mega_on=True)
        identical = off.pop("tokens") == on.pop("tokens")
        reduction = (
            round(off["dispatches_per_chunk_cycle"]
                  / on["dispatches_per_chunk_cycle"], 2)
            if on["dispatches_per_chunk_cycle"] > 0 else 0.0
        )
        return {
            "decoders": n_dec,
            "long_prompts": n_long,
            "prompt_tokens": plen,
            "chunk": chunk,
            "kv_layout": kv_layout,
            "megastep_off": off,
            "megastep_on": on,
            "dispatch_reduction_x": reduction,
            "fused_shapes": len(engine._megastep_shapes),
            "megastep_fallbacks": engine.megastep_fallbacks,
            "byte_identical": identical,
            "note": (
                f"busy chunked cycles pay {on['dispatches_per_chunk_cycle']} "
                f"dispatch(es) fused vs {off['dispatches_per_chunk_cycle']} "
                f"split ({reduction}x fewer); decoder throughput "
                f"{on['tok_s']} vs {off['tok_s']} tok/s; "
                f"{on['serving_cold_compiles']} serving-time cold compiles "
                f"in the fused leg ({len(engine._megastep_shapes)} fused "
                "shapes), byte-identical"
            ),
        }
    finally:
        engine.stop()


def _bench_metal() -> dict:
    """Down-to-the-metal fixture (ACP_BENCH_METAL=1): PR 20's two wins.

    (a) **Swap-in stall, prefetch off vs on**: an oversubscribed paged
    engine (the pressure workload tests/engine/test_prefetch.py pins) —
    preemptions swap KV to the host tier and resumes swap it back over
    several chunked cycles while survivors keep decoding. Reported: the
    p99 of the flight recorder's ``swap_in`` ``stall_s`` (blocked
    host->device copy seconds per restore, the ``host_stall``-attributed
    phase) with ``host_prefetch`` off (every restore chunk pays the
    blocking copy) vs on (chunks past the first commit rows staged a
    cycle early — ``acp_engine_kv_prefetch_commits_total`` counts the
    overlap). Byte-identical by contract.

    (b) **Dispatches per busy cycle with the absorbed phases**: the PR 13
    megastep workload shape (short decoders streaming while long prompts
    chunk through them) re-run with host-KV pool pressure so swap
    round-trips ride the measured window, and with the dispatch count
    now including the residuals PR 20 absorbs — standalone
    ``swap_scatter`` commits and plain ``prefill`` dispatches — split
    (``megastep=False``) vs fused. PR 13 recorded 1.12 with the residuals
    unfused; the fused leg's absolute number is the trend series
    (``metal_dispatches_per_busy_cycle``) and must hold at or under that
    bar. Byte-identical fused vs split.

    Knobs: ACP_BENCH_METAL_TASKS (default 6, part a),
    ACP_BENCH_METAL_KV_PAGES (10, part a), ACP_BENCH_METAL_DECODERS (6),
    ACP_BENCH_METAL_PROMPT (1024), ACP_BENCH_METAL_LONGS (4),
    ACP_BENCH_METAL_CHUNK (64), ACP_BENCH_METAL_TAIL_TOKENS (96)."""
    import dataclasses

    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
    from agentcontrolplane_tpu.models.llama import PRESETS
    from agentcontrolplane_tpu.observability.metrics import REGISTRY

    armed = os.environ.get("ACP_INVARIANTS", "") not in ("", "0")
    # the megastep CYCLE_KINDS plus the dispatches PR 20 absorbs:
    # standalone staged-restore scatters and (paged) plain start-0 prefills
    KINDS = (
        "megastep", "chunk", "decode", "spec_verify", "prefill_cont",
        "prefill", "spill", "swap_scatter",
    )

    def dispatches(eng) -> int:
        return sum(
            v["dispatches"]
            for k, v in eng.profiler.stats()["programs"].items()
            if k.split("[")[0] in KINDS
        )

    def chunk_cycles(eng) -> int:
        # prefill_round fires once per scheduler cycle that carried chunk
        # work (restore rounds included) — the busy-cycle denominator
        return sum(1 for _ in eng.flight.events(kind="prefill_round", last=4096))

    def commits() -> float:
        m = REGISTRY._metrics.get("acp_engine_kv_prefetch_commits_total")
        return 0.0 if m is None else m.values.get((), 0.0)

    def p99_ms(stalls: list[float]) -> float:
        if not stalls:
            return 0.0
        s = sorted(stalls)
        return round(s[min(len(s) - 1, int(0.99 * len(s)))] * 1e3, 2)

    # -- (a) swap-in stall p99: prefetch off vs on --------------------------
    n_req = int(os.environ.get("ACP_BENCH_METAL_TASKS", "6"))
    kv_pages = int(os.environ.get("ACP_BENCH_METAL_KV_PAGES", "10"))
    cfg = dataclasses.replace(
        PRESETS["tiny"], vocab_size=512, max_seq_len=256, n_kv_heads=2
    )
    eng = Engine(
        config=cfg,
        tokenizer=ByteTokenizer(),
        max_slots=4,
        max_ctx=64,
        prefill_buckets=(32, 64),
        decode_block_size=4,
        kv_layout="paged",
        page_size=8,
        kv_pages=kv_pages,
        host_kv_bytes=1 << 22,
        prefill_chunk=16,
        prefix_cache_entries=0,  # later legs must not skip earlier prefills
        check_invariants=armed,
    )
    eng.start()
    try:
        prompts = [[10 + i] * 20 for i in range(n_req)]
        sp = SamplingParams(temperature=0.0, max_tokens=12)
        solo = [eng.generate(list(p), sp).tokens for p in prompts]

        rounds = int(os.environ.get("ACP_BENCH_METAL_ROUNDS", "4"))

        def stall_leg(prefetch_on: bool, n_rounds: int = rounds) -> dict:
            # several pressure rounds per leg: each round forms ~1 swap
            # round-trip, and the p99 needs a population, not one sample
            eng.host_prefetch = prefetch_on
            t0 = time.monotonic()
            k0, s0 = commits(), eng.kv_swap_ins
            toks = []
            for _ in range(n_rounds):
                with eng.hold_admission():
                    futs = [eng.submit(list(p), sp) for p in prompts]
                toks.append([f.result(timeout=1800).tokens for f in futs])
            stalls = [
                e["detail"]["stall_s"]
                for e in eng.flight.events(kind="swap_in", last=4096)
                if e["t"] >= t0
            ]
            return {
                "tokens": toks,
                "stall_p99_ms": p99_ms(stalls),
                "swap_ins": eng.kv_swap_ins - s0,
                "commits": int(commits() - k0),
            }

        stall_leg(False, 1)  # warm both paths' shapes outside the measurement
        stall_leg(True, 1)
        s_off = stall_leg(False)
        s_on = stall_leg(True)
        stall_identical = all(
            rt == solo for rt in s_off["tokens"] + s_on["tokens"]
        )
        reduction = (
            round(s_off["stall_p99_ms"] / s_on["stall_p99_ms"], 2)
            if s_on["stall_p99_ms"] > 0 else 0.0
        )
        swap_part = {
            "tasks": n_req,
            "kv_pages": kv_pages,
            "prefetch_off_p99_ms": s_off["stall_p99_ms"],
            "prefetch_on_p99_ms": s_on["stall_p99_ms"],
            "stall_reduction_x": reduction,
            "swap_ins_off": s_off["swap_ins"],
            "swap_ins_on": s_on["swap_ins"],
            "prefetch_commits": s_on["commits"],
            "byte_identical": stall_identical,
        }
    finally:
        eng.stop()

    # -- (b) dispatches per busy cycle, split vs fused, absorbed phases -----
    from agentcontrolplane_tpu.testing import FAULTS

    n_dec = int(os.environ.get("ACP_BENCH_METAL_DECODERS", "6"))
    plen = int(os.environ.get("ACP_BENCH_METAL_PROMPT", "1024"))
    n_long = int(os.environ.get("ACP_BENCH_METAL_LONGS", "4"))
    chunk = int(os.environ.get("ACP_BENCH_METAL_CHUNK", "64"))
    dec_budget = int(os.environ.get("ACP_BENCH_METAL_TAIL_TOKENS", "96"))
    page = 16
    max_ctx = plen + 2 * chunk
    # comfortable pool (organic pressure preemption would be timing-shaped);
    # swap round-trips are injected DETERMINISTICALLY instead: each leg arms
    # ``engine.force_preempt`` mid-decode, so two decoders swap out to the
    # host tier and restore over chunked cycles while the longs keep
    # chunking — the staged scatter commits ride the measured busy cycles
    need = n_dec * ((48 + dec_budget) // page + 1) + n_long * (max_ctx // page)
    cfg = dataclasses.replace(PRESETS["tiny"], max_seq_len=max_ctx, vocab_size=512)
    eng = Engine(
        config=cfg,
        tokenizer=ByteTokenizer(),
        max_slots=n_dec + 2,
        max_ctx=max_ctx,
        prefill_buckets=(64, chunk, plen),
        decode_block_size=4,
        kv_layout="paged",
        page_size=page,
        kv_pages=need + 8,
        host_kv_bytes=64 << 20,
        prefill_chunk=chunk,
        prefix_cache_entries=0,
        check_invariants=armed,
    )
    eng.start()
    try:
        shorts = [[2 + ((i + j) % 200) for j in range(48)] for i in range(n_dec)]
        longs = [
            [1 + ((i + j) % 250) for j in range(plen - 8 * i)]
            for i in range(n_long)
        ]
        dec_sp = SamplingParams(temperature=0.0, max_tokens=dec_budget)
        one = SamplingParams(temperature=0.0, max_tokens=4)

        def dispatch_leg(mega_on: bool) -> dict:
            eng.megastep = mega_on
            d0, c0, s0 = dispatches(eng), chunk_cycles(eng), eng.kv_swap_ins
            futs = [eng.submit(list(s), dec_sp) for s in shorts]
            for f in futs:
                f.admitted.result(timeout=1800)
            # victims at ~10 decode blocks in carry 80+ rows: the restore
            # is multi-chunk, so its later chunks stage and absorb
            FAULTS.arm(
                "engine.force_preempt", after_steps=eng.decode_steps + 10,
                times=2,
            )
            long_futs = [eng.submit(list(p), one) for p in longs]
            results = [f.result(timeout=1800) for f in futs + long_futs]
            FAULTS.reset()
            cycles = max(1, chunk_cycles(eng) - c0)
            return {
                "tokens": [r.tokens for r in results],
                "per_cycle": round((dispatches(eng) - d0) / cycles, 2),
                "busy_cycles": cycles,
                "swap_ins": eng.kv_swap_ins - s0,
            }

        for mega_on in (False, True):  # compiles land outside the legs
            dispatch_leg(mega_on)
        eng.profiler.mark_prewarmed()

        d_off = dispatch_leg(mega_on=False)
        d_on = dispatch_leg(mega_on=True)
        dispatch_identical = d_off["tokens"] == d_on["tokens"]
        dispatch_part = {
            "decoders": n_dec,
            "long_prompts": n_long,
            "prompt_tokens": plen,
            "chunk": chunk,
            "kv_pages": need + 8,
            "split_per_busy_cycle": d_off["per_cycle"],
            "dispatches_per_busy_cycle": d_on["per_cycle"],
            "busy_cycles": d_on["busy_cycles"],
            "swap_ins": d_on["swap_ins"],
            "within_pr13_bar": d_on["per_cycle"] <= 1.12,
            "byte_identical": dispatch_identical,
        }
    finally:
        eng.stop()

    return {
        "swap_stall": swap_part,
        "dispatch": dispatch_part,
        "note": (
            f"swap-in stall p99 {swap_part['prefetch_on_p99_ms']}ms "
            f"prefetch-on vs {swap_part['prefetch_off_p99_ms']}ms off "
            f"({swap_part['stall_reduction_x']}x; "
            f"{swap_part['prefetch_commits']} staged commits landed); busy "
            f"cycles pay {dispatch_part['dispatches_per_busy_cycle']} "
            f"dispatch(es) with absorbed swap/plain phases vs "
            f"{dispatch_part['split_per_busy_cycle']} split "
            f"({dispatch_part['swap_ins']} swap round-trips in-window, "
            "PR 13 bar 1.12), both byte-identical"
        ),
    }


def _bench_tool_turn(engine) -> dict:
    """Multi-tool-turn fixture (overlapped tool execution): one turn whose
    generation closes TWO independent tool calls up front and then decodes
    ~50 further tokens. Overlap OFF reproduces the pre-overlap control
    plane — wait for the whole completion, then execute the calls
    sequentially; overlap ON dispatches each call the moment its braces
    close and executes them in parallel while decode continues. Reported
    latency is submit -> (generation done AND all tool results in). The
    generated text must be byte-identical between the modes — overlap
    moves when execution starts, never what is generated. Both legs run
    against the same warmed engine and an identical prompt (equal
    prefix-cache treatment), so the delta isolates tool scheduling.

    Knobs: ACP_BENCH_TOOL_TURN_TOOL_S (per-tool seconds, default 0.1),
    ACP_BENCH_TOOL_TURN_TAIL_TOKENS (decode tail, default 50)."""
    import threading

    from agentcontrolplane_tpu.engine.engine import SamplingParams

    tool_s = float(os.environ.get("ACP_BENCH_TOOL_TURN_TOOL_S", "0.1"))
    tail = int(os.environ.get("ACP_BENCH_TOOL_TURN_TAIL_TOKENS", "50"))
    calls = (
        '{"name": "web__fetch", "arguments": {"url": "https://a.test"}} '
        '{"name": "db__query", "arguments": {"sql": "select 1"}}'
    )
    sp = SamplingParams(
        temperature=0.0, max_tokens=tail,
        forced_prefix=tuple(engine.tokenizer.encode(calls)),
    )
    prompt = [1 + (i % 250) for i in range(63)]

    # warm: compiles the shapes and seeds the prefix cache so BOTH legs
    # see identical cache treatment
    engine.submit(list(prompt), sp).result(600)

    # overlap OFF: full completion, then the two tools back to back
    t0 = time.monotonic()
    r_off = engine.submit(list(prompt), sp).result(600)
    time.sleep(tool_s)
    time.sleep(tool_s)
    off_s = time.monotonic() - t0

    # overlap ON: execute each call the moment it closes, in parallel
    threads: list = []

    def on_tool_call(_idx, _tc):
        th = threading.Thread(target=time.sleep, args=(tool_s,), daemon=True)
        th.start()
        threads.append(th)

    t0 = time.monotonic()
    fut = engine.submit(list(prompt), sp, on_tool_call=on_tool_call, park=True)
    r_on = fut.result(600)
    for th in threads:
        th.join(timeout=60)
    on_s = time.monotonic() - t0

    saved_pct = round(100.0 * (1.0 - on_s / off_s), 1) if off_s > 0 else 0.0
    return {
        "tool_s": tool_s,
        "tail_tokens": tail,
        "calls": 2,
        "early_dispatched": len(threads),
        "overlap_off_ms": round(off_s * 1e3, 1),
        "overlap_on_ms": round(on_s * 1e3, 1),
        "saved_pct": saved_pct,
        "byte_identical": r_on.tokens == r_off.tokens and r_on.text == r_off.text,
        "note": (
            f"2 independent ~{tool_s * 1e3:.0f}ms tool calls emitted before a "
            f"{tail}-token decode tail: overlap-on {on_s * 1e3:.0f}ms vs "
            f"overlap-off {off_s * 1e3:.0f}ms ({saved_pct}% saved); "
            "generated text byte-identical"
        ),
    }


def _ab_overhead_legs(set_enabled, measure, legs: int) -> tuple[float, float, float]:
    """The interleaved on/off overhead protocol shared by the flight and
    profiler guards: one discarded warm-up pair (interpreter/allocator
    settling drifts the first CPU legs by 10-30%, swamping a 2% signal),
    then ``legs`` pairs with alternating mode order so residual monotone
    drift taxes both modes symmetrically, medians per mode (CPU legs are
    noisy), percent overhead. The caller owns saving/restoring the real
    enabled state around this."""
    on_s: list[float] = []
    off_s: list[float] = []
    set_enabled(True)
    measure(drain=True)
    set_enabled(False)
    measure(drain=True)
    for i in range(legs):
        order = (True, False) if i % 2 == 0 else (False, True)
        for enabled in order:
            set_enabled(enabled)
            (on_s if enabled else off_s).append(measure(drain=True)[0])
    on = sorted(on_s)[len(on_s) // 2]
    off = sorted(off_s)[len(off_s) // 2]
    overhead_pct = round(100.0 * (1.0 - on / off), 2) if off > 0 else 0.0
    return on, off, overhead_pct


def _bench_flight(engine, measure) -> dict:
    """Flight-recorder overhead guard (ACP_BENCH_FLIGHT=1): re-run the
    HEADLINE burst twice on the same warmed engine — recorder on (the
    always-on default) vs `flight.enabled=False` (the `ACP_FLIGHT=0`
    posture) — and report the throughput delta. The recorder's contract is
    <2% on this fixture: it records at dispatch granularity (one short
    lock + deque append per decode block / chunk / lifecycle edge, never
    per token), so its cost must vanish against the jitted dispatches.
    Legs interleave on/off to cancel slow drift; each leg drains before
    the next so the engine is idle at every start."""
    legs = max(1, int(os.environ.get("ACP_BENCH_FLIGHT_LEGS", "2")))
    was_enabled = engine.flight.enabled
    ev0 = engine.flight.stats()["recorded_total"]
    try:

        def set_enabled(v: bool) -> None:
            engine.flight.enabled = v

        on, off, overhead_pct = _ab_overhead_legs(set_enabled, measure, legs)
    finally:
        engine.flight.enabled = was_enabled
    events = engine.flight.stats()["recorded_total"] - ev0
    # the direct measurement the A/B legs bound from above: per-event
    # record() cost x events-per-burst is the recorder's whole bill
    engine.flight.enabled = True
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        engine.flight.record("decode_block", width=1, steps=1, active=1)
    per_event_us = (time.perf_counter() - t0) / n * 1e6
    engine.flight.enabled = was_enabled
    return {
        "legs": legs,
        "recorder_on_tok_s_per_chip": round(on, 1),
        "recorder_off_tok_s_per_chip": round(off, 1),
        "overhead_pct": overhead_pct,
        "within_2pct": overhead_pct < 2.0,
        "events_recorded": events,
        "record_cost_us_per_event": round(per_event_us, 2),
        "note": (
            f"headline burst, recorder on {on:.1f} vs off {off:.1f} "
            f"tok/s/chip (median of {legs} interleaved leg pair(s), one "
            f"warm-up pair discarded): {overhead_pct:+.2f}% overhead "
            f"(contract: < 2%); direct record() cost "
            f"{per_event_us:.2f}us/event at dispatch granularity"
        ),
    }


def _bench_prof(engine, measure) -> dict:
    """Dispatch-profiler overhead guard (ACP_BENCH_PROF=1): re-run the
    HEADLINE burst with the compute efficiency observatory on (the
    always-on default) vs ``profiler.enabled=False`` (the ``ACP_PROF=0``
    posture) and report the throughput delta — the same interleaved-legs
    protocol as the flight guard (_bench_flight), same <2%-on-this-fixture
    contract: the profiler records at dispatch granularity (one short lock
    + one registry observation per jitted dispatch, block_until_ready only
    on sampled legs), so its cost must vanish against the dispatches it
    measures. Also emits the measured burst's goodput ratio and top waste
    causes — the numbers the observatory exists to produce."""
    legs = max(1, int(os.environ.get("ACP_BENCH_PROF_LEGS", "2")))
    was_enabled = engine.profiler.enabled
    try:

        def set_enabled(v: bool) -> None:
            engine.profiler.enabled = v

        on, off, overhead_pct = _ab_overhead_legs(set_enabled, measure, legs)
        # the goodput numbers must describe the MEASURED burst, not the
        # engine's whole life (prewarm + other fixtures would pollute the
        # ratio, and off legs don't account at all — the trend sentinel
        # gates on this number): one more profiled burst bracketed by
        # ledger snapshots gives the clean window delta
        engine.profiler.enabled = True
        led0 = engine.profiler.ledger()
        measure(drain=True)
        led1 = engine.profiler.ledger()
        perf = engine.profiler.stats()
    finally:
        engine.profiler.enabled = was_enabled
    computed = led1["computed"] - led0["computed"]
    goodput = led1["goodput"] - led0["goodput"]
    ratio = round(goodput / computed, 4) if computed else 1.0
    waste = {
        k: led1["waste"][k] - led0["waste"].get(k, 0)
        for k in led1["waste"]
        if led1["waste"][k] - led0["waste"].get(k, 0)
    }
    top_waste = dict(sorted(waste.items(), key=lambda kv: -kv[1])[:3])
    return {
        "legs": legs,
        "profiler_on_tok_s_per_chip": round(on, 1),
        "profiler_off_tok_s_per_chip": round(off, 1),
        "overhead_pct": overhead_pct,
        "within_2pct": overhead_pct < 2.0,
        "goodput_ratio": ratio,
        "tokens_computed": computed,
        "top_waste": top_waste,
        "programs_profiled": len(perf["programs"]),
        "note": (
            f"headline burst, profiler on {on:.1f} vs off {off:.1f} "
            f"tok/s/chip (median of {legs} interleaved leg pair(s), one "
            f"warm-up pair discarded): {overhead_pct:+.2f}% overhead "
            f"(contract: < 2%); goodput ratio {ratio:.3f} over "
            f"{computed} computed token positions in one profiled burst, "
            f"top waste {top_waste}"
        ),
    }


def _bench_hol() -> dict:
    """Head-of-line-blocking fixture (chunked prefill): one long prompt is
    admitted while N short slots decode. Chunked OFF reproduces the
    monolithic at-admission prefill — every decoding slot stalls for the
    whole prefill; chunked ON co-schedules prefill chunks with decode
    blocks under the unified token budget, so each stall is one chunk
    long. Reported per leg: the decoders' inter-commit decode-stall
    p50/p99 and the latecomer's time-to-first-token. Generated tokens must
    be byte-identical between the legs (chunking moves WHEN prompt KV is
    written, never what is sampled).

    Builds its own tiny-config engine so the ~4k-token prefill is
    CPU-tractable; both legs share it (``prefill_chunk`` is a mutable
    knob, and the chunk loop dispatches the same continuation shapes the
    legacy spill path compiles — no cold compiles inside a measured leg
    after the warm pass). Knobs: ACP_BENCH_HOL_PROMPT (default 4096),
    ACP_BENCH_HOL_DECODERS (8), ACP_BENCH_HOL_CHUNK (256),
    ACP_BENCH_HOL_TAIL_TOKENS (per-decoder budget, default 96),
    ACP_BENCH_HOL_KV_LAYOUT (slot)."""
    import dataclasses

    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
    from agentcontrolplane_tpu.models.llama import PRESETS

    plen = int(os.environ.get("ACP_BENCH_HOL_PROMPT", "4096"))
    n_dec = int(os.environ.get("ACP_BENCH_HOL_DECODERS", "8"))
    chunk = int(os.environ.get("ACP_BENCH_HOL_CHUNK", "256"))
    dec_budget = int(os.environ.get("ACP_BENCH_HOL_TAIL_TOKENS", "96"))
    kv_layout = os.environ.get("ACP_BENCH_HOL_KV_LAYOUT", "slot")
    max_ctx = plen + 2 * chunk
    cfg = dataclasses.replace(PRESETS["tiny"], max_seq_len=max_ctx, vocab_size=512)
    engine = Engine(
        config=cfg,
        tokenizer=ByteTokenizer(),
        max_slots=n_dec + 1,
        max_ctx=max_ctx,
        prefill_buckets=(64, chunk),
        decode_block_size=4,
        kv_layout=kv_layout,
        page_size=16,
        # the cache would let leg 2 skip the long prefill leg 1 measured
        prefix_cache_entries=0,
        # opt-in per-dispatch state audits (see module docstring)
        check_invariants=os.environ.get("ACP_INVARIANTS", "") not in ("", "0"),
    )
    engine.start()
    try:
        long_prompt = [1 + (i % 250) for i in range(plen)]
        shorts = [[2 + ((i + j) % 200) for j in range(48)] for i in range(n_dec)]
        dec_sp = SamplingParams(temperature=0.0, max_tokens=dec_budget)
        one = SamplingParams(temperature=0.0, max_tokens=4)

        # warm: compiles every shape both legs hit (short-burst prefill,
        # all decay widths, the chunk/spill continuation at the chunk
        # bucket, the long final) — stalls measured below are serving, not
        # compiles
        warm = [
            engine.submit(list(s), SamplingParams(temperature=0.0, max_tokens=5))
            for s in shorts
        ]
        warm.append(engine.submit(list(long_prompt), one))
        for f in warm:
            f.result(timeout=1800)

        def leg(chunk_on: bool) -> dict:
            engine.prefill_chunk = chunk if chunk_on else 0
            arrivals: list[list[float]] = [[] for _ in range(n_dec)]
            futs = [
                engine.submit(
                    list(shorts[i]), dec_sp,
                    on_tokens=(
                        lambda toks, a=arrivals[i]: a.append(time.monotonic())
                    ),
                )
                for i in range(n_dec)
            ]
            deadline = time.monotonic() + 300
            while any(not a for a in arrivals) and time.monotonic() < deadline:
                time.sleep(0.002)  # all decoders streaming before the latecomer
            t_sub = time.monotonic()
            r_long = engine.submit(list(long_prompt), one).result(timeout=1800)
            dec_results = [f.result(timeout=1800) for f in futs]
            # stall percentiles over ONLY the gaps overlapping the
            # latecomer's submit -> first-token window (its prefill) —
            # pre-latecomer and post-prefill gaps are ordinary decode
            # cadence and would dilute the p50 toward "no stall"
            t_first = t_sub + r_long.ttft_ms / 1e3
            gaps = sorted(
                b - a
                for arr in arrivals
                for a, b in zip(arr, arr[1:])
                if b > t_sub and a < t_first
            )
            pick = lambda q: (
                gaps[min(len(gaps) - 1, int(q * len(gaps)))] if gaps else 0.0
            )
            return {
                "stall_p50_ms": round(pick(0.50) * 1e3, 1),
                "stall_p99_ms": round(pick(0.99) * 1e3, 1),
                "latecomer_ttft_ms": round(r_long.ttft_ms, 1),
                "tokens": [r.tokens for r in dec_results] + [r_long.tokens],
            }

        off = leg(chunk_on=False)
        on = leg(chunk_on=True)
        identical = on.pop("tokens") == off.pop("tokens")
        reduction = (
            round(off["stall_p99_ms"] / on["stall_p99_ms"], 2)
            if on["stall_p99_ms"] > 0 else 0.0
        )
        return {
            "prompt_tokens": plen,
            "decoders": n_dec,
            "chunk": chunk,
            "kv_layout": kv_layout,
            "chunked_off": off,
            "chunked_on": on,
            "stall_p99_reduction_x": reduction,
            "byte_identical": identical,
            "note": (
                f"{plen}-token latecomer vs {n_dec} decoders: decode-stall "
                f"p99 {off['stall_p99_ms']:.0f}ms chunked-off -> "
                f"{on['stall_p99_ms']:.0f}ms chunked-on ({reduction}x); "
                f"latecomer TTFT {off['latecomer_ttft_ms']:.0f}ms -> "
                f"{on['latecomer_ttft_ms']:.0f}ms; byte-identical={identical}"
            ),
        }
    finally:
        engine.stop()


def _bench_mem() -> dict:
    """KV memory-tier fixture (ACP_BENCH_MEM=1) — the two capacity
    multipliers from docs/serving-engine.md "KV memory tiers":

    (a) **swap vs recompute**: one request with a long prompt is forcibly
    preempted mid-decode; its resume either swaps the KV back from the
    host tier (host_kv_bytes on) or re-runs the whole prefill (off). The
    flight recorder's preempt -> resume-prefill_done window is the
    resume latency each way; the ratio is the recompute tax the host tier
    kills. Byte-identical across both legs and the unpreempted run.

    (b) **effective slots under shared-prefix dedup**: N tasks sharing
    one long persona prompt burst into a page pool deliberately too small
    for N private prefix copies. Dedup off (today) admits what fits and
    serializes the rest; dedup on shares one copy of the persona pages.
    Reported: peak concurrently-admitted slots each way. Byte-identical.

    Both parts build their own tiny-config engines so the long prefills
    are CPU-tractable. Knobs: ACP_BENCH_MEM_PROMPT (default 4096),
    ACP_BENCH_MEM_TASKS (8), ACP_BENCH_MEM_PERSONA (512),
    ACP_BENCH_MEM_HOST_BYTES (256 MiB)."""
    import dataclasses

    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
    from agentcontrolplane_tpu.models.llama import PRESETS
    from agentcontrolplane_tpu.testing import FAULTS

    plen = int(os.environ.get("ACP_BENCH_MEM_PROMPT", "4096"))
    n_tasks = int(os.environ.get("ACP_BENCH_MEM_TASKS", "8"))
    persona_len = int(os.environ.get("ACP_BENCH_MEM_PERSONA", "512"))
    host_bytes = int(os.environ.get("ACP_BENCH_MEM_HOST_BYTES", str(256 << 20)))
    page = 16
    armed = os.environ.get("ACP_INVARIANTS", "") not in ("", "0")

    def build(max_ctx, kv_pages, **kw):
        cfg = dataclasses.replace(
            PRESETS["tiny"], max_seq_len=max_ctx, vocab_size=512
        )
        eng = Engine(
            config=cfg,
            tokenizer=ByteTokenizer(),
            max_ctx=max_ctx,
            prefill_buckets=(64, 256),
            decode_block_size=4,
            kv_layout="paged",
            page_size=page,
            kv_pages=kv_pages,
            # the prefix cache would let later legs skip the prefills the
            # earlier legs measured — this fixture isolates the NEW tiers
            prefix_cache_entries=0,
            check_invariants=armed,
            **kw,
        )
        eng.start()
        return eng

    # -- (a) preempt -> resume: swap-in vs recompute-prefill ----------------
    max_ctx = plen + 256
    eng = build(max_ctx, kv_pages=plen // page + 64, max_slots=2,
                host_kv_bytes=host_bytes)
    try:
        prompt = [1 + (i % 250) for i in range(plen)]
        sp = SamplingParams(temperature=0.0, max_tokens=24)
        base = eng.generate(list(prompt), sp)  # also warms every shape

        def preempt_leg(swap_on: bool) -> tuple[list, float]:
            eng.set_host_kv_bytes(host_bytes if swap_on else 0)
            FAULTS.arm("engine.force_preempt", after_steps=2)
            fut = eng.submit(list(prompt), sp)
            r = fut.result(timeout=1800)
            FAULTS.reset()
            assert r.preempt_count >= 1, "fixture failed to preempt"
            tl = eng.flight.timeline(fut.rid) or []
            t_pre = next(e["t"] for e in tl if e["kind"] == "preempt")
            t_res = next(
                e["t"] for e in tl if e["kind"] == "prefill_done" and e["t"] > t_pre
            )
            return r.tokens, (t_res - t_pre) * 1e3

        # warm both resume paths (restore-scatter jits compile here, and
        # the recompute leg's spill shapes are warm from `base`)
        preempt_leg(True)
        preempt_leg(False)
        toks_on, resume_on_ms = preempt_leg(True)
        toks_off, resume_off_ms = preempt_leg(False)
        swap_identical = toks_on == toks_off == base.tokens
        speedup = round(resume_off_ms / resume_on_ms, 2) if resume_on_ms > 0 else 0.0
        swap_part = {
            "prompt_tokens": plen,
            "resume_swap_ms": round(resume_on_ms, 1),
            "resume_recompute_ms": round(resume_off_ms, 1),
            "swap_speedup_x": speedup,
            "swap_ins": eng.kv_swap_ins,
            "byte_identical": swap_identical,
        }
    finally:
        eng.stop()

    # -- (b) effective slots: shared-persona burst, dedup on/off ------------
    persona = [3 + (i % 200) for i in range(persona_len)]
    tails = [[7 + i, 9 + i, 11 + i, 13 + i] for i in range(n_tasks)]
    # pool sized so ONE persona copy + per-task suffixes fit, N private
    # copies do not: persona pages + per-task (suffix + decode + slack)
    kv_pages = persona_len // page + n_tasks * 6 + 1
    eng = build(max_ctx=1024, kv_pages=kv_pages, max_slots=n_tasks,
                park_max_s=0.0)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=16)
        solo = {}
        for i, t in enumerate(tails):
            solo[i] = eng.generate(persona + t, sp).tokens

        def burst_leg(dedup: bool) -> tuple[dict, int, int]:
            eng.prefix_dedup = dedup
            peak = [0]
            shared_peak = [0]

            def on_tokens(_toks):
                s = eng.stats()
                peak[0] = max(peak[0], s["active_slots"] + s["prefilling_slots"])
                shared_peak[0] = max(
                    shared_peak[0], s["memory"]["prefix_dedup"]["shared_pages"]
                )

            with eng.hold_admission():
                futs = [
                    eng.submit(persona + t, sp, on_tokens=on_tokens)
                    for t in tails
                ]
            toks = {i: f.result(timeout=1800).tokens for i, f in enumerate(futs)}
            return toks, peak[0], shared_peak[0]

        toks_off, slots_off, _ = burst_leg(False)
        toks_on, slots_on, shared_pages_peak = burst_leg(True)
        dedup_identical = toks_on == toks_off == solo
        ratio = round(slots_on / slots_off, 2) if slots_off else 0.0
        dedup_part = {
            "tasks": n_tasks,
            "persona_tokens": persona_len,
            "kv_pages": kv_pages - 1,
            "effective_slots_dedup_off": slots_off,
            "effective_slots_dedup_on": slots_on,
            "slot_capacity_x": ratio,
            "shared_pages_peak": shared_pages_peak,
            "byte_identical": dedup_identical,
        }
    finally:
        eng.stop()

    return {
        "swap": swap_part,
        "dedup": dedup_part,
        "note": (
            f"preempt->resume on a {plen}-token prompt: swap-in "
            f"{swap_part['resume_swap_ms']:.0f}ms vs recompute "
            f"{swap_part['resume_recompute_ms']:.0f}ms "
            f"({swap_part['swap_speedup_x']}x); {n_tasks} tasks sharing a "
            f"{persona_len}-token persona at {kv_pages - 1} pages: "
            f"{slots_off} -> {slots_on} concurrent slots "
            f"({ratio}x); byte-identical="
            f"{swap_identical and dedup_identical}"
        ),
    }


def _bench_scenarios() -> dict:
    """Scenario factory fixture (ACP_BENCH_SCENARIOS=1): replay the whole
    scenario library (scenarios/library.py) against a single engine and a
    2-replica fleet pool, recording each run's SLO percentile summary
    under ``scenarios.<name>.<single|fleet>`` — the blocks
    ``--slo-envelopes`` gates and ``--bench-trend`` trends.

    The single arm also replays the persona storm twice and records the
    ``byte_identical`` verdict (the replay-determinism contract the
    scenario tests pin per KV layout).

    Fault scenarios arm the global switchboard from the trace itself; the
    fleet arm's cocktail crashes replica ``r1`` mid-run, so it runs LAST
    and the pool is torn down right after. Knobs:
    ACP_BENCH_SCENARIO_SPEED (1.0), ACP_BENCH_SCENARIO_N (0 = library
    defaults)."""
    import dataclasses

    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
    from agentcontrolplane_tpu.faults import FAULTS
    from agentcontrolplane_tpu.fleet import FleetRouter
    from agentcontrolplane_tpu.kernel import Store
    from agentcontrolplane_tpu.models.llama import PRESETS
    from agentcontrolplane_tpu.scenarios import SCENARIOS, byte_identical, replay

    speed = float(os.environ.get("ACP_BENCH_SCENARIO_SPEED", "1.0"))
    n = int(os.environ.get("ACP_BENCH_SCENARIO_N", "0"))
    armed = os.environ.get("ACP_INVARIANTS", "") not in ("", "0")

    def build():
        cfg = dataclasses.replace(
            PRESETS["tiny"], max_seq_len=512, vocab_size=512
        )
        eng = Engine(
            config=cfg,
            tokenizer=ByteTokenizer(),
            max_ctx=256,
            prefill_buckets=(32, 64, 128),
            decode_block_size=4,
            kv_layout="paged",
            page_size=16,
            max_slots=4,
            check_invariants=armed,
        )
        eng.start()
        return eng

    def traces(crash_replica: str = "") -> list[tuple[str, dict]]:
        out = []
        for name, gen in SCENARIOS.items():
            kw = {"n": n} if n > 0 else {}
            if name == "fault_cocktail" and crash_replica:
                kw["crash_replica"] = crash_replica
            out.append((name, gen(**kw)))
        # the cocktail (and any replica crash it carries) goes last
        out.sort(key=lambda p: p[0] == "fault_cocktail")
        return out

    out: dict = {}

    # -- single-engine arm -------------------------------------------------
    engine = build()
    try:
        engine.prewarm(constrained=True)
        for name, trace in traces():
            report = replay(trace, engine, speed=speed, scenario=name)
            out.setdefault(name, {})["single"] = report.slo_doc()
            FAULTS.reset()
        storm = SCENARIOS["persona_storm"](**({"n": n} if n > 0 else {}))
        a = replay(storm, engine, speed=speed, scenario="persona_storm")
        b = replay(storm, engine, speed=speed, scenario="persona_storm")
        out["persona_storm"]["single"]["byte_identical"] = byte_identical(a, b)
    finally:
        engine.stop()

    # -- fleet arm ---------------------------------------------------------
    router = FleetRouter(store=Store(), heartbeat_interval=60.0)
    engines = [build() for _ in range(2)]
    for i, eng in enumerate(engines):
        router.add_replica(f"r{i}", eng)
    try:
        for name, trace in traces(crash_replica="r1"):
            report = replay(trace, router, speed=speed, scenario=name)
            out.setdefault(name, {})["fleet"] = report.slo_doc()
            FAULTS.reset()
    finally:
        router.stop()
        for eng in engines:
            try:
                eng.stop()
            except Exception:
                pass
    return out


def _bench_chaos() -> dict:
    """Gray-failure fixture (ACP_BENCH_CHAOS=1) — the robustness claims
    PR 19 makes measurable:

    - **hedging arm** — a 3-replica tiny fleet with ``engine.slow_cycle``
      pinned to ``r0`` (replica-scoped match) replays the persona storm
      twice: hedging OFF (requests homed to the gray replica ride it to
      the end) and hedging ON (the router's per-request watchdog
      re-dispatches stuck requests onto a healthy replica). Recorded:
      both arms' full SLO docs, the stuck-request tail ratio
      ``e2e_p99_improvement`` (off/on — >1 means hedging cut the tail),
      the hedge count, and the ``byte_identical`` verdict (a hedged
      winner must stream exactly what the unhedged run produced).
    - **chaos arm** — one seeded conductor run (``scenarios/chaos.py``)
      against a fresh fleet: the full cocktail lands and the invariant
      verdict (conservation, exactly-once streams, zero errors) is
      recorded — ``ok: true`` is the gate claim CI's chaos smoke pins.

    Knobs: ACP_BENCH_CHAOS_SPEED (10), ACP_BENCH_CHAOS_N (0 = library
    default), ACP_BENCH_CHAOS_DELAY_S (0.3 — must clear the engines'
    ``stall_min_s`` or throttled cycles never register as stalls),
    ACP_BENCH_CHAOS_TIMES (200), ACP_BENCH_CHAOS_HEDGE_S (0.3),
    ACP_BENCH_CHAOS_SEED (0)."""
    import dataclasses

    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
    from agentcontrolplane_tpu.faults import FAULTS
    from agentcontrolplane_tpu.fleet import FleetRouter
    from agentcontrolplane_tpu.kernel import Store
    from agentcontrolplane_tpu.models.llama import PRESETS
    from agentcontrolplane_tpu.scenarios import (
        SCENARIOS,
        byte_identical,
        replay,
        run_chaos,
    )

    speed = float(os.environ.get("ACP_BENCH_CHAOS_SPEED", "10"))
    n = int(os.environ.get("ACP_BENCH_CHAOS_N", "0"))
    delay_s = float(os.environ.get("ACP_BENCH_CHAOS_DELAY_S", "0.3"))
    times = int(os.environ.get("ACP_BENCH_CHAOS_TIMES", "200"))
    hedge_s = float(os.environ.get("ACP_BENCH_CHAOS_HEDGE_S", "0.3"))
    seed = int(os.environ.get("ACP_BENCH_CHAOS_SEED", "0"))
    armed = os.environ.get("ACP_INVARIANTS", "") not in ("", "0")
    storm_kw = {"n": n} if n > 0 else {}

    def build_engine():
        cfg = dataclasses.replace(
            PRESETS["tiny"], max_seq_len=512, vocab_size=512
        )
        eng = Engine(
            config=cfg,
            tokenizer=ByteTokenizer(),
            max_ctx=256,
            prefill_buckets=(32, 64, 128),
            decode_block_size=4,
            kv_layout="paged",
            page_size=16,
            max_slots=4,
            check_invariants=armed,
        )
        eng.start()
        eng.prewarm(constrained=True)
        # one honest busy request seeds the cadence floor (the stall
        # baseline) — prewarm never goes through the run loop, and an
        # unseeded floor leaves the stall watchdog deaf to the throttle
        eng.submit(
            "warm the cadence floor",
            SamplingParams(temperature=0.0, max_tokens=16),
        ).result(timeout=300)
        return eng

    def build_fleet(hedge_after_s: float):
        router = FleetRouter(
            store=Store(), heartbeat_interval=60.0,
            hedge_after_s=hedge_after_s,
        )
        engines = [build_engine() for _ in range(3)]
        for i, eng in enumerate(engines):
            router.add_replica(f"r{i}", eng)
        return router, engines

    def teardown(router, engines) -> None:
        router.stop()
        for eng in engines:
            try:
                eng.stop()
            except Exception:
                pass

    out: dict = {
        "slow_cycle": {"replica": "r0", "delay_s": delay_s, "times": times},
        "hedge_after_s": hedge_s,
    }
    reports: dict = {}
    for arm, hedge in (("hedging_off", 0.0), ("hedging_on", hedge_s)):
        router, engines = build_fleet(hedge)
        try:
            trace = SCENARIOS["persona_storm"](**storm_kw)
            FAULTS.arm(
                "engine.slow_cycle",
                times=times, delay_s=delay_s, replica="r0",
            )
            report = replay(trace, router, speed=speed, scenario="persona_storm")
            reports[arm] = report
            doc = report.slo_doc()
            health = router.stats().get("health") or {}
            doc["hedges"] = health.get("hedges", 0)
            doc["hedge_cancels"] = health.get("hedge_cancels", 0)
            out[arm] = doc
        finally:
            FAULTS.reset()
            teardown(router, engines)
    off = out["hedging_off"]["e2e_p99_ms"]
    on = out["hedging_on"]["e2e_p99_ms"]
    out["e2e_p99_improvement"] = round(off / on, 3) if on else None
    out["byte_identical"] = byte_identical(
        reports["hedging_off"], reports["hedging_on"]
    )

    # the seeded conductor verdict rides along so the perf doc also pins
    # "the cocktail was survivable" — not just "hedging is fast"
    router, engines = build_fleet(hedge_s)
    try:
        chaos = run_chaos(
            router, seed=seed, speed=speed,
            scenario_kwargs=storm_kw or None,
        )
        out["chaos"] = {
            "seed": seed,
            "ok": chaos.ok(),
            "violations": list(chaos.violations),
            "armed": len(chaos.ledger),
            "scheduled": len(chaos.schedule),
        }
    finally:
        teardown(router, engines)
    return out


def _bench_fleet() -> dict:
    """Fleet-tier fixture (ACP_BENCH_FLEET=1) — the two routing claims
    from docs/fleet.md, measured:

    (a) **affinity vs round-robin** on a same-persona burst: N personas x
    M turns against a 2-replica pool, each policy on freshly built
    engines. Affinity homes every persona's turns on one replica, so its
    prefix cache serves turn 2+ hot; round-robin alternates and halves
    the hit rate. Reported: pool-wide prefix-cache hit rate + TTFT p99
    each way.

    (b) **disaggregated handoff vs full recompute**: the same long-prompt
    request against a prefill+decode pool with the handoff on vs off.
    Reported: TTFT each way + the KV bytes the handoff moved (the wire
    cost recompute avoids paying in compute).

    The persona count defaults to an ODD number: with an even count the
    submit-order interleave makes round-robin assign each persona a fixed
    replica — accidental affinity, no contrast. Each replica's prefix
    cache is sized to hold affinity's per-replica share of the personas
    but not the whole roster round-robin smears onto every replica.

    Knobs: ACP_BENCH_FLEET_PERSONAS (5), ACP_BENCH_FLEET_TURNS (4),
    ACP_BENCH_FLEET_PERSONA (256 tokens), ACP_BENCH_FLEET_PROMPT (768),
    ACP_BENCH_FLEET_MAX_TOKENS (8)."""
    import dataclasses

    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
    from agentcontrolplane_tpu.fleet import FleetRouter
    from agentcontrolplane_tpu.kernel import Store
    from agentcontrolplane_tpu.models.llama import PRESETS

    n_personas = int(os.environ.get("ACP_BENCH_FLEET_PERSONAS", "5"))
    n_turns = int(os.environ.get("ACP_BENCH_FLEET_TURNS", "4"))
    persona_len = int(os.environ.get("ACP_BENCH_FLEET_PERSONA", "256"))
    plen = int(os.environ.get("ACP_BENCH_FLEET_PROMPT", "768"))
    max_tokens = int(os.environ.get("ACP_BENCH_FLEET_MAX_TOKENS", "8"))
    page = 16
    armed = os.environ.get("ACP_INVARIANTS", "") not in ("", "0")

    def build(max_ctx, **kw):
        cfg = dataclasses.replace(
            PRESETS["tiny"], max_seq_len=max_ctx, vocab_size=512
        )
        eng = Engine(
            config=cfg,
            tokenizer=ByteTokenizer(),
            max_ctx=max_ctx,
            prefill_buckets=(64, 256, 512),
            decode_block_size=4,
            kv_layout="paged",
            page_size=page,
            check_invariants=armed,
            **kw,
        )
        eng.start()
        return eng

    def percentile(vals, q):
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(q * len(vals)))] if vals else 0.0

    # -- (a) affinity vs round-robin on a same-persona burst ----------------
    personas = [
        [3 + p + (i % 200) for i in range(persona_len)]
        for p in range(n_personas)
    ]
    sp = SamplingParams(temperature=0.0, max_tokens=max_tokens)

    def routing_leg(policy: str) -> dict:
        router = FleetRouter(store=Store(), policy=policy,
                             heartbeat_interval=60.0)
        # cache sized for TWO generations (each turn's completion inserts
        # a new longer entry beside last turn's) of affinity's per-replica
        # SHARE of the personas — round-robin smears the whole roster
        # onto both replicas, needs ~2x this, and churns its caches
        cap = n_personas + 1
        engines = [build(1024, max_slots=4, prefix_cache_entries=cap)
                   for _ in range(2)]
        for i, eng in enumerate(engines):
            router.add_replica(f"r{i}", eng)
        try:
            # warm every shape on both replicas so the measured turns
            # compare routing, not compilation — a neutral prompt that
            # shares no prefix with any persona, run twice to also warm
            # the prefix-HIT prefill program (short remainder bucket)
            for eng in engines:
                eng.generate([2] * (persona_len + 8), sp)
                eng.generate([2] * (persona_len + 8), sp)
            base: list[dict] = []
            ttfts: list[float] = []
            # turn 0 is a throwaway warm burst: it compiles the
            # concurrent-batch shapes, homes the cold personas, and is
            # excluded from both the TTFT and hit-rate ledgers — the
            # measured turns compare STEADY-STATE routing
            for turn in range(n_turns + 1):
                # each turn is a concurrent burst: queue depth is what
                # spreads cold personas across replicas (sequential
                # submits would all tiebreak onto the same idle replica)
                pending = []
                for p, persona in enumerate(personas):
                    tail = [210 + turn, 220 + p, 230, 240] * 4
                    t0 = time.monotonic()
                    first = []

                    def on_tokens(_t, first=first, t0=t0):
                        if not first:
                            first.append((time.monotonic() - t0) * 1e3)

                    fut = router.submit(
                        persona + tail, sp, on_tokens=on_tokens,
                        affinity_key=f"persona-{p}",
                    )
                    pending.append((fut, first))
                for fut, first in pending:
                    fut.result(timeout=1800)
                    if turn > 0:
                        ttfts.append(first[0] if first else 0.0)
                if turn == 0:
                    base = [dict(eng.stats().get("prefix_cache") or {})
                            for eng in engines]
            hits = misses = 0
            for eng, b in zip(engines, base):
                pc = eng.stats().get("prefix_cache") or {}
                hits += pc.get("hits", 0) - b.get("hits", 0)
                misses += pc.get("misses", 0) - b.get("misses", 0)
            return {
                "prefix_hit_rate": round(hits / (hits + misses), 3)
                if hits + misses else 0.0,
                "ttft_p50_ms": round(percentile(ttfts, 0.50), 1),
                "ttft_p99_ms": round(percentile(ttfts, 0.99), 1),
                "affinity_hits": router.affinity_hits,
            }
        finally:
            router.stop()
            for eng in engines:
                eng.stop()

    rr = routing_leg("round_robin")
    aff = routing_leg("affinity")
    routing_part = {
        "personas": n_personas,
        "turns": n_turns,
        "persona_tokens": persona_len,
        "round_robin": rr,
        "affinity": aff,
    }

    # -- (b) disaggregated handoff vs full recompute ------------------------
    prompt = [1 + (i % 250) for i in range(plen)]
    max_ctx = plen + 256

    def handoff_leg(enabled: bool) -> tuple[float, int]:
        router = FleetRouter(
            store=Store(), heartbeat_interval=60.0,
            handoff_min_tokens=page if enabled else 0,
        )
        # prefix cache off: the local arm must pay the full prefill the
        # handoff arm imports over the wire
        prefill = build(max_ctx, max_slots=2, host_kv_bytes=256 << 20,
                        prefix_cache_entries=0)
        decode = build(max_ctx, max_slots=2, host_kv_bytes=256 << 20,
                       prefix_cache_entries=0)
        router.add_replica("pf", prefill, role="prefill")
        router.add_replica("dc", decode, role="decode")
        try:
            # warm both legs' shapes (prefill program + restore scatter)
            router.submit(list(prompt), sp).result(timeout=1800)
            warm_bytes = router.handoff_bytes
            t0 = time.monotonic()
            first = []

            def on_tokens(_t):
                if not first:
                    first.append((time.monotonic() - t0) * 1e3)

            # vary the tail so the warmed prefix cache can't serve it whole
            router.submit(prompt[:-4] + [251, 252, 253, 254], sp,
                          on_tokens=on_tokens).result(timeout=1800)
            return (first[0] if first else 0.0), \
                router.handoff_bytes - warm_bytes
        finally:
            router.stop()
            prefill.stop()
            decode.stop()

    ttft_local, _ = handoff_leg(False)
    ttft_handoff, wire_bytes = handoff_leg(True)
    handoff_part = {
        "prompt_tokens": plen,
        "ttft_handoff_ms": round(ttft_handoff, 1),
        "ttft_local_ms": round(ttft_local, 1),
        "handoff_bytes": wire_bytes,
    }

    return {
        "routing": routing_part,
        "handoff": handoff_part,
        "note": (
            f"{n_personas} personas x {n_turns} turns on 2 replicas: "
            f"prefix hit rate {rr['prefix_hit_rate']:.0%} (round-robin) -> "
            f"{aff['prefix_hit_rate']:.0%} (affinity), TTFT p99 "
            f"{rr['ttft_p99_ms']:.0f}ms -> {aff['ttft_p99_ms']:.0f}ms; "
            f"{plen}-token disaggregated prefill TTFT "
            f"{ttft_handoff:.0f}ms vs {ttft_local:.0f}ms local "
            f"({wire_bytes} KV bytes over the wire)"
        ),
    }


def _bench_quant() -> dict:
    """Quantized-serving fixture (ACP_BENCH_QUANT=1) — the capacity
    multiplier ISSUE 14 ships plus its accuracy price, recorded together:

    (a) **concurrent slots at a fixed HBM byte budget**: the SAME budget
    B is spent two ways — a bf16 KV pool of B / bf16_page_bytes pages, or
    an int8+scales pool of B / int8_page_bytes pages (~1.6x at tiny's
    head_dim 16; ~1.9x at production d=128). A burst of independent
    same-length tasks is driven through each engine and the peak
    concurrently-admitted slots measured; the bar is >= 1.5x (the
    acceptance criterion). Dedup/prefix caching are disabled so the
    multiplier is quantization's alone.

    (b) **the accuracy gate**: top-1 greedy agreement + logit MAE vs the
    bf16 path over the pinned fixture (engine/accuracy.py), for
    weights-only / kv-only / both, evaluated against the same pinned
    thresholds the test suite enforces — the bench doc records the
    numbers so the accuracy trajectory is inspectable next to the
    capacity it buys.

    Knobs: ACP_BENCH_QUANT_PROMPT (default 240), ACP_BENCH_QUANT_TASKS
    (12), ACP_BENCH_QUANT_BASE_TASKS (6, sizes the bf16 pool)."""
    import dataclasses

    import jax as _jax

    from agentcontrolplane_tpu.engine.accuracy import (
        accuracy_report,
        check_accuracy_gate,
        pinned_fixture,
        teacher_forced_logits,
    )
    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
    from agentcontrolplane_tpu.models.llama import PRESETS, init_params

    MIN_TOP1, MAX_MAE = 0.92, 0.05  # pinned with tests/engine/test_quant_kv.py
    plen = int(os.environ.get("ACP_BENCH_QUANT_PROMPT", "240"))
    n_tasks = int(os.environ.get("ACP_BENCH_QUANT_TASKS", "12"))
    base_tasks = int(os.environ.get("ACP_BENCH_QUANT_BASE_TASKS", "6"))
    page = 16
    max_tokens = 16
    armed = os.environ.get("ACP_INVARIANTS", "") not in ("", "0")
    cfg = dataclasses.replace(PRESETS["tiny"], max_seq_len=1024, vocab_size=512)

    # the fixed budget, in BYTES of KV pool: page bytes are computed for a
    # bf16 baseline (2 bytes/elem) vs int8+per-row-f32-scales, so the
    # multiplier reflects production serving even though the tiny CPU
    # config computes in f32 (the serving dtype never changes how many
    # pages a page-count-limited pool admits)
    elems = cfg.n_layers * page * cfg.n_kv_heads  # per page, per k/v side
    bf16_page_bytes = elems * cfg.head_dim * 2 * 2
    int8_page_bytes = elems * (cfg.head_dim + 4) * 2
    task_pages = -(-(plen + max_tokens) // page) + 1
    pages_bf16 = base_tasks * task_pages + 2
    budget_bytes = pages_bf16 * bf16_page_bytes
    pages_int8 = budget_bytes // int8_page_bytes

    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    def burst_leg(quantize_kv: bool, kv_pages: int) -> tuple[dict, int]:
        eng = Engine(
            config=cfg,
            tokenizer=ByteTokenizer(),
            # tp=1 explicitly: the fixture measures pool capacity, not
            # sharding, and must not depend on the host's device count
            mesh=make_mesh({"tp": 1}, devices=_jax.devices()[:1]),
            max_slots=n_tasks,
            max_ctx=512,
            prefill_buckets=(64, 256),
            decode_block_size=4,
            kv_layout="paged",
            page_size=page,
            kv_pages=kv_pages + 1,  # + the trash page
            page_lookahead_blocks=1,
            prefix_cache_entries=0,
            prefix_dedup=False,
            quantize_kv=quantize_kv,
            check_invariants=armed,
        )
        eng.start()
        try:
            sp = SamplingParams(temperature=0.0, max_tokens=max_tokens)
            prompts = [
                [1 + ((i * 7 + j) % 250) for j in range(plen)]
                for i in range(n_tasks)
            ]
            eng.generate(list(prompts[0]), sp)  # warm every shape
            peak = [0]

            def on_tokens(_t):
                s = eng.stats()
                peak[0] = max(peak[0], s["active_slots"] + s["prefilling_slots"])

            with eng.hold_admission():
                futs = [
                    eng.submit(list(p), sp, on_tokens=on_tokens)
                    for p in prompts
                ]
            toks = {i: f.result(timeout=1800).tokens for i, f in enumerate(futs)}
            return toks, peak[0]
        finally:
            eng.stop()

    _, slots_bf16 = burst_leg(False, pages_bf16)
    toks_a, slots_int8 = burst_leg(True, pages_int8)
    toks_b, _ = burst_leg(True, pages_int8)
    ratio = round(slots_int8 / slots_bf16, 2) if slots_bf16 else 0.0

    # (b) the accuracy gate, scored through the real serving numerics;
    # the bf16 baseline pass is shared across the three configurations
    params = init_params(PRESETS["tiny"], _jax.random.key(0))
    rows = pinned_fixture(PRESETS["tiny"].vocab_size)
    base_logits = teacher_forced_logits(params, PRESETS["tiny"], rows)
    gate: dict = {"min_top1": MIN_TOP1, "max_logit_mae": MAX_MAE}
    ok = True
    for name, (qw, qkv) in {
        "weights": (True, False), "kv": (False, True), "both": (True, True),
    }.items():
        rep = accuracy_report(
            PRESETS["tiny"], params, quantize_weights=qw, quantize_kv=qkv,
            rows=rows, baseline=base_logits,
        )
        rep["violations"] = check_accuracy_gate(rep, MIN_TOP1, MAX_MAE)
        ok = ok and not rep["violations"]
        gate[name] = rep

    return {
        "prompt_tokens": plen,
        "tasks": n_tasks,
        "page_budget_bytes": budget_bytes,
        "pages_bf16": pages_bf16,
        "pages_int8": pages_int8,
        "effective_slots_bf16": slots_bf16,
        "effective_slots_int8": slots_int8,
        "slot_capacity_x": ratio,
        "bar_x": 1.5,
        "capacity_bar_met": ratio >= 1.5,
        "deterministic": toks_a == toks_b,
        "accuracy_gate": gate,
        "accuracy_gate_passed": ok,
        "note": (
            f"{n_tasks} tasks x {plen}-token prompts at a fixed "
            f"{budget_bytes >> 10}KiB KV budget: bf16 {pages_bf16} pages -> "
            f"{slots_bf16} concurrent slots, int8 {pages_int8} pages -> "
            f"{slots_int8} slots ({ratio}x, bar 1.5x); accuracy gate "
            f"kv top-1 {gate['kv']['top1_agreement']}, both "
            f"{gate['both']['top1_agreement']} (min {MIN_TOP1}), "
            f"passed={ok}"
        ),
    }


def _bench_ttft(engine) -> dict:
    """BASELINE's second metric: p50/p95 task-create -> first-ToolCall-CR
    through the REAL operator with provider: tpu (configs 1+5 shape).
    tool_choice "required" teacher-forces the tool-call envelope so a
    random-weights model still produces a parseable ToolCall every time."""
    import asyncio

    from agentcontrolplane_tpu.api import ObjectMeta
    from agentcontrolplane_tpu.api.resources import (
        LLM, BaseConfig, LLMSpec, TPUProviderConfig,
    )
    from agentcontrolplane_tpu.operator import Operator, OperatorOptions
    from agentcontrolplane_tpu.testing import make_agent, make_task, setup_with_status

    n_tasks = int(os.environ.get("ACP_BENCH_TTFT_TASKS", "16"))
    preset = os.environ.get("ACP_BENCH_PRESET", "bench-1b")
    if engine.max_ctx < 256:
        # the rendered system+tools prompt plus the forced tool-call envelope
        # can't fit; the generation would hit max_ctx before closing the JSON
        return {"skipped": f"engine max_ctx {engine.max_ctx} < 256", "n": 0}

    # compile every program the staggered operator traffic will hit (token
    # table, every prefill bucket x batch size, every decode width) OUTSIDE
    # the measured window. The previous ad-hoc warm here missed the
    # mid-size batches and narrow widths that staggered reconcile arrivals
    # produce — each miss was a cold compile COUNTED INTO TTFT
    # (r1's 41s p50 was compile stalls, not serving latency).
    engine.prewarm(constrained=True)
    _mark("ttft_prewarmed")

    # segmentation (VERDICT r2 #2): engine-side submit->first-token is
    # tracked by the acp_engine_ttft_seconds reservoir; snapshot its
    # monotonic count so only THIS phase's observations are read back — the
    # difference to the end-to-end task-create->ToolCall-CR number is
    # control plane + prompt render + remaining generation + tool-call
    # parse + store writes
    from agentcontrolplane_tpu.observability.metrics import REGISTRY

    _n_before, _ = REGISTRY.series_window("acp_engine_ttft_seconds")

    async def run() -> dict:
        op = Operator(
            options=OperatorOptions(
                enable_rest=False, llm_probe=False,
                verify_channel_credentials=False, engine=engine,
            ),
        )
        op.task_reconciler.requeue_delay = 0.02
        op.toolcall_reconciler.poll_interval = 0.02
        store = op.store
        setup_with_status(
            store,
            LLM(
                metadata=ObjectMeta(name="tpu-llm"),
                spec=LLMSpec(
                    provider="tpu",
                    # tight tool-call budget: the grammar's budget-aware
                    # closure always yields a COMPLETE JSON object within
                    # max_tokens, and time-to-first-ToolCall includes the
                    # whole generation — every extra token is pure latency
                    parameters=BaseConfig(
                        model=preset,
                        max_tokens=int(os.environ.get("ACP_BENCH_TTFT_MAX_TOKENS", "24")),
                        temperature=0.7,
                    ),
                    tpu=TPUProviderConfig(preset=preset),
                    provider_config={"tool_choice": "required"},
                ),
            ),
            lambda o: (
                setattr(o.status, "ready", True),
                setattr(o.status, "status", "Ready"),
            ),
        )
        make_agent(store, name="leaf", llm="tpu-llm", system="leaf")
        make_agent(store, name="rooter", llm="tpu-llm", system="use tools",
                   sub_agents=("leaf",))
        await op.start()
        watch = store.watch("ToolCall")
        created: dict[str, float] = {}
        ttfts: list[float] = []
        try:
            for i in range(n_tasks):
                name = f"ttft-{i}"
                created[name] = time.monotonic()
                make_task(store, name=name, agent="rooter", user_message=f"task {i}")
            deadline = time.monotonic() + float(
                os.environ.get("ACP_BENCH_TTFT_DEADLINE_S", "240")
            )
            while len(ttfts) < n_tasks and time.monotonic() < deadline:
                ev = await watch.next(timeout=deadline - time.monotonic())
                if ev is None:
                    break
                if ev.type != "ADDED":
                    continue
                task_name = ev.object.metadata.labels.get("acp.tpu/task", "")
                if task_name in created:
                    ttfts.append((time.monotonic() - created.pop(task_name)) * 1e3)
        finally:
            watch.stop()
            await op.stop()
        if not ttfts:
            return {"error": "no ToolCalls observed", "n": 0}
        ttfts.sort()
        pick = lambda q: ttfts[min(len(ttfts) - 1, int(q * len(ttfts)))]
        out = {
            "p50": round(pick(0.50), 1),
            "p95": round(pick(0.95), 1),
            "n": len(ttfts),
            "target_ms": 500,
        }
        n_after, window = REGISTRY.series_window("acp_engine_ttft_seconds")
        new = n_after - _n_before
        if new > 0:
            eng = sorted(v * 1e3 for v in window[-min(new, len(window)):])
            epick = lambda q: eng[min(len(eng) - 1, int(q * len(eng)))]
            out["engine_submit_to_first_token_ms"] = {
                "p50": round(epick(0.50), 1),
                "p95": round(epick(0.95), 1),
                "n": len(eng),
            }
            # remainder = reconcile hops, prompt render, constrained-decode
            # completion beyond the first token, tool-call parse, CR writes.
            # Only meaningful when the sample sets correspond (a deadline
            # truncation leaves the engine series with straggler samples the
            # end-to-end set lacks).
            if len(eng) == len(ttfts):
                out["non_engine_p50_ms"] = round(out["p50"] - epick(0.50), 1)
        return out

    return asyncio.run(run())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", choices=["main", "ab"], default=None)
    ap.add_argument("--no-ttft", action="store_true")
    ap.add_argument("--only-ttft", action="store_true")
    ap.add_argument("--layout", default=None)
    ap.add_argument("--budget", type=float, default=None)
    args = ap.parse_args()
    if args.phase:
        _child(args)
    else:
        sys.exit(_parent())


if __name__ == "__main__":
    main()
