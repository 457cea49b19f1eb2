"""The program's own spans on the device trace's clock: every idle moment
of the chip named for the host code that ran in it.

The engine loop opens `jax.profiler.TraceAnnotation` spans named `acp.<phase>`
at its own boundaries (`agentcontrolplane_tpu/observability/profiler.py`,
`DispatchProfiler.phase`): `admit`, `park`, `launch`, `fetch`, `commit`,
`publish`, under one `acp.cycle` per busy iteration. They land on the host
plane ("/host:CPU") of the same `.xplane.pb` that holds the device planes
`trace_reduce` reads. A program without the spans (a parent commit) gives
`analyse(run) is None`, and every metric read from here is left out.

    read_profile(profile) -> [Span(start_ns, end_ns, name, cycle, program, call_ns)]
    attribute(op_intervals, spans) -> {"idle_s", "by_phase": {name: seconds}}
    alignment(spans, device_runs) -> the clock check, and a correction if the planes are offset
    analyse(run) -> all of it for one traced run, printed once as a `[spans]` line
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
from typing import NamedTuple

from . import spec, trace_reduce

class Span(NamedTuple):
    start_ns: int
    end_ns: int
    name: str  # without the "acp." prefix
    cycle: int
    program: str | None = None  # launches: the profiler's key of the program dispatched
    call_ns: int | None = None  # launches: when the jitted call began


PREFIX = "acp."
CYCLE = "cycle"
DECODE_MODULE = r"decode_block"  # the device's name for a decode block, split or alone
DECODE_PROGRAM = re.compile(r"^decode\[|^megastep\[.*[,+]d\d+x\d+")  # the profiler's key for the same
EDGE_RUNS = 2  # whole runs of a slice that may lack their spans: one at either edge


def find(run) -> str | None:
    """The run's `.xplane.pb`: the newest under `.acpbench_trace/<workload>-*`
    (`run.py` deletes the directory only after the metrics are read)."""
    dirs = glob.glob(os.path.join(spec.ROOT, ".acpbench_trace", run.cell["workload"]["name"] + "-*"))
    found = [p for p in map(trace_reduce.find_xplane, dirs) if p]
    return max(found, key=os.path.getmtime) if found else None


def read_profile(profile) -> list[Span]:
    """The `acp.*` events of the host planes, by start."""
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                stats = dict(e.stats)
                cycle = stats.get("step_num", stats.get("cycle", 0))
                start = int(e.start_ns)
                called = stats.get("call_us")
                spans.append(Span(start, start + int(e.duration_ns), e.name[len(PREFIX):], int(cycle),
                                  stats.get("program"), None if called is None else start + int(called) * 1000))
    return sorted(spans, key=lambda s: s[:3])


def device_runs(profile, pattern: str = DECODE_MODULE) -> list[tuple[int, int]]:
    """(start, end) of every run of a matching program on the first chip."""
    rx = re.compile(pattern)
    for plane in trace_reduce.device_planes(profile):
        line = trace_reduce._line(plane, trace_reduce.MODULES_LINE)
        if line is None:
            continue
        return sorted((int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events
                      if rx.search(trace_reduce.module_name(e.name)))
    return []


def innermost(spans) -> list[tuple[int, int, str]]:
    """Disjoint (start, end, phase) segments: each moment some span covers,
    under the covering span that started last. `acp.cycle` only groups the
    others, so it names a moment only where no phase does."""
    edges = sorted({t for s in spans for t in s[:2]})
    by_start = sorted(spans, key=lambda s: s[:3])
    out, live, i = [], [], 0
    for t0, t1 in zip(edges, edges[1:]):
        while i < len(by_start) and by_start[i][0] <= t0:
            live.append(by_start[i])
            i += 1
        live = [s for s in live if s[1] > t0]
        phases = [s for s in live if s[2] != CYCLE] or live
        if not phases:
            continue
        name = max(phases, key=lambda s: (s[0], -s[1]))[2]
        if out and out[-1][2] == name and out[-1][1] == t0:
            out[-1] = (out[-1][0], t1, name)
        else:
            out.append((t0, t1, name))
    return out


def _idle(ops: list[tuple[int, int]], start: int, end: int) -> list[tuple[int, int]]:
    """The complement of the union of `ops` inside [start, end]."""
    out, at = [], start
    for s, e in sorted(ops):
        if s > at:
            out.append((at, min(s, end)))
        at = max(at, e)
    if at < end:
        out.append((at, end))
    return [(s, e) for s, e in out if e > s]


def _overlap(intervals, segments) -> dict[str, int]:
    """Nanoseconds of `intervals` under each segment's name; what no segment
    covers is `unnamed`. Both sorted and disjoint."""
    out: dict[str, int] = {}
    j = 0
    for s, e in intervals:
        covered = 0
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            part = min(e, segments[k][1]) - max(s, segments[k][0])
            if part > 0:
                out[segments[k][2]] = out.get(segments[k][2], 0) + part
                covered += part
            k += 1
        out["unnamed"] = out.get("unnamed", 0) + (e - s) - covered
    return out


def attribute(op_intervals, spans, offset_ns: int = 0, windows=None) -> dict:
    """Every idle nanosecond of every chip inside its window (the reduced
    trace's `windows`: whole program runs; without them, from the first
    device op to the last), given to the innermost `acp.*` phase that
    covers it, else to `unnamed`; seconds, mean over chips. The parts sum
    to the reduced trace's `window_s - busy_s`. `offset_ns` is added to
    device times first (a clock correction, 0 when the planes agree)."""
    segments = innermost(spans)
    if windows is None:
        windows = [(min(s for ops in op_intervals for s, _, _ in ops),
                    max(e for ops in op_intervals for _, e, _ in ops))] * len(op_intervals)
    total: dict[str, int] = {}
    for ops, (start, end) in zip(op_intervals, windows):
        idle = _idle([(s + offset_ns, e + offset_ns) for s, e, _ in ops], start + offset_ns, end + offset_ns)
        for name, ns in _overlap(idle, segments).items():
            total[name] = total.get(name, 0) + ns
    n = len(op_intervals)
    by_phase = {name: ns / 1e9 / n for name, ns in total.items()}
    host = _overlap([(min(w[0] for w in windows) + offset_ns, max(w[1] for w in windows) + offset_ns)], segments)
    return {"idle_s": sum(total.values()) / 1e9 / n, "by_phase": by_phase,
            "host_s": {name: ns / 1e9 for name, ns in host.items() if name != "unnamed"}}


def _windows(spans) -> list[tuple[int, int, int]]:
    """For each launch of a decode block: (the launch's start, the start of
    its jitted call, the end of the fetch that follows it). The device's
    run of that block lies between the last two, if the two clocks are one.
    A launch span with no `call_ns` (none was recorded) stands in with its
    own start."""
    fetches = [s for s in spans if s.name == "fetch"]
    out, j = [], 0
    for s in spans:
        if s.name != "launch" or not s.program or not DECODE_PROGRAM.search(s.program):
            continue
        while j < len(fetches) and fetches[j].start_ns < s.end_ns:
            j += 1
        if j < len(fetches):
            out.append((s.start_ns, s.call_ns if s.call_ns is not None else s.start_ns, fetches[j].end_ns))
    return out


def _pair(windows, runs) -> list[tuple[tuple[int, int, int], tuple[int, int]]]:
    """(window, run) pairs, by where they lie and not by their order: a
    run and a window (from its jitted call to the end of the fetch after
    it) are paired when each is the other's nearest, middle to middle. The
    planes are a millisecond or two apart and blocks follow one another at
    a hundred or more, so the nearest is the run's own; a run whose launch
    is not in the trace (it began before the trace did) finds no window
    that finds it, and stays unpaired. An order says nothing once a block
    is missing on either side, and with the four whole blocks of a short
    slice, two pairs out of step can agree better among themselves than the
    four true ones (PERF.md, Findings, PR 32)."""
    if not windows or not runs:
        return []
    w_mid = [(w[1] + w[2]) // 2 for w in windows]
    r_mid = [(r[0] + r[1]) // 2 for r in runs]

    def nearest(t: int, mids: list[int]) -> int:
        return min(range(len(mids)), key=lambda i: abs(mids[i] - t))

    to_window = [nearest(t, w_mid) for t in r_mid]
    return [(windows[j], runs[k]) for k, j in enumerate(to_window) if nearest(w_mid[j], r_mid) == k]


def _held(pairs, offset_ns: int) -> int:
    return sum(1 for w, r in pairs if w[1] <= r[0] + offset_ns and r[1] + offset_ns <= w[2])


def alignment(spans, runs) -> dict:
    """Are host spans and device ops on one clock? A decode block cannot
    start on the device before its jitted call began (inside its
    `acp.launch` span), nor end after the `acp.fetch` that follows closed.
    Paired block by block, the two facts bound an offset d between the
    planes (device time + d = host time): d is at least every call start
    less device start, and at most every fetch end less device end. If 0
    lies outside the bounds the planes are offset: the reader corrects by
    the middle of the bounds and says so. No correction is made that is not
    credible: where more than `EDGE_RUNS` of the device's runs found no
    window of their own, no one offset holds for every pair, or the offset
    is longer than the shortest paired block (from its launch's start to
    its fetch's end: the pairing is then as good a block further on),
    `failed` says which, the `[spans]` line repeats it, and the planes are
    read as they are. `share` is the part of the paired blocks that start
    and end inside their window (after the correction, if one was made;
    `share_uncorrected` before); `latency_ms` the median from the jitted
    call's start to the device's."""
    windows = _windows(spans)
    pairs = _pair(windows, runs)
    out = {"runs": len(runs), "windows": len(windows), "blocks": len(pairs), "offset_ms": 0.0, "corrected": False,
           "failed": None, "bounds_ms": None, "share": 0.0, "share_uncorrected": 0.0, "latency_ms": None}
    if not pairs:
        out["failed"] = f"none of the device's {len(runs)} decode-block runs has a launch-to-fetch window of its own"
        return out
    lo = max(w[1] - r[0] for w, r in pairs)
    hi = min(w[2] - r[1] for w, r in pairs)
    offset = (lo + hi) // 2
    shortest = min(w[2] - w[0] for w, _ in pairs)
    out.update(bounds_ms=(lo / 1e6, hi / 1e6), share_uncorrected=_held(pairs, 0) / len(pairs))
    if len(runs) - len(pairs) > EDGE_RUNS:
        out["failed"] = f"{len(runs) - len(pairs)} of the device's decode-block runs have no window of their own"
    elif lo > hi:
        out["failed"] = "no one offset puts every paired run inside its window"
    elif abs(offset) > shortest:
        out["failed"] = f"an offset of {offset / 1e6:+.3f} ms is longer than a block of {shortest / 1e6:.3f} ms"
    if out["failed"] or lo <= 0 <= hi:
        offset = 0
    else:
        out.update(offset_ms=offset / 1e6, corrected=True)
    out["share"] = _held(pairs, offset) / len(pairs)
    out["latency_ms"] = statistics.median((r[0] + offset - w[1]) / 1e6 for w, r in pairs)
    return out


def analyse_profile(profile, reduced: dict) -> dict | None:
    spans = read_profile(profile)
    if not spans:
        return None
    start, end = reduced["windows"][0]
    align = alignment(spans, [r for r in device_runs(profile) if start <= r[0] and r[1] <= end])
    offset = int(align["offset_ms"] * 1e6) if align["corrected"] else 0
    out = attribute(reduced["op_intervals"], spans, offset, reduced["windows"])
    out["align"] = align
    out["blocks"] = trace_reduce.runs_of(reduced, DECODE_MODULE)
    out["spans"] = len(spans)
    return out


def analyse(run) -> dict | None:
    """Once a run: the attribution, kept on the run for the other readers,
    and its `[spans]` line. None where there is no trace or no span."""
    if run.trace is None:
        return None
    if not hasattr(run, "host_spans"):
        import jax

        path = find(run)
        run.host_spans = analyse_profile(jax.profiler.ProfileData.from_file(path), run.trace) if path else None
        print(f"[spans] {line(run.host_spans, run.trace)}", flush=True)
    return run.host_spans


def line(found: dict | None, reduced: dict) -> str:
    if found is None:
        return "no acp.* span on the host plane: the program opens none"
    a = found["align"]
    bounds = "none" if a["bounds_ms"] is None else f"{a['bounds_ms'][0]:+.3f}..{a['bounds_ms'][1]:+.3f}"
    verdict = (f"ALIGNMENT FAILED ({a['failed']}): the planes are read as they are" if a["failed"] else
               f"device planes CORRECTED by {a['offset_ms']:+.3f} ms, the middle of the bounds "
               f"({100 * a['share_uncorrected']:.1f}% held before)" if a["corrected"] else "no correction")
    clock = (f"clock: {a['blocks']} of the device's {a['runs']} whole decode-block runs are paired with a launch-to-"
             f"fetch window of the host plane's {a['windows']}; {100 * a['share']:.1f}% of them start after their "
             f"jitted call and end before their fetch does; call to device start p50 {a['latency_ms']} ms; offset of "
             f"the device planes bounded to {bounds} ms; {verdict}")
    ms = {k: round(v * 1e3, 3) for k, v in sorted(found["by_phase"].items())}
    host = {k: round(v * 1e3, 3) for k, v in sorted(found["host_s"].items())}
    return (f"{found['spans']} spans; idle ms by phase {json.dumps(ms)} of {found['idle_s'] * 1e3:.3f} "
            f"(window less busy {(reduced['window_s'] - reduced['busy_s']) * 1e3:.3f}); "
            f"host ms by phase in the slice {json.dumps(host)}; decode blocks {found['blocks']:g}; {clock}")


def idle_ms_per_block(run, phase: str) -> float | None:
    found = analyse(run)
    if found is None or not found["blocks"]:
        return None
    return found["by_phase"].get(phase, 0.0) * 1e3 / found["blocks"]


def idle_named_share(run) -> float | None:
    found = analyse(run)
    if found is None or not found["idle_s"]:
        return None
    named = sum(v for k, v in found["by_phase"].items() if k not in ("unnamed", CYCLE))
    return 100.0 * named / found["idle_s"]
