"""From the profiler's `.xplane.pb` to numbers, with nothing but JAX.

A device plane ("/device:TPU:<n>") has a line "XLA Ops" (one event per
operation the core ran, named for the HLO op) and a line "XLA Modules"
(one event per program run, named for the jitted function). Everything
here reduces those two lines; times are nanoseconds on the device clock.

    reduce(path) -> {
      "devices": n, "window_s", "busy_s" (union of op intervals, mean over chips),
      "ops": {name: seconds, mean over chips}, "modules": {name: {"n", "s"}},
      "gaps": {"<module before>-<module after>": seconds},   # idle between programs
      "op_intervals": per device [(start, end, name)] for readers that need overlap
      "windows": per device (start, end) of what was reduced, "slice_s": the first chip's
          window in seconds after the first op the profiler saw (for the host's clock)
    }

Whole program runs only. The profiler starts and stops in the middle of the
stream: a chip's first module event begins with the first op the profiler
saw, most of its ops missing, and its last may be cut at the stop. Counted
as runs they make every per-run number read low, by more the shorter the
slice (two cut blocks of eight in a 3 s slice at tp=4: a decode step 3.7%
short, idle a block 20% short; my chip runs, PR 29). So each chip's first
and last run are dropped with their ops, and its window runs from the end
of the first to the end of the last run kept: whole runs, each with the
idle gap before it.

Loops, conditionals and calls are dropped as the line is read: the ops they
run are on the line themselves, and an event that wraps them would cover
every idle moment and every collective inside a program.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def module_name(event_name: str) -> str:
    """'jit_decode_block(1234567)' -> 'jit_decode_block'."""
    return re.sub(r"\(\d+\)$", "", event_name.strip())


CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\d]*\s*=")  # they wrap the ops they run


def op_name(event_name: str) -> str:
    """'%fusion.212 = s32[4866048]{0} fusion(...)' -> 'fusion.212_s32_4866048_':
    the op and the shape it makes (none for a tuple), in the characters a
    name may have."""
    m = re.match(r"%?([\w.\-]+)\s*=\s*(\w+\[[\d,]*\])?", event_name)
    text = m.group(1) + (f"_{m.group(2)}" if m.group(2) else "") if m else event_name.split("(")[0]
    return re.sub(r"[^\w.\-]", "_", text)[:64]


def _union(intervals: list[tuple[int, int]]) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def device_planes(profile) -> list:
    return [p for p in profile.planes if p.name.startswith("/device:TPU:")]


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def reduce_profile(profile) -> dict | None:
    planes = device_planes(profile)
    per_dev = []
    for plane in planes:
        ops_line, mod_line = _line(plane, OPS_LINE), _line(plane, MODULES_LINE)
        if ops_line is None:
            continue
        ops = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name) for e in ops_line.events
               if not CONTAINERS.match(e.name)]
        mods = sorted(
            (int(e.start_ns), int(e.start_ns + e.duration_ns), module_name(e.name))
            for e in (mod_line.events if mod_line is not None else [])
        )
        if len(mods) < 3 or not ops:
            continue  # no whole run between a first and a last
        window = (mods[0][1], mods[-2][1])
        per_dev.append({"ops": [o for o in ops if window[0] <= o[0] < window[1]], "mods": mods[1:-1],
                        "cut": mods[0], "window": window, "first_op": min(o[0] for o in ops)})
    if not per_dev or not all(d["ops"] for d in per_dev):
        return None
    n = len(per_dev)
    busy = sum(_union([(s, e) for s, e, _ in d["ops"]]) for d in per_dev) / n
    ops: dict[str, float] = {}
    modules: dict[str, dict] = {}
    gaps: dict[str, float] = {}
    for d in per_dev:
        for s, e, name in d["ops"]:
            key = op_name(name)
            ops[key] = ops.get(key, 0.0) + (e - s) / 1e9 / n
        for s, e, name in d["mods"]:
            m = modules.setdefault(name, {"n": 0.0, "s": 0.0})
            m["n"] += 1.0 / n
            m["s"] += (e - s) / 1e9 / n
        prev = d["cut"]
        for s, e, name in d["mods"]:
            if s > prev[1]:
                key = f"{prev[2]}-{name}"
                gaps[key] = gaps.get(key, 0.0) + (s - prev[1]) / 1e9 / n
            if e > prev[1]:
                prev = (s, e, name)
    first = per_dev[0]
    return {
        "devices": n,
        "window_s": sum(d["window"][1] - d["window"][0] for d in per_dev) / 1e9 / n,
        "busy_s": busy / 1e9,
        "ops": ops,
        "modules": modules,
        "gaps": gaps,
        "op_intervals": [d["ops"] for d in per_dev],
        "windows": [d["window"] for d in per_dev],
        "slice_s": tuple((w - first["first_op"]) / 1e9 for w in first["window"]),
    }


def reduce(path: str) -> dict | None:
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(path))


def seconds_of(reduced: dict, table: str, pattern: str) -> float:
    """Seconds (mean over chips) of the ops or modules whose name matches."""
    rx = re.compile(pattern)
    rows = reduced[table]
    if table == "modules":
        return sum(v["s"] for k, v in rows.items() if rx.search(k))
    return sum(v for k, v in rows.items() if rx.search(k))


def runs_of(reduced: dict, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(v["n"] for k, v in reduced["modules"].items() if rx.search(k))


def exposed_seconds(reduced: dict, pattern: str) -> float:
    """Seconds (mean over chips) in which an op matching `pattern` runs on
    a chip and no other op of that chip's op line does."""
    rx = re.compile(pattern)
    total = 0.0
    for ops in reduced["op_intervals"]:
        mine = [(s, e) for s, e, name in ops if rx.search(name)]
        rest = [(s, e) for s, e, name in ops if not rx.search(name)]
        both = _union(mine + rest)
        total += both - _union(rest)
    return total / 1e9 / max(1, len(reduced["op_intervals"]))


def breakdown(reduced: dict, top: int = 10) -> dict:
    def rows(table):
        return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": rows(reduced["ops"]), "idle_gaps": rows(reduced["gaps"])}


def inventory(path: str, events: int = 6) -> list[str]:
    """What a trace holds, for a reader who has not seen one: planes, lines,
    and a few event names each."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = [e.name[:90] for e in evs[:events]]
            out.append(f"  line {line.name!r}: {len(evs)} events, e.g. {names}")
    return out
