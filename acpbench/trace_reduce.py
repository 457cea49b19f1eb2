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
    }

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
        if ops:
            per_dev.append({"ops": ops, "mods": mods})
    if not per_dev:
        return None
    n = len(per_dev)
    start = min(min(o[0] for o in d["ops"]) for d in per_dev)
    end = max(max(o[1] for o in d["ops"]) for d in per_dev)
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
        prev = None
        for s, e, name in d["mods"]:
            if prev is not None and s > prev[1]:
                key = f"{prev[2]}-{name}"
                gaps[key] = gaps.get(key, 0.0) + (s - prev[1]) / 1e9 / n
            if prev is None or e > prev[1]:
                prev = (s, e, name)
    return {
        "devices": n,
        "window_s": (end - start) / 1e9,
        "busy_s": busy / 1e9,
        "ops": ops,
        "modules": modules,
        "gaps": gaps,
        "op_intervals": [d["ops"] for d in per_dev],
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
