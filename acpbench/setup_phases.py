"""Where set-up went, by the program's own account.

`Engine.stats()["perf"]` (`agentcontrolplane_tpu/observability/profiler.py`)
partitions every program's first dispatch into what jax reported of it
(`trace_ms`, `lower_ms`, `compile_ms` where the persistent cache missed,
`load_ms` where it hit) and the remainder `run_ms`, and times the engine's
start by phase under `perf["setup"]`. The snapshot read is the one at the
window's open: a run may compile nothing after it, so every first dispatch
lies before it. A program without `perf["setup"]` (a parent commit,
`ACP_PROF=0`) gives `analyse(run) is None`: every metric read from here is
left out and nothing is printed.

    sums(perf) -> {"trace_s", "lower_s", "compile_s", "load_s", "run_s", "init_s", "prewarm_rest_s", "programs", "cache_misses"}
    analyse(run) -> the same for one run, printed once as a `[setup]` line
    value(run, key) -> one of them, for a reader
"""

from __future__ import annotations

import json

COSTLIEST = 12  # programs named on the line
PARTS = ("trace", "lower", "compile", "load", "run")


def sums(perf: dict) -> dict | None:
    """The nine numbers the readers report; None where the program keeps no
    account of its start. `(outside)`, jax's seconds on the engine's threads
    between programs, is in the four sums it has parts of."""
    setup = perf.get("setup")
    if setup is None:
        return None
    rows = list(perf.get("programs", {}).values()) + [setup.get("outside", {})]
    out = {f"{part}_s": sum(r.get(f"{part}_ms", 0.0) for r in rows) / 1e3 for part in PARTS}
    out["init_s"] = float(setup.get("phases", {}).get("init", {}).get("s", 0.0))
    out["prewarm_rest_s"] = float(setup.get("prewarm_rest_s", 0.0))
    out["programs"] = float(setup.get("programs", 0))
    out["cache_misses"] = float(setup.get("cache_misses", 0))
    return out


def analyse(run) -> dict | None:
    """Once a run: the sums, kept on the run for the other readers, and
    the `[setup]` line."""
    if not hasattr(run, "setup_phases"):
        perf = run.stats.get("open", {}).get("perf", {})
        run.setup_phases = sums(perf)
        if run.setup_phases is not None:
            print(f"[setup] {line(perf, run.setup_phases, getattr(run, 'setup_s', None))}", flush=True)
    return run.setup_phases


def value(run, key: str) -> float | None:
    found = analyse(run)
    return None if found is None else found[key]


def line(perf: dict, found: dict, setup_s: float | None) -> str:
    setup, programs = perf["setup"], perf.get("programs", {})
    phases = {name: round(row["s"], 3) for name, row in setup.get("phases", {}).items()}
    first_wall_s = sum(r.get("first_wall_ms", 0.0) for r in programs.values()) / 1e3
    five = " ".join(f"{part} {found[part + '_s']:.3f}" for part in PARTS)
    costliest = sorted(programs.items(), key=lambda kv: -kv[1].get("first_wall_ms", 0.0))[:COSTLIEST]
    lost = [key for key, r in programs.items() if r.get("cache_hit") is False]
    tables, stray = setup.get("compiles", 0), setup.get("unattributed", {}).get("compiles", 0)
    text = (f"phases s {json.dumps(phases)}; {int(found['programs'])} programs first dispatched in {first_wall_s:.3f}s; "
            f"with (outside) s: {five}; cache misses {int(found['cache_misses'])}, retraces {setup.get('retraces', 0)}, "
            f"first dispatches after prewarm {setup.get('after_prewarm', 0)}; backend compiles or loads {tables} in the "
            f"tables + {stray} unattributed = {tables + stray}; the cache's hits stood for {setup.get('saved_s', 0.0):.1f}s "
            f"of compiling; prewarm less its first dispatches {found['prewarm_rest_s']:.3f}s; costliest "
            f"(trace/lower/compile|load/run s): {', '.join(_program(k, r) for k, r in costliest) or 'none'}")
    if lost and any(r.get("cache_hit") for r in programs.values()):  # some hit: a warm run that was not
        text += f"; the cache had lost {len(lost)}: {' '.join(lost[:COSTLIEST])}"
    if setup_s is not None:
        rest = setup_s - found["init_s"] - first_wall_s - found["prewarm_rest_s"]
        # negative: `setup_s` is not this run's own clock (a rehearsal's stand-in)
        text += (f"; setup_s {setup_s:.3f} less init, first dispatches and prewarm's rest: {rest:.3f}s the benchmark's own "
                 "(imports, weights, the replay passes' waiting)" if rest >= 0 else "; setup_s is not this run's own")
    return text


def _program(key: str, r: dict) -> str:
    hit = {True: "hit", False: "miss", None: "-"}[r.get("cache_hit")]
    stage = r.get("load_ms", 0.0) if r.get("cache_hit") else r.get("compile_ms", 0.0)
    return (f"{key} {r.get('trace_ms', 0.0) / 1e3:.2f}/{r.get('lower_ms', 0.0) / 1e3:.2f}/{stage / 1e3:.2f}/"
            f"{r.get('run_ms', 0.0) / 1e3:.2f} {hit}")
