"""The nine per-layer metrics under `setup_s` (acpbench/setup_phases.py):
their entries in BENCHMARK.json, each reader's sum over a hand-written
`stats["open"]["perf"]`, and the one `[setup]` line a run."""

import os
from types import SimpleNamespace as NS

import pytest

from acpbench import setup_phases, spec

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
LAYERS = {
    "setup_trace_s": "programs", "setup_lower_s": "programs", "setup_compile_s": "programs",
    "setup_cache_load_s": "programs", "setup_first_run_s": "programs", "setup_engine_init_s": "KV manager",
    "setup_prewarm_rest_s": "scheduler", "setup_programs": "scheduler", "setup_cache_misses": "programs",
}


def _row(first_wall_ms, trace_ms, lower_ms, compile_ms, load_ms, cache_hit):
    run_ms = first_wall_ms - trace_ms - lower_ms - compile_ms - load_ms
    return {"first_wall_ms": first_wall_ms, "trace_ms": trace_ms, "lower_ms": lower_ms, "compile_ms": compile_ms,
            "load_ms": load_ms, "run_ms": run_ms, "cache_hit": cache_hit, "compiles": 1, "dispatches": 3}


PERF = {
    "programs": {
        "decode[paged,32x16]": _row(9000.0, 5000.0, 2500.0, 0.0, 1250.0, True),
        "prefill[paged,512x4]": _row(20000.0, 6000.0, 3000.0, 10000.0, 0.0, False),
        "prefill_cont[paged,512x4]": _row(2.0, 0.0, 0.0, 0.0, 0.0, None),
    },
    "setup": {
        "phases": {"init": {"s": 14.5, "n": 1, "jax_s": 3.0, "compiles": 2, "first_wall_s": 0.0},
                   "init.pool": {"s": 9.25, "n": 1, "jax_s": 2.5, "compiles": 1, "first_wall_s": 0.0},
                   "prewarm": {"s": 40.0, "n": 1, "jax_s": 0.0, "compiles": 0, "first_wall_s": 29.002}},
        "programs": 3, "compiles": 7, "cache_misses": 1, "after_prewarm": 0, "retraces": 2,
        "prewarm_rest_s": 10.998, "saved_s": 55.5,
        "outside": {"trace_ms": 500.0, "lower_ms": 250.0, "compile_ms": 125.0, "load_ms": 750.0, "cache_hit": False,
                    "compiles": 3},
        "unattributed": {"compiles": 4, "s": 1.5},
    },
}
EMPTY = {"programs": {}, "setup": {"phases": {}, "programs": 0, "compiles": 0, "cache_misses": 0, "after_prewarm": 0,
                                   "retraces": 0, "prewarm_rest_s": 0.0, "saved_s": 0.0,
                                   "outside": {"trace_ms": 0.0, "lower_ms": 0.0, "compile_ms": 0.0, "load_ms": 0.0,
                                               "cache_hit": None, "compiles": 0},
                                   "unattributed": {"compiles": 0, "s": 0.0}}}
# what the issue's table states: the programs' rows and (outside) summed, seconds
WANT = {
    "setup_trace_s": 11.5, "setup_lower_s": 5.75, "setup_compile_s": 10.125, "setup_cache_load_s": 2.0,
    "setup_first_run_s": 1.252, "setup_engine_init_s": 14.5, "setup_prewarm_rest_s": 10.998, "setup_programs": 3.0,
    "setup_cache_misses": 1.0,
}


def _run(perf, **kw):
    return NS(stats={"open": {"perf": perf}}, **kw)


def _entry(name):
    found = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert len(found) == 1, name
    return found[0]


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_the_entry_moves_setup_s_in_every_accepted_cell(name):
    m = _entry(name)
    assert m["workloads"] == CELLS[:10] and len(m["workloads"]) == 10  # explicit: a later cell is not given it unasked
    assert (m["source"], m["moves"], m["better"], m["layer"]) == ("program_counter", "setup_s", "lower", LAYERS[name])
    assert m["unit"] == ("count" if name in ("setup_programs", "setup_cache_misses") else "s")
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}


def test_they_are_the_only_metrics_under_setup_s_and_the_file_keeps_its_size():
    assert {m["name"] for m in BENCH["per_layer"] if m["moves"] == "setup_s"} == set(LAYERS)
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_the_reader_sums_what_the_table_states(name, capsys):
    read = spec.reader("per_layer", name).read
    assert read(_run(PERF)) == pytest.approx(WANT[name], abs=1e-9)
    assert read(_run(EMPTY)) == 0.0
    # a program without the account (a parent commit, ACP_PROF=0): nothing read, nothing printed
    capsys.readouterr()
    assert read(_run({"programs": PERF["programs"]})) is None and read(NS(stats={})) is None
    assert capsys.readouterr().out == ""


def test_no_reader_takes_the_runs_setup_s_into_its_value():
    for name in LAYERS:
        read = spec.reader("per_layer", name).read
        assert read(_run(PERF, setup_s=1.0)) == read(_run(PERF, setup_s=250.0)) == read(_run(PERF))


def test_one_setup_line_a_run_for_all_the_readers(capsys):
    run = _run(PERF, setup_s=100.0)
    for name in ("setup_trace_s", "setup_programs", "setup_cache_misses"):
        spec.reader("per_layer", name).read(run)
    out = capsys.readouterr().out
    lines = [line for line in out.splitlines() if line.startswith("[setup] ")]
    assert len(lines) == 1 == len(out.splitlines())
    line = lines[0]
    assert '"init": 14.5' in line and '"init.pool": 9.25' in line
    assert "trace 11.500 lower 5.750 compile 10.125 load 2.000 run 1.252" in line
    assert "3 programs first dispatched in 29.002s" in line
    assert "cache misses 1, retraces 2" in line and "7 in the tables + 4 unattributed = 11" in line
    assert "prefill[paged,512x4] 6.00/3.00/10.00/1.00 miss, decode[paged,32x16] 5.00/2.50/1.25/0.25 hit" in line
    assert "the cache had lost 1: prefill[paged,512x4]" in line
    # 100 - 14.5 (init) - 29.002 (first dispatches) - 10.998 (prewarm's rest)
    assert "45.500s the benchmark's own" in line


def test_the_line_names_at_most_twelve_programs_and_knows_a_borrowed_setup_s():
    programs = {f"decode[paged,{n}x16]": _row(100.0 + n, 50.0, 25.0, 0.0, 12.5, True) for n in range(1, 20)}
    perf = {"programs": programs, "setup": dict(PERF["setup"], programs=19, cache_misses=0)}
    line = setup_phases.line(perf, setup_phases.sums(perf), 1.0)  # a rehearsal's stand-in for the clock
    assert line.count("decode[paged,") == setup_phases.COSTLIEST
    assert "decode[paged,19x16]" in line and "decode[paged,7x16]" not in line
    assert "the cache had lost" not in line and line.endswith("setup_s is not this run's own")
    # a cold run lost nothing: no program hit, and a key that found its executable under another key asked no cache
    cold = {key: dict(r, cache_hit=False, compile_ms=r["load_ms"], load_ms=0.0) for key, r in programs.items()}
    cold["prefill_cont[paged,512x1]"] = _row(25.0, 0.0, 0.0, 0.0, 0.0, None)
    perf = {"programs": cold, "setup": dict(PERF["setup"], programs=20, cache_misses=19)}
    line = setup_phases.line(perf, setup_phases.sums(perf), None)
    assert "cache misses 19" in line and "the cache had lost" not in line and "setup_s" not in line
