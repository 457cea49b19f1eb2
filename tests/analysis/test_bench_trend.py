"""bench-trend: the perf-trajectory sentinel (analysis/bench_trend.py).

A sentinel that can't trip detects nothing: fixtures synthesize a
BENCH_PR*.json trajectory and assert both directions — healthy trends
pass, regressions past tolerance exit nonzero — plus the robustness
posture (missing metrics skipped, cross-platform samples never compared,
CPU headline samples tabulated but not judged, unparseable docs skipped).
"""

import json
from pathlib import Path

from agentcontrolplane_tpu.analysis.__main__ import main as lint_main
from agentcontrolplane_tpu.analysis.bench_trend import (
    check_trend,
    load_docs,
    main as trend_main,
)


def _doc(tmp_path: Path, pr: int, **fields) -> None:
    (tmp_path / f"BENCH_PR{pr}.json").write_text(json.dumps(fields))


def test_load_docs_orders_by_pr_and_skips_garbage(tmp_path):
    _doc(tmp_path, 10, value=2.0)
    _doc(tmp_path, 2, value=1.0)
    (tmp_path / "BENCH_PR7.json").write_text("{not json")
    (tmp_path / "OTHER.json").write_text("{}")
    docs = load_docs(tmp_path)
    assert [pr for pr, _, _ in docs] == [2, 10]


def test_healthy_trajectory_passes(tmp_path):
    plat = {"backend": "tpu"}
    _doc(tmp_path, 6, value=1000.0, platform=plat)
    _doc(tmp_path, 7, value=1100.0, platform=plat,
         flight={"overhead_pct": 0.5})
    _doc(tmp_path, 9, value=980.0, platform=plat,  # within -35% of 1100
         flight={"overhead_pct": 0.8},
         prof={"overhead_pct": 0.4, "goodput_ratio": 0.8})
    table, regressions = check_trend(tmp_path)
    assert regressions == []
    assert "decode_tok_s_per_chip" in table and "PR9" in table
    assert trend_main(tmp_path) == 0


def test_headline_regression_past_tolerance_trips(tmp_path):
    plat = {"backend": "tpu"}
    _doc(tmp_path, 6, value=1000.0, platform=plat)
    _doc(tmp_path, 7, value=500.0, platform=plat)  # -50% > the 35% tol
    _, regressions = check_trend(tmp_path)
    assert [r.metric for r in regressions] == ["decode_tok_s_per_chip"]
    assert "BENCH_PR6.json" in regressions[0].detail
    assert trend_main(tmp_path) == 1


def test_cpu_headline_samples_are_tabulated_but_never_judged(tmp_path):
    """CPU fallback throughput varies with machine load and fixture knobs
    (the real docs show 100x spread) — absolute-throughput metrics only
    judge accelerator-backend samples."""
    _doc(tmp_path, 6, value=8000.0, platform={"backend": "cpu"})
    _doc(tmp_path, 7, value=75.0, platform={"backend": "cpu"})
    table, regressions = check_trend(tmp_path)
    assert regressions == []
    assert "8000.000" in table and "75.000" in table


def test_cross_platform_samples_never_compared(tmp_path):
    _doc(tmp_path, 6, value=8000.0, platform={"backend": "gpu"})
    _doc(tmp_path, 7, value=75.0, platform={"backend": "tpu"})
    _, regressions = check_trend(tmp_path)
    assert regressions == []  # different accelerators: no baseline pair


def test_overhead_contract_ceiling_trips_absolutely(tmp_path):
    """The flight/prof overhead guards carry an absolute ceiling (their
    docs state a <2% contract; 3% is the noise-margin alarm) — one doc is
    enough to trip it, no baseline needed."""
    _doc(tmp_path, 12, platform={"backend": "cpu"},
         prof={"overhead_pct": 5.5})
    _, regressions = check_trend(tmp_path)
    assert [r.metric for r in regressions] == ["prof_overhead_pct"]
    assert "ceiling" in regressions[0].detail


def test_missing_metrics_and_empty_dir_are_skipped(tmp_path):
    _doc(tmp_path, 6, platform={"backend": "cpu"})  # no metrics at all
    _, regressions = check_trend(tmp_path)
    assert regressions == []
    empty = tmp_path / "empty"
    empty.mkdir()
    table, regressions = check_trend(empty)
    assert "no BENCH_PR" in table and regressions == []
    assert trend_main(empty) == 0


def test_runner_bench_trend_flag(tmp_path, capsys):
    _doc(tmp_path, 6, value=1000.0, platform={"backend": "tpu"})
    _doc(tmp_path, 7, value=400.0, platform={"backend": "tpu"})
    assert lint_main(["--bench-trend", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "regression" in out and "decode_tok_s_per_chip" in out
    # the repo's own trajectory is the advisory CI input: it must parse
    repo_root = Path(__file__).resolve().parents[2]
    assert lint_main(["--bench-trend", str(repo_root)]) in (0, 1)
