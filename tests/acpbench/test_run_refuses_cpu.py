"""The command on a machine without the cell's chips: no result, exit
code other than 0. And in a directory that holds only BENCHMARK.json and
the benchmark's own files: the same."""

import json
import os
import shutil
import subprocess
import sys

from acpbench import spec

BENCH = spec.benchmark()


def _run(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    return subprocess.run(
        [sys.executable if c == "python3" else c for c in BENCH["command"]]
        + ["--workload", workload, "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _result_lines(out):
    found = []
    for line in out.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and "metrics" in doc:
            found.append(doc)
    return found


def test_no_chip_no_number():
    for cell in BENCH["workloads"]:
        got = _run(spec.ROOT, cell["name"])
        assert got.returncode not in (0, None), got.stdout[-400:]
        assert not _result_lines(got.stdout)
        assert "TPU" in got.stderr


def test_only_the_benchmarks_files_is_not_enough(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(spec.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    got = _run(str(tmp_path), BENCH["workloads"][0]["name"])
    assert got.returncode != 0 and not _result_lines(got.stdout)
