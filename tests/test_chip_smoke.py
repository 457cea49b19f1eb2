"""chip_smoke.py's contract where there is no chip: it must FAIL — exit
non-zero, last stdout line `{"ok": false, ...}` — when jax finds no TPU,
and in a directory that holds the script and nothing else of the repo.
(The passing side needs a chip: `chiprun -- python chip_smoke.py`.)"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(script: str, cwd: str, *argv: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script, *argv],
        capture_output=True, text=True, timeout=300, env=env, cwd=cwd,
    )


@pytest.mark.parametrize("argv", [(), ("--chips", "4")], ids=["one-chip", "four-chips"])
def test_no_tpu_is_a_failure_not_a_cpu_run(argv):
    proc = _run(SCRIPT, REPO, *argv)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"  # as jax reports it
    assert "no TPU" in proc.stderr
    # it stopped at the device phase: nothing was served on the CPU
    assert "[serve" not in proc.stdout and "[kernel]" not in proc.stdout


def test_script_alone_without_the_program_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert proc.returncode != 0
    assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"] is False


def test_smoke_never_sets_the_platform():
    """No `jax.config.update("jax_platforms", ...)` on the smoke's path:
    where jax runs is the environment's business."""
    src = open(SCRIPT).read()
    assert "jax_platforms" not in src and "JAX_PLATFORMS" not in src


def test_parse_metrics_sums_label_sets():
    sys.path.insert(0, REPO)
    import chip_smoke

    text = (
        "# HELP acp_engine_kernel_fallbacks_total x\n"
        'acp_engine_kernel_fallbacks_total{kernel="paged_decode",reason="head_dim"} 1\n'
        'acp_engine_kernel_fallbacks_total{kernel="other",reason="y"} 2\n'
        "acp_engine_restarts_total 0\n"
    )
    got = chip_smoke.parse_metrics(text)
    assert got["acp_engine_kernel_fallbacks_total"] == 3.0
    assert got["acp_engine_restarts_total"] == 0.0
    assert "acp_engine_crashes_total" not in got  # absent = never counted
