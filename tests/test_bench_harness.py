"""The bench parent/child watchdog protocol (bench.py).

Rounds 1 and 2 both shipped BENCH_rNN.json = 0.0 because the bench's main
process initialized PJRT itself and hung on the attach. The round-3
contract: the parent NEVER touches PJRT, children report MARK/RESULT lines,
and the parent kills + retries a child that misses a mark deadline. These
tests drive that protocol against stub children (no JAX involved). The
device contract rides along: the required backend is ``tpu``, and no chip
means a non-zero exit with no number printed.
"""

from __future__ import annotations

import os
import sys
import textwrap
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench


@pytest.fixture
def stub_child(tmp_path, monkeypatch):
    """Point bench._THIS at a stub script; returns a setter for its body."""

    def make(body: str) -> str:
        path = tmp_path / "stub_child.py"
        path.write_text(
            "import sys, time, json\n" + textwrap.dedent(body)
        )
        monkeypatch.setattr(bench, "_THIS", str(path))
        return str(path)

    return make


def test_phase_run_collects_marks_and_results(stub_child):
    stub_child(
        """
        print("MARK attach_ok 1", flush=True)
        print("diagnostic noise", flush=True)
        print("MARK engine_built", flush=True)
        print('RESULT headline {"tok_s_per_chip": 123.4, "note": "n"}', flush=True)
        """
    )
    run = bench._PhaseRun(["--phase", "main"])
    status = run.run_schedule(
        [("attach_ok", 10), ("engine_built", 10), ("RESULT headline", 10)],
        hard_deadline=time.monotonic() + 30,
    )
    assert status == "ok"
    assert run.results["headline"]["tok_s_per_chip"] == 123.4


def test_phase_run_kills_child_that_misses_a_mark(stub_child):
    stub_child(
        """
        print("MARK attach_ok 1", flush=True)
        time.sleep(600)  # simulates a hung PJRT attach after the first mark
        """
    )
    run = bench._PhaseRun(["--phase", "main"])
    t0 = time.monotonic()
    status = run.run_schedule(
        [("attach_ok", 10), ("engine_built", 2), ("RESULT headline", 10)],
        hard_deadline=time.monotonic() + 60,
    )
    assert status == "engine_built"
    assert time.monotonic() - t0 < 30  # did not wait out the sleep
    assert run.proc.poll() is not None  # child is dead


def test_phase_run_keeps_partial_results_from_killed_child(stub_child):
    stub_child(
        """
        print("MARK attach_ok 1", flush=True)
        print("MARK engine_built", flush=True)
        print("MARK warm_done", flush=True)
        print('RESULT headline {"tok_s_per_chip": 999.0, "note": "n"}', flush=True)
        time.sleep(600)  # hangs during the TTFT leg
        """
    )
    run = bench._PhaseRun(["--phase", "main"])
    status = run.run_schedule(
        [("attach_ok", 10), ("engine_built", 10), ("warm_done", 10),
         ("RESULT headline", 10), ("RESULT ttft", 2)],
        hard_deadline=time.monotonic() + 60,
    )
    assert status == "RESULT ttft"
    assert run.results["headline"]["tok_s_per_chip"] == 999.0  # partial kept


def test_phase_run_child_exit_without_mark_is_a_miss(stub_child):
    stub_child(
        """
        print("MARK attach_ok 1", flush=True)
        sys.exit(3)  # crashed before building the engine
        """
    )
    run = bench._PhaseRun(["--phase", "main"])
    status = run.run_schedule(
        [("attach_ok", 10), ("engine_built", 5)],
        hard_deadline=time.monotonic() + 30,
    )
    assert status == "engine_built"


def test_unparseable_result_line_does_not_crash_reader(stub_child):
    stub_child(
        """
        print("RESULT headline {not json", flush=True)
        print('RESULT headline {"tok_s_per_chip": 1.0}', flush=True)
        """
    )
    run = bench._PhaseRun(["--phase", "main"])
    status = run.run_schedule(
        [("RESULT headline", 10)], hard_deadline=time.monotonic() + 30
    )
    assert status == "ok"
    assert run.results["headline"] == {"tok_s_per_chip": 1.0}


def test_parent_never_imports_engine_or_inits_pjrt():
    """Static contract: the parent path must not call jax.devices() or
    import the engine — only children may. Guards against regressing to the
    r01/r02 architecture."""
    import ast
    import inspect

    parent_src = textwrap.dedent(inspect.getsource(bench._parent)) + "\n" + textwrap.dedent(
        inspect.getsource(bench._parent_run)
    )
    tree = ast.parse(parent_src)
    calls = [
        n for n in ast.walk(tree)
        if isinstance(n, ast.Attribute) and n.attr in ("devices", "local_devices")
    ]
    assert not calls, "parent must never call jax.devices()"
    imports = [
        n for n in ast.walk(tree)
        if isinstance(n, (ast.Import, ast.ImportFrom))
        and "agentcontrolplane_tpu" in ast.dump(n)
    ]
    assert not imports, "parent must not import the engine package"


def _fake_probe(monkeypatch, backend: str, kind: str):
    import subprocess as sp

    def fake_run(argv, **kw):
        return sp.CompletedProcess(
            argv, 0,
            stdout='{"backend": "%s", "n": 1, "device_kind": "%s"}\n' % (backend, kind),
            stderr="",
        )

    monkeypatch.setattr(bench.subprocess, "run", fake_run)


def test_probe_rejects_cpu_fallback(monkeypatch):
    """When JAX finds no chip it silently reports CPU devices. The probe
    must read that as no chip, not success."""
    monkeypatch.delenv("ACP_BENCH_ALLOW_CPU", raising=False)
    _fake_probe(monkeypatch, "cpu", "cpu")
    assert bench._probe_once(5.0) is None
    monkeypatch.setenv("ACP_BENCH_ALLOW_CPU", "1")
    assert bench._probe_once(5.0)["backend"] == "cpu"


@pytest.mark.parametrize("backend", ["gpu", "METAL", "remote-plugin", ""])
def test_probe_requires_the_tpu_backend_by_name(monkeypatch, backend):
    """"tpu" is required, not merely "not cpu": any other platform name is
    no chip — with or without the CPU opt-in."""
    _fake_probe(monkeypatch, backend, "some accelerator")
    monkeypatch.delenv("ACP_BENCH_ALLOW_CPU", raising=False)
    assert bench._probe_once(5.0) is None
    monkeypatch.setenv("ACP_BENCH_ALLOW_CPU", "1")
    assert bench._probe_once(5.0) is None


def test_probe_accepts_tpu_backend(monkeypatch):
    _fake_probe(monkeypatch, "tpu", "TPU v5 lite")
    info = bench._probe_once(5.0)
    assert info == {"backend": "tpu", "n": 1, "device_kind": "TPU v5 lite"}


def test_parent_flushes_headline_incrementally(stub_child, monkeypatch, capsys):
    """r3 root cause (b): the driver SIGKILLed before the final emit. The
    parent must re-print the JSON line the moment the headline result lands,
    so the freshest flushed line already carries the number."""
    import json

    stub_child(
        """
        print("MARK attach_ok 1", flush=True)
        print("MARK engine_built", flush=True)
        print("MARK warm_done", flush=True)
        print('RESULT headline {"tok_s_per_chip": 777.0, "note": "stub"}', flush=True)
        """
    )
    monkeypatch.setattr(
        bench, "_probe_once",
        lambda *a, **k: {"backend": "tpu", "n": 1, "device_kind": "TPU v5e"},
    )
    monkeypatch.setenv("ACP_BENCH_TTFT", "0")
    monkeypatch.setenv("ACP_BENCH_AB", "0")
    monkeypatch.setenv("ACP_BENCH_TOTAL_BUDGET_S", "600")
    bench._parent()
    lines = [
        json.loads(ln)
        for ln in capsys.readouterr().out.strip().splitlines()
        if ln.startswith("{")
    ]
    # ≥3 flushes: platform probe, headline capture, final
    assert len(lines) >= 3
    assert lines[0]["platform"]["backend"] == "tpu"
    assert lines[0]["value"] == 0.0
    # the headline-capture flush (not just the final one) carries the number
    assert lines[1]["value"] == 777.0
    assert lines[-1]["value"] == 777.0
    assert lines[-1]["vs_baseline"] == 0.777


def test_no_chip_is_nonzero_and_prints_no_number(monkeypatch, capsys, tmp_path):
    """No tpu backend: the parent returns non-zero and stdout carries no
    JSON line at all — not a zero headline, and not an older run's number
    (a last-known-good file lying around must never be surfaced)."""
    import json

    stale = tmp_path / "last_known_good.json"
    stale.write_text(json.dumps({
        "value": 1428.9, "platform": {"backend": "tpu", "device_kind": "TPU v5e"},
    }))
    monkeypatch.setenv("ACP_BENCH_LKG_PATH", str(stale))
    monkeypatch.setenv("ACP_BENCH_PR_DOC", str(tmp_path / "BENCH_PR0.json"))
    monkeypatch.setattr(bench, "_probe_once", lambda *a, **k: None)
    assert bench._parent() != 0
    out = capsys.readouterr().out
    assert out.strip() == "", f"no chip must print no result, got: {out!r}"
    assert not (tmp_path / "BENCH_PR0.json").exists()


def test_no_chip_does_not_wait_for_one(monkeypatch):
    """One probe, no retry window: a missing chip is reported at once
    instead of sleeping for minutes in case a link comes back."""
    calls = []

    def probe(timeout_s):
        calls.append(timeout_s)
        return None

    monkeypatch.setattr(bench, "_probe_once", probe)
    monkeypatch.setattr(
        bench.time, "sleep",
        lambda s: (_ for _ in ()).throw(AssertionError("parent slept waiting for a chip")),
    )
    assert bench._parent() != 0
    assert len(calls) == 1


def test_bench_cli_without_a_chip_exits_nonzero_with_empty_stdout(tmp_path):
    """The acceptance check, end to end: `python bench.py` where JAX sees
    only the CPU (and without the ACP_BENCH_ALLOW_CPU opt-in) exits
    non-zero and prints no number."""
    import subprocess

    env = {
        k: v for k, v in os.environ.items()
        if not k.startswith("ACP_BENCH_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, bench.__file__],
        capture_output=True, text=True, timeout=240, env=env, cwd=str(tmp_path),
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "no tpu backend" in proc.stderr


def test_child_refuses_a_non_tpu_attach(monkeypatch):
    """The child applies the same rule as the probe (the chip can vanish
    between the two): one definition, `_backend_ok`."""
    monkeypatch.delenv("ACP_BENCH_ALLOW_CPU", raising=False)
    assert bench._backend_ok("tpu")
    assert not bench._backend_ok("cpu")
    assert not bench._backend_ok("gpu")
    assert not bench._backend_ok(None)
    monkeypatch.setenv("ACP_BENCH_ALLOW_CPU", "1")
    assert bench._backend_ok("cpu") and bench._backend_ok("tpu")
    assert not bench._backend_ok("gpu")


def test_flops_model_matches_hand_count():
    """The MFU denominator/numerator on a tiny known config: hand-counted
    matmul weights and attention-score FLOPs must agree exactly."""
    from types import SimpleNamespace

    c = SimpleNamespace(
        dim=8, n_layers=2, n_heads=2, n_kv_heads=1, head_dim=4,
        ffn_dim=16, vocab_size=32, n_experts=0, experts_per_token=0,
    )
    # per layer: Wq 8*8 + Wk 8*4 + Wv 8*4 + Wo 8*8 = 192; mlp 3*8*16 = 384
    # total: 2*(192+384) + lm_head 8*32 = 1408
    assert bench._matmul_params(c) == 1408.0
    # decode at ctx=10: 2*1408 + 4*2*2*4*10 = 2816 + 640
    assert bench._flops_per_token(c, 10.0) == 2816.0 + 640.0
    # MoE variant: active experts replace the dense FFN, router added
    cm = SimpleNamespace(
        dim=8, n_layers=2, n_heads=2, n_kv_heads=1, head_dim=4,
        ffn_dim=16, vocab_size=32, n_experts=4, experts_per_token=2,
    )
    # mlp: 3*8*16*2 + 8*4 = 800; total 2*(192+800) + 256 = 2240
    assert bench._matmul_params(cm) == 2240.0


def test_peak_flops_lookup():
    assert bench._peak_flops_per_chip("TPU v5e") == 197e12
    # the string a directly attached v5e reports (chip_smoke.py prints it)
    assert bench._peak_flops_per_chip("TPU v5 lite") == 197e12
    assert bench._peak_flops_per_chip("TPU v4") == 275e12
    # an unknown device is None (callers then report no MFU), never a default
    assert bench._peak_flops_per_chip("cpu") is None
    assert bench._peak_flops_per_chip("TPU v9 hypothetical") is None
    assert bench._peak_flops_per_chip("") is None


def test_parent_surfaces_mfu_from_headline(stub_child, monkeypatch, capsys):
    import json

    stub_child(
        """
        print("MARK attach_ok 1", flush=True)
        print("MARK engine_built", flush=True)
        print("MARK warm_done", flush=True)
        print('RESULT headline {"tok_s_per_chip": 777.0, "mfu": 0.31, "note": "stub"}', flush=True)
        """
    )
    monkeypatch.setattr(
        bench, "_probe_once",
        lambda *a, **k: {"backend": "tpu", "n": 1, "device_kind": "TPU v5e"},
    )
    monkeypatch.setenv("ACP_BENCH_TTFT", "0")
    monkeypatch.setenv("ACP_BENCH_AB", "0")
    monkeypatch.setenv("ACP_BENCH_TOTAL_BUDGET_S", "600")
    bench._parent()
    lines = [
        json.loads(ln)
        for ln in capsys.readouterr().out.strip().splitlines()
        if ln.startswith("{")
    ]
    assert lines[-1]["mfu"] == 0.31


def test_parent_emits_json_line_even_when_run_raises(monkeypatch, capsys):
    """A parent-side crash must still print the one JSON line (driver
    contract) — the r01/r02 artifacts were unusable precisely because a
    failure path skipped the emit."""
    import json

    def boom(doc, notes):
        doc["value"] = 0.0
        raise RuntimeError("synthetic parent failure")

    monkeypatch.setattr(bench, "_parent_run", boom)
    bench._parent()
    out = capsys.readouterr().out.strip().splitlines()
    doc = json.loads(out[-1])
    assert doc["metric"] == "decode_tok_s_per_chip"


def test_burst_flops_counts_lm_head_once_per_prefill():
    """The engine's prefill computes logits only at the LAST prompt
    position, so the lm_head matmul must be charged once per prefill —
    charging it per prompt token overstates prefill FLOPs (and MFU)."""
    from types import SimpleNamespace

    c = SimpleNamespace(
        dim=8, n_layers=2, n_heads=2, n_kv_heads=1, head_dim=4,
        ffn_dim=16, vocab_size=32, n_experts=0, experts_per_token=0,
    )
    head = 2.0 * c.dim * c.vocab_size  # 512
    P = 10  # prompt_len
    per_tok = bench._flops_per_token(c, P / 2.0)
    # one prefill, no decode: P layer-tokens + ONE head matmul
    got = bench._burst_model_flops(c, P, prefills=1, gen_tokens=0, mean_ctx=0.0)
    assert got == P * (per_tok - head) + head
    assert got < P * per_tok  # strictly below the old per-token-head count
    # decode tokens still pay the head every step (they each sample)
    got2 = bench._burst_model_flops(c, P, prefills=1, gen_tokens=3, mean_ctx=12.0)
    assert got2 == got + 3 * bench._flops_per_token(c, 12.0)


def test_write_pr_doc_emits_and_respects_absence(tmp_path, monkeypatch):
    """ACP_BENCH_PR_DOC persists the final doc (per-PR perf trajectory);
    unset, nothing is written and the headline contract is untouched."""
    import json

    import bench

    doc = {"metric": "decode_tok_s_per_chip", "value": 1.0,
           "tool_turn": {"saved_pct": 42.0}}
    monkeypatch.delenv("ACP_BENCH_PR_DOC", raising=False)
    bench._write_pr_doc(doc)  # no env -> no-op, no crash

    path = tmp_path / "BENCH_PR999.json"
    monkeypatch.setenv("ACP_BENCH_PR_DOC", str(path))
    bench._write_pr_doc(doc)
    saved = json.loads(path.read_text())
    assert saved["tool_turn"]["saved_pct"] == 42.0
    assert saved["measured_at"]  # provenance stamp rides along
