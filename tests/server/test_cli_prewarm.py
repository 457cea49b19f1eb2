"""`acp-tpu run` prewarms in a background thread. A prewarm that raises
must not die silently while the server stays up: the engine is stopped,
the error kept, and the failure hook runs (cmd_run exits non-zero on it;
chip_smoke.py joins the same thread and fails on `error`)."""

from __future__ import annotations

from agentcontrolplane_tpu.cli import EnginePrewarm


class _StubEngine:
    def __init__(self, fail: bool):
        self.fail = fail
        self.stopped = False
        self.prewarmed_with = None

    def prewarm(self, constrained: bool = False) -> None:
        self.prewarmed_with = constrained
        if self.fail:
            raise RuntimeError("Mosaic failed to compile TPU kernel: synthetic")

    def stop(self) -> None:
        self.stopped = True


def test_prewarm_failure_stops_the_engine_and_reports():
    eng = _StubEngine(fail=True)
    hooked = []
    t = EnginePrewarm(eng, on_failure=lambda: hooked.append(True))
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert isinstance(t.error, RuntimeError) and "Mosaic" in str(t.error)
    assert eng.stopped, "a failed prewarm must fail the engine, not leave it serving"
    assert hooked == [True]


def test_prewarm_success_leaves_the_engine_running():
    eng = _StubEngine(fail=False)
    hooked = []
    t = EnginePrewarm(eng, on_failure=lambda: hooked.append(True))
    t.start()
    t.join(timeout=30)
    assert t.error is None and not eng.stopped and hooked == []
    assert eng.prewarmed_with is True  # the grammar-masked programs too
