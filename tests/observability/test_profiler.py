"""DispatchProfiler unit behavior (observability/profiler.py): program
aggregation, sampled device timing, the cold-compile observatory, and the
goodput/waste ledger's conservation-by-construction."""

import numpy as np
import pytest

from agentcontrolplane_tpu.observability.metrics import REGISTRY
from agentcontrolplane_tpu.observability.profiler import (
    DispatchProfiler,
    WASTE_CAUSES,
)


def counter(name: str, **labels) -> float:
    m = REGISTRY._metrics.get(name)
    if m is None:
        return 0.0
    return m.values.get(tuple(sorted(labels.items())), 0.0)


def _conserved(prof: DispatchProfiler) -> bool:
    led = prof.ledger()
    return led["computed"] == led["goodput"] + sum(led["waste"].values())


def test_record_aggregates_per_program_and_observes_histogram():
    prof = DispatchProfiler(enabled=True, sample_every=2)
    for _ in range(5):
        t0 = prof.start()
        prof.record("decode[slot,4x4]", t0, out=np.zeros(4),
                    real_tokens=12, padded_tokens=4, real_slots=3,
                    padded_slots=1)
    doc = prof.stats()
    p = doc["programs"]["decode[slot,4x4]"]
    assert p["dispatches"] == 5
    assert p["real_tokens"] == 60 and p["padded_tokens"] == 20
    assert p["padding_pct"] == 25.0
    assert p["real_slots"] == 15 and p["padded_slots"] == 5
    # sampling: first always blocks, then every 2nd (dispatches 0, 2, 4)
    assert p["device_samples"] == 3
    assert p["device_ms_mean"] is not None
    assert p["host_ms_mean"] >= 0.0 and p["first_wall_ms"] >= 0.0
    count, window = REGISTRY.series_window(
        "acp_engine_dispatch_seconds", {"program": "decode[slot,4x4]"}
    )
    assert count >= 5


def test_cold_compiles_only_after_mark_prewarmed():
    prof = DispatchProfiler(enabled=True)
    before = counter("acp_engine_cold_compiles_total")
    prof.record("prefill[slot,64x1]", prof.start())
    assert prof.stats()["cold_compiles"]["serving"] == 0
    assert counter("acp_engine_cold_compiles_total") == before
    prof.mark_prewarmed()
    # an already-known program stays warm
    prof.record("prefill[slot,64x1]", prof.start())
    assert prof.stats()["cold_compiles"]["serving"] == 0
    # a NEW program key after prewarm is a serving-time cold compile
    prof.record("prefill[slot,128x1]", prof.start())
    doc = prof.stats()
    assert doc["cold_compiles"]["serving"] == 1
    assert doc["cold_compiles"]["events"][0]["program"] == "prefill[slot,128x1]"
    assert doc["programs"]["prefill[slot,128x1]"]["cold"] is True
    assert doc["programs"]["prefill[slot,64x1]"]["cold"] is False
    assert counter("acp_engine_cold_compiles_total") == before + 1


def test_cold_compile_records_flight_event():
    from agentcontrolplane_tpu.observability.flight import FlightRecorder

    flight = FlightRecorder(enabled=True)
    prof = DispatchProfiler(flight=flight, enabled=True)
    prof.mark_prewarmed()
    prof.record("spill[paged,2048x4]", prof.start())
    evs = flight.events(kind="cold_compile")
    assert len(evs) == 1
    assert evs[0]["detail"]["program"] == "spill[paged,2048x4]"
    assert "wall_s" in evs[0]["detail"]


def test_ledger_conservation_by_construction_and_reclassify_zero_sum():
    prof = DispatchProfiler(enabled=True)
    prof.account(goodput=100, pad_bucket=28, prewarm=10)
    prof.account(goodput=50, pad_width=6, spec_rejected=4)
    assert _conserved(prof)
    led = prof.ledger()
    assert led["computed"] == 198 and led["goodput"] == 150
    prof.reclassify("preempt_discard", 40)
    assert _conserved(prof)
    led = prof.ledger()
    assert led["goodput"] == 110 and led["waste"]["preempt_discard"] == 40
    # clamp: reclassifying more than the available goodput stays zero-sum
    prof.reclassify("dedup_rewind", 10_000)
    assert _conserved(prof)
    led = prof.ledger()
    assert led["goodput"] == 0 and led["waste"]["dedup_rewind"] == 110
    # zero/negative reclassify is a no-op
    prof.reclassify("swap_recompute", 0)
    prof.reclassify("swap_recompute", -5)
    assert _conserved(prof)


def test_unknown_waste_cause_raises():
    prof = DispatchProfiler(enabled=True)
    with pytest.raises(KeyError):
        prof.account(goodput=1, bogus_cause=2)
    prof.account(goodput=1)
    with pytest.raises(KeyError):
        prof.reclassify("bogus_cause", 1)
    assert set(prof.ledger()["waste"]) == set(WASTE_CAUSES)


def test_publish_pushes_delta_counters_and_ratio_gauge():
    prof = DispatchProfiler(enabled=True)
    base_good = counter("acp_engine_tokens_computed_total", cause="goodput")
    base_pad = counter("acp_engine_tokens_computed_total", cause="pad_bucket")
    prof.account(goodput=30, pad_bucket=10)
    prof.publish()
    assert counter("acp_engine_tokens_computed_total", cause="goodput") == base_good + 30
    assert counter("acp_engine_tokens_computed_total", cause="pad_bucket") == base_pad + 10
    # delta-based: a second publish with no new activity adds nothing
    prof.publish()
    assert counter("acp_engine_tokens_computed_total", cause="goodput") == base_good + 30
    assert counter("acp_engine_goodput_ratio") == pytest.approx(0.75)
    # per-program token split publishes too
    prof.record("chunk[slot,32x2]", prof.start(), real_tokens=40, padded_tokens=24)
    base_real = counter(
        "acp_engine_dispatch_tokens_total", program="chunk[slot,32x2]", kind="real"
    )
    prof.publish()
    assert counter(
        "acp_engine_dispatch_tokens_total", program="chunk[slot,32x2]", kind="real"
    ) == base_real + 40


def test_disabled_profiler_is_inert():
    prof = DispatchProfiler(enabled=False)
    assert prof.start() == 0.0
    prof.record("decode[slot,1x4]", 0.0, real_tokens=4)
    prof.account(goodput=10, pad_width=2)
    prof.reclassify("preempt_discard", 5)
    prof.publish()
    doc = prof.stats()
    assert doc["enabled"] is False
    assert doc["programs"] == {}
    assert doc["goodput"]["computed"] == 0
    assert doc["goodput"]["ratio"] == 1.0


def test_env_knobs(monkeypatch):
    monkeypatch.setenv("ACP_PROF", "0")
    assert DispatchProfiler().enabled is False
    monkeypatch.setenv("ACP_PROF", "1")
    monkeypatch.setenv("ACP_PROF_SAMPLE", "7")
    prof = DispatchProfiler()
    assert prof.enabled is True and prof.sample_every == 7


# -- engine-cycle phases -------------------------------------------------------


def _phase_s(prof: DispatchProfiler) -> dict:
    return {k: v["s"] for k, v in prof.stats()["phases"].items()}


def test_nested_phases_partition_the_wall_time():
    """Self time, not inclusive: a phase opened inside another suspends the
    outer one, so the rows sum to the outermost span's wall time."""
    import time

    prof = DispatchProfiler(enabled=True)
    t0 = time.monotonic()
    with prof.phase("admit"):
        time.sleep(0.02)
        with prof.phase("launch"):
            time.sleep(0.03)
            with prof.phase("fetch"):
                time.sleep(0.01)
        time.sleep(0.02)
    wall = time.monotonic() - t0
    s = _phase_s(prof)
    assert 0.039 <= s["admit"] < 0.06      # 20 + 20 ms, not 100
    assert 0.029 <= s["launch"] < 0.045    # the fetch inside it is not launch
    assert 0.009 <= s["fetch"] < 0.025
    assert sum(s.values()) == pytest.approx(wall, abs=2e-3)
    assert sum(s.values()) <= wall


def test_phase_rows_count_each_span_and_same_name_nests():
    prof = DispatchProfiler(enabled=True)
    for _ in range(3):
        with prof.phase("launch"):
            with prof.phase("launch"):
                pass
    doc = prof.stats()
    assert doc["phases"]["launch"]["n"] == 6
    assert set(doc["phases"]["launch"]) == {"s", "n"}
    assert doc["cycles"] == 0 and doc["blocks"] == 0


def test_cycle_opens_once_an_iteration_has_work():
    prof = DispatchProfiler(enabled=True)
    prof.cycle(busy=False)              # an idle iteration: no cycle
    with prof.phase("park"):
        pass
    assert prof.cycle_n == 0
    prof.cycle(busy=False)              # a request arrives while parked
    with prof.phase("park"):
        pass
    prof.begin_cycle()
    prof.begin_cycle()                  # idempotent
    with prof.phase("admit"):
        pass
    assert prof.cycle_n == 1
    prof.cycle(busy=True)               # closes cycle 1, opens cycle 2
    assert prof.cycle_n == 2
    with prof.phase("launch"):
        pass
    prof.end_cycle()
    doc = prof.stats()
    assert doc["cycles"] == 2
    assert doc["phases"]["cycle"]["n"] == 2
    assert doc["phases"]["park"]["n"] == 2 and doc["phases"]["admit"]["n"] == 1
    assert prof._stack() == []


def test_record_counts_decode_blocks_and_its_sampled_wait_is_fetch():
    prof = DispatchProfiler(enabled=True, sample_every=2)
    for i in range(4):
        with prof.phase("launch"):
            t0 = prof.start()
            prof.record("decode[paged,4x4]", t0, out=np.zeros(4), blocks=1)
        with prof.phase("launch"):
            t0 = prof.start()
            prof.record("prefill[paged,32x1]", t0, out=np.zeros(4))
    doc = prof.stats()
    assert doc["blocks"] == 4
    assert doc["phases"]["launch"]["n"] == 8
    # dispatches 0 and 2 of each program block until ready: that wait is fetch
    assert doc["phases"]["fetch"]["n"] == 4


def test_stamp_is_the_latest_phase_boundary_or_the_clock():
    import time

    prof = DispatchProfiler(enabled=True)
    with prof.phase("admit"):
        opened = prof.stamp()
        time.sleep(0.005)
        assert prof.stamp() == opened   # no boundary since: no new clock reading
    assert prof.stamp() - opened >= 0.005
    off = DispatchProfiler(enabled=False)
    a = off.stamp()
    time.sleep(0.002)
    assert off.stamp() > a


def test_disabled_profiler_records_no_phase():
    prof = DispatchProfiler(enabled=False)
    prof.cycle(busy=True)
    with prof.phase("launch"):
        assert prof.phase("fetch") is prof.phase("commit")  # one shared no-op object
    prof.begin_cycle()
    prof.end_cycle()
    doc = prof.stats()
    assert doc["phases"] == {} and doc["cycles"] == 0 and doc["blocks"] == 0
    assert prof.cycle_n == 0


def test_a_phase_that_raises_still_closes():
    prof = DispatchProfiler(enabled=True)
    prof.cycle(busy=True)
    with pytest.raises(RuntimeError):
        with prof.phase("launch"):
            raise RuntimeError("boom")
    prof.end_cycle()                    # the loop's way out
    with prof.phase("admit"):
        pass
    doc = prof.stats()
    assert doc["phases"]["launch"]["n"] == 1 and doc["phases"]["cycle"]["n"] == 1
    assert prof._stack() == []


def test_perf_cli_prints_the_phase_table(monkeypatch, capsys):
    """`acp-tpu perf` renders `perf.phases` as ms per cycle and share of
    the loop's busy time (park is waiting, not busy)."""
    import contextlib
    from types import SimpleNamespace as NS

    from agentcontrolplane_tpu import cli

    prof = DispatchProfiler(enabled=True)
    prof.cycle(busy=False)
    with prof.phase("park"):
        pass
    for _ in range(4):
        prof.cycle(busy=True)
        with prof.phase("launch"):
            t0 = prof.start()
            prof.record("decode[paged,4x4]", t0, blocks=1)
        with prof.phase("commit"):
            pass
    prof.end_cycle()
    doc = prof.stats()

    @contextlib.contextmanager
    def client(args, timeout=None):
        yield NS(get=lambda path: NS(status_code=200, json=lambda: doc, text=""))

    monkeypatch.setattr(cli, "_client", client)
    assert cli.cmd_perf(NS(json=False, top=10)) == 0
    out = capsys.readouterr().out
    assert "engine loop: 4 busy cycles, 4 decode blocks" in out
    rows = {line.split()[0]: line.split() for line in out.splitlines() if line and line.split()[0] in doc["phases"]}
    assert set(rows) == {"park", "launch", "commit", "cycle"}
    assert rows["launch"][1] == "4" and rows["launch"][3].endswith("%")
    assert rows["park"][3] == "-"
    shares = sum(float(r[3].rstrip("%")) for name, r in rows.items() if name != "park")
    assert shares == pytest.approx(100.0, abs=0.3)
    # a server that predates the phases prints the rest as before
    doc = {k: v for k, v in doc.items() if k not in ("phases", "cycles", "blocks")}
    assert cli.cmd_perf(NS(json=False, top=10)) == 0
    assert "engine loop" not in capsys.readouterr().out
