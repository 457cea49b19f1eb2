"""DispatchProfiler unit behavior (observability/profiler.py): program
aggregation, sampled device timing, the cold-compile observatory, and the
goodput/waste ledger's conservation-by-construction."""

import time

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


# -- set-up: a first dispatch partitioned, the engine's start by phase ---------


def _row(prof: DispatchProfiler, key: str) -> dict:
    return prof.stats()["programs"][key]


def _parts(row: dict) -> float:
    return row["trace_ms"] + row["lower_ms"] + row["compile_ms"] + row["load_ms"]


def _dispatch(prof: DispatchProfiler, key: str, fn, *args):
    t0 = prof.start()
    out = fn(*args)
    prof.record(key, t0, out=out)
    return out


def _dawdle(seconds: float) -> None:
    """Python that a trace runs and a compiled program does not: it makes a
    trace take a known time."""
    time.sleep(seconds)


def _fresh_jit(body_sleep_s: float = 0.0):
    """A jitted function jit's own caches have not seen (they key on the
    function object), whose Python body (what a trace runs) takes
    ``body_sleep_s``."""
    import jax

    def body(x):
        _dawdle(body_sleep_s)
        return x * 2 + 1

    return jax.jit(body)


@pytest.fixture(scope="module")
def warmed():
    """A tiny CPU engine after ``prewarm()`` and one request, and its perf."""
    import dataclasses

    import jax

    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
    from agentcontrolplane_tpu.models.llama import PRESETS
    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    config = dataclasses.replace(PRESETS["tiny"], vocab_size=512, max_seq_len=256, n_kv_heads=2)
    eng = Engine(config=config, tokenizer=ByteTokenizer(), mesh=make_mesh({"tp": 1}, devices=jax.devices()[:1]),
                 max_slots=2, max_ctx=64, prefill_buckets=(32,), prefill_batch_max=2, width_buckets=(1,),
                 decode_block_size=4, kv_layout="paged", page_size=8)
    eng.start()
    try:
        eng.prewarm()
        eng.submit("set-up", SamplingParams(temperature=0.0, max_tokens=6)).result(timeout=600)
        yield eng, eng.stats()["perf"]
    finally:
        eng.stop()


def test_a_first_dispatch_is_partitioned_not_clamped(warmed):
    """jax's four stages lie inside the first dispatch's wall time, so
    ``run_ms`` is what is left of it and never a clamp at 0."""
    perf = warmed[1]
    assert perf["programs"]
    for key, row in perf["programs"].items():
        parts = [row[k] for k in ("trace_ms", "lower_ms", "compile_ms", "load_ms", "run_ms")]
        assert min(parts) >= 0, (key, row)
        assert _parts(row) <= row["first_wall_ms"] * 1.02, (key, row)
        assert row["run_ms"] == pytest.approx(max(0.0, row["first_wall_ms"] - _parts(row)), abs=0.01)
        assert row["cache_hit"] in (True, False, None) and row["compiles"] >= 0
    assert any(row["trace_ms"] > 0 for row in perf["programs"].values())
    assert any(row["compiles"] >= 1 for row in perf["programs"].values())


def test_the_engines_start_is_timed_by_phase(warmed):
    setup = warmed[1]["setup"]
    phases = setup["phases"]
    assert {"init", "init.params", "init.pool", "init.programs", "prewarm", "prewarm.phases", "prewarm.freeze"} <= set(phases)
    assert 0 < phases["init.pool"]["s"] <= phases["init"]["s"]
    assert phases["init.params"]["s"] + phases["init.pool"]["s"] + phases["init.programs"]["s"] <= phases["init"]["s"]
    assert phases["prewarm.phases"]["s"] + phases["prewarm.freeze"]["s"] <= phases["prewarm"]["s"]
    assert all(p["n"] == 1 and p["jax_s"] <= p["s"] for p in phases.values())
    # the constructor's own jits (the pool's init program, the base key) are the phases', in no program's row
    assert phases["init.pool"]["compiles"] >= 1 and phases["init.pool"]["jax_s"] > 0
    assert phases["init"]["first_wall_s"] == 0  # nothing is dispatched before the engine thread runs
    # every program but the request's own was first dispatched inside prewarm
    inside = sum(r["first_wall_ms"] for r in warmed[1]["programs"].values() if not r["cold"]) / 1e3
    assert phases["prewarm"]["first_wall_s"] == pytest.approx(inside, rel=1e-3)
    assert setup["prewarm_rest_s"] == pytest.approx(phases["prewarm"]["s"] - phases["prewarm"]["first_wall_s"], abs=1e-5)
    assert setup["prewarm_rest_s"] >= 0
    assert setup["programs"] == len(warmed[1]["programs"])
    assert setup["after_prewarm"] == warmed[1]["cold_compiles"]["serving"]
    assert setup["retraces"] == warmed[1]["cold_compiles"]["retraces"]
    counted = (sum(r["compiles"] for r in warmed[1]["programs"].values()) + setup["outside"]["compiles"]
               + sum(p["compiles"] for p in phases.values()))
    assert setup["compiles"] == counted


def test_a_crash_recoverys_pool_is_counted_too(warmed):
    eng = warmed[0]
    before = eng.stats()["perf"]["setup"]["phases"]["init.pool"]
    eng._crashed = True
    eng._stopping = True
    eng._queue.put(None)
    eng._thread.join(timeout=30)
    assert eng.ensure_running()
    after = eng.stats()["perf"]["setup"]["phases"]["init.pool"]
    assert after["n"] == before["n"] + 1 and after["s"] > before["s"]


def test_two_engines_register_the_listeners_once(warmed):
    from jax._src import monitoring

    from agentcontrolplane_tpu.engine.engine import Engine
    from agentcontrolplane_tpu.observability import profiler

    def registered():
        return (monitoring.get_event_duration_listeners().count(profiler._on_duration),
                monitoring.get_event_listeners().count(profiler._on_event),
                monitoring.get_scalar_listeners().count(profiler._on_scalar))

    assert registered() == (1, 1, 1)
    eng = warmed[0]
    second = Engine(config=eng.config, tokenizer=eng.tokenizer, mesh=eng.mesh, max_slots=2, max_ctx=64,
                    prefill_buckets=(32,), kv_layout="paged", page_size=8)
    assert second.profiler is not eng.profiler and "init" in second.stats()["perf"]["setup"]["phases"]
    for _ in range(3):
        DispatchProfiler(enabled=True)
    assert registered() == (1, 1, 1)


def test_a_jit_traced_inside_a_jit_is_counted_once():
    import jax
    import jax.numpy as jnp

    inner = _fresh_jit(body_sleep_s=0.06)

    def body(x):
        _dawdle(0.06)
        return inner(x) + inner(x + 1)  # the second call finds the first's trace

    prof = DispatchProfiler(enabled=True)
    _dispatch(prof, "outer", jax.jit(body), jnp.ones(3))
    row = _row(prof, "outer")
    # the inner trace's 60 ms lie inside the outer's 120: 180 would count them twice
    assert 115 <= row["trace_ms"] < 175, row
    assert _parts(row) <= row["first_wall_ms"] * 1.02
    assert row["compiles"] == 1 and row["lower_ms"] > 0 and row["compile_ms"] + row["load_ms"] > 0


def test_a_key_that_compiles_twice_is_a_retrace():
    import jax.numpy as jnp

    fn = _fresh_jit(body_sleep_s=0.02)
    three, five = jnp.ones(3), jnp.ones(5)  # made here: an eager op between dispatches compiles too
    prof = DispatchProfiler(enabled=True)
    prof.mark_prewarmed()
    _dispatch(prof, "k", fn, three)
    first = _row(prof, "k")
    _dispatch(prof, "k", fn, three)  # jit's cache holds it: nothing compiles
    assert prof.stats()["cold_compiles"]["retraces"] == 0 and _row(prof, "k")["compiles"] == 1
    _dispatch(prof, "k", fn, five)  # the key says nothing of the shape
    doc = prof.stats()
    row = doc["programs"]["k"]
    assert row["compiles"] == 2 and doc["cold_compiles"]["retraces"] == 1 == doc["setup"]["retraces"]
    assert doc["cold_compiles"]["serving"] == 1  # the key's first dispatch, not its retrace
    event = doc["cold_compiles"]["events"][-1]
    assert event["program"] == "k" and event["retrace"] is True and event["compiles"] == 1 and event["trace_ms"] >= 20
    assert {"trace_ms", "lower_ms", "compile_ms", "load_ms"} <= set(doc["cold_compiles"]["events"][0])
    # the row still partitions its FIRST dispatch; the retrace's seconds are (outside)'s
    assert all(row[k] == first[k] for k in ("trace_ms", "lower_ms", "compile_ms", "load_ms", "run_ms", "first_wall_ms"))
    assert doc["setup"]["outside"]["trace_ms"] >= 20 and doc["setup"]["outside"]["compiles"] == 0
    assert doc["setup"]["compiles"] == 2


def _feed_backend_compile(backend_s: float, cache: str, load_s: float = 0.0, saved_s: float = 0.0):
    """jax's own events for one pass through the backend, as
    ``compiler.compile_or_get_cached`` fires them."""
    import jax.monitoring as monitoring

    from agentcontrolplane_tpu.observability import profiler

    monitoring.record_scalar(profiler._BACKEND, time.time(), fun_name="fed")
    if cache != "off":
        monitoring.record_event(profiler._CACHE_ASKED)
    if cache == "hit":
        monitoring.record_event(profiler._CACHE_HIT)
        monitoring.record_event_duration_secs(profiler._CACHE_SAVED, saved_s)
        monitoring.record_event_duration_secs(profiler._CACHE_LOAD, load_s)
    monitoring.record_event_duration_secs(profiler._BACKEND, backend_s, fun_name="fed")


def test_a_cache_miss_and_a_cache_hit_are_told_apart(tmp_path):
    import jax

    prof = DispatchProfiler(enabled=True)
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        t0 = prof.start()
        _feed_backend_compile(0.4, "miss")
        prof.record("missed", t0)
        t0 = prof.start()
        _feed_backend_compile(0.3, "hit", load_s=0.25, saved_s=3.0)
        prof.record("hit", t0)
        t0 = prof.start()
        _feed_backend_compile(0.3, "hit", load_s=0.25, saved_s=3.0)
        _feed_backend_compile(0.4, "miss")
        prof.record("both", t0)
        jax.config.update("jax_compilation_cache_dir", None)
        t0 = prof.start()
        _feed_backend_compile(0.2, "asked-with-no-directory")
        prof.record("off", t0)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    doc = prof.stats()
    rows = doc["programs"]
    assert (rows["missed"]["cache_hit"], rows["missed"]["compile_ms"], rows["missed"]["load_ms"]) == (False, 400.0, 0.0)
    assert (rows["hit"]["cache_hit"], rows["hit"]["compile_ms"], rows["hit"]["load_ms"]) == (True, 0.0, 250.0)
    assert (rows["both"]["cache_hit"], rows["both"]["compile_ms"], rows["both"]["load_ms"]) == (False, 400.0, 250.0)
    assert (rows["off"]["cache_hit"], rows["off"]["compile_ms"], rows["off"]["compiles"]) == (None, 200.0, 1)
    assert doc["setup"]["cache_misses"] == 2 and doc["setup"]["compiles"] == 5
    assert doc["setup"]["saved_s"] == pytest.approx(6.0)
    # fed seconds pass the wall time of a dispatch that did nothing: the remainder is held at 0
    assert rows["hit"]["run_ms"] == 0.0


def test_what_jax_compiles_outside_a_dispatch_is_dropped_nowhere():
    import threading

    import jax.numpy as jnp

    from agentcontrolplane_tpu.observability import profiler

    x = jnp.ones(2)
    prof = DispatchProfiler(enabled=True)
    with prof.setup("init"):
        with prof.setup("init.pool"):
            _fresh_jit()(x)
        _fresh_jit()(x)
    _fresh_jit()(x)  # no dispatch, no phase: the thread's last profiler's (outside)
    setup = prof.stats()["setup"]
    # a phase's jax seconds and compiles are of the events that found it innermost
    assert setup["phases"]["init.pool"]["compiles"] == 1 == setup["phases"]["init"]["compiles"]
    assert 0 < setup["phases"]["init.pool"]["jax_s"] <= setup["phases"]["init.pool"]["s"] <= setup["phases"]["init"]["s"]
    assert setup["outside"]["compiles"] == 1 and setup["outside"]["trace_ms"] > 0
    assert setup["compiles"] == 3 and setup["programs"] == 0

    before = profiler.unattributed()["compiles"]
    stranger = threading.Thread(target=lambda: _fresh_jit()(x))
    stranger.start()
    stranger.join(timeout=60)
    assert not stranger.is_alive()
    after = prof.stats()["setup"]
    assert after["unattributed"]["compiles"] == before + 1 and after["compiles"] == 3


def test_a_setup_phase_that_raises_still_closes_and_a_disabled_one_is_inert():
    from agentcontrolplane_tpu.observability import profiler

    prof = DispatchProfiler(enabled=True)
    with pytest.raises(RuntimeError):
        with prof.setup("init"):
            raise RuntimeError("boom")
    assert prof.stats()["setup"]["phases"]["init"]["n"] == 1
    assert profiler._thread().phases == [] and prof._setup_open == []
    off = DispatchProfiler(enabled=False)
    assert off.setup("init") is off.phase("admit")  # the one shared no-op object
    with off.setup("init"):
        pass
    assert "setup" not in off.stats() and off.stats()["cold_compiles"]["retraces"] == 0


def test_perf_cli_prints_the_setup_phases_and_the_costliest_first_dispatches(monkeypatch, capsys, warmed):
    import contextlib
    from types import SimpleNamespace as NS

    from agentcontrolplane_tpu import cli

    doc = warmed[1]

    @contextlib.contextmanager
    def client(args, timeout=None):
        yield NS(get=lambda path: NS(status_code=200, json=lambda: doc, text=""))

    monkeypatch.setattr(cli, "_client", client)
    assert cli.cmd_perf(NS(json=False, top=3)) == 0
    lines = capsys.readouterr().out.splitlines()
    at = next(i for i, line in enumerate(lines) if line.startswith("set-up: "))
    assert any(line.startswith("PROGRAM") for line in lines[:at])  # under the table it printed before
    assert f"{doc['setup']['programs']} programs first dispatched" in lines[at]
    named = {line.split()[0] for line in lines[at:]}
    assert {"init", "init.pool", "prewarm", "prewarm.phases"} <= named
    costliest = sorted(doc["programs"], key=lambda k: -doc["programs"][k]["first_wall_ms"])[:5]
    head = next(i for i, line in enumerate(lines) if line.startswith("FIRST DISPATCH ms"))
    assert [line.split()[0] for line in lines[head + 1:]] == costliest
    assert lines[head + 1].split()[-1] in ("hit", "miss", "-")
