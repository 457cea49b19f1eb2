"""fleet/health.py: the per-replica gray-failure state machine — pure
hysteresis unit tests plus the router-integration path (a stalling
replica degrades, sheds its affinity homes, stops winning new ones, and
recovers once the throttle lifts)."""

from __future__ import annotations

import dataclasses
import time

import jax
import pytest

from agentcontrolplane_tpu.engine.engine import PRESETS, Engine, SamplingParams
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.fleet import FleetRouter
from agentcontrolplane_tpu.fleet.health import (
    DEAD,
    DEGRADED,
    HEALTHY,
    HealthPolicy,
    HealthSample,
    ReplicaHealth,
)
from agentcontrolplane_tpu.kernel import Store
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import FAULTS

TOK = ByteTokenizer()
CFG = dataclasses.replace(PRESETS["tiny"], vocab_size=512, max_seq_len=256,
                          n_kv_heads=2)


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    FAULTS.reset()


# -- state machine (no engine) ------------------------------------------------


def test_degrade_needs_consecutive_bad_samples():
    """Hysteresis: one stall burst never flips routing; degrade_after
    consecutive bad samples do, with the reason in the ledger."""
    m = ReplicaHealth("r0", HealthPolicy(degrade_after=2))
    assert m.observe(HealthSample(stalls=0)) is None   # baseline sample
    assert m.observe(HealthSample(stalls=1)) is None   # bad #1
    assert m.observe(HealthSample(stalls=1)) is None   # clean: streak resets
    assert m.observe(HealthSample(stalls=2)) is None   # bad #1 again
    assert m.state == HEALTHY
    assert m.observe(HealthSample(stalls=3)) == DEGRADED  # bad #2
    idx, frm, to, reason = m.transitions[-1]
    assert (frm, to) == (HEALTHY, DEGRADED)
    assert "stalls+1" in reason


def test_recovery_hysteresis_and_ledger():
    m = ReplicaHealth("r0", HealthPolicy(degrade_after=1, recover_after=3))
    m.observe(HealthSample(stalls=0))
    assert m.observe(HealthSample(stalls=5)) == DEGRADED
    # two clean samples, then a relapse: the good streak resets
    assert m.observe(HealthSample(stalls=5)) is None
    assert m.observe(HealthSample(stalls=5)) is None
    assert m.observe(HealthSample(stalls=6)) is None  # bad (already degraded)
    assert m.state == DEGRADED
    for _ in range(2):
        assert m.observe(HealthSample(stalls=6)) is None
    assert m.observe(HealthSample(stalls=6)) == HEALTHY
    assert [(frm, to) for _, frm, to, _ in m.transitions] == [
        (HEALTHY, DEGRADED), (DEGRADED, HEALTHY),
    ]
    assert m.transitions[-1][3] == "recovered"


def test_queue_trend_and_goodput_signals():
    pol = HealthPolicy(degrade_after=1, queue_trend_len=2, queue_min=4,
                       goodput_floor=0.5)
    m = ReplicaHealth("r0", pol)
    # strictly-growing depth below queue_min never counts
    for depth in (0, 1, 2, 3):
        assert m.observe(HealthSample(queue_depth=depth)) is None
    # ...but crossing queue_min with the streak going trips the trend
    assert m.observe(HealthSample(queue_depth=5)) == DEGRADED
    assert "queue_trend:5" in m.transitions[-1][3]

    m2 = ReplicaHealth("r1", pol)
    # a starved goodput ratio only counts while work is queued
    assert m2.observe(HealthSample(queue_depth=0, goodput_ratio=0.1)) is None
    assert m2.observe(HealthSample(queue_depth=2, goodput_ratio=0.1)) == DEGRADED
    assert "goodput:0.10" in m2.transitions[-1][3]


def test_dead_is_terminal():
    m = ReplicaHealth("r0", HealthPolicy(recover_after=1))
    assert m.observe(HealthSample(alive=False)) == DEAD
    assert m.transitions[-1][3] == "lease"
    # observation never resurrects: re-registration is an operator act
    for _ in range(5):
        assert m.observe(HealthSample()) is None
    assert m.state == DEAD
    assert m.mark_dead() is None  # idempotent mirror


def test_replayed_sample_stream_reproduces_ledger():
    """The judgment is a pure function of the sample stream — the chaos
    conductor's determinism story depends on this."""
    stream = [
        HealthSample(stalls=0), HealthSample(stalls=2),
        HealthSample(stalls=4), HealthSample(queue_depth=3, stalls=4),
        HealthSample(stalls=4), HealthSample(stalls=4),
        HealthSample(stalls=4), HealthSample(stalls=4),
        HealthSample(alive=False),
    ]
    a = ReplicaHealth("r0")
    b = ReplicaHealth("r0")
    for s in stream:
        a.observe(s)
    for s in stream:
        b.observe(s)
    assert a.transitions == b.transitions
    assert [(frm, to) for _, frm, to, _ in a.transitions] == [
        (HEALTHY, DEGRADED),   # two stall deltas back to back
        (DEGRADED, HEALTHY),   # four clean samples recover
        (HEALTHY, DEAD),       # lease loss is terminal
    ]
    assert a.transitions[-1][3] == "lease"


# -- router integration -------------------------------------------------------


def make_engine(**kw):
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    kw.setdefault("check_invariants", True)
    eng = Engine(
        config=CFG, tokenizer=TOK, mesh=mesh, max_slots=4, max_ctx=64,
        prefill_buckets=(32, 64), decode_block_size=4, kv_layout="paged",
        page_size=8, **kw,
    )
    eng.start()
    return eng


def teardown_pool(router, engines):
    router.stop()
    for eng in engines:
        try:
            eng.stop()
        except Exception:
            pass


def _wait_for(predicate, timeout_s=30.0, interval_s=0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return False


def test_stalling_replica_degrades_sheds_affinity_and_recovers():
    """The tentpole integration path: ``engine.slow_cycle`` pinned to the
    affinity-homed replica trips the stall watchdog; the health machine
    degrades it within a couple of watchdog ticks; its persona keys are
    shed and NEW homes land on the healthy replica; once the throttle
    budget drains, clean samples recover it."""
    router = FleetRouter(
        store=Store(), heartbeat_interval=60.0,
        # >= the engines' stall cadence (stall_min_s=0.02 + 0.08 throttle)
        # so consecutive watchdog samples each see a fresh stall delta
        watchdog_interval_s=0.1,
        health_policy=HealthPolicy(degrade_after=2, recover_after=3),
    )
    engines = [make_engine() for _ in range(2)]
    for i, eng in enumerate(engines):
        eng.stall_mult, eng.stall_min_s = 2.0, 0.02
        router.add_replica(f"r{i}", eng)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=2)
        # enough post-compile cycles to settle the target's cadence floor
        # (the stall baseline) before the throttle lands
        router.submit("warm the persona", SamplingParams(temperature=0.0,
                      max_tokens=16), affinity_key="p").result(timeout=120)
        target = router._affinity["p"]
        FAULTS.arm("engine.slow_cycle", times=12, delay_s=0.08,
                   replica=target)
        # keep the gray replica's scheduler busy so cycles (and stalls)
        # actually happen while the throttle budget drains
        slow = router.submit(
            "ride the gray replica", SamplingParams(temperature=0.0,
                                                    max_tokens=24),
            affinity_key="p",
        )
        assert _wait_for(lambda: router._health_state(target) == DEGRADED), \
            "stalling replica never degraded"
        # leaving healthy shed the re-homeable keys...
        assert "p" not in router._affinity
        # ...and a NEW home must land on the healthy survivor
        other = [r.id for r in router.pool.replicas() if r.id != target][0]
        router.submit("home a fresh persona", sp,
                      affinity_key="q").result(timeout=120)
        assert router._affinity["q"] == other
        slow.result(timeout=180)
        # throttle budget drained: clean samples recover the replica
        assert _wait_for(lambda: router._health_state(target) == HEALTHY), \
            "replica never recovered after the throttle lifted"
        stats = router.stats()
        by_id = {r["id"]: r for r in stats["replicas"]}
        assert by_id[target]["stalls"] > 0
        assert by_id[target]["health"] == HEALTHY
        assert stats["health"]["transitions"] >= 2
    finally:
        teardown_pool(router, engines)


def test_dead_replica_mirrors_into_health_ledger():
    """The lease/error path owns death; the monitor mirrors it (gauge,
    ledger) and the state is terminal."""
    router = FleetRouter(store=Store(), heartbeat_interval=60.0,
                         watchdog_interval_s=0.05)
    engines = [make_engine() for _ in range(2)]
    for i, eng in enumerate(engines):
        router.add_replica(f"r{i}", eng)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=16)
        router.submit("warm the persona", SamplingParams(temperature=0.0,
                      max_tokens=2), affinity_key="p0").result(timeout=120)
        target = router._affinity["p0"]
        survivor = [r.id for r in router.pool.replicas()
                    if r.id != target][0]
        FAULTS.arm("fleet.replica_crash", times=1, after_steps=1,
                   replica=target)
        router.submit("crash the homed replica", sp,
                      affinity_key="p0").result(timeout=180)
        assert _wait_for(lambda: router._health_state(target) == DEAD)
        assert router._health_state(survivor) == HEALTHY
    finally:
        teardown_pool(router, engines)
