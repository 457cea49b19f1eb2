"""Hedged re-dispatch (fleet/router.py watchdog): a request stuck
pre-first-token on a gray replica races a second attempt on a healthy
one — first delivery wins, streams stay exactly-once and byte-identical.
Also pins the all-replicas-down shed contract and lease deposition
during an in-flight hedge."""

from __future__ import annotations

import dataclasses
import time

import jax
import pytest

from agentcontrolplane_tpu.engine.engine import (
    PRESETS,
    Engine,
    EngineOverloadedError,
    SamplingParams,
)
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.fleet import FleetRouter
from agentcontrolplane_tpu.fleet.health import HealthPolicy
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


def _wait_for(predicate, timeout_s=30.0, interval_s=0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return False


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


def make_hedging_pool(n=2, **router_kw):
    """A pool tuned so a throttled replica degrades within a few watchdog
    ticks and a stuck request hedges shortly after."""
    router_kw.setdefault("hedge_after_s", 0.3)
    router_kw.setdefault("watchdog_interval_s", 0.1)
    router_kw.setdefault("health_policy", HealthPolicy(degrade_after=1))
    router_kw.setdefault("heartbeat_interval", 60.0)
    router = FleetRouter(store=Store(), **router_kw)
    engines = [make_engine() for _ in range(n)]
    for i, eng in enumerate(engines):
        eng.stall_mult, eng.stall_min_s = 2.0, 0.02
        router.add_replica(f"r{i}", eng)
    return router, engines


def teardown_pool(router, engines, extra=()):
    router.stop()
    for eng in list(engines) + list(extra):
        try:
            eng.stop()
        except Exception:
            pass


def warm_floor(router):
    """One unthrottled request per replica so every engine's cadence
    floor (the stall baseline) reflects honest post-compile cycles."""
    sp = SamplingParams(temperature=0.0, max_tokens=16)
    for replica in router.pool.replicas():
        replica.engine.submit("warm the cadence floor", sp).result(timeout=120)


def saturate_then_throttle(router, target, delay_s=0.3, times=40):
    """Pin a request pre-first-token on ``target``: fill every slot with
    decoy work FIRST (so the next submit parks in the waiting queue,
    zero tokens delivered), then throttle the cycles. Stalls record at
    the END of throttled cycles, so degradation can only outrun a
    request's first token when that request can't even prefill."""
    decoy_sp = SamplingParams(temperature=0.0, max_tokens=48)
    decoys = [
        router.pool.get(target).engine.submit(f"decoy {i}", decoy_sp)
        for i in range(4)  # == max_slots
    ]
    FAULTS.arm("engine.slow_cycle", times=times, delay_s=delay_s,
               replica=target)
    return decoys


def test_hedge_rescues_stuck_request_byte_identical():
    """The acceptance guarantee: a request stuck pre-first-token on a
    throttled replica is hedge re-dispatched onto the healthy one; the
    caller sees one contiguous stream, byte-identical to a clean single
    engine; the loser attempt is cancelled (no double delivery)."""
    router, engines = make_hedging_pool(2)
    baseline = make_engine()
    try:
        warm_floor(router)
        prompt = "tell me about gray failures"
        sp = SamplingParams(temperature=0.0, max_tokens=24)
        router.submit("warm the persona", SamplingParams(temperature=0.0,
                      max_tokens=2), affinity_key="p").result(timeout=120)
        target = router._affinity["p"]
        decoys = saturate_then_throttle(router, target)
        streamed = []
        fut = router.submit(prompt, sp, affinity_key="p",
                            on_tokens=streamed.extend)
        result = fut.result(timeout=180)
        expected = baseline.submit(prompt, sp).result(timeout=120)
        assert result.text == expected.text
        assert result.tokens == expected.tokens
        # exactly-once: the stream IS the result, no replayed prefix
        assert streamed == list(result.tokens)
        assert router.hedges == 1
        stats = router.stats()
        assert stats["health"]["hedges"] == 1
        # the winner came from the healthy replica, not the gray one
        assert router.pool.get(target).alive  # gray, not dead
        for d in decoys:
            d.result(timeout=180)
    finally:
        teardown_pool(router, engines, extra=[baseline])


def test_all_replicas_dead_sheds_with_pool_retry_after():
    """Satellite pin: when every replica is dead, submit() must shed
    (503-style EngineOverloadedError with a Retry-After) instead of
    raising out of an empty candidate list."""
    router, engines = make_hedging_pool(2, hedge_after_s=0.0)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        # kill both replicas through the normal crash path, one at a time
        for victim in ("r0", "r1"):
            FAULTS.arm("fleet.replica_crash", times=1, replica=victim)
            try:
                router.submit(f"crash {victim}", sp).result(timeout=120)
            except RuntimeError:
                pass  # the last crash has no survivor to fail over to
        assert not router.pool.alive()
        # a FRESH submission into the dead pool: shed, never a crash
        with pytest.raises(EngineOverloadedError) as exc_info:
            router.submit("anyone home?", sp).result(timeout=30)
        assert "no live replicas" in str(exc_info.value)
        assert exc_info.value.retry_after_s > 0
    finally:
        teardown_pool(router, engines)


def test_lease_deposition_during_inflight_hedge_no_double_delivery():
    """Satellite: the gray replica CRASHES (lease deposed, survivor
    adopts) with a hedged request AND a mid-stream request in flight.
    The mid-stream sentinel never hedges (tokens already delivered) so
    it is the router's observer of the death: its attempt fails, the
    survivor adopts the lease, and the failover resumes its stream with
    NO replayed prefix — both requests byte-identical, exactly-once."""
    # hedge holdoff past the throttled prefill (~0.3 s) so the sentinel
    # delivers its first token before it could ever look stuck
    router, engines = make_hedging_pool(2, hedge_after_s=0.5)
    baseline = make_engine()
    try:
        warm_floor(router)
        router.submit("warm the persona", SamplingParams(temperature=0.0,
                      max_tokens=2), affinity_key="p").result(timeout=120)
        target = router._affinity["p"]
        FAULTS.arm("engine.slow_cycle", times=40, delay_s=0.3,
                   replica=target)
        # the sentinel: homed on target, streams slowly under the
        # throttle — its delivered tokens exempt it from hedging, so its
        # attempt stays live on the gray replica until the crash
        sent_sp = SamplingParams(temperature=0.0, max_tokens=40)
        sent_streamed = []
        sentinel = router.submit("survive the deposition", sent_sp,
                                 affinity_key="p",
                                 on_tokens=sent_streamed.extend)
        # fill the remaining slots so the hedged request stays queued;
        # all submits land inside the first throttled cycle, before the
        # watchdog can degrade the target and shed the "p" home
        decoy_sp = SamplingParams(temperature=0.0, max_tokens=48)
        router.pool.get(target).engine.submit("decoy a", decoy_sp)
        router.pool.get(target).engine.submit("decoy b", decoy_sp)
        router.pool.get(target).engine.submit("decoy c", decoy_sp)
        prompt = "tell me about lease fencing"
        sp = SamplingParams(temperature=0.0, max_tokens=24)
        streamed = []
        fut = router.submit(prompt, sp, affinity_key="p",
                            on_tokens=streamed.extend)
        result = fut.result(timeout=180)  # hedge rescues it
        assert _wait_for(lambda: len(sent_streamed) > 0), \
            "sentinel never started streaming"
        # now depose the gray replica mid-sentinel-stream: the crash pops
        # on its next throttled cycle, the sentinel's attempt fails, and
        # the survivor adopts the lease + resumes the stream
        FAULTS.arm("fleet.replica_crash", times=1, replica=target)
        sent_result = sentinel.result(timeout=180)
        expected = baseline.submit(prompt, sp).result(timeout=120)
        sent_expected = baseline.submit("survive the deposition",
                                        sent_sp).result(timeout=120)
        assert result.text == expected.text
        assert streamed == list(result.tokens)
        assert sent_result.text == sent_expected.text
        assert sent_result.tokens == sent_expected.tokens
        # exactly-once across the failover: the resumed stream continues
        # where the dead replica left off, no replayed prefix
        assert sent_streamed == list(sent_result.tokens)
        assert router.hedges >= 1
        dead = router.pool.get(target)
        survivor = [r for r in router.pool.replicas()
                    if r.id != target][0]
        assert _wait_for(lambda: not dead.alive), "crash never landed"
        assert router.pool.lease_holder(dead).endswith("/" + survivor.id)
    finally:
        teardown_pool(router, engines, extra=[baseline])
