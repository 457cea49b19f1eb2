"""The engine serving LFM2-MoE: short and long slots in one batch held to the
model's own forward pass, and the per-slot state carried through chunked
prefill, preemption, a host swap, a park and the prefix cache; what it
refuses, in words; state and counters as capabilities apart; the heap
frozen by prewarm. The family's operators and programs: `test_lfm2.py`.

CPU, `lfm2-tiny`, float32, seeded weights, the invariant checker armed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.models import lfm2, preset, programs
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import greedy_reference

CFG = preset("lfm2-tiny")
MAX_CTX = 128  # the engines' and the padded reference's
PARAMS = None
ONE_CHIP = lambda: make_mesh({"tp": 1}, devices=jax.devices()[:1])  # noqa: E731


def make_engine(**kw):
    global PARAMS
    if PARAMS is None:
        PARAMS = lfm2.init_params(CFG, jax.random.key(0))
    opts = dict(max_slots=4, max_ctx=MAX_CTX, kv_layout="paged", page_size=8, kv_pages=80,
                prefill_buckets=(16, 32, 64), width_buckets=(2, 4), decode_block_size=4, check_invariants=True)
    eng = Engine(config=CFG, params=PARAMS, mesh=ONE_CHIP(), **{**opts, **kw})
    eng.start()
    return eng


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, 256, n)] for n in lengths]




GREEDY = SamplingParams(temperature=0.0, max_tokens=10)


def test_engine_serves_it_as_the_other_models_and_counts_its_experts():
    eng = make_engine()
    try:
        ps = prompts(20, 37, 50)
        futures = [eng.submit(p, GREEDY) for p in ps]
        for p, f in zip(ps, futures):
            assert f.result(300).tokens == greedy_reference(lfm2.forward, PARAMS, CFG, p, 10, MAX_CTX)
        st = eng.stats()
        moe, layers = st["moe"], CFG.n_layers - CFG.num_dense_layers
        assert moe["held"] == 8 and st["model"]["layers"] == 12
        for part, tokens in (("prefill", sum(map(len, ps))),):
            assert moe[part]["pairs_routed"] == tokens * CFG.experts_per_token * layers
            assert moe[part]["pairs_held"] == sum(moe[part]["tokens_per_held_expert"]) == moe[part]["pairs_routed"]
        assert moe["decode"]["expert_layers"] == eng.decode_steps * layers
        # the programs keep the names the trace readers match on, counters or not
        assert eng._jit_decode_paged.__wrapped__.__name__ == "decode_block"
        assert eng._jit_prefill_paged.__wrapped__.__name__ == "prefill_and_sample"
        assert 0 < moe["decode"]["experts_read"] <= moe["decode"]["expert_layers"] * 8
        assert st["kv_pages"]["state_refused"] == 0
    finally:
        eng.stop()


def test_chunked_prefill_carries_the_state_across_chunk_boundaries():
    eng = make_engine(prefill_buckets=(16, 32), prefill_chunk=16)
    try:
        for p in prompts(70, 41, seed=3):
            assert eng.generate(p, GREEDY).tokens == greedy_reference(lfm2.forward, PARAMS, CFG, p, 10, MAX_CTX)
    finally:
        eng.stop()


@pytest.mark.parametrize("host_kv_bytes", [0, 1 << 22], ids=["recompute", "host-swap"])
def test_preempt_and_resume_reproduce_the_uninterrupted_tokens(host_kv_bytes):
    """An oversubscribed pool preempts; the resume recomputes the state (no
    host tier) or restores pages and state from the host entry saved at the
    one length whose state was kept."""
    eng = make_engine(kv_pages=14, host_kv_bytes=host_kv_bytes)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=12)
        ps = prompts(*[20] * 6, seed=1)
        solo = [eng.generate(p, sp).tokens for p in ps]
        with eng.hold_admission():
            futures = [eng.submit(p, sp) for p in ps]
        assert [f.result(300).tokens for f in futures] == solo
        assert eng.preemptions >= 1
        if host_kv_bytes:
            assert eng.kv_swap_outs >= 1 and eng.kv_swap_ins >= 1 and eng.state_restores >= 1
    finally:
        eng.stop()


def test_a_parked_turn_resumes_from_the_saved_state():
    eng = make_engine()
    try:
        turn1 = prompts(29)[0]
        turn2 = turn1 + prompts(15, seed=9)[0]
        cold = greedy_reference(lfm2.forward, PARAMS, CFG, turn2, 8, MAX_CTX)
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        eng.submit(turn1, sp, park=True).result(120)
        assert eng.stats()["parked_slots"] == 1
        before = eng.state_restores
        assert eng.generate(turn2, sp).tokens == cold
        assert eng.park_adoptions == 1 and eng.state_restores == before + 1
    finally:
        eng.stop()


def test_a_prefix_hit_is_taken_where_the_state_was_saved_and_only_there():
    eng = make_engine(prefix_dedup=True)
    try:
        base = prompts(45)[0]  # saved at its last page boundary: 40 tokens
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        eng.generate(base, sp)
        longer = base + prompts(9, seed=4)[0]
        hits = eng.stats()["prefix_cache"]["hits"]
        assert eng.generate(longer, sp).tokens == greedy_reference(lfm2.forward, PARAMS, CFG, longer, 6, MAX_CTX)
        assert eng.stats()["prefix_cache"]["hits"] == hits + 1 and eng.state_restores >= 1
        with eng._prefix_lock:
            assert {e["cut"] for e in eng._prefix_cache.values()} <= {40, 48} and all(
                "state" in e for e in eng._prefix_cache.values())
        # live leaders' pages are never shared (no state at the common cut): dedup is a miss
        with eng.hold_admission():
            futures = [eng.submit(base + [7, i], sp) for i in range(3)]
        for i, f in enumerate(futures):
            assert f.result(120).tokens == greedy_reference(lfm2.forward, PARAMS, CFG, base + [7, i], 6, MAX_CTX)
        assert eng.prefix_shares == 0
    finally:
        eng.stop()


def test_a_host_entry_without_a_state_is_a_miss():
    from agentcontrolplane_tpu.ops.paged import HostKVEntry

    eng = make_engine(host_kv_bytes=1 << 22, prefix_cache_entries=0)
    try:
        p = prompts(44)[0]
        L, HD = CFG.n_attention, CFG.n_kv_heads * CFG.head_dim
        rows = np.ones((L, 32, HD), np.float32)  # wrong K/V: it must never be restored
        assert eng.inject_host_kv(HostKVEntry(rid="x", tokens=tuple(p[:32]), rows={"k": rows, "v": rows}))
        assert eng.generate(p, GREEDY).tokens == greedy_reference(lfm2.forward, PARAMS, CFG, p, 10, MAX_CTX)
        assert eng.state_refused >= 1 and eng.kv_swap_ins == 0
    finally:
        eng.stop()


@pytest.mark.parametrize("kw,words", [
    ({"spec_len": 4}, "rolled back"), ({"kv_layout": "slot"}, "paged"), ({"quantize": "int8"}, "int8"),
])
def test_what_the_engine_cannot_do_for_it_is_refused_in_words(kw, words):
    with pytest.raises(ValueError, match=words):
        Engine(config=CFG, mesh=ONE_CHIP(), max_slots=2, max_ctx=64, **{"kv_layout": "paged", "page_size": 8, **kw})


def test_the_seam_gives_each_family_its_programs():
    assert programs(CFG).has_state and not programs(preset("tiny")).has_state
    assert programs(preset("tiny")).prefill_paged_batch.__module__.endswith("models.llama")
    with pytest.raises(KeyError, match="lfm2-24b-a2b-ep8"):
        preset("no-such-model")
    full = preset("lfm2-24b-a2b-ep8")
    assert (full.n_layers, full.n_attention, full.n_conv, len(full.held)) == (40, 10, 30, 8)


@pytest.mark.parametrize("capability", ["state-without-counters", "counters-without-state"])
def test_state_and_counters_are_capabilities_apart(capability, monkeypatch):
    """The engine asks a family for its per-slot state and for its device
    counters separately: a family with one and not the other serves."""
    import types

    from agentcontrolplane_tpu import models
    from agentcontrolplane_tpu.engine import engine as engine_module

    if capability == "state-without-counters":
        family = types.SimpleNamespace(**{**vars(models._LFM2), "counters": None})
        monkeypatch.setattr(engine_module, "programs", lambda config: family)
        eng, p = make_engine(), prompts(20)[0]
        try:
            assert eng.generate(p, GREEDY).tokens == greedy_reference(lfm2.forward, PARAMS, CFG, p, 10, MAX_CTX)
            st = eng.stats()
            assert "moe" not in st and st["kv_pages"]["state_saves"] >= 0
        finally:
            eng.stop()
        return
    tiny_llama = preset("tiny")
    seen = types.SimpleNamespace(**{
        **vars(models._LLAMA),
        "counters": lambda cache: jnp.sum(cache["k"] != 0, dtype=jnp.uint32)[None],
        "describe_counters": lambda config, total: {"kv_nonzero": {"n": 0 if total is None else int(total[0])}},
    })
    monkeypatch.setattr(engine_module, "programs", lambda config: seen)
    eng = Engine(config=tiny_llama, mesh=ONE_CHIP(), max_slots=2, max_ctx=64, kv_layout="paged", page_size=8,
                 prefill_buckets=(16, 32), width_buckets=(2,), decode_block_size=4)
    eng.start()
    try:
        assert eng.stats()["kv_nonzero"] == {"n": 0}
        eng.generate(prompts(12)[0], SamplingParams(temperature=0.0, max_tokens=4))
        assert eng.stats()["kv_nonzero"]["n"] > 0
    finally:
        eng.stop()


def test_prewarm_freezes_the_heap_and_stop_gives_it_back():
    import gc

    eng = make_engine(prefill_buckets=(16,), width_buckets=(2,), max_slots=2, prefix_cache_entries=0)
    before = gc.get_freeze_count()  # what a test plugin may have frozen already
    try:
        eng.prewarm()
        assert gc.get_freeze_count() > before + 1000
    finally:
        eng.stop()
    assert gc.get_freeze_count() == 0
