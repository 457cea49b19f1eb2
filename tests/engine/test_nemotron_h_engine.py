"""Nemotron-H served by the engine: admission, decode blocks, a chunked
prompt, preemption and resume with the state's tree saved and installed
(recompute and host swap), a parked turn, and the two groups of counters.
No engine option is new. The reference is the family's plain `forward`
through `agentcontrolplane_tpu.testing.greedy_reference` (one padded,
compiled program for the file: PR 50).

CPU, `nemotron-h-tiny` (the published list's first 11 blocks), float32.
"""

import jax
import numpy as np
import pytest

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.models import nemotron_h as nh
from agentcontrolplane_tpu.models import preset
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import greedy_reference

CFG = preset("nemotron-h-tiny")
MAX_CTX = 128  # the engines' and the padded reference's
PARAMS = None
GREEDY = SamplingParams(temperature=0.0, max_tokens=10)


def make_engine(**kw):
    global PARAMS
    if PARAMS is None:
        PARAMS = nh.init_params(CFG, jax.random.key(0))
    opts = dict(max_slots=4, max_ctx=MAX_CTX, kv_layout="paged", page_size=8, kv_pages=80,
                prefill_buckets=(16, 32, 64), width_buckets=(2, 4), decode_block_size=4, check_invariants=True)
    eng = Engine(config=CFG, params=PARAMS, mesh=make_mesh({"tp": 1}, devices=jax.devices()[:1]), **{**opts, **kw})
    eng.start()
    return eng


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, 256, n)] for n in lengths]


def reference(prompt, n):
    return greedy_reference(nh.forward, PARAMS, CFG, prompt, n, MAX_CTX)


def test_engine_serves_it_as_the_other_models_and_counts_both_its_mechanisms():
    eng = make_engine()
    try:
        ps = prompts(20, 37, 50)
        futures = [eng.submit(p, GREEDY) for p in ps]
        for p, f in zip(ps, futures):
            assert f.result(300).tokens == reference(p, 10)
        st = eng.stats()
        ssm_, moe_, m, e = st["ssm"], st["moe"], CFG.n_mamba, CFG.n_moe
        assert st["model"]["layers"] == 11 and (m, e, CFG.n_attention) == (5, 5, 1)
        assert ssm_["state_bytes_per_slot"] == m * (8 * 8 * 16 * 4 + 3 * 128 * 4) == CFG.state_bytes_per_slot
        assert ssm_["prefill"]["tokens"] == sum(map(len, ps)) * m and ssm_["prefill"]["rows"] == 3 * m
        assert ssm_["prefill"]["chunks"] == 3 * m  # each prompt inside one chunk
        assert ssm_["decode"]["mamba_layers"] == eng.decode_steps * m
        assert 0 < ssm_["decode"]["tokens"] == ssm_["decode"]["rows"] <= ssm_["decode"]["mamba_layers"] * 4
        assert (moe_["experts"], moe_["held"], moe_["experts_per_token"]) == (16, 4, 3)
        assert moe_["decode"]["expert_layers"] == eng.decode_steps * e
        assert moe_["prefill"]["pairs_routed"] == sum(map(len, ps)) * e * 3
        assert moe_["decode"]["pairs_routed"] == ssm_["decode"]["tokens"] // m * e * 3
        for phase in ("decode", "prefill"):
            got = moe_[phase]
            assert 0 < got["pairs_held"] == sum(got["tokens_per_held_expert"]) < got["pairs_routed"]
            assert 0 < got["experts_read"] <= got["expert_layers"] * 4
        # the programs keep the names the trace readers match on
        assert eng._jit_decode_paged.__wrapped__.__name__ == "decode_block"
        assert eng._jit_prefill_paged.__wrapped__.__name__ == "prefill_and_sample"
        assert st["kv_pages"]["state_refused"] == 0
    finally:
        eng.stop()


def test_chunked_prefill_carries_the_state_across_chunk_boundaries():
    eng = make_engine(prefill_buckets=(16, 32), prefill_chunk=16)
    try:
        for p in prompts(70, 41, seed=3):
            assert eng.generate(p, GREEDY).tokens == reference(p, 10)
    finally:
        eng.stop()


@pytest.mark.parametrize("host_kv_bytes", [0, 1 << 22], ids=["recompute", "host-swap"])
def test_preempt_and_resume_reproduce_the_uninterrupted_tokens(host_kv_bytes):
    """An oversubscribed pool preempts; the resume recomputes the state (no
    host tier) or restores pages and the state's tree (`saved_state` /
    `install_state`) from the host entry saved at the one length whose state
    was kept."""
    eng = make_engine(kv_pages=14, host_kv_bytes=host_kv_bytes)
    entries = []
    if host_kv_bytes:
        put = eng._host_pool.put
        eng._host_pool.put = lambda e: (entries.append(e), put(e))[1]
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=12)
        ps = prompts(*[20] * 6, seed=1)
        solo = [eng.generate(p, sp).tokens for p in ps]
        assert solo[0] == reference(ps[0], 12)
        with eng.hold_admission():
            futures = [eng.submit(p, sp) for p in ps]
        assert [f.result(300).tokens for f in futures] == solo
        assert eng.preemptions >= 1
        if host_kv_bytes:
            assert eng.kv_swap_outs >= 1 and eng.kv_swap_ins >= 1 and eng.state_restores >= 1
            assert entries
            for e in entries:  # the state of a host entry is the family's tree, numpy leaves
                assert set(e.state) == {"ssm", "conv"} and all(isinstance(a, np.ndarray) for a in e.state.values())
                assert e.state["ssm"].shape == (CFG.n_mamba,) + CFG.state_shape
    finally:
        eng.stop()


def test_a_parked_turn_resumes_from_the_saved_state():
    eng = make_engine()
    try:
        turn1 = prompts(29)[0]
        turn2 = turn1 + prompts(15, seed=9)[0]
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        eng.submit(turn1, sp, park=True).result(120)
        assert eng.stats()["parked_slots"] == 1
        before = eng.state_restores
        assert eng.generate(turn2, sp).tokens == reference(turn2, 8)
        assert eng.park_adoptions == 1 and eng.state_restores == before + 1
    finally:
        eng.stop()
