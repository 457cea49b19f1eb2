"""The engine serving Kanana-2 through the paths that move a slot's pages leaf
by leaf: short and long slots in one batch held to the model's own forward
pass, chunked prefill, a prefix hit, dedup, preemption and resume, a host
swap and back, a park and an export; what it refuses, in words; and what the
seam (`models.programs`) says of a family. The family's operators and
programs: `test_kanana.py`.

CPU, `kanana-tiny`, float32, seeded weights, the invariant checker armed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.models import kanana, preset, programs
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import greedy_reference

ONE_CHIP = lambda: make_mesh({"tp": 1}, devices=jax.devices()[:1])  # noqa: E731
CFG = preset("kanana-tiny")
MAX_CTX = 128  # the engines' and the padded reference's
PARAMS = None


def make_engine(**kw):
    global PARAMS
    if PARAMS is None:
        PARAMS = kanana.init_params(CFG, jax.random.key(0))
        PARAMS["ff"]["router_bias"] = 0.03 * jax.random.normal(jax.random.key(9), PARAMS["ff"]["router_bias"].shape)
    # armed: the engine audits its own books (pages, refcounts, host entries, the cache's leaves) after every cycle
    opts = dict(max_slots=4, max_ctx=MAX_CTX, kv_layout="paged", page_size=8, kv_pages=80,
                prefill_buckets=(16, 32, 64), width_buckets=(2, 4), decode_block_size=4, check_invariants=True)
    eng = Engine(config=CFG, params=PARAMS, mesh=ONE_CHIP(), **{**opts, **kw})
    eng.start()
    return eng


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, 256, n)] for n in lengths]




GREEDY = SamplingParams(temperature=0.0, max_tokens=10)


def test_engine_serves_short_and_long_slots_in_one_batch_and_counts():
    eng = make_engine()
    try:
        ps = prompts(9, 37, 60)
        with eng.hold_admission():
            futures = [eng.submit(p, GREEDY) for p in ps]
        for p, f in zip(ps, futures):
            assert f.result(300).tokens == greedy_reference(kanana.forward, PARAMS, CFG, p, 10, MAX_CTX)
        st = eng.stats()
        assert set(eng.cache) == {"kv", "state"} and eng.cache["kv"].shape[-1] == CFG.row_stored
        latent, moe = st["latent"], st["moe"]
        assert latent["row_values"] == CFG.row_width and latent["layers"] == CFG.n_layers
        assert latent["decode"]["rows_expanded"] == 0 and latent["decode"]["rows_read"] > 0
        assert latent["prefill"]["rows_read"] == sum(map(len, ps)) <= latent["prefill"]["rows_expanded"]
        assert moe["shared_width"] == CFG.shared_width and moe["held"] == CFG.n_experts
        assert st["kv_pages"]["pages_per_turn"] == 0  # the CPU's reference: no kernel
    finally:
        eng.stop()


def test_chunked_prefill_reads_latent_rows_it_did_not_write():
    eng = make_engine(prefill_buckets=(16, 32), prefill_chunk=16)
    try:
        for p in prompts(70, 41, seed=3):
            assert eng.generate(p, GREEDY).tokens == greedy_reference(kanana.forward, PARAMS, CFG, p, 10, MAX_CTX)
        assert eng.stats()["latent"]["prefill"]["rows_expanded"] > 70 + 41  # gathered rows expanded again
    finally:
        eng.stop()


@pytest.mark.parametrize("host_kv_bytes", [0, 1 << 22], ids=["recompute", "host-swap"])
def test_preempt_and_resume_reproduce_the_uninterrupted_tokens(host_kv_bytes):
    """An oversubscribed pool preempts; the resume recomputes, or restores
    the slot's pages from a host entry whose one leaf is `kv`."""
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
            assert eng.kv_swap_outs >= 1 and eng.kv_swap_ins >= 1
    finally:
        eng.stop()


def test_a_prefix_hit_and_dedup_share_latent_pages():
    eng = make_engine(prefix_dedup=True)
    try:
        base = prompts(45)[0]
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        eng.generate(base, sp)
        longer = base + prompts(9, seed=4)[0]
        hits = eng.stats()["prefix_cache"]["hits"]
        assert eng.generate(longer, sp).tokens == greedy_reference(kanana.forward, PARAMS, CFG, longer, 6, MAX_CTX)
        assert eng.stats()["prefix_cache"]["hits"] == hits + 1
        fresh = prompts(41, seed=8)[0]
        with eng.hold_admission():
            futures = [eng.submit(fresh + [7, i], sp) for i in range(3)]
        for i, f in enumerate(futures):
            assert f.result(120).tokens == greedy_reference(kanana.forward, PARAMS, CFG, fresh + [7, i], 6, MAX_CTX)
        assert eng.prefix_shares >= 1
    finally:
        eng.stop()


def test_a_parked_turn_is_adopted_and_an_export_is_injected_elsewhere():
    eng, other = make_engine(), make_engine(host_kv_bytes=1 << 22, prefix_cache_entries=0)
    try:
        turn1 = prompts(29)[0]
        turn2 = turn1 + prompts(15, seed=9)[0]
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        eng.submit(turn1, sp, park=True).result(120)
        assert eng.stats()["parked_slots"] == 1
        assert eng.generate(turn2, sp).tokens == greedy_reference(kanana.forward, PARAMS, CFG, turn2, 8, MAX_CTX)
        assert eng.park_adoptions == 1
        # the disaggregation handoff: one leaf travels, token-major
        out = eng.submit(turn2, sp, export_kv=True).result(120)
        entry = out.kv_handoff
        assert set(entry.rows) == {"kv"} and entry.rows["kv"].shape == (CFG.n_layers, entry.cut, CFG.row_stored)
        assert entry.nbytes == entry.rows["kv"].nbytes
        assert other.inject_host_kv(entry)
        assert other.generate(turn2, sp).tokens == out.tokens and other.kv_swap_ins == 1
    finally:
        eng.stop()
        other.stop()


@pytest.mark.parametrize("kw,words", [
    ({"spec_len": 4}, "verify program"), ({"kv_layout": "slot"}, "paged pool"), ({"quantize": "int8"}, "weight-only int8"),
    ({"quantize_kv": True}, "scale twin"),
])
def test_what_the_family_does_not_serve_is_refused_in_words(kw, words):
    with pytest.raises(ValueError, match=words):
        Engine(config=CFG, mesh=ONE_CHIP(), max_slots=2, max_ctx=64, **{"kv_layout": "paged", "page_size": 8, **kw})


def test_tensor_parallelism_and_int8_rows_are_refused_in_words():
    with pytest.raises(ValueError, match="shared by all heads"):
        Engine(config=CFG, mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]), max_slots=2, max_ctx=64,
               kv_layout="paged", page_size=8)
    with pytest.raises(ValueError, match="scale twin"):
        kanana.init_paged_cache(CFG, 9, 8, quantize_kv=True)


@pytest.mark.parametrize("name", ["tiny", "lfm2-tiny", "jamba-tiny", "mellum-tiny", "kanana-tiny"])
def test_a_family_names_the_leaf_its_walk_fetches(name):
    """`page_leaf` is a leaf of the family's own paged cache, shaped
    [layers, pages, page rows, ...]: the engine sizes the walk from it and
    not from whichever leaf comes first (`mellum` keeps rings beside it)."""
    config = preset(name)
    model = programs(config)
    make = getattr(model, "init_paged_cache", None)
    if make is None:  # the dense family's pool is made by `ops.paged.init_kv_pages`
        from agentcontrolplane_tpu.ops.paged import init_kv_pages

        cache = init_kv_pages(config.n_layers, 5, 8, config.n_kv_heads, config.head_dim, jnp.float32)
    else:
        cache = jax.eval_shape(lambda: make(config, 5, 8, max_slots=2))
    leaf = cache[model.page_leaf]
    assert leaf.shape[1:3] == (5, 8) and model.page_leaf != "state"


@pytest.mark.parametrize("first", ["agentcontrolplane_tpu.parallel.mesh", "agentcontrolplane_tpu.models"])
def test_the_seam_can_be_imported_whichever_of_the_two_modules_comes_first(first):
    """`parallel/mesh.py` imports `models` for `LlamaConfig`, and the dense
    family's layout is `parallel/mesh.py`'s: the seam asks for it when a
    layout is asked for, not while it is being imported (the engine-free
    study imports the mesh first, and failed so on the chip: PERF.md, PR 44)."""
    import os
    import subprocess
    import sys

    code = (f"import {first}; from agentcontrolplane_tpu import models; from agentcontrolplane_tpu.parallel import mesh; "
            "assert callable(models.programs(models.preset('tiny')).shardings.params)")
    done = subprocess.run([sys.executable, "-c", code], env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]


def test_the_seam_says_what_the_engine_asks_of_a_family():
    """No state a slot, counters on the device, no layout of its own over a
    mesh (held whole), its own refusals and its own walk: the engine keeps no
    flag for any of it."""
    model = programs(CFG)
    assert model.family == "kanana" and not model.has_state and not model.window_cache
    assert model.counters is kanana.counters and model.shardings is None
    assert programs(preset("tiny")).shardings is not None and programs(preset("tiny")).refusals({}) == []
    asked = {"kv_layout": "paged", "spec_len": 0, "tp": 1, "sp": 1, "quantize_weights": False, "quantize_kv": False,
             "coordination": False, "host_kv_bytes": 1 << 20}
    assert not any(hit for hit, _ in model.refusals(asked))
    assert any(hit for hit, _ in programs(preset("mellum-tiny")).refusals(asked))  # its ring is carried nowhere
    full = preset("kanana-2-30b-a3b-ep16")
    assert (full.n_layers, full.first_dense, len(full.held), full.shared_width) == (48, 1, 8, 1536)
    import inspect

    from agentcontrolplane_tpu.engine import engine

    text = inspect.getsource(engine)
    assert 'cache["k"]' not in text and 'cache["v"]' not in text and "entry.k" not in text
