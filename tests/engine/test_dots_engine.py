"""The engine serving dots3-note-prev's language model through the paths
that move a slot's pages leaf by leaf (the full layers' latent rows `kv` and
their indexer's keys `ik`) and that hold a ring of latent rows a slot (`wkv`,
the sliding layers'): short and long slots in one batch held to the model's
own forward pass (greedy, against `testing.greedy_reference`),
`stats()["sparse"]` and `stats()["window"]`, chunked prefill over cached rows
and a ring as it stands, preemption and a resumed request (recompute), the
ring's books; what it refuses, in words. The family's operators and
programs: `test_dots.py`.

CPU, `dots-tiny` (contexts past its `topk` of 8 rows and its window of 9),
float32, seeded weights, the invariant checker armed.
"""

import jax
import numpy as np
import pytest

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.models import dots, preset, programs
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import greedy_reference

ONE_CHIP = lambda: make_mesh({"tp": 1}, devices=jax.devices()[:1])  # noqa: E731
CFG = preset("dots-tiny")
MAX_CTX = 128  # the engines' and the padded reference's
PARAMS = None
GREEDY = SamplingParams(temperature=0.0, max_tokens=10)


def make_engine(**kw):
    global PARAMS
    if PARAMS is None:
        PARAMS = dots.init_params(CFG, jax.random.key(0))
    # armed: the engine audits its own books (pages, refcounts, rings, the cache's leaves) after every cycle
    opts = dict(max_slots=4, max_ctx=MAX_CTX, kv_layout="paged", page_size=8, kv_pages=80,
                prefill_buckets=(16, 32, 64), width_buckets=(2, 4), decode_block_size=4, check_invariants=True)
    eng = Engine(config=CFG, params=PARAMS, mesh=ONE_CHIP(), **{**opts, **kw})
    eng.start()
    return eng


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, 256, n)] for n in lengths]


def reference(prompt, n):
    return greedy_reference(dots.forward, PARAMS, CFG, prompt, n, MAX_CTX)


def test_engine_serves_lanes_under_and_past_topk_and_window_in_one_batch_and_counts_both():
    eng = make_engine()
    try:
        ps = prompts(3, 37, 60)  # the first lane stays under topk (8 rows) and the window (9) for its first steps
        with eng.hold_admission():
            futures = [eng.submit(p, GREEDY) for p in ps]
        for p, f in zip(ps, futures):
            assert f.result(300).tokens == reference(p, 10)
        ring = 16 // 8 + 1  # the window of 9 rows on whole pages of 16 rows' worth, and a page of slack
        assert set(eng.cache) == {"kv", "ik", "wkv", "state"}
        assert eng.cache["kv"].shape == (2, 80, 8, 128) and eng.cache["ik"].shape == (2, 80, 8, 16)
        assert eng.cache["wkv"].shape == (3, (4 + 1) * ring, 8, 128)
        st = eng.stats()
        # the page list's leaves alone cost a page: the rings are no part of it
        assert st["kv_pages"]["leaves"] == ["ik", "kv"] and st["kv_pages"]["page_bytes"] == (128 + 16) * 4 * 8 * 2
        sparse, window, moe = st["sparse"], st["window"], st["moe"]
        assert (sparse["topk"], sparse["layers"], sparse["index_heads"], sparse["index_values"]) == (8, 2, 4, 16)
        dec, pre = sparse["decode"], sparse["prefill"]
        assert dec["steps"] == eng.decode_steps and 1 <= pre["steps"] <= 3
        assert 0 < dec["rows_chosen"] < dec["rows_dense"] <= dec["rows_scored"]
        assert dec["rows_chosen"] % 2 == 0 and dec["lanes_past_topk"] > 0
        assert pre["rows_dense"] == sum(n * (n + 1) // 2 for n in map(len, ps)) * 2
        assert pre["rows_chosen"] == sum(sum(min(t + 1, 8) for t in range(n)) for n in map(len, ps)) * 2
        assert (window["window"], window["window_layers"], window["full_layers"]) == (9, 3, 2)
        assert (window["pages_per_slot"], window["rows_per_slot"], window["slots_holding"]) == (ring, ring * 8, 0)
        wd = window["decode"]
        assert wd["steps"] == dec["steps"] and 0 < wd["rows_read"] < wd["rows_unwindowed"] and wd["slots_past_window"] > 0
        assert window["prefill"]["rows_read"] == sum(sum(min(t + 1, 9) for t in range(n)) for n in map(len, ps))
        assert (moe["experts"], moe["held"], moe["experts_per_token"], moe["shared_width"]) == (16, 16, 2, 32)
        assert st["kv_pages"]["pages_per_turn"] == 0  # no compiled walk: XLA's gathers serve both kinds of layer
        assert eng._jit_decode_paged.__wrapped__.__name__ == "decode_block"
    finally:
        eng.stop()


def test_chunked_prefill_reads_rows_and_a_ring_it_did_not_write():
    eng = make_engine(prefill_buckets=(16, 32), prefill_chunk=16)
    try:
        for p in prompts(70, 41, seed=3):
            assert eng.generate(p, GREEDY).tokens == reference(p, 10)
    finally:
        eng.stop()


def test_preempt_and_resume_recompute_pages_and_ring():
    """An oversubscribed pool preempts; the resumed request's prefill writes
    its pages and its slot's ring again (nothing of either is carried: the
    family refuses a host tier), and every request's tokens are its solo
    run's."""
    eng = make_engine(kv_pages=14)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=12)
        ps = prompts(*[20] * 6, seed=1)
        solo = [eng.generate(p, sp).tokens for p in ps]
        assert solo[0] == reference(ps[0], 12)
        with eng.hold_admission():
            futures = [eng.submit(p, sp) for p in ps]
        assert [f.result(300).tokens for f in futures] == solo
        assert eng.preemptions >= 1 and eng.stats()["window"]["slots_holding"] == 0
    finally:
        eng.stop()


def test_prefix_entries_dedup_and_parks_are_off_for_a_family_with_a_ring():
    eng = make_engine(prefix_cache_entries=8, prefix_dedup=True, park_max_s=5.0)
    try:
        base = prompts(45)[0]
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        eng.generate(base, sp)
        longer = base + prompts(9, seed=4)[0]
        assert eng.generate(longer, sp).tokens == reference(longer, 6)  # a whole prefill: no entry was kept
        assert "prefix_cache" not in eng.stats() and eng.park_adoptions == 0
    finally:
        eng.stop()


@pytest.mark.parametrize("kw,words", [
    ({"spec_len": 4}, "speculation needs the per-slot state rolled back"), ({"kv_layout": "slot"}, "state lives beside the paged pool"),
    ({"quantize": "int8"}, "weight-only int8"), ({"quantize_kv": True}, "window cache and its pages are kept in the model's dtype"),
    ({"host_kv_bytes": 1 << 20}, "window cache is not carried to the host"),
])
def test_what_the_family_does_not_serve_is_refused_in_words(kw, words):
    with pytest.raises(ValueError, match=words):
        Engine(config=CFG, mesh=ONE_CHIP(), max_slots=2, max_ctx=64, **{"kv_layout": "paged", "page_size": 8, **kw})


def test_tensor_parallelism_and_int8_rows_are_refused_in_words_and_the_seam_names_the_family():
    with pytest.raises(ValueError, match="tensor or context parallelism"):
        Engine(config=CFG, mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]), max_slots=2, max_ctx=64,
               kv_layout="paged", page_size=8)
    with pytest.raises(ValueError, match="a rounded key of the indexer"):
        dots.init_paged_cache(CFG, 9, 8, quantize_kv=True)
    with pytest.raises(NotImplementedError, match="rebuilt by a prefill"):
        dots.install_state({}, 0, None)
    seam = programs(CFG)
    assert (seam.family, seam.has_state, seam.window_cache, seam.draft_step, seam.page_leaf) == ("dots", True, True, None, "kv")
    assert seam.walk(CFG, 16, CFG.dtype, 1, False) is None and seam.shardings is None
    assert preset("dots3-note-prev").n_layers == 46 and programs(preset("dots3-note-prev")) is seam
