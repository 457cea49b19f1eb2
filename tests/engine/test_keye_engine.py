"""The engine serving Keye-VL-2.0's language model through the paths that move
a slot's pages leaf by leaf, the indexer's keys (`ik`) among them: short and
long slots in one batch held to the model's own forward pass (greedy, against
`testing.greedy_reference`), `stats()["sparse"]`, chunked prefill over cached
`ik` rows, a prefix hit, preemption and a resumed request (recompute and host
swap), a park and an export; what it refuses, in words. The family's operators
and programs: `test_keye.py`.

CPU, `keye-tiny` (contexts past its `topk` of 8 rows), float32, seeded
weights, the invariant checker armed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.models import keye, preset, programs
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import greedy_reference

ONE_CHIP = lambda: make_mesh({"tp": 1}, devices=jax.devices()[:1])  # noqa: E731
CFG = preset("keye-tiny")
MAX_CTX = 128  # the engines' and the padded reference's
PARAMS = None
GREEDY = SamplingParams(temperature=0.0, max_tokens=10)


def make_engine(**kw):
    global PARAMS
    if PARAMS is None:
        PARAMS = keye.init_params(CFG, jax.random.key(0))
    # armed: the engine audits its own books (pages, refcounts, host entries, the cache's leaves) after every cycle
    opts = dict(max_slots=4, max_ctx=MAX_CTX, kv_layout="paged", page_size=8, kv_pages=80,
                prefill_buckets=(16, 32, 64), width_buckets=(2, 4), decode_block_size=4, check_invariants=True)
    eng = Engine(config=CFG, params=PARAMS, mesh=ONE_CHIP(), **{**opts, **kw})
    eng.start()
    return eng


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, 256, n)] for n in lengths]


def reference(prompt, n):
    return greedy_reference(keye.forward, PARAMS, CFG, prompt, n, MAX_CTX)


def test_engine_serves_lanes_under_and_past_topk_in_one_batch_and_counts_what_it_chose():
    eng = make_engine()
    try:
        ps = prompts(3, 37, 60)  # the first lane stays under topk (8 rows) for its first steps
        with eng.hold_admission():
            futures = [eng.submit(p, GREEDY) for p in ps]
        for p, f in zip(ps, futures):
            assert f.result(300).tokens == reference(p, 10)
        assert set(eng.cache) == {"kv", "ik", "state"} and eng.cache["ik"].shape[1:] == (80, 8, CFG.ik_stored)
        # K and V as one row of 32-bit words: float32 here, two words a pair of values (bfloat16: one)
        assert eng.cache["kv"].shape == (CFG.n_layers, 80, 8, 2 * CFG.n_kv_heads * CFG.head_dim)
        assert eng.cache["kv"].dtype == jnp.uint32
        st = eng.stats()
        # what says the one-row pool is what serves: two leaves a page, K and V's bytes and the key's a row and layer
        row = (2 * CFG.n_kv_heads * CFG.head_dim + CFG.ik_stored) * 4
        assert st["kv_pages"]["leaves"] == ["ik", "kv"] and st["kv_pages"]["page_bytes"] == row * 8 * CFG.n_layers
        sparse, moe = st["sparse"], st["moe"]
        assert (sparse["topk"], sparse["layers"], sparse["index_heads"], sparse["index_values"]) == (8, 3, 4, 8)
        assert sparse["ik_row_bytes_stored"] == 128 * 4  # float32 here; 256 B in bfloat16
        dec, pre = sparse["decode"], sparse["prefill"]
        assert dec["steps"] == eng.decode_steps and 1 <= pre["steps"] <= 3  # prompts of one bucket share a dispatch
        # every layer of every live lane chose min(rows it could see, 8): the short lane's first steps see 4 to 8
        assert 0 < dec["rows_chosen"] < dec["rows_dense"] <= dec["rows_scored"]
        assert dec["rows_chosen"] % CFG.n_layers == 0 and dec["lanes_past_topk"] > 0
        assert pre["rows_dense"] == sum(n * (n + 1) // 2 for n in map(len, ps)) * CFG.n_layers
        assert pre["rows_chosen"] == sum(sum(min(t + 1, 8) for t in range(n)) for n in map(len, ps)) * CFG.n_layers
        assert (moe["experts"], moe["held"], moe["experts_per_token"]) == (16, 16, 2)
        assert st["kv_pages"]["pages_per_turn"] == 0  # no compiled walk: the rows are fetched by XLA's gather
        assert eng._jit_decode_paged.__wrapped__.__name__ == "decode_block"
    finally:
        eng.stop()


def test_once_every_lane_is_past_topk_a_step_chooses_lanes_x_topk_x_layers():
    eng = make_engine(width_buckets=(2,), max_slots=2)
    try:
        ps = prompts(20, 30, seed=2)
        with eng.hold_admission():
            futures = [eng.submit(p, SamplingParams(temperature=0.0, max_tokens=9)) for p in ps]
        for f in futures:
            f.result(300)
        dec = eng.stats()["sparse"]["decode"]
        # the first token comes from the prefill: 8 decode steps a lane, both lanes live in every one of them
        assert dec["rows_chosen"] == 2 * 8 * CFG.index_topk * CFG.n_layers and dec["lanes_past_topk"] == 2 * 8
    finally:
        eng.stop()


def test_chunked_prefill_reads_ik_rows_it_did_not_write():
    eng = make_engine(prefill_buckets=(16, 32), prefill_chunk=16)
    try:
        for p in prompts(70, 41, seed=3):
            assert eng.generate(p, GREEDY).tokens == reference(p, 10)
    finally:
        eng.stop()


@pytest.mark.parametrize("host_kv_bytes", [0, 1 << 22], ids=["recompute", "host-swap"])
def test_preempt_and_resume_carry_the_pools_two_leaves(host_kv_bytes):
    """An oversubscribed pool preempts; the resumed request recomputes, or
    has its pages restored from a host entry whose leaves are `kv` and `ik`,
    moved by the engine's leaf-generic helpers with no line for either: the
    pages a swap-in wrote hold the entry's rows bit for bit, both leaves."""
    eng = make_engine(kv_pages=14, host_kv_bytes=host_kv_bytes)
    entries, restored = [], []
    if host_kv_bytes:
        put, swap_in = eng._host_pool.put, eng._swap_in_rows
        eng._host_pool.put = lambda e: (entries.append(e), put(e))[1]

        def swap_in_and_read_back(slot, entry, start, n):
            took = swap_in(slot, entry, start, n)
            pages = np.asarray(eng._slot_pages[slot][start // 8: (start + n) // 8])
            for name, rows in entry.rows.items():
                back = np.asarray(eng.cache[name])[:, pages]  # [L, pages, P, width]
                restored.append((name, np.array_equal(back.reshape(back.shape[0], n, -1), rows[:, start: start + n])))
            return took

        eng._swap_in_rows = swap_in_and_read_back
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
            assert eng.kv_swap_outs >= 1 and eng.kv_swap_ins >= 1 and entries
            for e in entries:
                assert set(e.rows) == {"kv", "ik"} and e.rows["ik"].shape == (CFG.n_layers, e.cut, CFG.ik_stored)
                assert e.rows["kv"].shape == (CFG.n_layers, e.cut, 2 * CFG.n_kv_heads * CFG.head_dim) and e.rows["kv"].any()
                assert e.rows["kv"].dtype == np.uint32  # the pool's words as they are: a restore is bit for bit
            assert {name for name, _ in restored} == {"kv", "ik"} and all(same for _, same in restored)
    finally:
        eng.stop()


def test_a_prefix_hit_a_park_and_an_export_serve_it():
    eng, other = make_engine(prefix_dedup=True), make_engine(host_kv_bytes=1 << 22, prefix_cache_entries=0)
    try:
        base = prompts(45)[0]
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        eng.generate(base, sp)
        longer = base + prompts(9, seed=4)[0]
        hits = eng.stats()["prefix_cache"]["hits"]
        assert eng.generate(longer, sp).tokens == reference(longer, 6)  # a continuation over cached ik rows
        assert eng.stats()["prefix_cache"]["hits"] == hits + 1
        turn1 = prompts(29, seed=5)[0]
        turn2 = turn1 + prompts(15, seed=9)[0]
        eng.submit(turn1, sp, park=True).result(120)
        assert eng.generate(turn2, sp).tokens == reference(turn2, 6) and eng.park_adoptions == 1
        out = eng.submit(turn2, sp, export_kv=True).result(120)
        assert set(out.kv_handoff.rows) == {"kv", "ik"}
        assert other.inject_host_kv(out.kv_handoff)
        assert other.generate(turn2, sp).tokens == out.tokens and other.kv_swap_ins == 1
    finally:
        eng.stop()
        other.stop()


@pytest.mark.parametrize("kw,words", [
    ({"spec_len": 4}, "verify program over chosen rows"), ({"kv_layout": "slot"}, "indexer's keys live in the paged pool"),
    ({"quantize": "int8"}, "weight-only int8"), ({"quantize_kv": True}, "indexer's keys are kept in the model's dtype"),
])
def test_what_the_family_does_not_serve_is_refused_in_words(kw, words):
    with pytest.raises(ValueError, match=words):
        Engine(config=CFG, mesh=ONE_CHIP(), max_slots=2, max_ctx=64, **{"kv_layout": "paged", "page_size": 8, **kw})


def test_tensor_parallelism_and_int8_pages_are_refused_in_words_and_the_seam_names_the_family():
    with pytest.raises(ValueError, match="one choice of rows"):
        Engine(config=CFG, mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]), max_slots=2, max_ctx=64,
               kv_layout="paged", page_size=8)
    with pytest.raises(ValueError, match="int8 key of the"):
        keye.init_paged_cache(CFG, 9, 8, quantize_kv=True)
    seam = programs(CFG)
    assert (seam.family, seam.has_state, seam.window_cache, seam.draft_step, seam.page_leaf) == ("keye", False, False, None, "kv")
    assert seam.walk(CFG, 16, CFG.dtype, 1, False) is None and seam.shardings is None
