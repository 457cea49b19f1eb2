"""Ouro through the normal path: the program against the plain reference
(acpbench/families/ouro_reference.py, which imports nothing of the program)
for the forward pass, prefill then decode through a pool `loops` times as
deep as the weights, and continuation; the Pallas walk in interpret mode at a
query group of one; one loop against the dense family's own programs; the
exit choice at thresholds 0.5 and 1; each loop reading its own cache layers
and no other; every control of the reference over a limit; and the engine
serving it through the paths that move a slot's pages leaf by leaf: a prefix
hit, dedup, preemption and resume, a host swap and back, an export; what it
refuses, in words; what it counts.

CPU, tiny sizes (2 loops x 3 layers and 4 x 2, hidden 64, 4 heads of 16),
float32 and bfloat16, seeded weights.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acpbench import check, spec
from acpbench.families import ouro_reference, ouro_weights
from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.models import llama, ouro, preset, programs
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import greedy_reference

FILE = spec.load_json(spec.os.path.join(spec.ROOT, "tests/acpbench/data/tiny-config-ouro.json"))
PAGE = FILE["engine"]["page_size"]
ONE_CHIP = lambda: make_mesh({"tp": 1}, devices=jax.devices()[:1])  # noqa: E731
SHAPES = {"2x3": {}, "4x2": {"total_ut_steps": 4, "num_hidden_layers": 2, "layer_types": ["full_attention"] * 2}}


def tiny(**over):
    config = dict(FILE)
    config["check"] = dict(FILE["check"], **over.pop("check", {}))
    return {**config, **over}


def built(config, seed=5, dtype=jnp.float32):
    family = spec.family(config)
    pc = dataclasses.replace(family.program_config(config), dtype=dtype)
    return family, pc, ONE_CHIP(), family.weights(config, pc, ONE_CHIP(), seed)


def sample(config, seed=5):
    return check.sample(config["check"], config["vocab_size"], PAGE, seed)


# -- the program against the plain reference ---------------------------------------------------


@pytest.mark.parametrize("shape", list(SHAPES))
def test_forward_agrees_with_the_plain_reference(shape):
    config = tiny(**SHAPES[shape])
    family, pc, _, params = built(config)
    tokens = np.random.default_rng(1).integers(0, 256, (2, 40)).astype(np.int32)
    rows = np.tile(np.arange(40), (2, 1))
    want = family.reference_logits(config, params, tokens, rows)
    got = ouro.forward(params, jnp.asarray(tokens), pc)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("shape,dtype,limit", [("2x3", jnp.float32, 1e-4), ("4x2", jnp.float32, 1e-4),
                                               ("2x3", jnp.bfloat16, 0.06)], ids=["2x3-f32", "4x2-f32", "2x3-bf16"])
def test_program_agrees_with_the_plain_reference_through_the_pool(shape, dtype, limit):
    """Prefill, then decode through the cache across page boundaries, against
    the reference's full forward pass: logits, not tokens."""
    config = tiny(**SHAPES[shape])
    family, pc, mesh, params = built(config, seed=2**31 + 7, dtype=dtype)
    s = sample(config, 2**31 + 7)
    got = family.cached_logits(config, pc, params, mesh, s, False)
    numbers = check.compare(got, check.reference_logits(lambda *a, **k: family.reference_logits(config, params, *a, **k), s))
    assert numbers["finite"] and numbers["logit_rel_rms"] < limit and numbers["decode_rel_rms"] < limit, numbers
    if dtype == jnp.float32:
        assert abs(numbers["cache_excess"]) < 0.5 and numbers["top1_agree"] == 1.0


def _pool_after_prefill(pc, params, tokens, lengths, pages=12):
    B, T = tokens.shape
    cache = ouro.init_paged_cache(pc, pages, PAGE)
    ids = 1 + np.arange(B * (T // PAGE), dtype=np.int32).reshape(B, T // PAGE)
    ids = np.where(np.arange(T // PAGE)[None] < -(-lengths[:, None] // PAGE), ids, 0)
    toks = np.where(np.arange(T)[None] < lengths[:, None], tokens, 0)
    return ouro.prefill_paged_batch(params, cache, jnp.asarray(toks), jnp.asarray(lengths), jnp.asarray(ids), pc), ids


def test_continuation_reads_in_each_loop_that_loops_rows_it_did_not_write():
    config = tiny()
    family, pc, _, params = built(config)
    tokens = np.random.default_rng(3).integers(0, 256, (2, 48)).astype(np.int32)
    starts = np.array([16, 24], np.int32)  # page-aligned: row 0 holds pages 1-2, row 1 pages 5-7
    (cache, _), ids = _pool_after_prefill(pc, params, tokens[:, :32], starts)
    more = np.array([9, 16], np.int32)  # row 0 writes page 10, row 1 pages 11 and 9
    suffix = np.stack([np.pad(tokens[b, starts[b]: starts[b] + more[b]], (0, 16 - more[b])) for b in range(2)])
    new = np.array([[10, 0], [11, 9]], np.int32)
    tables = np.array([[*ids[0, :2], 10, 0, 0], [*ids[1, :3], 11, 9]], np.int32)
    cache, logits = ouro.prefill_paged_continue(params, cache, jnp.asarray(suffix), jnp.asarray(more),
                                                jnp.asarray(starts), jnp.asarray(new), jnp.asarray(tables), pc)
    want = family.reference_logits(config, params, tokens, (starts + more - 1)[:, None])[:, 0]
    assert float(jnp.max(jnp.abs(logits - want))) < 2e-4 * float(jnp.max(jnp.abs(want)))
    counts = ouro.describe_counters(pc, np.asarray(ouro.counters(cache)))["loops"]["prefill"]
    assert counts["tokens"] == int(starts.sum() + more.sum()) and counts["passes"] == 2 * counts["tokens"]
    # and a decode step goes on from the continued rows
    step = (jnp.asarray(tokens[np.arange(2), starts + more]), jnp.asarray(starts + more), jnp.asarray(tables), jnp.ones(2, bool))
    _, logits = ouro.decode_step_paged(params, cache, *step, pc)
    want = family.reference_logits(config, params, tokens, (starts + more)[:, None])[:, 0]
    assert float(jnp.max(jnp.abs(logits - want))) < 2e-4 * float(jnp.max(jnp.abs(want)))


def test_the_pallas_walk_in_interpret_mode_serves_a_query_group_of_one():
    config = tiny()
    _, pc, _, params = built(config)
    tokens = np.random.default_rng(4).integers(0, 256, (2, 32)).astype(np.int32)
    lengths = np.array([30, 11], np.int32)
    (cache, _), ids = _pool_after_prefill(pc, params, tokens, lengths)
    tables = np.concatenate([ids, np.zeros((2, 1), np.int32)], axis=1)
    args = (params, cache, jnp.asarray([7, 9]), jnp.asarray(lengths), jnp.asarray(tables), jnp.ones(2, bool), pc)
    _, by_reference = ouro.decode_step_paged(*args)
    _, by_kernel = ouro.decode_step_paged(*args, interpret=True)
    assert pc.n_heads // pc.n_kv_heads == 1
    assert float(jnp.max(jnp.abs(by_kernel - by_reference))) < 1e-4 * float(jnp.max(jnp.abs(by_reference)))


def test_one_loop_is_the_dense_familys_own_program_bit_for_bit():
    """`loops=1`: the dense family's prefill and decode with `post_norms`, its
    last norm applied once, which is then the loop's: the same arithmetic in
    the same order, so the same bits."""
    config = tiny(total_ut_steps=1)
    _, pc, _, params = built(config)
    dense = llama.LlamaConfig(**{f.name: getattr(pc, f.name) for f in dataclasses.fields(llama.LlamaConfig)})
    dense_params = {k: v for k, v in params.items() if not k.startswith("gate_")}
    dense_params["layers"] = {**params["layers"], **{n: jnp.swapaxes(params["layers"][n], 1, 2) for n in ouro.OUT_FIRST}}
    tokens = np.random.default_rng(5).integers(0, 256, (2, 32)).astype(np.int32)
    lengths = np.array([32, 13], np.int32)
    (cache, logits), ids = _pool_after_prefill(pc, params, tokens, lengths)
    pool = llama.init_paged_cache(dense, 12, PAGE)
    toks = np.where(np.arange(32)[None] < lengths[:, None], tokens, 0)
    pool, dense_logits = llama.prefill_paged_batch(dense_params, pool, jnp.asarray(toks), jnp.asarray(lengths),
                                                   jnp.asarray(ids), dense)
    assert jnp.array_equal(logits, dense_logits) and jnp.array_equal(cache["k"], pool["k"])
    tables = np.concatenate([ids, np.array([[9], [0]], np.int32)], axis=1)
    step = (jnp.asarray([7, 9]), jnp.asarray(lengths), jnp.asarray(tables), jnp.ones(2, bool))
    cache, logits = ouro.decode_step_paged(params, cache, *step, pc)
    pool, dense_logits = llama.decode_step_paged(dense_params, pool, *step, dense)
    assert jnp.array_equal(logits, dense_logits) and jnp.array_equal(cache["v"], pool["v"])


@pytest.mark.parametrize("threshold,loops_read", [(1, {1}), (0.5, {0, 1})])
def test_the_exit_choice_is_held_to_the_reference(threshold, loops_read):
    """At the published threshold every row reads the last loop; at 0.5 the
    loops chosen differ by token, in the program as in the reference."""
    config = tiny(early_exit_threshold=threshold)
    family, pc, _, params = built(config)
    tokens = np.random.default_rng(6).integers(0, 256, (3, 48)).astype(np.int32)
    rows = np.tile(np.arange(48), (3, 1))
    _, gates = ouro_reference.states(params, family._sizes(config), tokens, rows)
    chosen = np.asarray(ouro_reference.exit_choice(gates, float(threshold)))
    assert set(np.unique(chosen)) == loops_read
    assert np.array_equal(np.asarray(ouro.exit_choice(gates, float(threshold))), chosen)
    want = family.reference_logits(config, params, tokens, rows)
    got = ouro.forward(params, jnp.asarray(tokens), pc)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4 * float(jnp.max(jnp.abs(want)))
    lam = np.asarray(jax.nn.sigmoid(gates))
    assert 0.2 < lam.min() and lam.max() < 0.8  # the value policy: a gate never saturates


def test_each_loop_reads_its_own_cache_layers_and_no_other(monkeypatch):
    """Loop t's walks are handed cache layers `t * L .. t * L + L - 1` in
    order (the tables they take, run without jit so that they can be read); a
    pool poisoned with NaN in the LATER loops' cache layers leaves loop t's
    new rows as they were, and poisoned in loop t's alone it does not."""
    config = tiny(**SHAPES["4x2"])
    _, pc, _, params = built(config)
    L, pages = pc.n_layers, 12
    tokens = np.random.default_rng(7).integers(0, 256, (2, 32)).astype(np.int32)
    lengths = np.array([21, 32], np.int32)
    (cache, _), ids = _pool_after_prefill(pc, params, tokens, lengths, pages)
    tables = np.concatenate([ids, np.array([[0], [9]], np.int32)], axis=1)
    step = (jnp.asarray([7, 9]), jnp.asarray(lengths), jnp.asarray(tables), jnp.ones(2, bool))
    seen = []
    walk = ouro.paged_decode_attention_reference_cache_plus_new

    def spy(q, k, v, handed, *rest, **kw):
        seen.append(sorted({int(i) // pages for i in np.asarray(handed).ravel()}))
        return walk(q, k, v, handed, *rest, **kw)

    monkeypatch.setattr(ouro, "paged_decode_attention_reference_cache_plus_new", spy)
    with jax.disable_jit():
        ouro.decode_step_paged(params, cache, *step, pc)
    monkeypatch.undo()
    decode = jax.jit(lambda pool: ouro.decode_step_paged(params, pool, *step, pc))
    clean, clean_logits = decode(cache)
    assert seen == [[i] for i in range(pc.loops * L)]  # t * L + l, each walk one cache layer
    spoil = lambda bad: {**cache, **{n: jnp.where(bad[:, None, None, None], jnp.nan, cache[n]) for n in ("k", "v")}}  # noqa: E731
    loop_of = jnp.arange(pc.cache_layers) // L
    page, row = int(tables[0, 21 // PAGE]), 21 % PAGE
    for t in range(pc.loops):
        later, _ = decode(spoil(loop_of > t))
        upto = (t + 1) * L  # what loops 0..t wrote does not depend on a later loop's rows
        assert jnp.array_equal(later["k"][:upto, page, row], clean["k"][:upto, page, row])
        _, own = decode(spoil(loop_of == t))
        assert not bool(jnp.all(jnp.isfinite(own)))
    written = np.asarray(clean["k"][:, page, row]) != np.asarray(cache["k"][:, page, row])
    assert written.any(axis=-1).all() and written.shape[0] == pc.loops * L  # every cache layer, one commit
    assert bool(jnp.all(jnp.isfinite(clean_logits)))


# -- the controls ------------------------------------------------------------------------------------

LIMIT = 0.02  # over the float32 program's 1e-5 and under every control's reading at the tiny size


@pytest.mark.parametrize("control,least", [
    ("int8_inputs", 0.02), ("loops_3", 0.3), ("shared_cache", 0.1), ("no_loop_norm", 0.1), ("no_post_norms", 0.3),
    ("exit_first", 0.3)])
def test_each_reference_control_reads_over_the_limit(control, least):
    config = tiny(total_ut_steps=4, num_hidden_layers=2, layer_types=["full_attention"] * 2)
    family, _, _, params = built(config)
    s = sample(config)
    reference = lambda *a, **k: family.reference_logits(config, params, *a, **k)  # noqa: E731
    want = check.reference_logits(reference, s)
    read = check.compare(check.reference_logits(reference, s, lower=control), want)["logit_rel_rms"]
    assert read > least >= LIMIT, (control, read)


def test_the_stated_precision_passes_and_an_unknown_control_is_an_error():
    config = tiny()
    family, _, _, params = built(config)
    s = sample(config)
    reference = lambda *a, **k: family.reference_logits(config, params, *a, **k)  # noqa: E731
    want = check.reference_logits(reference, s)
    stated = check.compare(check.reference_logits(reference, s, lower="bf16_rest"), want)["logit_rel_rms"]
    below = check.compare(check.reference_logits(reference, s, lower="int8_inputs"), want)["logit_rel_rms"]
    assert 0 < stated < FILE["check"]["limits"]["logit_rel_rms"] and below > 1.5 * stated
    with pytest.raises(ValueError, match="no control 'int4'"):
        family.reference_logits(config, params, [[0]], [[0]], lower="int4")


def test_the_cache_control_is_seen():
    config = tiny()
    family, pc, mesh, params = built(config)
    s = sample(config)
    want = check.reference_logits(lambda *a, **k: family.reference_logits(config, params, *a, **k), s)
    sound = check.compare(family.cached_logits(config, pc, params, mesh, s, False), want)
    int8 = check.compare(family.cached_logits(config, pc, params, mesh, s, False, kv_int8=True), want)
    assert int8["decode_rel_rms"] > 100 * sound["decode_rel_rms"] and int8["cache_excess"] > 0.5


def test_the_value_policy_keeps_the_state_in_hand_through_every_loop():
    config = tiny(total_ut_steps=4)
    family, pc, _, params = built(config)
    gain = (2 * pc.n_layers) ** -0.5
    post = params["layers"]["ln1_post"]
    assert abs(float(jnp.mean(post)) - gain) < 0.1 * gain and float(jnp.std(post)) > 0.2 * gain
    assert float(jnp.std(params["norm"])) > 0.2 and params["gate_w"].dtype == jnp.float32
    assert params["layers"]["wq"].shape == (pc.n_layers, pc.n_heads * pc.head_dim, pc.dim)  # outputs first
    assert float(jnp.std(params["layers"]["wq"])) > 1.8 * float(jnp.std(params["layers"]["wk"]))
    tokens = np.random.default_rng(8).integers(0, 256, (2, 40)).astype(np.int32)
    hs, _ = ouro_reference.states(params, family._sizes(config), tokens, np.tile(np.arange(40), (2, 1)))
    rms = np.sqrt(np.mean(np.square(np.asarray(hs)), axis=(1, 2, 3)))
    assert rms.shape == (4,) and 0.8 < rms.min() and rms.max() < 1.3  # neither blown up nor drowned
    assert ouro_weights.OUT_FIRST == ouro.OUT_FIRST


# -- the engine ------------------------------------------------------------------------------

CFG = preset("ouro-tiny")
MAX_CTX = 128  # the engines' and the padded reference's
PARAMS = None


def make_engine(**kw):
    global PARAMS
    if PARAMS is None:
        PARAMS = ouro.init_params(CFG, jax.random.key(0))
    # armed: the engine audits its own books (pages, refcounts, host entries, the cache's leaves) after every cycle
    opts = dict(max_slots=4, max_ctx=MAX_CTX, kv_layout="paged", page_size=8, kv_pages=80, prefill_batch_max=1,
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
            assert f.result(300).tokens == greedy_reference(ouro.forward, PARAMS, CFG, p, 10, MAX_CTX)
        st = eng.stats()
        assert set(eng.cache) == {"k", "v", "state"} and eng.cache["k"].shape[0] == CFG.loops * CFG.n_layers
        assert (st["model"]["layers"], st["model"]["cache_layers"]) == (3, 6)
        assert st["kv_pages"]["page_bytes"] == 2 * 6 * 8 * 64 * 4  # K and V, six cache layers of 8 rows of 64 float32
        loops = st["loops"]
        assert (loops["loops"], loops["layers"], loops["cache_layers"], loops["exit_threshold"]) == (2, 3, 6, 1.0)
        assert loops["prefill"]["tokens"] == sum(map(len, ps)) and loops["prefill"]["exit_at"] == [0, 3]
        for phase in (loops["decode"], loops["prefill"]):
            assert phase["passes"] == 2 * phase["tokens"] and phase["cache_rows"] == 6 * phase["tokens"]
        assert loops["decode"]["tokens"] >= 27 and loops["decode"]["exit_at"] == [0, loops["decode"]["tokens"]]
        assert st["kv_pages"]["pages_per_turn"] == 0  # the CPU's reference: no kernel
    finally:
        eng.stop()


def test_chunked_prefill_and_a_prefix_hit_read_each_loops_own_rows():
    eng = make_engine(prefill_buckets=(16, 32), prefill_chunk=16, prefix_dedup=True)
    try:
        for p in prompts(70, 41, seed=3):
            assert eng.generate(p, GREEDY).tokens == greedy_reference(ouro.forward, PARAMS, CFG, p, 10, MAX_CTX)
        base = prompts(45)[0]
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        eng.generate(base, sp)
        longer = base + prompts(9, seed=4)[0]
        hits = eng.stats()["prefix_cache"]["hits"]
        assert eng.generate(longer, sp).tokens == greedy_reference(ouro.forward, PARAMS, CFG, longer, 6, MAX_CTX)
        assert eng.stats()["prefix_cache"]["hits"] == hits + 1
        fresh = prompts(41, seed=8)[0]
        with eng.hold_admission():
            futures = [eng.submit(fresh + [7, i], sp) for i in range(3)]
        for i, f in enumerate(futures):
            assert f.result(120).tokens == greedy_reference(ouro.forward, PARAMS, CFG, fresh + [7, i], 6, MAX_CTX)
        assert eng.prefix_shares >= 1
    finally:
        eng.stop()


@pytest.mark.parametrize("host_kv_bytes", [0, 1 << 22], ids=["recompute", "host-swap"])
def test_preempt_and_resume_reproduce_the_uninterrupted_tokens(host_kv_bytes):
    """An oversubscribed pool preempts; the resume recomputes, or restores
    the slot's pages, every cache layer of them, from a host entry."""
    # 14 pages of 8 rows: a prefill dispatch may stack an eighth of them, so prompts spill over 8-token buckets
    eng = make_engine(kv_pages=14, host_kv_bytes=host_kv_bytes, prefill_buckets=(8,))
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


def test_a_parked_turn_is_adopted_and_an_export_carries_every_cache_layer():
    eng, other = make_engine(), make_engine(host_kv_bytes=1 << 22, prefix_cache_entries=0)
    try:
        turn1 = prompts(29)[0]
        turn2 = turn1 + prompts(15, seed=9)[0]
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        eng.submit(turn1, sp, park=True).result(120)
        assert eng.stats()["parked_slots"] == 1
        assert eng.generate(turn2, sp).tokens == greedy_reference(ouro.forward, PARAMS, CFG, turn2, 8, MAX_CTX)
        assert eng.park_adoptions == 1
        out = eng.submit(turn2, sp, export_kv=True).result(120)
        entry = out.kv_handoff
        assert set(entry.rows) == {"k", "v"} and entry.rows["k"].shape == (6, entry.cut, 64)
        assert entry.nbytes == 2 * 6 * entry.cut * 64 * 4  # the leaf's bytes, not the config's three layers'
        assert other.inject_host_kv(entry)
        assert other.generate(turn2, sp).tokens == out.tokens and other.kv_swap_ins == 1
    finally:
        eng.stop()
        other.stop()


@pytest.mark.parametrize("kw,words", [
    ({"spec_len": 4}, "verify program"), ({"kv_layout": "slot"}, "deeper than its weights"),
    ({"quantize": "int8"}, "weight-only int8"), ({"quantize_kv": True}, "model's dtype"),
    ({"prefill_batch_max": 8, "prefill_buckets": (64,)}, "temporary the size of the cache"),
])
def test_what_the_family_does_not_serve_is_refused_in_words(kw, words):
    with pytest.raises(ValueError, match=words):
        Engine(config=CFG, mesh=ONE_CHIP(), max_slots=2, max_ctx=64,
               **{"kv_layout": "paged", "page_size": 8, "prefill_batch_max": 1, "prefill_buckets": (8,), **kw})


def test_tensor_parallelism_and_int8_pages_are_refused_in_words():
    with pytest.raises(ValueError, match="no sharding here"):
        Engine(config=CFG, mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]), max_slots=2, max_ctx=64,
               kv_layout="paged", page_size=8, prefill_batch_max=1, prefill_buckets=(8,))
    with pytest.raises(ValueError, match="model's dtype"):
        ouro.init_paged_cache(CFG, 9, 8, quantize_kv=True)


def test_the_seam_says_what_the_engine_asks_of_the_family():
    model = programs(CFG)
    assert model.family == "ouro" and not model.has_state and not model.window_cache and model.page_leaf == "k"
    assert model.counters is ouro.counters and model.shardings is None
    assert programs(preset("tiny")).family == "llama"  # the MRO finds the derived config's row first
    full = preset("ouro-2.6b")
    assert (full.n_layers, full.loops, full.cache_layers, full.n_kv_heads, full.head_dim) == (48, 4, 192, 16, 128)
    assert full.post_norms and full.exit_threshold == 1.0 and full.vocab_size == 49152
    leaf = jax.eval_shape(lambda: model.init_paged_cache(full, 3, 16))["k"]
    assert leaf.shape == (192, 3, 16, 2048) and 2 * leaf.size * 2 // 3 // 16 == 1_572_864  # bytes a token
    asked = {"kv_layout": "paged", "spec_len": 0, "tp": 1, "sp": 1, "quantize_weights": False, "quantize_kv": False,
             "coordination": False, "host_kv_bytes": 1 << 20, "prefill_rows": 512, "pool_rows": 5136}
    assert not any(hit for hit, _ in model.refusals(asked))
    assert [why for hit, why in model.refusals({**asked, "prefill_rows": 8 * 2048}) if hit][0].startswith(
        "prefill_batch_max x its widest prefill bucket = 16384 rows")
    import inspect

    from agentcontrolplane_tpu.engine import engine

    text = inspect.getsource(engine)
    assert "ouro" not in text.replace("models/ouro.py", "")  # no branch on the family's name
    assert text.count("config.n_layers") == 3  # the model's report and the two slot-layout paths it is refused


@pytest.mark.parametrize("name,page", [("ouro-2.6b", 25_165_824), ("qwen2.5-7b", 917_504), ("ouro-tiny", 2 * 6 * 16 * 64 * 4)])
def test_a_pages_bytes_are_read_off_the_familys_pool(name, page):
    from agentcontrolplane_tpu.models import page_bytes

    assert page_bytes(preset(name), 16) == page  # 192 cache layers where the config says 48 layers


def test_the_clis_pool_is_what_the_chip_holds_beside_the_weights_not_four_times_it(monkeypatch):
    """64 slots of 2,048 tokens at 1.5 MiB a token would be 206 GB: the pool
    is cut to what a 16 GB chip holds beside 5.34 GB of weights; a family
    whose slots fit keeps its slot-equivalent pool."""
    import types

    from agentcontrolplane_tpu import cli
    from agentcontrolplane_tpu.models import kv_pages_that_fit

    full = preset("ouro-2.6b")
    pages = kv_pages_that_fit(full, 64, 2048, 16, int(16.9e9), int(5.34e9))
    assert pages == 392 and pages * 25_165_824 + 5.34e9 < 0.9 * 16.9e9 < (pages + 1) * 25_165_824 + 5.34e9
    assert kv_pages_that_fit(preset("tiny"), 4, 128, 16, int(16e9), 10**6) == 4 * 8 + 1
    args = types.SimpleNamespace(tpu_preset="ouro-2.6b", tpu_slots=64, tpu_ctx=2048, tpu_tp=0, tpu_quantize=None,
                                 tpu_quantize_weights=False, tpu_quantize_kv=False)
    assert cli._kv_pages_from_memory(args) == 0  # the CPU reports no memory: the engine's own default stands
    device = types.SimpleNamespace(memory_stats=lambda: {"bytes_limit": int(16.9e9)})
    monkeypatch.setattr(jax, "local_devices", lambda: [device])
    assert cli._kv_pages_from_memory(args) == 392
