"""Jamba through the normal path: the two kernels of the recurrence (Pallas
interpret mode) against a token-by-token recurrence; the program against the
plain reference (acpbench/families/jamba_reference.py, which imports nothing
of the program) for prefill, continuation from a carried state and decode
through pages and state; and the engine carrying the tree-valued state
through chunked prefill, preempt, host swap, park and the prefix cache.

CPU, tiny sizes, float32 (so that agreement is to rounding, not to
bfloat16), seeded weights.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acpbench import check, spec
from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.models import jamba, preset, programs
from agentcontrolplane_tpu.ops.pallas import ssm_scan as ssm
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import greedy_reference

FILE = spec.load_json(spec.os.path.join(spec.ROOT, "acpbench/configs/jamba2-3b-bf16-v5e1.json"))
M, A = "mamba", "full_attention"
PATTERNS = {"mamba-first": [M, A], "attention-first": [A, M, M], "tiny": [M, A, M, M]}


def tiny(layer_types, **over):
    """The configuration's file at toy widths: same keys, same family."""
    config = dict(FILE, hidden_size=32, intermediate_size=64, num_attention_heads=4, num_key_value_heads=1,
                  head_dim=16, vocab_size=512, mamba_dt_rank=8, layer_types=list(layer_types),
                  num_hidden_layers=len(layer_types))
    config["check"] = dict(FILE["check"], sequences=3, prefill_bucket=32, min_prompt=8, decode_steps=4)
    return {**config, **over}


def built(config, seed=5):
    family = spec.family(config)
    pc = dataclasses.replace(family.program_config(config), dtype=jnp.float32)
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1])
    return family, pc, mesh, family.weights(config, pc, mesh, seed)


# -- the kernels ---------------------------------------------------------------


def recurrence(delta, u, b, c, a, h0, upto):
    """The reference's recurrence, one token at a time in numpy: h after
    `upto[r]` tokens of row r, and y of every token."""
    R, T, D = delta.shape
    h, y, at = h0.copy(), np.zeros((R, T, D)), np.zeros_like(h0)
    for r in range(R):
        if upto[r] == 0:
            at[r] = h[r]
    for t in range(T):
        h = np.exp(delta[:, t, None, :] * a[None]) * h + (delta[:, t] * u[:, t])[:, None, :] * b[:, t, :, None]
        y[:, t] = (h * c[:, t, :, None]).sum(1)
        for r in range(R):
            if upto[r] == t + 1:
                at[r] = h[r]
    return y, at


def scan_inputs(R, T, D, N, lengths, seed=0):
    rng = np.random.default_rng(seed)
    delta = rng.uniform(1e-3, 1e-1, (R, T, D))
    delta = np.where(np.arange(T)[None, :, None] < np.asarray(lengths)[:, None, None], delta, 0.0)
    u, b, c = rng.normal(size=(R, T, D)), rng.normal(size=(R, T, N)), rng.normal(size=(R, T, N))
    a = -np.tile(np.arange(1, N + 1, dtype=np.float64)[:, None], (1, D))
    return delta, u, b, c, a, rng.normal(size=(R, N, D))


@pytest.mark.parametrize("chunks,lengths,snaps", [
    (1, (128, 70, 5), (128, 64, 0)),  # a snapshot at the end, inside, at the start
    (2, (256, 130, 17), (256, 128, 16)),  # at the last chunk's end, on a chunk's edge, inside the first
    (3, (384, 300, 129), (-1, 288, 112)),  # none due, inside the third chunk, inside a chunk the row ends in
], ids=["1-chunk", "2-chunks", "3-chunks"])
def test_the_scan_kernel_agrees_with_the_recurrence_for_ragged_rows(chunks, lengths, snaps):
    T, D, N = chunks * ssm.CHUNK, 256, 16
    args = scan_inputs(3, T, D, N, lengths, seed=chunks)
    want_y, want_end = recurrence(*args, upto=lengths)
    _, want_snap = recurrence(*args, upto=snaps)
    f32 = [jnp.asarray(x, jnp.float32) for x in args]
    n_chunks = jnp.asarray([-(-n // ssm.CHUNK) for n in lengths], jnp.int32)
    for fn in (functools.partial(ssm.ssm_scan, interpret=True, col_tile=128), ssm.ssm_scan_reference):
        y, end, snap = fn(*f32, jnp.asarray(snaps, jnp.int32), n_chunks)
        for r, n in enumerate(lengths):
            np.testing.assert_allclose(y[r, :n], want_y[r, :n], atol=2e-5)
            np.testing.assert_allclose(end[r], want_end[r], atol=2e-5)
            if snaps[r] >= 0:
                np.testing.assert_allclose(snap[r], want_snap[r], atol=2e-5)
        assert bool(jnp.all(jnp.isfinite(y)))


@pytest.mark.parametrize("S", [8, 16, 3])
def test_the_update_kernel_steps_one_layers_lanes_in_place_and_no_other_row(S):
    D, N, layers, slots = 256, 16, 3, 17
    delta, u, b, c, a, _ = scan_inputs(1, S, D, N, (S,), seed=S)
    delta[0, 1] = 0.0  # an inactive lane: its state passes through exactly
    state = np.random.default_rng(1).normal(size=(layers, slots, N, D)).astype(np.float32)
    want_y = np.zeros((S, D))
    want = state.copy().astype(np.float64)
    for s in range(S):
        y, h = recurrence(delta[:, s:s + 1], u[:, s:s + 1], b[:, s:s + 1], c[:, s:s + 1], a, want[1, s][None], upto=(1,))
        want_y[s], want[1, s] = y[0, 0], h[0]
    f32 = [jnp.asarray(x[0], jnp.float32) for x in (delta, u, b, c)]
    for fn in (functools.partial(ssm.ssm_update, interpret=True, col_tile=128), ssm.ssm_update_reference):
        y, out = fn(jnp.asarray(state), jnp.int32(1), *f32, jnp.asarray(a, jnp.float32))
        np.testing.assert_allclose(y, want_y, atol=2e-5)
        np.testing.assert_allclose(out, want, atol=2e-5)
        assert np.array_equal(out[1, 1], state[1, 1]) and np.array_equal(out[0], state[0])
        assert np.array_equal(out[1, S:], state[1, S:])


# -- the program against the plain reference -----------------------------------


@pytest.mark.parametrize("name", list(PATTERNS))
def test_program_agrees_with_the_plain_reference_through_pages_and_state(name):
    """Prefill of the prompt, N decode steps through pool and state, and
    the same rows again by prefill of the longer row."""
    config = tiny(PATTERNS[name])
    family, pc, mesh, params = built(config, seed=2**31 + 7)
    s = check.sample(config["check"], config["vocab_size"], 8, 11)
    want = check.reference_logits(functools.partial(family.reference_logits, config, params), s)
    numbers = check.compare(family.cached_logits(config, pc, params, mesh, s, False), want)
    assert numbers["finite"] and numbers["top1_agree"] == 1.0
    assert numbers["prefill_rel_rms"] < 2e-5 and numbers["decode_rel_rms"] < 2e-5, numbers


@pytest.mark.parametrize("control,least", [("int8", 3e-3), ("h_bf16", 1e-4), ("nonorm", 3e-2), ("nobias", 3e-2)])
def test_each_reference_control_moves_the_logits(control, least):
    config = tiny(PATTERNS["tiny"])
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], 8, 3)
    reference = functools.partial(family.reference_logits, config, params)
    want = check.reference_logits(reference, s)
    moved = check.compare(check.reference_logits(reference, s, lower=control), want)["logit_rel_rms"]
    assert moved > least, (control, moved)
    with pytest.raises(ValueError, match="no control"):
        family.reference_logits(config, params, [[0]], [[0]], lower="int4")


@pytest.mark.parametrize("control", ["zero_state", "h_bf16", "state_swap", "quantize_kv"])
def test_each_cache_control_is_seen(control):
    """Each leaves the prefill's rows alone and moves the decode rows."""
    config = tiny(PATTERNS["tiny"])
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], 8, 3)
    want = check.reference_logits(functools.partial(family.reference_logits, config, params), s)
    *got, state = family.cache_readings(config, pc, params, mesh, s, False, **{control: True})
    numbers = check.compare(tuple(got), want)
    assert numbers["prefill_rel_rms"] < 2e-5 and numbers["cache_excess"] > 10.0, numbers
    # the stored state is read itself: every fault of the state shows there, int8 pages do not
    assert (state["state_rel_rms"] > 1e-3) == (control != "quantize_kv"), state


def test_a_state_stored_below_the_stated_precision_is_refused(capsys):
    """The family's own numbers on the stored state. Float32 stored: the
    decode path's `h` agrees with the prefill's and its low mantissa bits
    are in use. `h` at rest in bfloat16 between the steps: every value is
    one a 16-bit float holds, the logits come back not numbers and
    `check.decide` says not correct."""
    config = tiny(PATTERNS["tiny"])
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], 8, 3)
    want = check.reference_logits(functools.partial(family.reference_logits, config, params), s)
    # at float32 and toy widths `cache_excess` is a ratio of two roundings: the other limits stand
    limits = {k: v for k, v in config["check"]["limits"].items() if k != "cache_excess"}
    state_limits = config["check"]["state_limits"]
    sound = check.compare(family.cached_logits(config, pc, params, mesh, s, False), want)
    said = capsys.readouterr().out
    assert "state_rel_rms=" in said and "state_16bit_share=" in said and "EXCEEDED" not in said
    assert check.decide({**sound, "greedy_regret": 0.0, "stream_mismatch": 0}, limits)[0]
    *_, state = family.cache_readings(config, pc, params, mesh, s, False)
    assert state["state_rel_rms"] < 1e-5, state
    assert state["state_16bit_share"] < 0.1 * state_limits["state_16bit_share"], state
    *_, state = family.cache_readings(config, pc, params, mesh, s, False, h_bf16=True)
    assert state["state_16bit_share"] == 1.0 and state["state_rel_rms"] > 1e-3, state
    *_, state = family.cache_readings(config, pc, params, mesh, s, False, zero_state=True)
    assert state["state_rel_rms"] > state_limits["state_rel_rms"], state
    lowered = check.compare(family.cached_logits(config, pc, params, mesh, s, False, h_bf16=True), want)
    assert "state_16bit_share=1 limit=0.01 EXCEEDED" in capsys.readouterr().out
    assert not lowered["finite"]
    assert not check.decide({**lowered, "greedy_regret": 0.0, "stream_mismatch": 0}, limits)[0]


def test_a_continuation_from_an_installed_state_equals_one_prefill_of_the_whole_row():
    """The first 16 tokens by one prefill with a snapshot at 16; the saved
    state installed into ANOTHER slot; the rest as a continuation there: the
    last token's logits, the end state and the reference's logits agree."""
    config = tiny(PATTERNS["tiny"])
    family, pc, mesh, params = built(config)
    rng = np.random.default_rng(2)
    T, cut, P = 32, 16, 8
    tokens = rng.integers(0, 512, (1, T)).astype(np.int32)
    i32 = lambda *x: jnp.asarray(x, jnp.int32)  # noqa: E731
    pages = jnp.arange(1, 1 + T // P, dtype=jnp.int32)[None]
    cache = jamba.init_paged_cache(pc, 9, P, max_slots=2)
    whole, want = jamba.prefill_paged_batch(params, cache, tokens, i32(T), pages, (i32(0), i32(-1)), pc)
    head = np.zeros((1, T), np.int32)
    head[0, :cut] = tokens[0, :cut]
    first, _ = jamba.prefill_paged_batch(
        params, cache, head, i32(cut), pages.at[0, cut // P:].set(0), (i32(0), i32(cut)), pc)
    moved = jamba.install_state(first, 1, jamba.saved_state(first, 0))
    tail = np.zeros((1, T), np.int32)
    tail[0, : T - cut] = tokens[0, cut:]
    ids = jnp.zeros((1, T // P), jnp.int32).at[0, : (T - cut) // P].set(pages[0, cut // P:])
    done, got = jamba.prefill_paged_continue(
        params, moved, tail, i32(T - cut), i32(cut), ids, pages, (i32(1), i32(-1)), pc)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(done["state"][name][:, 1], whole["state"][name][:, 0], atol=2e-5)
    ref = family.reference_logits(config, params, tokens, [[T - 1]])
    np.testing.assert_allclose(got, ref[:, 0], atol=2e-4)
    # the mid chunk's writes are the continuation's without the head
    mid = jamba.prefill_paged_continue_kv(
        params, moved, tail, i32(T - cut), i32(cut), ids, pages, (i32(1), i32(-1)), pc)
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(jax.tree_util.tree_leaves(mid), jax.tree_util.tree_leaves(done)))


m_, a_ = "mamba", "attention"
# name -> (layer_types, the layers as `segments` lays them out)
LAYOUTS = {
    "published": (jamba.JambaConfig().layer_types, [(2, ((m_, 7), (a_, 1), (m_, 6)))]),
    "no-period": ((m_, a_, a_, m_, m_), [(1, ((m_, 1),)), (2, ((a_, 1),)), (2, ((m_, 1),))]),
    "attention-first": ((a_, m_, m_, a_, m_, m_, m_), [(2, ((a_, 1), (m_, 2))), (1, ((m_, 1),))]),
    "period-of-two": ((m_, a_) * 3, [(3, ((m_, 1), (a_, 1)))]),
}


@pytest.mark.parametrize("name", list(LAYOUTS))
def test_any_layer_pattern_serves_what_forward_computes(name):
    """The pattern's layout for a decode step (`models/stack.py segments`);
    prefill and continuation (through the one body that switches on the
    kind) and decode steps through that layout give `forward`'s logits and
    the K, V, `h` and conv columns of one prefill."""
    from agentcontrolplane_tpu.models.stack import segments

    from .test_lfm2 import serves_what_forward_computes

    kinds, layout = LAYOUTS[name]
    assert segments(tuple(kinds)) == layout
    serves_what_forward_computes(jamba, dataclasses.replace(CFG, layer_types=tuple(kinds)), ("ssm", "conv"))


# -- the engine carries the state ------------------------------------------

CFG = preset("jamba-tiny")
MAX_CTX = 128  # the engines' and the padded reference's
PARAMS = None
ONE_CHIP = lambda: make_mesh({"tp": 1}, devices=jax.devices()[:1])  # noqa: E731


def make_engine(**kw):
    global PARAMS
    if PARAMS is None:
        PARAMS = jamba.init_params(CFG, jax.random.key(0))
    opts = dict(max_slots=4, max_ctx=MAX_CTX, kv_layout="paged", page_size=8, kv_pages=80,
                prefill_buckets=(16, 32, 64), width_buckets=(2, 4), decode_block_size=4, check_invariants=True)
    eng = Engine(config=CFG, params=PARAMS, mesh=ONE_CHIP(), **{**opts, **kw})
    eng.start()
    return eng


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, 256, n)] for n in lengths]




GREEDY = SamplingParams(temperature=0.0, max_tokens=10)


def test_engine_serves_it_as_the_other_models_and_counts_its_recurrence():
    eng = make_engine()
    try:
        ps = prompts(20, 37, 50)
        futures = [eng.submit(p, GREEDY) for p in ps]
        for p, f in zip(ps, futures):
            assert f.result(300).tokens == greedy_reference(jamba.forward, PARAMS, CFG, p, 10, MAX_CTX)
        st = eng.stats()
        ssm_, m = st["ssm"], CFG.n_mamba
        assert st["model"]["layers"] == 4 and m == 3
        assert ssm_["state_bytes_per_slot"] == m * (16 * 64 * 4 + 3 * 64 * 4)
        assert ssm_["prefill"]["tokens"] == sum(map(len, ps)) * m and ssm_["prefill"]["rows"] == 3 * m
        assert ssm_["prefill"]["chunks"] == 3 * m  # each prompt inside one chunk
        assert ssm_["decode"]["mamba_layers"] == eng.decode_steps * m
        assert 0 < ssm_["decode"]["tokens"] == ssm_["decode"]["rows"] <= ssm_["decode"]["mamba_layers"] * 4
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
            assert eng.generate(p, GREEDY).tokens == greedy_reference(jamba.forward, PARAMS, CFG, p, 10, MAX_CTX)
    finally:
        eng.stop()


@pytest.mark.parametrize("host_kv_bytes", [0, 1 << 22], ids=["recompute", "host-swap"])
def test_preempt_and_resume_reproduce_the_uninterrupted_tokens(host_kv_bytes):
    """An oversubscribed pool preempts; the resume recomputes the state (no
    host tier) or restores pages and the state's tree from the host entry
    saved at the one length whose state was kept."""
    eng = make_engine(kv_pages=14, host_kv_bytes=host_kv_bytes)
    entries = []
    if host_kv_bytes:
        put = eng._host_pool.put
        eng._host_pool.put = lambda e: (entries.append(e), put(e))[1]
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
            assert entries
            for e in entries:  # the state of a host entry is the family's tree, numpy leaves
                assert set(e.state) == {"ssm", "conv"} and all(isinstance(a, np.ndarray) for a in e.state.values())
                assert e.nbytes > sum(a.nbytes for a in e.state.values())
    finally:
        eng.stop()


def test_a_parked_turn_resumes_from_the_saved_state():
    eng = make_engine()
    try:
        turn1 = prompts(29)[0]
        turn2 = turn1 + prompts(15, seed=9)[0]
        cold = greedy_reference(jamba.forward, PARAMS, CFG, turn2, 8, MAX_CTX)
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        eng.submit(turn1, sp, park=True).result(120)
        assert eng.stats()["parked_slots"] == 1
        before = eng.state_restores
        assert eng.generate(turn2, sp).tokens == cold
        assert eng.park_adoptions == 1 and eng.state_restores == before + 1
    finally:
        eng.stop()


def test_a_prefix_hit_is_taken_where_the_state_was_saved():
    eng = make_engine(prefix_dedup=True)
    try:
        base = prompts(45)[0]  # saved at its last page boundary: 40 tokens
        sp = SamplingParams(temperature=0.0, max_tokens=6)
        eng.generate(base, sp)
        longer = base + prompts(9, seed=4)[0]
        hits = eng.stats()["prefix_cache"]["hits"]
        assert eng.generate(longer, sp).tokens == greedy_reference(jamba.forward, PARAMS, CFG, longer, 6, MAX_CTX)
        assert eng.stats()["prefix_cache"]["hits"] == hits + 1 and eng.state_restores >= 1
        with eng._prefix_lock:
            assert all(set(e["state"]) == {"ssm", "conv"} for e in eng._prefix_cache.values())
    finally:
        eng.stop()


def test_the_seam_chooses_among_three_families_by_the_configs_type():
    from agentcontrolplane_tpu import models

    assert programs(CFG) is models._JAMBA and programs(CFG).has_state and programs(CFG).family == "jamba"
    assert programs(preset("lfm2-tiny")).family == "lfm2" and programs(preset("tiny")).family == "llama"
    with pytest.raises(KeyError, match="jamba2-3b"):
        preset("no-such-model")
    # a subclass is its parent's family; a config of no listed type is refused, not served as the dense family
    assert programs(type("Wider", (jamba.JambaConfig,), {})()).family == "jamba"
    with pytest.raises(TypeError, match="no model family serves"):
        programs(object())
    with pytest.raises(ValueError, match="mixes both kinds"):
        jamba.plan(dataclasses.replace(CFG, layer_types=("mamba", "mamba")))
    full = preset("jamba2-3b")
    assert (full.n_layers, full.n_attention, full.n_mamba) == (28, 2, 26)
    assert [i for i, t in enumerate(full.layer_types) if t == "attention"] == [7, 21]
    assert full.state_bytes_per_slot == 26 * (5120 * 16 * 4 + 3 * 5120 * 2) == 9_318_400
    with pytest.raises(ValueError, match="rolled back"):
        Engine(config=CFG, mesh=ONE_CHIP(), max_slots=2, max_ctx=64, kv_layout="paged", page_size=8, spec_len=4)
