"""Mellum2 through the normal path: the window walk (Pallas interpret mode)
against the ring's XLA reference; YaRN's tables against the closed form; the
program against the plain reference (acpbench/families/mellum_reference.py,
which imports nothing of the program) for the forward pass, prefill then
decode through both caches past the window and across a ring wrap, and
continuation; the four shares of a layer's experts against the uncut layer;
and the engine serving short and long slots in one batch, keeping at most
`window + page` rows a slot of a window layer, returning both caches on
every finish, and refusing what it cannot carry.

CPU, tiny sizes (2 periods of 3 window layers and a full one, window 32,
page 8, 8 experts top-2), float32, seeded weights.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acpbench import check, spec
from acpbench.families import mellum_reference
from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.invariants import verify_engine
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.models import experts, mellum, preset, programs
from agentcontrolplane_tpu.ops import paged
from agentcontrolplane_tpu.ops.pallas.paged_attention import paged_decode_attention_cache_plus_new
from agentcontrolplane_tpu.ops.rope import apply_rope, rope_frequencies, yarn_correction_range, yarn_scale_frequencies
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import compiled, greedy_reference

FILE = spec.load_json(spec.os.path.join(spec.ROOT, "tests/acpbench/data/tiny-config-mellum.json"))
PUBLISHED = spec.load_json(spec.os.path.join(spec.ROOT, "acpbench/configs/mellum2-12b-a2.5b-bf16-v5e1-ep4.json"))
WINDOW, PAGE = FILE["sliding_window"], FILE["engine"]["page_size"]
RING = WINDOW // PAGE + 1


def tiny(**over):
    config = dict(FILE)
    config["check"] = dict(FILE["check"], sequences=3, prefill_bucket=96, min_prompt=WINDOW + 8, decode_steps=12)
    return {**config, **over}


def built(config, seed=5):
    family = spec.family(config)
    pc = dataclasses.replace(family.program_config(config), dtype=jnp.float32)
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1])
    return family, pc, mesh, family.weights(config, pc, mesh, seed)


# -- the window walk -----------------------------------------------------------------


@pytest.mark.parametrize("lens", [
    (0, 1, 15, 16, 17, 63),  # under the window: the whole context, as the full layers' walk reads it
    (64, 65, 79, 80, 81, 200),  # at the window, across its first page boundaries, well past it
    (127, 128, 129, 1000, 1023, 1024),  # every residue of the ring after many wraps
], ids=["under", "crossing", "wrapped"])
def test_the_window_walk_reads_the_windows_rows_and_no_others(lens):
    S, H, H_kv, d, P, W, L = 6, 8, 2, 128, 16, 64, 3
    ring = paged.ring_size(W, P)
    NW = (S + 1) * ring
    key = jax.random.key(0)
    kp, vp = (jax.random.normal(jax.random.fold_in(key, i), (L * NW, P, H_kv * d), jnp.float32) for i in (1, 2))
    q = jax.random.normal(jax.random.fold_in(key, 3), (S, H, d), jnp.float32)
    kn, vn = (jax.random.normal(jax.random.fold_in(key, i), (S, H_kv, d), jnp.float32) for i in (4, 5))
    n = jnp.asarray(lens, jnp.int32)
    first = jnp.maximum(n + 1 - W, 0)
    tables = paged.layer_tables(paged.ring_tables(jnp.arange(S, dtype=jnp.int32), ring), 1, NW)
    want = paged.paged_decode_attention_reference_cache_plus_new(
        q, kp, vp, tables, n, kn, vn, row_positions=paged.ring_positions(n, ring, P), starts=first)
    got = paged_decode_attention_cache_plus_new(q, kp, vp, tables, n, kn, vn, interpret=True, starts=first, ring=ring)
    np.testing.assert_allclose(got, want, atol=5e-6)
    # against attention over the rows laid out by position, with no ring at all
    pos = np.asarray(paged.ring_positions(n, ring, P))
    rows_k, rows_v = np.asarray(kp)[np.asarray(tables)].reshape(S, ring * P, H_kv, d), np.asarray(vp)[np.asarray(tables)].reshape(S, ring * P, H_kv, d)
    for s in range(S):
        seen = (pos[s] >= int(first[s])) & (pos[s] < lens[s])
        assert seen.sum() == min(lens[s], W - 1)
        k = np.concatenate([rows_k[s][seen], np.asarray(kn)[s][None]]).repeat(H // H_kv, axis=1)
        v = np.concatenate([rows_v[s][seen], np.asarray(vn)[s][None]]).repeat(H // H_kv, axis=1)
        w = jax.nn.softmax(np.einsum("hd,khd->hk", np.asarray(q)[s], k) / math.sqrt(d), axis=-1)
        np.testing.assert_allclose(np.asarray(got)[s], np.einsum("hk,khd->hd", w, v), atol=2e-5)


def test_a_ring_holds_the_newest_pages_each_in_its_place():
    pos = np.asarray(paged.ring_positions(jnp.asarray([0, 1, 8, 9, 40, 41, 83]), 5, 8))
    assert (pos[0] == -1).all()
    assert sorted(set(pos[1][pos[1] >= 0] // 8)) == [0] and sorted(set(pos[3][pos[3] >= 0] // 8)) == [0, 1]
    assert sorted(set(pos[4] // 8)) == [0, 1, 2, 3, 4] and sorted(set(pos[5] // 8)) == [1, 2, 3, 4, 5]
    for row in pos:  # a position sits at row p % P of ring page (p // P) % ring
        for at, p in enumerate(row):
            assert p < 0 or ((p // 8) % 5, p % 8) == divmod(at, 8)
    with pytest.raises(ValueError, match="must divide the window"):
        paged.ring_size(100, 16)


# -- the two ropes -------------------------------------------------------------------


def test_yarn_tables_equal_the_closed_form():
    """At the published sizes: low 18, high 35, the ramp between, cos and
    sin times 0.1 ln 16 + 1."""
    yarn = PUBLISHED["rope_parameters"]["full_attention"]
    d, theta = PUBLISHED["head_dim"], float(yarn["rope_theta"])
    assert yarn_correction_range(d, theta, 8192, 32, 1) == (18, 35)
    assert yarn["attention_factor"] == pytest.approx(0.1 * math.log(16) + 1)
    got = np.asarray(yarn_scale_frequencies(rope_frequencies(d, theta), 16.0, 8192, 32.0, 1.0, theta))
    k = np.arange(d // 2)
    base = theta ** (-2.0 * k / d)
    ramp = np.clip((k - 18) / (35 - 18), 0, 1)
    np.testing.assert_allclose(got, (1 - ramp) * base + ramp * base / 16, rtol=1e-6)
    np.testing.assert_allclose(got[:19], base[:19], rtol=1e-6)  # up to `low`: the frequency as it was
    np.testing.assert_allclose(got[35:], base[35:] / 16, rtol=1e-6)  # from `high` on: slowed by the factor
    want, factor = mellum_reference.frequencies(
        {"head_dim": d, "rope_theta": theta, "yarn": (16.0, 8192, 32.0, 1.0, yarn["attention_factor"])}, True)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    x = jax.random.normal(jax.random.key(1), (1, 5, 2, d))
    positions = jnp.asarray([[0, 1, 1000, 8192, 7679]])
    turned = apply_rope(x, positions, theta, yarn=(16.0, 8192, 32.0, 1.0, factor))
    ang = np.asarray(positions, np.float32)[0][:, None] * got  # float32, as the program turns
    a, b = np.asarray(x)[0, :, :, : d // 2], np.asarray(x)[0, :, :, d // 2:]
    cos, sin = np.cos(ang)[:, None] * factor, np.sin(ang)[:, None] * factor
    np.testing.assert_allclose(np.asarray(turned)[0], np.concatenate([a * cos - b * sin, b * cos + a * sin], -1),
                               atol=2e-4)
    # the window layers' table is the plain one
    np.testing.assert_allclose(apply_rope(x, positions, theta), apply_rope(x, positions, theta, yarn=None))


# -- the program against the plain reference ------------------------------------------


def test_forward_logits_agree_with_the_plain_reference():
    config = tiny()
    family, pc, mesh, params = built(config)
    tokens = np.random.default_rng(0).integers(0, 256, size=(2, 100))
    rows = np.tile(np.arange(100), (2, 1))
    want = family.reference_logits(config, params, tokens, rows)
    got = mellum.forward(params, jnp.asarray(tokens), pc)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("min_prompt", [WINDOW - 10, WINDOW + 8], ids=["crossing-the-window", "past-the-window"])
def test_program_agrees_with_the_plain_reference_through_both_caches(min_prompt):
    """Prefill of the prompt, N decode steps through pages and ring, and the
    same rows again by prefill of the longer row. Prompts from 22 tokens on
    cross the window inside the decode steps; those past it read a ring
    that wraps (40 rows a slot, contexts to 96)."""
    config = tiny()
    config["check"]["min_prompt"] = min_prompt
    family, pc, mesh, params = built(config, seed=2**31 + 7)
    s = check.sample(config["check"], config["vocab_size"], PAGE, 11)
    if min_prompt < WINDOW:
        s["lengths"][1], s["lengths"][2] = WINDOW - 5, WINDOW - 10  # cross the window inside the 12 steps
        s["rows"] = s["lengths"][:, None] - 1 + np.arange(s["N"] + 1)[None, :]
    want = check.reference_logits(functools.partial(family.reference_logits, config, params), s)
    numbers = check.compare(family.cached_logits(config, pc, params, mesh, s, False), want)
    assert numbers["finite"] and numbers["top1_agree"] == 1.0
    assert numbers["prefill_rel_rms"] < 2e-5 and numbers["decode_rel_rms"] < 2e-5, numbers


@pytest.mark.parametrize("control,least", [("int8", 3e-3), ("bf16_rest", 1e-3), ("window_off", 1e-2), ("one_rope", 1e-2),
                                           ("nonorm", 1e-2)])
def test_each_reference_control_moves_the_logits(control, least):
    config = tiny()
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], PAGE, 3)
    reference = functools.partial(family.reference_logits, config, params)
    want = check.reference_logits(reference, s)
    moved = check.compare(check.reference_logits(reference, s, lower=control), want)["logit_rel_rms"]
    assert moved > least, (control, moved)
    with pytest.raises(ValueError, match="no control"):
        family.reference_logits(config, params, [[0]], [[0]], lower="int4")


@pytest.mark.parametrize("control", ["window_minus_page", "kv_int8"])
def test_each_cache_control_is_seen(control):
    """Each leaves the prefill's rows alone and moves the decode rows."""
    config = tiny()
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], PAGE, 3)
    want = check.reference_logits(functools.partial(family.reference_logits, config, params), s)
    numbers = check.compare(family.cached_logits(config, pc, params, mesh, s, False, **{control: True}), want)
    assert numbers["prefill_rel_rms"] < 2e-5 and numbers["cache_excess"] > 10.0, numbers


def _rows(params, pc, tokens, starts, lengths, cache, tables, slots, T, head=True):
    """One continuation dispatch of rows `starts .. starts + lengths`."""
    B = len(starts)
    toks = np.zeros((B, T), np.int32)
    ids = np.zeros((B, T // PAGE), np.int32)
    for b in range(B):
        toks[b, : lengths[b]] = tokens[b, starts[b]: starts[b] + lengths[b]]
        n = -(-lengths[b] // PAGE)
        ids[b, :n] = tables[b, starts[b] // PAGE: starts[b] // PAGE + n]
    lanes = (jnp.asarray(slots, jnp.int32), jnp.full((B,), -1, jnp.int32))
    fn = mellum.prefill_paged_continue if head else mellum.prefill_paged_continue_kv  # one program, however many chunks
    return compiled(fn, pc)(params, cache, jnp.asarray(toks), jnp.asarray(lengths, jnp.int32),
                            jnp.asarray(starts, jnp.int32), jnp.asarray(ids), jnp.asarray(tables), lanes)


@pytest.mark.parametrize("chunk", [16, 64], ids=["chunk-under-the-ring", "chunk-over-the-ring"])
def test_a_chunked_row_then_decode_agrees_with_the_reference(chunk):
    """One row of 150 tokens a chunk at a time, each continuation reading
    the ring the one before left, then 8 decode steps through the ring the
    last chunk left, against the reference's whole pass. A 64-token chunk is
    longer than the 40-row ring: it overwrites rows its own first queries
    need, and reads them before it does."""
    config = tiny()
    family, pc, mesh, params = built(config)
    tokens = np.random.default_rng(9).integers(0, 256, size=(1, 176)).astype(np.int32)
    n = 150
    want = family.reference_logits(config, params, tokens, n - 1 + np.arange(9)[None, :])
    per = 176 // PAGE
    tables = (1 + np.arange(per, dtype=np.int32)).reshape(1, per)
    cache = mellum.init_paged_cache(pc, per + 1, PAGE, max_slots=1)
    whole = list(range(0, n - n % chunk if n % chunk else n - chunk, chunk))
    for start in whole:
        cache = _rows(params, pc, tokens, [start], [chunk], cache, tables, [0], chunk, head=False)
    start = whole[-1] + chunk
    cache, logits = _rows(params, pc, tokens, [start], [n - start], cache, tables, [0], chunk)
    np.testing.assert_allclose(logits, want[:, 0], atol=3e-4)
    step = jax.jit(lambda c, t, m: mellum.decode_step_paged(params, c, t, m, jnp.asarray(tables), jnp.ones((1,), bool), pc))
    for j in range(8):
        cache, logits = step(cache, jnp.asarray(tokens[:, n + j]), jnp.asarray([n + j], jnp.int32))
        np.testing.assert_allclose(logits, want[:, j + 1], atol=3e-4)
    counts = mellum.describe_counters(pc, np.asarray(mellum.counters(cache)))["window"]
    assert counts["decode"]["steps"] == 8 and counts["decode"]["slots_past_window"] == 8
    assert counts["decode"]["rows_read"] == 8 * WINDOW and counts["decode"]["rows_unwindowed"] == sum(range(151, 159))
    assert counts["prefill"]["steps"] == len(whole) + 1 and counts["prefill"]["rows_unwindowed"] == n * (n + 1) // 2


def test_the_four_shares_of_a_layers_experts_add_up_to_the_uncut_layer():
    """The cut of the published file at the tiny size: 8 experts split four
    ways. Each share computes its own experts' part of every token's sum;
    the four parts add up to the layer with all eight held, in the program's
    grouped layer and in the reference's loop alike."""
    config = tiny()
    family, pc, mesh, params = built(config)
    x = jax.random.normal(jax.random.key(3), (2, 24, pc.dim), jnp.float32)
    ff = jax.tree_util.tree_map(lambda a: a[5], params["ff"])
    sizes = {"experts_per_token": 2, "norm_topk_prob": True, "layer_types": tuple(FILE["layer_types"])}
    whole_ref = mellum_reference.layer_output(params, {**sizes, "held": tuple(range(8))}, 5, x)
    whole, shares, shares_ref = None, [], []
    for held in [tuple(range(8))] + [(2 * i, 2 * i + 1) for i in range(4)]:
        c = dataclasses.replace(pc, experts_held=held)
        mine = {name: ff[name][jnp.asarray(held)] for name in ("w1", "w3", "w2")}
        y, _ = experts.routed_ff(x, ff, tuple(mine[n] for n in ("w1", "w3", "w2")), 0, c, jnp.ones((2, 24), bool), chunk=True)
        if len(held) == 8:
            whole = y
            continue
        shares.append(y)
        cut = {**params, "ff": {**params["ff"], **{n: params["ff"][n][:, jnp.asarray(held)] for n in mine}}}
        shares_ref.append(mellum_reference.layer_output(cut, {**sizes, "held": held}, 5, x))
    np.testing.assert_allclose(sum(shares), whole, atol=2e-5)
    np.testing.assert_allclose(sum(shares_ref), whole_ref, atol=2e-5)
    np.testing.assert_allclose(whole, whole_ref, atol=2e-5)
    assert float(jnp.max(jnp.abs(shares[0]))) > 0 and float(jnp.max(jnp.abs(shares[0] - whole))) > 1e-3


def test_a_long_prefills_experts_run_a_chunk_of_tokens_at_a_time(monkeypatch):
    """Past `MOE_CHUNK` tokens the routed FF takes its rows a chunk at a time
    (a long prefill's sorted copies of its rows stay a chunk wide): the same
    logits, the same counters but the experts read (an expert is read once
    a chunk that routes to it), with the routing made or given."""
    config = tiny()
    family, pc, mesh, params = built(config)
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 256, size=(2, 64)), jnp.int32)
    lengths = jnp.asarray([64, 50], jnp.int32)
    ids = jnp.asarray(1 + np.arange(16).reshape(2, 8), jnp.int32)
    lanes = (jnp.arange(2, dtype=jnp.int32), jnp.full((2,), -1, jnp.int32))
    route = jnp.asarray(mellum_reference.route(params, family._sizes(config), np.asarray(tokens)))

    def prefill(given):
        cache = mellum.init_paged_cache(pc, 17, PAGE, max_slots=2)
        cache, logits = mellum.prefill_paged_batch(params, cache, tokens, lengths, ids, lanes, pc, route=given)
        return logits, np.asarray(mellum.counters(cache))[1]

    whole = [prefill(None), prefill(route)]
    monkeypatch.setattr(experts, "MOE_CHUNK", 32)
    for (want, counts), given in zip(whole, (None, route)):
        got, chunked = prefill(given)
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert (chunked[:3] == counts[:3]).all() and (chunked[4:] == counts[4:]).all() and chunked[3] >= counts[3]


# -- the engine ---------------------------------------------------------------------


class NoStop(ByteTokenizer):
    stop_tokens = frozenset()


CFG = preset("mellum-tiny")
MAX_CTX = 256  # the engines' and the padded reference's
PARAMS = mellum.init_params(CFG, jax.random.key(0))
GREEDY = SamplingParams(temperature=0.0, max_tokens=24)


def make_engine(**over):
    opts = dict(kv_layout="paged", page_size=PAGE, max_slots=4, max_ctx=MAX_CTX, prefill_buckets=(32, 64, 128),
                width_buckets=(2,), decode_block_size=4, tokenizer=NoStop(), check_invariants=True)
    eng = Engine(config=CFG, params=PARAMS, mesh=make_mesh({"tp": 1}, devices=jax.devices()[:1]), **{**opts, **over})
    eng.start()
    return eng


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=n).tolist() for n in lengths]


def test_engine_serves_short_and_long_slots_in_one_batch_and_counts_its_window():
    """Six requests over four slots: some stay under the 32-token window,
    some start under it and cross it inside a decode block, some start past
    it and wrap their ring. Greedy tokens equal the full forward pass's."""
    eng = make_engine()
    try:
        ps = prompts(5, 25, 40, 70, 100, 120)
        budgets = (10, 30, 50, 40, 20, 60)
        futures = [eng.submit(p, SamplingParams(temperature=0.0, max_tokens=m)) for p, m in zip(ps, budgets)]
        for p, m, f in zip(ps, budgets, futures):
            assert f.result(300).tokens == greedy_reference(mellum.forward, PARAMS, CFG, p, m, MAX_CTX)
        st = eng.stats()
        w = st["window"]
        assert (w["window"], w["window_layers"], w["full_layers"]) == (WINDOW, 6, 2)
        # a window layer holds at most window + page rows a slot, at any context
        assert w["pages_per_slot"] == RING and w["rows_per_slot"] == WINDOW + PAGE
        assert eng.cache["wk"].shape == (6, (4 + 1) * RING, PAGE, 2 * 16) and eng.cache["k"].shape[0] == 2
        assert w["decode"]["steps"] == eng.decode_steps
        assert 0 < w["decode"]["rows_read"] < w["decode"]["rows_unwindowed"]
        assert 0 < w["decode"]["slots_past_window"] < 4 * w["decode"]["steps"]
        assert w["prefill"]["steps"] >= 1 and w["prefill"]["slots_past_window"] == 4  # the four prompts over 32 tokens
        assert st["moe"]["experts"] == st["moe"]["held"] == 8 and st["moe"]["decode"]["expert_layers"] == 8 * eng.decode_steps
        # every finish returned both caches
        assert w["slots_holding"] == 0 and st["kv_pages"]["free"] == st["kv_pages"]["total"]
        assert verify_engine(eng) == []
        # the programs keep the names the trace readers match on
        assert eng._jit_decode_paged.__wrapped__.__name__ == "decode_block"
        assert eng._jit_prefill_paged.__wrapped__.__name__ == "prefill_and_sample"
        # what is off for the family reads off
        assert "prefix_cache" not in st and not st["memory"]["prefix_dedup"]["enabled"] and st["tool_overlap"]["park_max_s"] == 0
    finally:
        eng.stop()


def test_the_audit_sees_a_ring_held_by_no_slot_and_a_slot_without_its_ring():
    eng = make_engine()
    try:
        with eng.hold_admission():
            futures = [eng.submit(p, GREEDY) for p in prompts(40, 50, 60, seed=2)]
        for f in futures:
            f.result(300)
        assert verify_engine(eng) == []
        eng._window_rings[3] = "ghost"
        assert any("window rings held by no occupied slot" in p for p in verify_engine(eng))
        eng._window_rings.clear()
    finally:
        eng.stop()


@pytest.mark.parametrize("chunked", [False, True], ids=["spill", "prefill_chunk"])
def test_prompts_over_the_widest_bucket_go_through_continuations(chunked):
    """A 150-token prompt over buckets of at most 64: the spill's (or the
    chunk loop's) continuations read and write the ring chunk by chunk."""
    eng = make_engine(prefill_buckets=(32, 64), **({"prefill_chunk": 32} if chunked else {}))
    try:
        for p in prompts(150, 70, seed=3):
            assert eng.generate(p, GREEDY).tokens == greedy_reference(mellum.forward, PARAMS, CFG, p, 24, MAX_CTX)
        assert verify_engine(eng) == []
    finally:
        eng.stop()


def test_preempt_and_resume_rebuild_the_ring_and_reproduce_the_tokens():
    """An oversubscribed full-layer pool preempts; the resumed request's
    prefill writes its ring anew, in whichever slot it lands."""
    eng = make_engine(kv_pages=30)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=40)
        ps = prompts(*[45] * 6, seed=1)
        solo = [eng.generate(p, sp).tokens for p in ps]
        with eng.hold_admission():
            futures = [eng.submit(p, sp) for p in ps]
        assert [f.result(300).tokens for f in futures] == solo
        assert eng.preemptions >= 1
        assert eng.stats()["window"]["slots_holding"] == 0 and verify_engine(eng) == []
    finally:
        eng.stop()


REFUSED = {
    "slot-layout": (dict(kv_layout="slot"), "kv_layout='slot'"),
    "speculation": (dict(spec_len=4), "spec_len > 0"),
    "host-swap": (dict(host_kv_bytes=1 << 20), "host_kv_bytes > 0"),
    "int8-pages": (dict(quantize_kv=True), "quantize_kv"),
    "int8-weights": (dict(quantize="int8"), "weight-only int8"),
    "tensor-parallel": (dict(mesh=None), "tensor or context parallelism"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_each_option_the_family_does_not_serve_is_refused_in_words(case):
    over, words = REFUSED[case]
    opts = dict(config=CFG, params=PARAMS, kv_layout="paged", page_size=PAGE, max_slots=2, max_ctx=128,
                prefill_buckets=(32,), mesh=make_mesh({"tp": 1}, devices=jax.devices()[:1]))
    if case == "tensor-parallel":
        over = dict(mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match="the mellum family does not serve with") as e:
        Engine(**{**opts, **over})
    assert words in str(e.value)


def test_a_handoff_is_refused_and_a_park_is_not_taken():
    eng = make_engine(park_max_s=30.0, prefix_cache_entries=4, prefix_dedup=True)
    try:
        assert eng.park_max_s == 0 and not eng.prefix_dedup and not eng._prefix_enabled
        with pytest.raises(ValueError, match="does not serve with export_kv"):
            eng.submit(prompts(20)[0], GREEDY, export_kv=True)
        p = prompts(40, seed=7)[0]
        assert eng.submit(p, GREEDY, park=True).result(300).tokens == greedy_reference(mellum.forward, PARAMS, CFG, p, 24, MAX_CTX)
        # the same prompt again: no prefix entry, no parked slot, no shared page served it
        assert eng.generate(p, GREEDY).tokens == greedy_reference(mellum.forward, PARAMS, CFG, p, 24, MAX_CTX)
        st = eng.stats()
        assert st["parked_slots"] == 0 and st["tool_overlap"]["parks"] == 0 and "prefix_cache" not in st
        with pytest.raises(NotImplementedError, match="saves no state"):
            mellum.saved_state(eng.cache, 0)
    finally:
        eng.stop()


def test_the_llama_family_is_still_refused_past_its_window():
    from agentcontrolplane_tpu.models.llama import PRESETS

    gemma = dataclasses.replace(PRESETS["tiny"], sliding_window=64)
    with pytest.raises(ValueError, match="no window cache"):
        Engine(config=gemma, max_ctx=128, max_slots=2, mesh=make_mesh({"tp": 1}, devices=jax.devices()[:1]))


def test_the_seam_names_the_family_and_its_two_caches():
    m = programs(CFG)
    assert (m.family, m.has_state, m.window_cache) == ("mellum", True, True)
    assert programs(preset("mellum2-12b-a2.5b-ep4")) is m and not programs(preset("lfm2-tiny")).window_cache
    c = preset("mellum2-12b-a2.5b-ep4")
    assert (c.n_layers, c.n_window, c.n_full, c.span, len(c.held)) == (28, 21, 7, 4, 16)
    with pytest.raises(ValueError, match="strict period"):
        mellum.period(dataclasses.replace(CFG, layer_types=("sliding_attention", "full_attention", "full_attention")))
    with pytest.raises(ValueError, match="int8 window pages"):
        mellum.init_paged_cache(CFG, 9, PAGE, quantize_kv=True)
