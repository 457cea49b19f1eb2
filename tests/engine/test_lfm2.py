"""LFM2-MoE through the normal path: the program against the plain reference
(acpbench/families/lfm2_reference.py, which imports nothing of the
program) for each kind of layer and the whole pattern, prefill and then
decode through pages and per-slot state; the eight shares of an expert
layer summing to the uncut layer; and the engine carrying the state
through preempt, host swap, park and the prefix cache.

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
from acpbench.families import lfm2_reference, lfm2_weights
from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.models import lfm2, preset, programs
from agentcontrolplane_tpu.ops.moe import moe_ffn_reference, route_scores, route_topk, routed_experts
from agentcontrolplane_tpu.parallel.mesh import make_mesh

FILE = spec.load_json(spec.os.path.join(spec.ROOT, "acpbench/configs/lfm2-24b-a2b-bf16-v5e1-ep8.json"))
A, C = "full_attention", "conv"
PATTERNS = {
    "conv-dense": ([C, C], 2),
    "conv-experts": ([C, C, C], 1),
    "attention-experts": ([C, A, A], 1),
    "whole-pattern": ([C, C] + [A, C, C, C] * 2 + [A, C], 2),
}


def tiny(layer_types, dense, held=4, **over):
    """The configuration's file at toy widths: same keys, same family."""
    config = dict(FILE, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=512,
                  num_experts=16, num_experts_held=held, num_experts_per_tok=4,
                  layer_types=list(layer_types), num_hidden_layers=len(layer_types), num_dense_layers=dense)
    config["check"] = dict(FILE["check"], sequences=3, prefill_bucket=32, min_prompt=8, decode_steps=4)
    return {**config, **over}


def built(config, seed=5):
    family = spec.family(config)
    pc = dataclasses.replace(family.program_config(config), dtype=jnp.float32)
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1])
    return family, pc, mesh, family.weights(config, pc, mesh, seed)


@pytest.mark.parametrize("name", list(PATTERNS))
def test_program_agrees_with_the_plain_reference_through_pages_and_state(name):
    config = tiny(*PATTERNS[name])
    family, pc, mesh, params = built(config, seed=2**31 + 7)
    s = check.sample(config["check"], config["vocab_size"], 8, 11)
    want = check.reference_logits(functools.partial(family.reference_logits, config, params), s)
    numbers = check.compare(family.cached_logits(config, pc, params, mesh, s, False), want)
    assert numbers["finite"] and numbers["top1_agree"] == 1.0
    assert numbers["prefill_rel_rms"] < 2e-5 and numbers["decode_rel_rms"] < 2e-5, numbers


a, c_ = "attention", "conv"
# name -> (layer_types, num_dense_layers, the expert layers as `segments` lays them out)
LAYOUTS = {
    "published": (lfm2.Lfm2Config().layer_types, 2, [(9, ((a, 1), (c_, 3))), (1, ((a, 1),)), (1, ((c_, 1),))]),
    "no-period": ((c_, a, a, c_, c_), 0, [(1, ((c_, 1),)), (2, ((a, 1),)), (2, ((c_, 1),))]),
    "one-kind": ((c_, c_, c_, c_), 1, [(3, ((c_, 1),))]),
    "attention-in-the-prologue": ((a, c_, a, c_, c_, a, c_, c_, c_), 2, [(2, ((a, 1), (c_, 2))), (1, ((c_, 1),))]),
}


def serves_what_forward_computes(model, cfg, state_leaves):
    """A prefill of 16 tokens, a continuation of 8 and four decode steps each
    give `model.forward`'s logits of the same weights and tokens, and they
    leave the K, V and `state_leaves` that one prefill of all 28 tokens
    leaves (`tests/engine/test_jamba.py` runs its family through this too)."""
    params = model.init_params(cfg, jax.random.key(3))
    B, P, cut, mid, T = 2, 8, 16, 24, 28
    tokens = np.random.default_rng(4).integers(0, cfg.vocab_size, (B, 32)).astype(np.int32)
    want = model.forward(params, jnp.asarray(tokens[:, :T]), cfg)
    close = functools.partial(np.testing.assert_allclose, rtol=2e-5, atol=2e-5)
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    full = lambda v: jnp.full((B,), v, jnp.int32)  # noqa: E731
    pages = i32([[1, 2, 3, 4], [5, 6, 7, 8]])
    lanes = (i32([0, 1]), full(-1))
    empty = model.init_paged_cache(cfg, 9, P, max_slots=B)
    whole, _ = model.prefill_paged_batch(params, empty, tokens, full(T), pages, lanes, cfg)

    padded = lambda rows: np.pad(rows, ((0, 0), (0, 32 - rows.shape[1])))  # noqa: E731
    cache, got = model.prefill_paged_batch(
        params, empty, padded(tokens[:, :cut]), full(cut), pages.at[:, cut // P:].set(0), lanes, cfg)
    close(got, want[:, cut - 1])
    ids = jnp.zeros((B, 4), jnp.int32).at[:, 0].set(pages[:, cut // P])
    cache, got = model.prefill_paged_continue(
        params, cache, padded(tokens[:, cut:mid]), full(mid - cut), full(cut), ids, pages, lanes, cfg)
    close(got, want[:, mid - 1])
    for t in range(mid, T):
        cache, got = model.decode_step_paged(params, cache, i32(tokens[:, t]), full(t), pages, jnp.ones((B,), bool), cfg)
        close(got, want[:, t])
    for leaf in state_leaves:
        close(cache["state"][leaf][:, :B], whole["state"][leaf][:, :B])
    for leaf in ("k", "v"):  # the 28 rows of each slot, the last page's four unwritten rows apart
        rows = lambda tree: np.asarray(tree[leaf])[:, np.asarray(pages)].reshape(cfg.n_attention, B, 32, cfg.n_kv_heads * cfg.head_dim)[:, :, :T]  # noqa: E731
        close(rows(cache), rows(whole))


@pytest.mark.parametrize("name", list(LAYOUTS))
def test_any_layer_pattern_serves_what_forward_computes(name):
    """The pattern's layout for a decode step (the longest repeated stretch
    a loop over its periods, a run of one kind an inner loop or, alone,
    written out, the rest likewise); prefill and continuation (through the
    one body that switches on the kind) and decode steps through that layout
    give `forward`'s logits and one prefill's cache."""
    kinds, dense, layout = LAYOUTS[name]
    assert lfm2.segments(tuple(kinds[dense:])) == layout
    cfg = dataclasses.replace(preset("lfm2-tiny"), layer_types=tuple(kinds), num_dense_layers=dense)
    serves_what_forward_computes(lfm2, cfg, ("conv",))


@pytest.mark.parametrize("control,least", [("int8", 3e-3), ("nobias", 3e-3), ("nonorm", 3e-2), ("capacity", 1e-2)])
def test_each_reference_control_moves_the_logits(control, least):
    config = tiny(*PATTERNS["whole-pattern"])
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], 8, 3)
    reference = functools.partial(family.reference_logits, config, params)
    want = check.reference_logits(reference, s)
    moved = check.compare(check.reference_logits(reference, s, lower=control), want)["logit_rel_rms"]
    assert moved > least, (control, moved)
    with pytest.raises(ValueError, match="no control"):
        family.reference_logits(config, params, [[0]], [[0]], lower="int4")


@pytest.mark.parametrize("control,key,least", [("zero_state", "cache_excess", 10.0), ("quantize_kv", "cache_excess", 10.0)])
def test_each_cache_control_is_seen(control, key, least):
    """The conv state zeroed between prefill and decode, and int8 pages:
    both leave the prefill's rows alone and move the decode rows."""
    config = tiny(*PATTERNS["whole-pattern"])
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], 8, 3)
    want = check.reference_logits(functools.partial(family.reference_logits, config, params), s)
    numbers = check.compare(family.cached_logits(config, pc, params, mesh, s, False, **{control: True}), want)
    assert numbers["prefill_rel_rms"] < 2e-5 and numbers[key] > least, numbers


def test_the_cache_check_forces_the_references_routing_on_the_rows_read_twice():
    """In float32 the program chooses as the reference does, so the forced
    readings equal the free ones; a routing that is NOT the program's own
    choice moves the rows it is given for (every row but the prompt's own
    prefill, which routes freely) and leaves that one alone."""
    from acpbench.families import lfm2_reference

    config = tiny(*PATTERNS["whole-pattern"])
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], 8, 3)
    route = np.asarray(lfm2_reference.route(params, family._sizes(config), s["tokens"]))
    assert route.shape == (len(config["layer_types"]) - 2, s["B"], s["T"] + s["N"], 4) and route.max() < 16
    forced = family.cached_logits(config, pc, params, mesh, s, False)
    free = family.cached_logits(config, pc, params, mesh, s, False, free_routing=True)
    for a, b in zip(forced, free):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
    real = lfm2_reference.route
    try:  # every choice moved on by one expert: another routing than the program's own
        lfm2_reference.route = lambda *a: (real(*a) + 1) % config["num_experts"]
        pre, dec = family.cached_logits(config, pc, params, mesh, s, False)
    finally:
        lfm2_reference.route = real
    np.testing.assert_allclose(np.asarray(pre[:, 0]), np.asarray(free[0][:, 0]), atol=2e-4)
    assert float(jnp.max(jnp.abs(pre[:, 1:] - free[0][:, 1:]))) > 1e-2
    assert float(jnp.max(jnp.abs(dec - free[1]))) > 1e-2
    np.testing.assert_allclose(np.asarray(dec), np.asarray(pre[:, 1:]), atol=2e-4)  # the pair still agrees


def test_a_slot_reading_another_slots_table_is_seen_by_greedy_regret():
    """The family's structural control (`lfm2_study.table_swap`): the
    reference's own greedy tokens read 0, and an emitter that reads another
    request's prompt emits tokens the reference ranks far down."""
    from acpbench.families import lfm2_study

    config = tiny(*PATTERNS["whole-pattern"])
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], 8, 3)
    reference = functools.partial(family.reference_logits, config, params)
    emitted = [[] for _ in range(s["B"])]
    for _ in range(4):  # the reference's own greedy continuation of each prompt
        width = int(s["lengths"].max()) + 4
        tokens = np.zeros((s["B"], width), np.int32)
        for b, e in enumerate(emitted):
            n = int(s["lengths"][b])
            tokens[b, :n], tokens[b, n: n + len(e)] = s["tokens"][b, :n], e
        rows = (s["lengths"] - 1 + len(emitted[0]))[:, None]
        for b, t in enumerate(np.asarray(jnp.argmax(reference(tokens, rows), -1))[:, 0]):
            emitted[b].append(int(t))
    path = {"returned": emitted, "streamed": emitted, "finish": ["length"] * s["B"], "budget": 4}
    assert check.engine_numbers(reference, s, path)["greedy_regret"] == 0.0
    assert lfm2_study.table_swap(reference, s, path) > 0.3


def test_the_eight_shares_sum_to_the_uncut_layer():
    """A layer's output summed over the eight chips' shares (each told
    which eight of 64 it holds, each routing over all 64) is the uncut
    reference's layer."""
    N, D, F, E, k = 24, 64, 32, 64, 4
    keys = jax.random.split(jax.random.key(3), 6)
    x = jax.random.normal(keys[0], (N, D))
    layer = {"router": jax.random.normal(keys[1], (D, E)) * D ** -0.5,
             "router_bias": 0.02 * jax.random.normal(keys[2], (E,)),
             "w1": jax.random.normal(keys[3], (E, D, F)) * D ** -0.5,
             "w3": jax.random.normal(keys[4], (E, D, F)) * D ** -0.5,
             "w2": jax.random.normal(keys[5], (E, F, D)) * F ** -0.5}
    model = {"experts_per_token": k, "held": tuple(range(E)), "use_expert_bias": True,
             "norm_topk_prob": True, "routed_scaling_factor": 1.0}
    whole = lfm2_reference._experts(x[None], layer, model, None)[0]
    total, landed = jnp.zeros_like(whole), 0
    for share in range(8):
        held = tuple(range(8 * share, 8 * share + 8))
        ids = np.array(held)
        y, counts = routed_experts(x, layer["router"], layer["w1"][ids], layer["w3"][ids], layer["w2"][ids], k,
                                   held=held, score="sigmoid", bias=layer["router_bias"], interpret=share % 2 == 0)
        total, landed = total + y, landed + int(counts[1])
        assert int(counts[0]) == N * k
    assert landed == N * k  # every (token, choice) pair landed on exactly one share
    np.testing.assert_allclose(total, whole, atol=2e-5)


def test_mixtral_flags_run_through_the_same_layer():
    """Softmax over the chosen, no bias, all experts held: the per-token
    reference `moe_ffn_reference`, on both compute paths."""
    N, D, F, E = 13, 64, 128, 4
    keys = jax.random.split(jax.random.key(0), 5)
    x, r = jax.random.normal(keys[0], (N, D)), jax.random.normal(keys[1], (D, E))
    w1, w3 = (jax.random.normal(k, (E, D, F)) * D ** -0.5 for k in keys[2:4])
    w2 = jax.random.normal(keys[4], (E, F, D)) * F ** -0.5
    want = moe_ffn_reference(x, r, w1, w3, w2, 2)
    for kw in ({}, {"interpret": True}):
        got, counts = routed_experts(x, r, w1, w3, w2, 2, **kw)
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert int(counts[0]) == int(counts[1]) == 2 * N
    logits = jax.random.normal(keys[0], (9, 8))
    np.testing.assert_allclose(route_scores(logits, 2)[1], route_topk(logits, 2)[1], atol=1e-6)


def test_padding_lanes_route_nowhere_and_an_unchosen_expert_has_no_tile():
    N, D, F, E = 16, 64, 32, 8
    keys = jax.random.split(jax.random.key(1), 5)
    x, r = jax.random.normal(keys[0], (N, D)), jax.random.normal(keys[1], (D, E))
    w1, w3 = (jax.random.normal(k, (E, D, F)) * D ** -0.5 for k in keys[2:4])
    w2 = jax.random.normal(keys[4], (E, F, D)) * F ** -0.5
    valid = jnp.arange(N) < 5
    y, counts = routed_experts(x, r, w1, w3, w2, 2, valid=valid, interpret=True)
    assert int(counts[0]) == int(counts[1]) == 10 and float(jnp.abs(y[5:]).max()) == 0.0
    assert int(counts[2]) == int((counts[3:] > 0).sum()) <= 8


def test_the_selection_bias_changes_the_choice_for_a_stated_share_of_tokens():
    """lfm2_weights.BIAS_STD beside sigmoid scores of unit-variance logits:
    the top 4 of 64 differ with and without it for between a quarter and
    three quarters of tokens, and the weights of the chosen never hold it."""
    logits = jax.random.normal(jax.random.key(2), (4096, 64))
    bias = lfm2_weights.BIAS_STD * jax.random.normal(jax.random.key(3), (64,))
    with_b, w = route_scores(logits, 4, "sigmoid", bias)
    without, _ = route_scores(logits, 4, "sigmoid", None)
    share = float(jnp.mean(jnp.any(jnp.sort(with_b, -1) != jnp.sort(without, -1), axis=-1)))
    assert 0.25 < share < 0.75, share
    s = jnp.take_along_axis(jax.nn.sigmoid(logits), with_b, axis=-1)
    np.testing.assert_allclose(w, s / (s.sum(-1, keepdims=True) + 1e-6), atol=1e-6)


# -- the engine carries the state ------------------------------------------

CFG = preset("lfm2-tiny")
PARAMS = None
ONE_CHIP = lambda: make_mesh({"tp": 1}, devices=jax.devices()[:1])  # noqa: E731


def make_engine(**kw):
    global PARAMS
    if PARAMS is None:
        PARAMS = lfm2.init_params(CFG, jax.random.key(0))
    opts = dict(max_slots=4, max_ctx=128, kv_layout="paged", page_size=8, kv_pages=80,
                prefill_buckets=(16, 32, 64), width_buckets=(2, 4), decode_block_size=4, check_invariants=True)
    eng = Engine(config=CFG, params=PARAMS, mesh=ONE_CHIP(), **{**opts, **kw})
    eng.start()
    return eng


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, 256, n)] for n in lengths]


def reference_greedy(prompt, n):
    """The model's own full forward, no cache and no state, token by token."""
    toks = list(prompt)
    for _ in range(n):
        logits = lfm2.forward(PARAMS, jnp.asarray([toks]), CFG)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


GREEDY = SamplingParams(temperature=0.0, max_tokens=10)


def test_engine_serves_it_as_the_other_models_and_counts_its_experts():
    eng = make_engine()
    try:
        ps = prompts(20, 37, 50)
        futures = [eng.submit(p, GREEDY) for p in ps]
        for p, f in zip(ps, futures):
            assert f.result(300).tokens == reference_greedy(p, 10)
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
            assert eng.generate(p, GREEDY).tokens == reference_greedy(p, 10)
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
        cold = reference_greedy(turn2, 8)
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
        assert eng.generate(longer, sp).tokens == reference_greedy(longer, 6)
        assert eng.stats()["prefix_cache"]["hits"] == hits + 1 and eng.state_restores >= 1
        with eng._prefix_lock:
            assert {e["cut"] for e in eng._prefix_cache.values()} <= {40, 48} and all(
                "state" in e for e in eng._prefix_cache.values())
        # live leaders' pages are never shared (no state at the common cut): dedup is a miss
        with eng.hold_admission():
            futures = [eng.submit(base + [7, i], sp) for i in range(3)]
        for i, f in enumerate(futures):
            assert f.result(120).tokens == reference_greedy(base + [7, i], 6)
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
        assert eng.generate(p, GREEDY).tokens == reference_greedy(p, 10)
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
            assert eng.generate(p, GREEDY).tokens == reference_greedy(p, 10)
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


# -- the page walk at head width 64 -----------------------------------------


@pytest.mark.parametrize("H,H_kv,dtype", [(8, 2, jnp.float32), (32, 8, jnp.bfloat16)])
def test_interpreted_walk_at_head_width_64_agrees_with_the_reference(H, H_kv, dtype):
    from agentcontrolplane_tpu.ops.paged import paged_decode_attention_reference
    from agentcontrolplane_tpu.ops.pallas.paged_attention import heads_per_window, paged_decode_attention

    S, P, NP, MP, d = 3, 16, 40, 6, 64
    ks = jax.random.split(jax.random.key(1), 4)
    q = jax.random.normal(ks[0], (S, H, d)).astype(dtype)
    kp, vp = (jax.random.normal(k, (NP, P, H_kv, d)).astype(dtype) for k in ks[1:3])
    tables = (jax.random.permutation(ks[3], NP - 1)[: S * MP].reshape(S, MP) + 1).astype(jnp.int32)
    lens = jnp.array([5, 37, 96], jnp.int32)
    got = paged_decode_attention(q, kp, vp, tables, lens, interpret=True)
    want = paged_decode_attention_reference(q, kp, vp, tables, lens)
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), atol=2e-6 if dtype == jnp.float32 else 2e-2)
    assert heads_per_window(64, 8) == 2 and heads_per_window(128, 4) == 1
    assert heads_per_window(64, 3) == 0 and heads_per_window(64, 8, quantized=True) == 0 and heads_per_window(16, 2) == 0
