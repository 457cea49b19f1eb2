"""LFM2-MoE through the normal path: the program against the plain reference
(acpbench/families/lfm2_reference.py, which imports nothing of the
program) for each kind of layer and the whole pattern, prefill and then
decode through pages and per-slot state; any layer pattern through the
decode step's layout; the eight shares of an expert layer summing to the
uncut layer; the page walk at head width 64. The engine serving it:
`test_lfm2_engine.py`.

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
from agentcontrolplane_tpu.models import lfm2, preset, stack
from agentcontrolplane_tpu.ops.moe import moe_ffn_reference, route_scores, route_topk, routed_experts
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import compiled

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
    # compiled, as the engine runs them (eagerly, every op of every layer is a program of its own)
    forward, prefill, continuation, step = (
        compiled(f, cfg) for f in (model.forward, model.prefill_paged_batch, model.prefill_paged_continue, model.decode_step_paged))
    B, P, cut, mid, T = 2, 8, 16, 24, 28
    tokens = np.random.default_rng(4).integers(0, cfg.vocab_size, (B, 32)).astype(np.int32)
    want = forward(params, jnp.asarray(tokens[:, :T]))
    close = functools.partial(np.testing.assert_allclose, rtol=2e-5, atol=2e-5)
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    full = lambda v: jnp.full((B,), v, jnp.int32)  # noqa: E731
    pages = i32([[1, 2, 3, 4], [5, 6, 7, 8]])
    lanes = (i32([0, 1]), full(-1))
    empty = model.init_paged_cache(cfg, 9, P, max_slots=B)
    whole, _ = prefill(params, empty, tokens, full(T), pages, lanes)

    padded = lambda rows: np.pad(rows, ((0, 0), (0, 32 - rows.shape[1])))  # noqa: E731
    cache, got = prefill(params, empty, padded(tokens[:, :cut]), full(cut), pages.at[:, cut // P:].set(0), lanes)
    close(got, want[:, cut - 1])
    ids = jnp.zeros((B, 4), jnp.int32).at[:, 0].set(pages[:, cut // P])
    cache, got = continuation(params, cache, padded(tokens[:, cut:mid]), full(mid - cut), full(cut), ids, pages, lanes)
    close(got, want[:, mid - 1])
    for t in range(mid, T):
        cache, got = step(params, cache, i32(tokens[:, t]), full(t), pages, jnp.ones((B,), bool))
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
    assert stack.segments(tuple(kinds[dense:])) == layout
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
