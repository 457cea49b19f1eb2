"""Nemotron-H through the normal path: the two Mamba-2 kernels (Pallas
interpret mode) against the per-token recurrence; the latent, ungated expert
layer against a per-token double; the program against the plain reference
(acpbench/families/nemotron_h_reference.py, which imports nothing of the
program) for prefill, continuation from a carried state and decode through
pages and state; the eight shares of an expert layer against the uncut one.

CPU, tiny sizes, float32 (so that agreement is to rounding, not to
bfloat16), seeded weights.
"""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acpbench import check, spec
from acpbench.families import nemotron_h_reference
from agentcontrolplane_tpu.models import nemotron_h as nh
from agentcontrolplane_tpu.models import preset, programs
from agentcontrolplane_tpu.ops import moe
from agentcontrolplane_tpu.ops.pallas import ssd
from agentcontrolplane_tpu.parallel.mesh import make_mesh

TINY = spec.load_json(os.path.join(spec.ROOT, "tests/acpbench/data/tiny-config-nemotron-h.json"))


def tiny(**over):
    config = dict(TINY, **over)
    config["check"] = dict(TINY["check"], sequences=3, prefill_bucket=32, min_prompt=8, decode_steps=4)
    return config


def built(config, seed=5):
    family = spec.family(config)
    pc = dataclasses.replace(family.program_config(config), dtype=jnp.float32)
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1])
    return family, pc, mesh, family.weights(config, pc, mesh, seed)


# -- the kernels ---------------------------------------------------------------

H, P, G, N = 8, 16, 2, 16
HP = ssd.heads_per_tile(P, H // G)


def scan_inputs(R, T, lengths, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    dt = jnp.asarray(rng.uniform(1e-3, 0.5, (R, T, H)), jnp.float32)
    dt = jnp.where(jnp.arange(T)[None, :, None] < jnp.asarray(lengths)[:, None, None], dt, 0.0)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), jnp.float32)
    return dt, f(R, T, H, P), f(R, T, G, N), f(R, T, G, N), a, ssd.stored(f(R, H, P, N), HP)


def test_the_stored_order_is_a_permutation_and_back():
    s = jnp.arange(3 * H * P * N, dtype=jnp.float32).reshape(3, H, P, N)
    assert HP == 4 and ssd.stored(s, HP).shape == (3, H // HP, N, HP * P)
    assert bool(jnp.array_equal(ssd.logical(ssd.stored(s, HP), P), s))
    assert ssd.heads_per_tile(64, 16) == 2 and ssd.heads_per_tile(64, 1) == 1 and ssd.heads_per_tile(256, 4) == 1


@pytest.mark.parametrize("T,lengths,snaps", [
    (300, [300, 130, 0], [-1, -1, -1]),  # across two chunk edges, a padded tail, an empty row
    (300, [300, 130, 7], [0, 128, 0]),  # a snapshot at the row's start and on a chunk's edge
    (300, [288, 140, 64], [48, 112, 64]),  # inside a chunk, and at a row's own end inside a chunk
    (256, [256, 256, 200], [256, 16, 192]),  # at the last chunk's end
    (64, [64, 33, 1], [32, 16, 0]),  # rows shorter than a chunk
])
def test_the_chunked_scan_agrees_with_the_per_token_recurrence(T, lengths, snaps):
    dt, x, b, c, a, h0 = scan_inputs(3, T, lengths)
    snap = jnp.asarray(snaps, jnp.int32)
    want_y, want_end, want_snap = ssd.ssd_scan_reference(dt, x, b, c, a, h0, snap)
    y, end, snapped = ssd.ssd_scan(dt, x, b, c, a, h0, snap, -(-jnp.asarray(lengths) // ssd.CHUNK), interpret=True)
    valid = (jnp.arange(T)[None, :] < jnp.asarray(lengths)[:, None])[..., None, None]
    scale = float(jnp.max(jnp.abs(want_y)))
    assert float(jnp.max(jnp.abs(jnp.where(valid, y - want_y, 0.0)))) < 2e-5 * scale
    np.testing.assert_allclose(end, want_end, atol=2e-5 * float(jnp.max(jnp.abs(want_end))))
    np.testing.assert_allclose(snapped, want_snap, atol=2e-5 * float(jnp.max(jnp.abs(want_snap))))
    if min(lengths) == 0:  # an empty row's state passes through exactly
        assert bool(jnp.array_equal(end[lengths.index(0)], h0[lengths.index(0)]))


def test_a_continuation_of_the_scan_from_its_own_snapshot_ends_where_one_scan_ends():
    dt, x, b, c, a, h0 = scan_inputs(2, 256, [256, 256], seed=3)
    cut = 144
    snap = jnp.full((2,), cut, jnp.int32)
    _, end, mid = ssd.ssd_scan(dt, x, b, c, a, h0, snap, jnp.full((2,), 2), interpret=True)
    y2, end2, _ = ssd.ssd_scan(dt[:, cut:], x[:, cut:], b[:, cut:], c[:, cut:], a, mid, jnp.full((2,), -1),
                               jnp.full((2,), 1), interpret=True)
    y, _, _ = ssd.ssd_scan_reference(dt, x, b, c, a, h0, snap)
    np.testing.assert_allclose(end2, end, atol=1e-4 * float(jnp.max(jnp.abs(end))))
    np.testing.assert_allclose(y2, y[:, cut:], atol=1e-4 * float(jnp.max(jnp.abs(y))))


def test_the_decays_are_differences_of_the_running_sum_and_never_overflow():
    """Heads that forget within a token or two (dt A of -40 a token): a
    quotient of two exponentials of the running sum is 0 / 0 inside the
    chunk; the kernel's differences are finite and right."""
    dt, x, b, c, a, h0 = scan_inputs(1, 128, [128], seed=4)
    a = jnp.full((H,), -80.0)
    want = ssd.ssd_scan_reference(dt, x, b, c, a, h0, jnp.asarray([-1]))
    got = ssd.ssd_scan(dt, x, b, c, a, h0, jnp.asarray([-1]), jnp.asarray([1]), interpret=True)
    for g, w in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.max(jnp.abs(w))))
    from acpbench.families.nemotron_h_reference import _quotient_chunks

    y = _quotient_chunks(dt, x, jnp.repeat(b, H // G, axis=2), jnp.repeat(c, H // G, axis=2), a)
    assert not bool(jnp.all(jnp.isfinite(y)))


def update_inputs(S, heads=H, head_dim=P, groups=G, d_state=N, layers=3, slots=9):
    rng = np.random.default_rng(S)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    hp = ssd.heads_per_tile(head_dim, heads // groups)
    state = f(layers, slots, heads // hp, d_state, hp * head_dim)
    dt = jnp.asarray(rng.uniform(1e-3, 0.5, (S, heads)), jnp.float32).at[1].set(0.0)  # lane 1 idle
    a = -jnp.asarray(rng.uniform(1, 16, (heads,)), jnp.float32)
    rows = (dt, f(S, heads, head_dim), f(S, groups, d_state), f(S, groups, d_state), a)
    after = dict(z=f(S, heads * head_dim), d=f(heads), norm=f(heads * head_dim), eps=1e-5)
    return state, rows, after


@pytest.mark.parametrize("S", [8, 4, 3])
def test_the_update_kernel_steps_one_layers_lanes_in_place_and_no_other_row(S):
    state, (dt, x, b, c, a), after = update_inputs(S)
    want_y, want = ssd.ssd_update_reference(state, jnp.int32(1), dt, x, b, c, a, dtype=jnp.float32, **after)
    y, got = ssd.ssd_update(state, jnp.int32(1), dt, x, b, c, a, dtype=jnp.float32, interpret=True, **after)
    np.testing.assert_allclose(y, want_y, atol=1e-5)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert bool(jnp.array_equal(got[0], state[0])) and bool(jnp.array_equal(got[2], state[2]))
    assert bool(jnp.array_equal(got[1, S:], state[1, S:])) and bool(jnp.array_equal(got[1, 1], state[1, 1]))
    # and the twin is the recurrence: one token of the per-token scan from the same state, then the skip, the gate
    # and the grouped norm as the prefill runs them
    y1, end, _ = ssd.ssd_scan_reference(dt[:, None], x[:, None], b[:, None], c[:, None], a, state[1, :S],
                                        jnp.full((S,), -1))
    y1 = ssd.gate_norm(y1[:, 0], x, after["z"], after["d"], after["norm"], G, after["eps"])
    np.testing.assert_allclose(want_y, y1, atol=1e-5)
    np.testing.assert_allclose(want[1, :S], end, atol=1e-6)


@pytest.mark.parametrize("S,lanes,sizes,dtype", [
    (8, 1, {}, jnp.float32), (8, 4, {}, jnp.float32), (6, 4, {}, jnp.bfloat16),  # the tiny sizes; S no multiple of `lanes`
    (6, 4, dict(heads=8, head_dim=64, groups=2, d_state=128), jnp.float32),  # a block at the published widths
    (3, 1, dict(heads=8, head_dim=64, groups=2, d_state=128), jnp.bfloat16),
    (4, 4, dict(heads=8, head_dim=64, groups=4, d_state=128), jnp.bfloat16),  # hp = 2 = a group's heads
])
def test_the_fused_update_returns_the_gated_normed_row_and_the_stepped_state(S, lanes, sizes, dtype):
    """One call from the conv's rows to the row `mamba_out_proj` reads: the
    output row in the model's dtype, the stepped state, a `dt = 0` lane's
    state bit for bit, the other layers of the stack and the slots past the
    lanes untouched, at any `lanes` a grid step."""
    state, (dt, x, b, c, a), after = update_inputs(S, **sizes)
    want_y, want = ssd.ssd_update_reference(state, jnp.int32(1), dt, x, b, c, a, dtype=dtype, **after)
    y, got = ssd.ssd_update(state, jnp.int32(1), dt, x, b, c, a, dtype=dtype, interpret=True, lanes=lanes, **after)
    assert y.dtype == want_y.dtype == dtype and y.shape == (S, x.shape[1] * x.shape[2])
    want_row = want_y.astype(jnp.float32)
    atol = (1e-5 if dtype == jnp.float32 else 2 ** -7) * float(jnp.max(jnp.abs(want_row)))  # a bfloat16 row: its last bit
    np.testing.assert_allclose(y.astype(jnp.float32), want_row, atol=atol)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert bool(jnp.array_equal(got[1, 1], state[1, 1]))  # dt = 0: the decay is 1 and the input term 0, exactly
    assert bool(jnp.array_equal(got[0], state[0])) and bool(jnp.array_equal(got[2], state[2]))
    assert bool(jnp.array_equal(got[1, S:], state[1, S:]))
    # the row is the plain epilogue over the recurrence's y, a group's norm over its own channels alone
    groups, heads = b.shape[1], x.shape[1]
    h = ssd.logical(want[1, :S], x.shape[2])  # [S, H, P, N]
    y0 = jnp.einsum("shpn,shn->shp", h, jnp.repeat(c, heads // groups, axis=1))
    plain = ssd.gate_norm(y0, x, after["z"], after["d"], after["norm"], groups, after["eps"])
    np.testing.assert_allclose(want_row, plain, atol=atol)


# -- the latent, ungated expert layer ---------------------------------------------


def latent_double(x, u, router_w, bias, w1, w2, k, held, scale):
    """`moe_ffn_reference` extended: the router reads x, the experts read u,
    an expert is `w2 relu(w1 u)^2`; sigmoid scores, the bias in the choice
    only, the chosen renormalised and scaled; absent experts add nothing."""
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + bias, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * scale
    local = {e: i for i, e in enumerate(held)}
    out = np.zeros((x.shape[0], w2.shape[-1]), np.float32)
    for n in range(x.shape[0]):
        for j in range(k):
            e = int(idx[n, j])
            if e in local:
                h = np.square(np.maximum(np.asarray(u[n]) @ np.asarray(w1[local[e]]), 0.0))
                out[n] += float(w[n, j]) * (h @ np.asarray(w2[local[e]]))
    return out


@pytest.mark.parametrize("tokens,path", [(9, "ragged"), (9, "interpret"), (150, "ragged"), (150, "interpret")])
def test_routed_experts_serves_latent_ungated_experts_apart_from_the_routers_input(tokens, path):
    rng = np.random.default_rng(tokens)
    D, U, F, E, k, held = 32, 16, 24, 16, 3, (2, 3, 5, 11)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    x, u, router, bias = f(tokens, D), f(tokens, U), f(D, E) * D ** -0.5, f(E) * 0.05
    w1, w2 = f(len(held), U, F) * U ** -0.5, f(len(held), F, U) * F ** -0.5
    y, counts = moe.routed_experts(x, router, w1, None, w2, k, held=held, score="sigmoid", bias=bias, scale=5.0,
                                   act=nh.relu2, u=u, kernel=False, interpret=path == "interpret")
    want = latent_double(x, u, router, bias, w1, w2, k, held, 5.0)
    assert y.shape == (tokens, U)
    np.testing.assert_allclose(y, want, atol=2e-4 * np.abs(want).max())
    assert int(counts[0]) == tokens * k and 0 < int(counts[1]) < tokens * k


def test_the_plan_is_chosen_by_the_rows_an_expert_can_expect():
    # every accepted cell's rule is the parent's: the pairs alone, at 128 experts or fewer
    for experts in (None, 8, 64, 128):
        assert [moe.row_tile(p, experts) for p in (96, 1024, 2047, 2048, 16384)] == [16, 16, 16, 128, 128]
    # this model: 128 lanes at top-22 of 512 are a decode step (5.5 rows an expert); a prefill of 512 tokens is not
    assert moe.row_tile(128 * 22, 512) == 16 and moe.row_tile(372 * 22, 512) == 16
    assert moe.row_tile(373 * 22, 512) == 128 and moe.row_tile(512 * 22, 512) == 128


def test_a_decode_steps_expert_matrices_are_one_unit_each_under_the_vmem_a_kernel_gets_unasked():
    from agentcontrolplane_tpu.ops.pallas.moe_gmm import _DEFAULT_VMEM_BYTES, chunk_plan, vmem_bytes

    for K, Ncols in ((1024, 2688), (2688, 1024)):
        chunk, depth = chunk_plan(K, Ncols, 1, 2, 16)
        assert chunk == Ncols and depth == 2, "a whole expert's matrix a fetch, the next one's under its product"
        for tm in (16, 128):
            assert vmem_bytes(K, 1, 2, tm, *chunk_plan(K, Ncols, 1, 2, tm)) <= _DEFAULT_VMEM_BYTES


# -- the program against the plain reference ------------------------------------------


def test_program_agrees_with_the_plain_reference_through_pages_and_state():
    """Prefill of the prompt, N decode steps through pool and state, and
    the same rows again by prefill of the longer row; the decode steps'
    state against the prefill's (the update against the scan)."""
    config = tiny()
    family, pc, mesh, params = built(config, seed=2**31 + 7)
    s = check.sample(config["check"], config["vocab_size"], 8, 11)
    want = check.reference_logits(functools.partial(family.reference_logits, config, params), s)
    pre, dec, state = family.cache_readings(config, pc, params, mesh, s, False)
    numbers = check.compare((pre, dec), want)
    assert numbers["finite"] and numbers["top1_agree"] == 1.0
    assert numbers["prefill_rel_rms"] < 2e-5 and numbers["decode_rel_rms"] < 2e-5, numbers
    assert state["state_rel_rms"] < 1e-5 and state["state_16bit_share"] < 0.01, state


@pytest.mark.parametrize("control,least", [("int8", 3e-3), ("recurrence_bf16", 1e-4), ("latent_skip", 3e-2)])
def test_each_reference_control_moves_the_logits(control, least):
    config = tiny()
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], 8, 3)
    ref = functools.partial(family.reference_logits, config, params)
    moved = check.compare(check.reference_logits(ref, s, lower=control), check.reference_logits(ref, s))
    assert moved["logit_rel_rms"] > least, moved
    with pytest.raises(ValueError, match="no control"):
        ref(s["tokens"], s["rows"], lower="no-such-control")


def test_the_quotient_control_is_the_recurrence_until_a_divisor_underflows():
    config = tiny()
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], 8, 3)
    ref = functools.partial(family.reference_logits, config, params)
    moved = check.compare(check.reference_logits(ref, s, lower="decay_quotient"), check.reference_logits(ref, s))
    assert moved["logit_rel_rms"] < 1e-4  # 36 tokens of dt A over -0.05 a token: no divisor near zero yet


@pytest.mark.parametrize("control", ["zero_state", "h_bf16", "state_swap", "recurrence_bf16"])
def test_each_cache_control_is_seen(control, capsys):
    config = tiny()
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], 8, 5)
    want = check.reference_logits(functools.partial(family.reference_logits, config, params), s)
    pre, dec, state = family.cache_readings(config, pc, params, mesh, s, False, **{control: True})
    numbers = check.compare((pre, dec), want)
    if control == "recurrence_bf16":
        assert state["state_16bit_share"] == 1.0 and state["state_rel_rms"] > 1e-3
    elif control == "h_bf16":
        assert state["state_16bit_share"] == 1.0
        lowered = family.cached_logits(config, pc, params, mesh, s, False, h_bf16=True)
        assert "state_16bit_share=1 limit=0.01 EXCEEDED" in capsys.readouterr().out
        assert not check.compare(lowered, want)["finite"]
    else:
        assert state["state_rel_rms"] > 0.1 and numbers["decode_rel_rms"] > 10 * numbers["prefill_rel_rms"], (state, numbers)


def test_a_continuation_from_an_installed_state_equals_one_prefill_of_the_whole_row():
    """The first 16 tokens by one prefill with a snapshot at 16; the saved
    state installed into ANOTHER slot; the rest as a continuation there: the
    last token's logits, the end state and the reference's logits agree."""
    config = tiny()
    family, pc, mesh, params = built(config)
    rng = np.random.default_rng(2)
    T, cut, PG = 32, 16, 8
    tokens = rng.integers(0, 512, (1, T)).astype(np.int32)
    i32 = lambda *x: jnp.asarray(x, jnp.int32)  # noqa: E731
    pages = jnp.arange(1, 1 + T // PG, dtype=jnp.int32)[None]
    cache = nh.init_paged_cache(pc, 9, PG, max_slots=2)
    whole, want = nh.prefill_paged_batch(params, cache, tokens, i32(T), pages, (i32(0), i32(-1)), pc)
    head = np.zeros((1, T), np.int32)
    head[0, :cut] = tokens[0, :cut]
    first, _ = nh.prefill_paged_batch(params, cache, head, i32(cut), pages.at[0, cut // PG:].set(0), (i32(0), i32(cut)), pc)
    moved = nh.install_state(first, 1, nh.saved_state(first, 0))
    tail = np.zeros((1, T), np.int32)
    tail[0, : T - cut] = tokens[0, cut:]
    ids = jnp.zeros((1, T // PG), jnp.int32).at[0, : (T - cut) // PG].set(pages[0, cut // PG:])
    done, got = nh.prefill_paged_continue(params, moved, tail, i32(T - cut), i32(cut), ids, pages, (i32(1), i32(-1)), pc)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(done["state"][name][:, 1], whole["state"][name][:, 0], atol=2e-5)
    ref = family.reference_logits(config, params, tokens, [[T - 1]])
    np.testing.assert_allclose(got, ref[:, 0], atol=2e-4)
    mid = nh.prefill_paged_continue_kv(params, moved, tail, i32(T - cut), i32(cut), ids, pages, (i32(1), i32(-1)), pc)
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(jax.tree_util.tree_leaves(mid), jax.tree_util.tree_leaves(done)))


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The share test of the guide's section 4: each of eight chips holds two
    of the tiny model's 16 experts and routes over all 16; the eight shares'
    routed parts (through the program's own layer, latent projections and
    all) plus the shared expert counted once are the reference's uncut layer."""
    config = tiny()
    family, pc, mesh, params = built(config)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(2, 24, pc.dim)), jnp.float32)
    sizes = dict(family._sizes(config), held=tuple(range(16)))
    whole = dict(params, moe=dict(params["moe"]))
    key = jax.random.key(3)  # the uncut layer's experts: 16 of them, the file's four among them
    for name in ("w1", "w2"):
        shape = list(params["moe"][name].shape)
        shape[1] = 16
        whole["moe"][name] = jax.random.normal(jax.random.fold_in(key, len(name) + shape[2]), shape) * shape[2] ** -0.5
    want = nemotron_h_reference.layer_output(whole, sizes, 1, x)
    shared = want - nemotron_h_reference.layer_output(whole, sizes, 1, x, shared=False)
    total = shared
    w = jax.tree_util.tree_map(lambda a: a[1], whole["moe"])
    for share in range(8):
        held = (2 * share, 2 * share + 1)
        c = dataclasses.replace(pc, experts_held=held)
        stacks = (w["w1"][jnp.asarray(held)], w["w2"][jnp.asarray(held)])
        y, counts = nh._latent_moe(x, w, stacks, 0, c, jnp.ones(x.shape[:2], bool))
        part = nemotron_h_reference.layer_output(
            dict(whole, moe=dict(whole["moe"], w1=whole["moe"]["w1"][:, jnp.asarray(held)],
                                 w2=whole["moe"]["w2"][:, jnp.asarray(held)])), dict(sizes, held=held), 1, x, shared=False)
        np.testing.assert_allclose(y - shared, part, atol=2e-5)
        total = total + (y - shared)
        assert int(counts[1]) == x.shape[0] * x.shape[1] * pc.experts_per_token
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_the_seam_finds_the_family_by_the_configs_type_and_the_presets_are_the_issues():
    from agentcontrolplane_tpu import models
    from agentcontrolplane_tpu.engine.engine import Engine
    from agentcontrolplane_tpu.models.stack import segments

    cfg = preset("nemotron-h-tiny")
    assert programs(cfg) is models._NEMOTRON_H and programs(cfg).has_state and programs(cfg).family == "nemotron_h"
    assert programs(cfg).draft_step is None and not programs(cfg).window_cache
    full = preset("nemotron-3-super-120b-a12b")
    assert (full.n_layers, full.n_mamba, full.n_moe, full.n_attention) == (88, 40, 40, 8)
    assert full.layer_types[:11] == cfg.layer_types == nh.pattern("MEMEMEM*EME")
    assert full.state_shape == (64, 128, 128) and full.conv_channels == 10240 and full.d_inner == 8192
    cut = dataclasses.replace(full, layer_types=full.layer_types[:11])
    assert cut.state_bytes_per_slot == 5 * (4 * 2 ** 20 + 61_440) == 21_278_720
    m, e = "mamba", "moe"
    assert segments(cut.layer_types) == [(3, ((m, 1), (e, 1))), (1, ((m, 1),)), (1, (("attention", 1),)),
                                         (1, ((e, 1),)), (1, ((m, 1),)), (1, ((e, 1),))]
    with pytest.raises(ValueError, match="unknown characters"):
        nh.pattern("MEX")
    with pytest.raises(ValueError, match="mixes the three kinds"):
        nh.plan(dataclasses.replace(cfg, layer_types=nh.pattern("MEME")))
    with pytest.raises(ValueError, match="rolled back"):
        Engine(config=cfg, mesh=make_mesh({"tp": 1}, devices=jax.devices()[:1]), max_slots=2, max_ctx=64,
               kv_layout="paged", page_size=8, spec_len=4)
