"""K-EXAONE through the normal path, the programs: the forward pass and the
MTP module against the plain reference (acpbench/families/exaone_reference.py,
which imports nothing of the program); prefill then verify-and-draft steps
through both caches under every pattern of kept and refused drafts, main
logits and drafted logits; the ring after a refused row; a chunked row; the
accept's distribution; the eight shares of a layer's experts; what the seam
says of a family that drafts. (The verify step's walks, kernel against
gather: `test_exaone_walks.py`, a file of its own for the file's budget.)

CPU, tiny sizes (a dense window layer, then window, window, full, window; a
window of 16, pages of 8, 16 experts top-2, the MTP module), float32, seeded
weights.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acpbench import check, spec
from acpbench.families import exaone_reference
from acpbench.families.exaone import forced_sampler
from agentcontrolplane_tpu.models import exaone, experts, preset, programs
from agentcontrolplane_tpu.ops import paged
from agentcontrolplane_tpu.ops.sampling import masked_logits, speculative_sample
from agentcontrolplane_tpu.parallel.mesh import make_mesh

FILE = spec.load_json(spec.os.path.join(spec.ROOT, "tests/acpbench/data/tiny-config-exaone.json"))
WINDOW, PAGE = FILE["sliding_window"], FILE["engine"]["page_size"]
RING = WINDOW // PAGE + 1


def tiny(**over):
    config = dict(FILE)
    config["check"] = dict(FILE["check"], sequences=3, prefill_bucket=64, min_prompt=WINDOW + 8, decode_steps=12)
    return {**config, **over}


def built(config, seed=5):
    family = spec.family(config)
    pc = dataclasses.replace(family.program_config(config), dtype=jnp.float32)
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1])
    return family, pc, mesh, family.weights(config, pc, mesh, seed)


def test_forward_and_drafted_logits_agree_with_the_plain_reference():
    config = tiny()
    family, pc, mesh, params = built(config)
    tokens = np.random.default_rng(0).integers(0, 256, size=(2, 70))
    rows = np.tile(np.arange(70), (2, 1))
    got, drafted = exaone.forward(params, jnp.asarray(tokens), pc)
    want = family.reference_logits(config, params, tokens, rows)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4 * float(jnp.max(jnp.abs(want)))
    want = family.reference_draft_logits(config, params, tokens, rows[:, :-1])
    assert drafted.shape == want.shape == (2, 69, 256)
    assert float(jnp.max(jnp.abs(drafted - want))) < 2e-4 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("control,least", [("int8", 3e-3), ("bf16_rest", 1e-3), ("rope_on_full", 1e-2),
                                           ("window_off", 1e-2), ("nonorm", 1e-3), ("bias_off", 1e-3),
                                           ("route_scale_off", 1e-3), ("shared_off", 1e-2)])
def test_each_reference_control_moves_the_logits(control, least):
    config = tiny()
    family, pc, mesh, params = built(config)
    s = check.sample(config["check"], config["vocab_size"], PAGE, 3)
    reference = functools.partial(family.reference_logits, config, params)
    want = check.reference_logits(reference, s)
    moved = check.compare(check.reference_logits(reference, s, lower=control), want)["logit_rel_rms"]
    assert moved > least, (control, moved)


def test_the_drafters_control_moves_the_drafted_logits_and_not_the_stacks():
    config = tiny()
    family, pc, mesh, params = built(config)
    tokens = np.random.default_rng(1).integers(0, 256, size=(2, 40))
    rows = np.tile(np.arange(39), (2, 1))
    sound = family.reference_draft_logits(config, params, tokens, rows)
    off = family.reference_draft_logits(config, params, tokens, rows, lower="mtp_prev_hidden_off")
    assert float(jnp.sqrt(jnp.sum((off - sound) ** 2) / jnp.sum(sound ** 2))) > 0.1
    np.testing.assert_array_equal(family.reference_logits(config, params, tokens, rows, lower="mtp_prev_hidden_off"),
                                  family.reference_logits(config, params, tokens, rows))
    with pytest.raises(ValueError, match="no control 'int4'"):
        family.reference_logits(config, params, tokens, rows, lower="int4")


# -- the caches after refused rows, a chunked row, the walks ------------------------------------


def _cache_and_prefill(pc, params, tokens, lengths, B, T, per):
    cache = exaone.init_paged_cache(pc, B * per + 1, PAGE, max_slots=B)
    tables = (1 + np.arange(B * per, dtype=np.int32)).reshape(B, per)
    ids = np.zeros((B, T // PAGE), np.int32)
    for b in range(B):
        n = -(-int(lengths[b]) // PAGE)
        ids[b, :n] = tables[b, :n]
    prompt = np.where(np.arange(T)[None] < lengths[:, None], tokens[:, :T], 0)
    cache, logits = exaone.prefill_paged_batch(
        params, cache, jnp.asarray(prompt), jnp.asarray(lengths), jnp.asarray(ids),
        (jnp.arange(B), jnp.full((B,), -1, jnp.int32)), pc)
    return cache, jnp.asarray(tables), logits


def test_the_ring_after_refused_rows_is_the_ring_of_the_same_tokens_decoded_without_a_draft():
    """The same committed tokens three times from one prefill: by verify
    steps in which every draft is ANOTHER token and refused, by the same
    steps with yet another refused draft, and by the one-row decode step
    that has no drafter. Below the length every row of the ring and of the
    full layer's pages is the same BIT FOR BIT between the two drafted runs
    (a refused row's K/V lay past the length and was overwritten in place:
    what was drafted leaves no trace), and equals the undrafted program's to
    float32 rounding (a one-row program sums in another order). 144 rows at
    the published sizes (here 24) hold the window a two-row step reads."""
    pc = dataclasses.replace(preset("exaone-tiny"), dtype=jnp.float32)
    params = exaone.init_params(pc, jax.random.key(2))
    B, T, steps = 2, 32, 21  # past a ring of 24 rows, across page edges
    tokens = np.random.default_rng(4).integers(0, 256, size=(B, 80)).astype(np.int32)
    lengths = np.array([19, 26], np.int32)
    first, tables, _ = _cache_and_prefill(pc, params, tokens, lengths, B, T, 10)
    caches = {"drafted": first, "drafted_otherwise": jax.tree_util.tree_map(jnp.copy, first),
              "plain": jax.tree_util.tree_map(jnp.copy, first)}
    live = jnp.ones((B,), bool)
    verify = jax.jit(lambda ca, tok, n, d, nxt: exaone.verify_step_paged(
        params, ca, tok, n, tables, live, forced_sampler(d, jnp.zeros((B,), bool), nxt), pc)[0])
    decode = jax.jit(lambda ca, tok, n: exaone.decode_step_paged(params, ca, tok, n, tables, live, pc)[0])
    rows = np.arange(B)
    for j in range(steps):
        n = lengths + j
        tok, nxt = jnp.asarray(tokens[rows, n]), np.stack([tokens[rows, n + 1], tokens[rows, n + 2]], 1)
        for name, shift in (("drafted", 3), ("drafted_otherwise", 101)):
            caches[name] = verify(caches[name], tok, jnp.asarray(n), jnp.asarray((nxt[:, 0] + shift) % 256), jnp.asarray(nxt))
        caches["plain"] = decode(caches["plain"], tok, jnp.asarray(n))
    end = lengths + steps
    ring_rows = np.asarray(paged.ring_positions(jnp.asarray(end), RING, PAGE))  # [B, RING * PAGE] positions held

    def held_rows(cache):
        out = []
        for name in ("wk", "wv"):
            a = np.asarray(cache[name]).reshape(pc.n_window, B + 1, RING * PAGE, -1)
            for slot in range(B):
                held = (ring_rows[slot] >= end[slot] - WINDOW) & (ring_rows[slot] < end[slot])
                assert held.sum() == WINDOW
                out.append(a[:, slot, held])
        for name in ("k", "v"):  # the stack's full layer (the MTP block's rows are the drafter's alone)
            a = np.asarray(cache[name])[: pc.n_full]
            for slot in range(B):
                out.append(a[:, np.asarray(tables)[slot]].reshape(pc.n_full, -1, a.shape[-1])[:, : end[slot]])
        return out

    for a, b, c in zip(*(held_rows(caches[name]) for name in ("drafted", "drafted_otherwise", "plain"))):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_allclose(a, c, atol=2e-5)
    # the drafter's own layer and pending rows too: nothing of a refused draft is left
    np.testing.assert_array_equal(caches["drafted"]["state"]["hid"][:B, 0], caches["drafted_otherwise"]["state"]["hid"][:B, 0])
    assert RING * PAGE >= WINDOW + 1 and 128 // 16 + 1 == 9 and 9 * 16 >= 129


def test_a_chunked_row_leaves_the_drafter_what_a_whole_prefill_leaves():
    """A row prefilled whole and the same row as a first chunk and a
    continuation: the same last logits, the same pending hidden state, and
    the same MTP pages for every position below the last (the continuation
    runs the pending row of the chunk before it, whose next token is its
    own first)."""
    pc = dataclasses.replace(preset("exaone-tiny"), dtype=jnp.float32)
    params = exaone.init_params(pc, jax.random.key(3))
    B, T, per = 2, 64, 10
    tokens = np.random.default_rng(5).integers(0, 256, size=(B, 80)).astype(np.int32)
    lengths = np.array([45, 61], np.int32)
    whole, tables, want = _cache_and_prefill(pc, params, tokens, lengths, B, T, per)
    first = np.array([32, 32], np.int32)
    chunked, _, _ = _cache_and_prefill(pc, params, tokens, first, B, 32, per)
    rest = lengths - first
    rows = np.where(np.arange(32)[None] < rest[:, None], tokens[:, 32:64], 0)
    ids = np.where(np.arange(4)[None] < -(-rest[:, None] // PAGE), np.asarray(tables)[:, 4:8], 0)
    chunked, got = exaone.prefill_paged_continue(
        params, chunked, jnp.asarray(rows), jnp.asarray(rest), jnp.asarray(first), jnp.asarray(ids), tables,
        (jnp.arange(B), jnp.full((B,), -1, jnp.int32)), pc)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(chunked["state"]["hid"][:B, 0], whole["state"]["hid"][:B, 0], atol=2e-5)
    np.testing.assert_array_equal(chunked["state"]["pend"][:B], [1, 1])
    for slot in range(B):
        flat = lambda c: np.asarray(c["k"])[pc.n_full, np.asarray(tables)[slot]].reshape(-1, c["k"].shape[-1])  # noqa: E731
        np.testing.assert_allclose(flat(chunked)[: lengths[slot] - 1], flat(whole)[: lengths[slot] - 1], atol=2e-5)


# -- the accept ------------------------------------------------------------------------------------------


def test_the_accept_emits_the_verified_distribution_whatever_the_drafted_one():
    """Speculative sampling proper over a vocabulary of 12: drafts drawn
    from `q`, judged against `p`, 40,000 lanes of one seed. The first token
    is distributed as `p` (chi-square, 11 degrees of freedom: 31.3 is the
    0.1% point), the share kept is `sum min(p, q)`, and the token after a
    kept draft as row 1's `p`."""
    V, S = 12, 40000
    rng = np.random.default_rng(7)
    p0, p1, q = (rng.normal(size=V) * 1.2 for _ in range(3))
    q[3] = -1e30  # a token the drafter never proposes
    temps = jnp.full((S,), 0.7, jnp.float32)
    tile = lambda v: jnp.tile(jnp.asarray(v, jnp.float32)[None], (S, 1))  # noqa: E731
    k_draft, k_accept = jax.random.split(jax.random.key(51))
    draft = jax.random.categorical(k_draft, tile(q) / 0.7, axis=-1).astype(jnp.int32)
    kept, first, second = speculative_sample(jnp.stack([tile(p0), tile(p1)], 1), tile(q), draft, k_accept, temps)
    soft = lambda v: np.exp(v / 0.7 - np.max(v / 0.7)) / np.sum(np.exp(v / 0.7 - np.max(v / 0.7)))  # noqa: E731
    want, want_q = soft(p0), soft(q)
    counts = np.bincount(np.asarray(first), minlength=V)
    chi2 = float(np.sum((counts - S * want) ** 2 / (S * want)))
    assert chi2 < 31.3, chi2
    assert counts[3] > 0  # reached through the residual alone
    assert abs(float(jnp.mean(kept)) - np.minimum(want, want_q).sum()) < 0.01
    after = np.bincount(np.asarray(second)[np.asarray(kept)], minlength=V)
    n = after.sum()
    assert float(np.sum((after - n * soft(p1)) ** 2 / (n * soft(p1)))) < 31.3
    # the draft's own distribution is NOT p: the test above is not vacuous
    drawn = np.bincount(np.asarray(draft), minlength=V)
    assert float(np.sum((drawn - S * want) ** 2 / (S * want))) > 1000


def test_at_temperature_zero_the_accept_is_the_equality_test():
    p = jnp.asarray([[[0.0, 3.0, 1.0], [2.0, 0.0, 0.5]], [[0.0, 3.0, 1.0], [2.0, 0.0, 0.5]]], jnp.float32)
    q = jnp.asarray([[0.0, 9.0, 0.0], [9.0, 0.0, 0.0]], jnp.float32)
    kept, first, second = speculative_sample(p, q, jnp.asarray([1, 0], jnp.int32), jax.random.key(0), jnp.zeros((2,)))
    assert kept.tolist() == [True, False] and first.tolist() == [1, 1] and second[0] == 0
    masked = masked_logits(p[:, 0], jnp.asarray([1, 0]), jnp.asarray([1.0, 1.0]))
    assert (masked[0] > -1e29).sum() == 1 and (masked[1] > -1e29).sum() == 3


# -- the cut ----------------------------------------------------------------------------------------------


def test_the_eight_shares_of_a_layers_experts_add_up_to_the_uncut_layer():
    """The cut of the published file at the tiny size: 16 experts split eight
    ways. Each share computes its own experts' part of every token's sum and
    the shared expert whole; the eight routed parts and the shared expert
    counted ONCE add up to the layer with all sixteen held, in the program's
    grouped layer and in the reference's loop alike."""
    config = tiny()
    family, pc, mesh, params = built(config)
    x = jax.random.normal(jax.random.key(3), (2, 24, pc.dim), jnp.float32)
    layer = 2  # the third sparse layer (layer 3 of the model)
    sizes = {"experts_per_token": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
             "layer_types": tuple(FILE["layer_types"])}
    whole_held = tuple(range(16))
    # the file's weights hold 2 experts: draw a layer with all 16 to cut from
    pc16 = dataclasses.replace(pc, experts_held=whole_held)
    full = exaone.init_params(pc16, jax.random.key(9))
    ff = {**jax.tree_util.tree_map(lambda a: a[layer], full["ff"]),
          "router_bias": 0.03 * jax.random.normal(jax.random.key(4), (16,), jnp.float32)}
    params16 = {**full, "ff": {**full["ff"], "router_bias": jnp.tile(ff["router_bias"][None], (4, 1))}}
    shared = (jax.nn.silu(x @ ff["sw1"]) * (x @ ff["sw3"])) @ ff["sw2"]
    whole_ref = exaone_reference.layer_output(params16, {**sizes, "held": whole_held}, layer + 1, x)
    whole, parts, parts_ref = None, [], []
    for held in [whole_held] + [(2 * i, 2 * i + 1) for i in range(8)]:
        c = dataclasses.replace(pc, experts_held=held)
        mine = tuple(ff[name][jnp.asarray(held)] for name in ("w1", "w3", "w2"))
        y, _ = experts.routed_ff(x, ff, mine, 0, c, jnp.ones((2, 24), bool), score="sigmoid", bias=True, scale=c.routed_scaling_factor, chunk=True, shared=True)
        if len(held) == 16:
            whole = y
            continue
        parts.append(y - shared)
        cut = {**params16, "ff": {**params16["ff"], **{n: params16["ff"][n][:, jnp.asarray(held)] for n in ("w1", "w3", "w2")}}}
        parts_ref.append(exaone_reference.layer_output(cut, {**sizes, "held": held}, layer + 1, x) - shared)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=3e-5)
    np.testing.assert_allclose(sum(parts_ref) + shared, whole_ref, atol=3e-5)
    np.testing.assert_allclose(whole, whole_ref, atol=3e-5)
    assert float(jnp.max(jnp.abs(parts[0]))) > 0 and float(jnp.max(jnp.abs(shared))) > 0


def test_the_seam_names_a_family_that_drafts_and_what_it_keeps():
    m = programs(preset("exaone-tiny"))
    assert (m.family, m.has_state, m.window_cache, m.draft_rows) == ("exaone", True, True, 2)
    assert m.draft_step is exaone.verify_step_paged
    for other in ("mellum-tiny", "kanana-tiny", "lfm2-tiny", "tiny"):
        assert programs(preset(other)).draft_step is None and programs(preset(other)).draft_rows == 1
    c = preset("k-exaone-236b-a23b")
    assert (c.n_layers, c.n_window, c.n_full, c.first_dense, c.window, len(c.held)) == (48, 36, 12, 1, 128, 128)
    assert exaone.plan(c)["before"] == {"sliding_attention": 1, "full_attention": 0}
    tiny_c = preset("exaone-tiny")
    cache = jax.eval_shape(lambda: exaone.init_paged_cache(tiny_c, 9, PAGE, max_slots=4))
    assert cache["k"].shape[0] == tiny_c.n_full + 1 == 2  # the MTP block's layer, last
    assert cache["wk"].shape[:2] == (tiny_c.n_window, (4 + 1) * RING)
    assert cache["state"]["hid"].shape == (5, 2, tiny_c.dim) and cache["state"]["pend"].shape == (5,)
    assert cache["state"]["counts"].shape[1] == 1 + 3 + 16 + 4 + 4
    with pytest.raises(ValueError, match="int8 pages"):
        exaone.init_paged_cache(tiny_c, 9, PAGE, quantize_kv=True)
    with pytest.raises(ValueError, match="one kind only"):
        exaone.plan(dataclasses.replace(tiny_c, layer_types=("sliding_attention",) * 3))
    d = exaone.describe_counters(tiny_c, None)
    assert set(d) == {"moe", "window", "drafter"} and d["drafter"]["tokens_per_step"] == 0.0
