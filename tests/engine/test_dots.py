"""dots3-note-prev's language model through the normal path: the program
against the plain reference (acpbench/families/dots_reference.py, which
imports nothing of the program) for `forward`, prefill, continuation and
decode through the pool of three leaves (latent rows and indexer keys on the
page list, a ring of latent rows a slot), with the choice of rows and
experts free and with it given; absorbed against expanded attention; a
context that crosses `topk` and the window's edge inside one run of decode
steps; the blocked continuation against the plain one; the masked kernel at
unequal key and value widths; the sixteen shares of an expert layer summing
to the uncut layer with the shared expert counted once; every `assumed`
control and every cache control seen by the comparison. The engine serving
it: `test_dots_engine.py`.

CPU, tiny sizes (a dense full layer, an expert full layer, three sliding
layers; 4 heads over a latent of 24, a window of 9 rows at 2 heads over a
latent of 40, an indexer of 4 heads of 16 that chooses 8 rows, 16 experts
top-2 of which 2 held), float32, seeded weights. Budget: this file adds ~60 s
to the tier-1 run (913 s of its 1,470 at PR 60), `test_dots_engine.py` ~30 s,
`test_dots_compile.py` ~60 s, `tests/acpbench/test_dots_spec.py` ~30 s.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acpbench import check, spec
from acpbench.families import dots as family_module
from acpbench.families import dots_reference
from agentcontrolplane_tpu.models import dots, keye, preset
from agentcontrolplane_tpu.ops import attention, paged
from agentcontrolplane_tpu.ops.moe import routed_experts
from agentcontrolplane_tpu.parallel.mesh import make_mesh

FILE = spec.load_json(spec.os.path.join(spec.ROOT, "tests/acpbench/data/tiny-config-dots.json"))
PAGE = FILE["engine"]["page_size"]
ONE_CHIP = lambda: make_mesh({"tp": 1}, devices=jax.devices()[:1])  # noqa: E731


@functools.lru_cache(maxsize=None)
def built(seed=5):
    family = spec.family(FILE)
    pc = dataclasses.replace(family.program_config(FILE), dtype=jnp.float32)
    return family, pc, ONE_CHIP(), family.weights(FILE, pc, ONE_CHIP(), seed)


def sizes():
    return family_module._sizes(FILE)


def text_tokens(B=2, T=40, seed=1):
    tokens = np.random.default_rng(seed).integers(0, 256, (B, T)).astype(np.int32)
    return tokens, np.tile(np.arange(T), (B, 1))


def lanes(B):
    return jnp.arange(B, dtype=jnp.int32), jnp.zeros((B,), jnp.int32)


# -- the program against the plain reference ---------------------------------------------------------


def test_forward_agrees_with_the_plain_reference_free_and_given():
    """Free, both sides choose the same rows and experts (float32: nothing
    for rounding to decide) and the logits agree; given the program's
    choices, the reference gives the same logits again, and given OTHER rows
    (the most recent 8) it does not: `select=` is read, by both."""
    family, pc, mesh, params = built()
    tokens, rows = text_tokens()
    got, (chose, routed) = dots.forward(params, jnp.asarray(tokens), pc, tell=True)
    want = dots_reference.logits(params, sizes(), tokens, rows)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)
    assert chose.shape == (pc.n_full, 2, 40, 5) and routed.shape == (pc.n_layers - pc.first_dense, 2, 40, 2)
    free = dots_reference.choices(params, sizes(), tokens, against=chose)
    assert np.array_equal(free["select"], chose) and np.array_equal(free["route"], routed)
    t = np.arange(tokens.shape[1])
    assert np.array_equal(np.asarray(free["both"])[0, 0], np.minimum(t + 1, pc.index_topk))
    assert float(jnp.max(free["missed_weight"])) == 0.0
    given = dots_reference.logits(params, sizes(), tokens, rows, select=chose, route=routed)
    np.testing.assert_allclose(got, given, atol=5e-5, rtol=5e-5)
    recent = jnp.packbits((t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - pc.index_topk), axis=-1, bitorder="little")
    other = jnp.broadcast_to(recent, chose.shape)
    moved = dots_reference.logits(params, sizes(), tokens, rows, select=other, route=routed)
    assert float(jnp.abs(moved - want).max()) > 0.05
    np.testing.assert_allclose(dots.forward(params, jnp.asarray(tokens), pc, select=other, route=routed), moved,
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("seed", [11, 2**31 + 7], ids=["seed-11", "seed-over-31-bits"])
def test_program_agrees_with_the_plain_reference_through_the_pool_and_the_rings(seed):
    """The family's cache check as every run of the cell makes it: the
    prompt's prefill and 8 decode steps through the pool's leaves and the
    slots' rings, free and telling; the longer prefills given; the reference
    given the same (prompts of 24-56 choose 8 rows of theirs and see 9, page
    8: the rings of 3 pages have wrapped). The choices agree with the free
    float32 reference's to the row."""
    family, pc, mesh, params = built(seed)
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, seed)
    pre, dec, chosen = family.cache_readings(FILE, pc, params, mesh, s, False)
    want = check.reference_logits(functools.partial(family.reference_logits, FILE, params), s)
    numbers = check.compare((pre, dec), want)
    assert numbers["finite"] and numbers["top1_agree"] == 1.0
    assert numbers["prefill_rel_rms"] < 3e-5 and numbers["decode_rel_rms"] < 3e-5, numbers
    assert set(chosen.values()) == {0.0} and "select_cache_miss" in chosen


def paged_setup(pc, B, M):
    cache = dots.init_paged_cache(pc, 1 + B * M, PAGE, max_slots=B)
    tables = (1 + jnp.arange(B * M, dtype=jnp.int32)).reshape(B, M)
    return cache, tables


def prefilled(pc, params, tokens, lengths, M=8, T=40):
    B = tokens.shape[0]
    cache, tables = paged_setup(pc, B, M)
    lengths = jnp.asarray(lengths, jnp.int32)
    ids = jnp.where(jnp.arange(T // PAGE)[None] < -(-lengths // PAGE)[:, None], tables[:, : T // PAGE], 0)
    prompt = jnp.where(jnp.arange(T)[None] < lengths[:, None], jnp.asarray(tokens)[:, :T], 0)
    cache, logits = dots.prefill_paged_batch(params, cache, prompt, lengths, ids, lanes(B), pc)
    return cache, tables, lengths, logits


@pytest.mark.parametrize("interpret", [False, True], ids=["top_k", "kernel"])
def test_decode_steps_cross_topk_and_the_windows_edge_in_one_run_and_the_counters_count(interpret):
    """One decode program either side of `topk` (8 rows) and of the window
    (9 rows): a lane of 5 cached rows walks to 13 beside lanes of 20 and 33,
    every step against `forward` at its own length; the short lane's list is
    padded and masked until it holds 8 rows, and its ring holds fewer rows
    than the window until it has 9; and what the counters count. With the
    choice `jax.lax.top_k`'s (what a CPU serves) and the chip's kernel's,
    interpreted (PR 62): the same numbers, `lanes_tied` among them."""
    family, pc, mesh, params = built()
    tokens, _ = text_tokens(B=3, T=48, seed=2)
    full = dots.forward(params, jnp.asarray(tokens), pc)
    cache, tables, lengths, logits = prefilled(pc, params, tokens, [5, 20, 33])
    np.testing.assert_allclose(logits, full[jnp.arange(3), lengths - 1], atol=5e-5, rtol=5e-5)
    live = []
    step = jax.jit(lambda ca, tok, n: dots.decode_step_paged(params, ca, tok, n, tables, jnp.ones((3,), bool), pc, tell=True,
                                                             interpret=interpret))
    for j in range(8):
        cache, logits, (rows, experts) = step(cache, jnp.asarray(tokens)[jnp.arange(3), lengths + j], lengths + j)
        np.testing.assert_allclose(logits, full[jnp.arange(3), lengths + j], atol=5e-5, rtol=5e-5)
        rows = np.asarray(rows)  # [full-type layers, lanes, topk]
        assert rows.shape == (pc.n_full, 3, pc.index_topk) and experts.shape == (4, 3, 1, 2)
        assert ((rows[:, 0] >= 0).sum(-1) == min(6 + j, 8)).all() and ((rows[:, 1:] >= 0).sum(-1) == 8).all()
        live.append([6 + j, 21 + j, 34 + j])
    got = dots.describe_counters(pc, np.asarray(dots.counters(cache)))
    sparse, window = got["sparse"], got["window"]
    seen = np.asarray(live)
    assert sparse["decode"] == {"steps": 8, "rows_scored": 8 * 3 * 8 * PAGE * 2, "rows_chosen": int(np.minimum(seen, 8).sum()) * 2,
                                "rows_dense": int(seen.sum()) * 2, "lanes_past_topk": int((seen > 8).sum()), "lanes_tied": 0}
    assert window["decode"] == {"steps": 8, "rows_read": int(np.minimum(seen, 9).sum()), "rows_unwindowed": int(seen.sum()),
                                "slots_past_window": int((seen > 9).sum())}
    assert sparse["prefill"]["rows_dense"] == sum(n * (n + 1) // 2 for n in (5, 20, 33)) * 2
    assert (sparse["topk"], sparse["layers"], sparse["row_values"], sparse["ik_row_bytes_stored"]) == (8, 2, 32, 16 * 4)
    assert (window["window"], window["window_layers"], window["full_layers"], window["row_values"]) == (9, 3, 2, 48)
    # the three leaves: the full layers' latent row and key on the page list, the sliding layers' row in rings
    ring = paged.ring_size(pc.window, PAGE)
    assert (pc.window, ring) == (16, 3)
    assert cache["kv"].shape == (2, 25, PAGE, 128) and cache["ik"].shape == (2, 25, PAGE, 16)
    assert cache["wkv"].shape == (3, 4 * ring, PAGE, 128)
    assert not np.asarray(cache["kv"])[..., 32:].any() and not np.asarray(cache["wkv"])[..., 48:].any()  # stored on whole tiles


def test_absorbed_attention_is_the_expanded_attention():
    """A full layer's and a sliding layer's decode step (absorbed: the query
    through `W_UK`, the row as it lies, `W_UV` after the softmax) against the
    same rows expanded to per-head K and V and attended plainly."""
    family, pc, mesh, params = built()
    key = jax.random.key(7)
    for g, w, window in ((pc.full, jax.tree_util.tree_map(lambda a: a[0], params["full"]), 0),
                         (pc.swa, jax.tree_util.tree_map(lambda a: a[0], params["swa"]), 9)):
        S, C = 3, 24
        rows = jax.random.normal(jax.random.fold_in(key, g.n_heads), (S, C, g.row_stored)).at[..., g.row_width:].set(0.0)
        q_nope = jax.random.normal(jax.random.fold_in(key, 1), (S, g.n_heads, g.nope))
        q_pe = jax.random.normal(jax.random.fold_in(key, 2), (S, g.n_heads, g.rope))
        k, v = dots._expand(rows, w["wuk"], w["wuv"], g)
        positions = jnp.full((S, 1), C - 1)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)[:, None]
        key_pos = jnp.broadcast_to(jnp.arange(C), (S, C))
        want = attention.continue_attention(q, k, v, positions, key_pos, window=window)[:, 0]
        q_lat = jnp.einsum("shn,hnc->shc", q_nope, w["wuk"])
        q_row = jnp.concatenate([q_lat, q_pe, jnp.zeros((S, g.n_heads, g.row_stored - g.row_width))], axis=-1)
        # the cached rows as one ring a lane: C - 1 rows in pages of 8, the new token's own as the self term
        ring = (C - 1 + PAGE - 1) // PAGE
        pool = jnp.zeros((S * ring, PAGE, g.row_stored)).reshape(S, ring * PAGE, -1).at[:, : C - 1].set(rows[:, : C - 1])
        pool = pool.reshape(S * ring, PAGE, g.row_stored)
        ids = jnp.arange(S * ring).reshape(S, ring)
        seq_lens = jnp.full((S,), C - 1)
        row_positions = jnp.broadcast_to(jnp.arange(ring * PAGE), (S, ring * PAGE))
        first = jnp.maximum(seq_lens + 1 - window, 0) if window else jnp.zeros((S,), jnp.int32)
        o_lat = paged.ring_latent_decode_attention_cache_plus_new(q_row, pool, ids, seq_lens, rows[:, C - 1], g.kv_rank,
                                                                  g.qk_head_dim, row_positions, first)
        got = jnp.einsum("shc,hcv->shv", o_lat, w["wuv"])
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("interpret", [False, True], ids=["top_k", "kernel"])
def test_the_sparse_latent_step_attends_over_the_given_rows_alone_and_takes_the_new_row_from_its_argument(interpret):
    """`ops.paged.sparse_latent_decode_attention_cache_plus_new` given a list
    of positions (the new token's own among them) against a masked dense
    softmax over the same latent rows; free, its choice is `top_k` of the
    index scores with the new row's score in its place, as a set: found by
    `top_k` off the TPU and by the kernel on it (interpreted here)."""
    rng = np.random.default_rng(3)
    S, H, W, V, M, topk = 2, 3, 128, 96, 4, 6
    C = M * PAGE
    pool = {"kv": jnp.asarray(rng.normal(size=(1 + S * M, PAGE, W)), jnp.float32),
            "ik": jnp.asarray(rng.normal(size=(1 + S * M, PAGE, 16)), jnp.float32)}
    tables = (1 + jnp.arange(S * M, dtype=jnp.int32)).reshape(S, M)
    seq_lens = jnp.asarray([19, 27], jnp.int32)
    new = {"kv": jnp.asarray(rng.normal(size=(S, W)), jnp.float32), "ik": jnp.asarray(rng.normal(size=(S, 16)), jnp.float32)}
    q = jnp.asarray(rng.normal(size=(S, H, W)), jnp.float32)
    qi, wi = jnp.asarray(rng.normal(size=(S, 4, 16)), jnp.float32), jnp.asarray(rng.normal(size=(S, 4)), jnp.float32)
    out, chosen, tied = paged.sparse_latent_decode_attention_cache_plus_new(q, pool, tables, seq_lens, new, qi, wi, topk, V, 64,
                                                                            interpret=interpret)
    assert not np.asarray(tied).any()
    rows = pool["kv"][tables].reshape(S, C, W)
    keys = pool["ik"][tables].reshape(S, C, 16)
    for b in range(S):
        n = int(seq_lens[b])
        ctx = jnp.concatenate([rows[b, :n], new["kv"][b][None]])
        scores = attention.index_scores(qi[b][None, None], wi[b][None, None], jnp.concatenate([keys[b, :n], new["ik"][b][None]])[None])[0, 0]
        want_rows = sorted(np.asarray(jax.lax.top_k(scores, topk)[1]).tolist())
        assert sorted(np.asarray(chosen[b]).tolist()) == want_rows
        seen = np.zeros(n + 1, bool)
        seen[want_rows] = True
        logits = jnp.where(seen[None], q[b] @ ctx.T * 64 ** -0.5, -jnp.inf)
        np.testing.assert_allclose(out[b], jax.nn.softmax(logits, axis=-1) @ ctx[:, :V], atol=2e-5, rtol=2e-5)
    given = jnp.asarray([[0, 5, 19, -1, -1, -1], [27, 3, 2, 1, -1, -1]], jnp.int32)
    out, told, _tied = paged.sparse_latent_decode_attention_cache_plus_new(q, pool, tables, seq_lens, new, qi, wi, topk, V, 64,
                                                                           given, interpret)
    assert np.array_equal(told, given)
    for b in range(S):
        n = int(seq_lens[b])
        ctx = jnp.concatenate([rows[b, :n], new["kv"][b][None]])
        picked = ctx[np.asarray(given[b])[np.asarray(given[b]) >= 0]]
        np.testing.assert_allclose(out[b], jax.nn.softmax(q[b] @ picked.T * 64 ** -0.5, axis=-1) @ picked[:, :V],
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block", [8, 16], ids=["blocks-of-8", "blocks-of-16"])
def test_a_continuation_in_blocks_of_query_rows_is_the_whole_one_and_reads_rows_it_did_not_write(monkeypatch, block):
    """A prompt prefilled to a page-aligned cut and continued (the engine's
    chunks and a resumed request's tail) gives the whole prompt's logits,
    with the continuation's rows taken `CONTINUE_BLOCK` at a time (the mask a
    block at a time, a group of heads expanded and attended a block at a
    time; a sliding layer over its ring and the band of its own keys) and
    with the keys folded `KEY_BLOCK` at a time; the decode step after it
    reads both parts' rows through pages and ring."""
    family, pc, mesh, params = built()
    monkeypatch.setattr(dots, "CONTINUE_BLOCK", block)
    monkeypatch.setattr(keye, "KEY_BLOCK", 32)
    tokens, _ = text_tokens(B=2, T=64, seed=4)
    full = dots.forward(params, jnp.asarray(tokens), pc)
    cut, lengths = jnp.asarray([16, 24], jnp.int32), jnp.asarray([47, 52], jnp.int32)
    cache, tables, _, _ = prefilled(pc, params, tokens, cut, M=8, T=32)
    T = 32
    rest = lengths - cut
    chunk = jnp.stack([jnp.where(jnp.arange(T) < rest[b], jnp.roll(jnp.asarray(tokens)[b], -int(cut[b]))[:T], 0) for b in range(2)])
    ids = jnp.stack([jnp.where(jnp.arange(T // PAGE) < -(-rest[b] // PAGE),
                               jnp.roll(tables[b], -int(cut[b]) // PAGE)[: T // PAGE], 0) for b in range(2)])
    cache, logits = dots.prefill_paged_continue(params, cache, chunk, rest, cut, ids, tables, lanes(2), pc)
    np.testing.assert_allclose(logits, full[jnp.arange(2), lengths - 1], atol=5e-5, rtol=5e-5)
    cache, logits = dots.decode_step_paged(params, cache, jnp.asarray(tokens)[jnp.arange(2), lengths], lengths, tables,
                                           jnp.ones((2,), bool), pc)
    np.testing.assert_allclose(logits, full[jnp.arange(2), lengths], atol=5e-5, rtol=5e-5)


def test_the_masked_kernel_takes_a_key_width_and_a_value_width_and_a_prefill_through_it_is_the_prefill_through_xla():
    """`ops/pallas/masked_attention.py` interpreted at keys of 256 (192
    values and 64 zeros) beside values of 128, the scale the 192's, against
    the masked dense softmax; `serves` states the widths; and the family's
    prefill through the kernel (head groups, keys padded to a lane tile: the
    tiny widths 24 -> 128) is its prefill through `causal_attention`."""
    from agentcontrolplane_tpu.ops.pallas import masked_attention as ma

    rng = np.random.default_rng(5)
    T, H = 512, 2
    q = jnp.asarray(rng.normal(size=(T, H, 192)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(T, H, 192)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(T, H, 128)), jnp.float32)
    t = np.arange(T)
    mask = jnp.asarray((t[None, :] <= t[:, None]) & (rng.random((T, T)) < 0.3) | (t[None, :] == t[:, None]))
    widen = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, 64)))  # noqa: E731
    got = ma.masked_attention(widen(q), widen(k), v, mask.astype(jnp.int8), scale=192 ** -0.5, interpret=True)
    want = attention.causal_attention(q[None], k[None], v[None], keep=mask[None])[0]
    assert got.shape == (T, H, 128)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert ma.serves(512, 256, 128) and ma.serves(512, 128, 128)
    assert not ma.serves(512, 192, 128) and not ma.serves(512, 256, 64) and not ma.serves(300, 256, 128)
    with pytest.raises(ValueError, match="widths of whole lane tiles"):
        ma.masked_attention(q, k, v, mask.astype(jnp.int8), interpret=True)
    family, pc, mesh, params = built()
    tokens, _ = text_tokens(B=1, T=512, seed=6)
    plain, (chose, _routed) = dots.forward(params, jnp.asarray(tokens), pc, tell=True, rows=jnp.asarray([[40, 300, 511]]))
    kernel = dots.forward(params, jnp.asarray(tokens), pc, interpret=True, rows=jnp.asarray([[40, 300, 511]]))
    np.testing.assert_allclose(kernel, plain, atol=1e-4, rtol=1e-4)
    assert chose.shape == (2, 1, 512, 64)


def test_the_sixteen_shares_of_an_expert_layer_and_one_shared_expert_sum_to_the_uncut_layer():
    """A layer's FF summed over sixteen chips' routed shares (each told which
    16 of 256 it holds, each routing over all 256 by the sigmoid and the
    bias, top 8 renormalised) plus the shared expert ONCE is the uncut
    reference's layer; nothing stands in for the absent chips in a share."""
    N, D, F, E, k = 24, 64, 32, 256, 8
    keys = jax.random.split(jax.random.key(3), 9)
    x = jax.random.normal(keys[0], (N, D))
    layer = {"router": jax.random.normal(keys[1], (D, E)) * D ** -0.5,
             "router_bias": 0.03 * jax.random.normal(keys[2], (E,)),
             "w1": jax.random.normal(keys[3], (E, D, F)) * D ** -0.5,
             "w3": jax.random.normal(keys[4], (E, D, F)) * D ** -0.5,
             "w2": jax.random.normal(keys[5], (E, F, D)) * F ** -0.5,
             "sw1": jax.random.normal(keys[6], (D, F)) * D ** -0.5, "sw3": jax.random.normal(keys[7], (D, F)) * D ** -0.5,
             "sw2": jax.random.normal(keys[8], (F, D)) * F ** -0.5}
    model = {"experts_per_token": k, "held": tuple(range(E)), "norm_topk_prob": True, "routed_scaling_factor": 1.0}
    whole = dots_reference._experts(x[None], layer, model, None)[0][0]
    shared = dots_reference._experts(x[None], layer, model, None, routed=False)[0][0]
    total, landed = shared, 0
    for share in range(16):
        held = tuple(range(16 * share, 16 * share + 16))
        ids = np.array(held)
        y, counts = routed_experts(x, layer["router"], layer["w1"][ids], layer["w3"][ids], layer["w2"][ids], k, held=held,
                                   score="sigmoid", bias=layer["router_bias"], renormalize=True, interpret=share % 8 == 0)
        total, landed = total + y, landed + int(counts[1])
        assert float(jnp.abs(y + shared - whole).max()) > 0.01  # a share is not the layer
    assert landed == N * k  # every (token, choice) pair landed on exactly one share
    np.testing.assert_allclose(total, whole, atol=5e-5)
    # and through the program's own expert layer: one chip's share is its routed part plus the shared expert
    family, pc, mesh, params = built()
    h = jax.random.normal(keys[0], (1, 12, pc.dim))
    e = 1
    mine = jax.tree_util.tree_map(lambda a: a[e], {n: params["ff"][n] for n in ("ln2", "router", "router_bias", "sw1", "sw3", "sw2")})
    stacks = tuple(params["ff"][n].reshape((-1,) + params["ff"][n].shape[2:]) for n in ("w1", "w3", "w2"))
    y, _counts = dots._experts(h, mine, stacks, jnp.int32(e), pc, jnp.ones((1, 12), bool))
    np.testing.assert_allclose(y, dots_reference.layer_output(params, sizes(), e, h), atol=5e-5, rtol=5e-5)


# -- the controls ----------------------------------------------------------------------------------


@pytest.mark.parametrize("control,least", [
    ("int8", 5e-3), ("gate_off", 0.1), ("rescale_off", 0.1), ("index_norm_off", 0.02), ("index_rope_off", 0.02),
    ("recent", 0.05), ("dense", 0.05), ("window_off", 0.05), ("shared_off", 0.1), ("bf16_free", 1e-4),
])
def test_each_reference_control_moves_the_logits(control, least):
    family, pc, mesh, params = built()
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, 3)
    want = dots_reference.logits(params, sizes(), s["tokens"], s["rows"])
    moved = check.compare(family.reference_logits(FILE, params, s["tokens"], s["rows"], lower=control), want)
    assert moved["logit_rel_rms"] > least, (control, moved["logit_rel_rms"])


def test_an_unknown_control_is_an_error_and_the_family_documents_its_own():
    family, pc, mesh, params = built()
    with pytest.raises(ValueError, match="no control 'fp4'"):
        family.reference_logits(FILE, params, [[0]], [[0]], lower="fp4")
    for name in dots_reference.CONTROLS:
        assert f'"{name}"' in family_module.__doc__ + dots_reference.__doc__, name
    big, tiny = preset("dots3-note-prev"), preset("dots-tiny")
    assert (big.index_topk, big.sliding_window_size, big.window, big.n_full, big.n_sliding) == (2048, 513, 528, 13, 33)
    assert (big.full.row_width, big.full.row_stored, big.swa.row_width, big.swa.row_stored) == (576, 640, 1088, 1152)
    assert (round(big.full.a_q, 2), round(big.full.a_kv, 2), round(big.swa.a_kv, 2)) == (2.24, 3.16, 2.24)
    assert (tiny.index_topk, tiny.sliding_window_size, tiny.n_full, tiny.n_sliding) == (8, 9, 2, 3)
    with pytest.raises(ValueError, match="leading dense layers are full_attention"):
        dots.layer_kinds(dataclasses.replace(tiny, layer_types=("sliding_attention",) + tiny.layer_types[1:]))


@functools.lru_cache(maxsize=None)
def cache_reading(seed, **control):
    """The cache check's numbers under a control of the cache (none: the sound program's, read once a seed)."""
    family, pc, mesh, params = built()
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, seed)
    pre, dec, chosen = family.cache_readings(FILE, pc, params, mesh, s, False, **control)
    want = family.reference_logits(FILE, params, s["tokens"], s["rows"])  # given what that program chose
    return {**check.compare((pre, dec), want), **chosen}


@pytest.mark.parametrize("control,number,least,seed", [
    # keys of 16 values rounded to int8 move one choice in two hundred here, and the sample of seed 3 holds none
    ({"ik_int8": True}, "select_cache_miss_all", 1e-3, 4), ({"kv_int8": True}, "cache_excess", 0.5, 3),
    ({"wkv_int8": True}, "cache_excess", 0.5, 3), ({"ring_short": 1}, "cache_excess", 0.5, 3),
    ({"ik_crossed": True}, "select_cache_miss", 0.3, 3)])
def test_each_cache_control_is_seen(control, number, least, seed):
    """int8 indexer keys and another request's keys choose other rows in the
    decode steps (the logits, compared with the choice given, do not see it:
    the choices' own numbers do); int8 latent rows, of the page list or of
    the rings, and a ring one row short show in the cache's excess."""
    sound, seen = cache_reading(seed), cache_reading(seed, **control)
    assert seen[number] > least > abs(sound[number]), (sound[number], seen[number])


def test_past_a_limit_on_the_choices_the_decode_logits_are_not_numbers(capsys):
    family, pc, mesh, params = built()
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, 4)
    strict = dict(FILE, check=dict(FILE["check"], select_limits={"select_cache_miss_all": 0.001}))
    _pre, dec = family.cached_logits(strict, pc, params, mesh, s, False, ik_int8=True)
    assert not np.isfinite(np.asarray(dec)).any() and "select_cache_miss_all=" in capsys.readouterr().out
    _pre, dec = family.cached_logits(FILE, pc, params, mesh, s, False)
    assert np.isfinite(np.asarray(dec)).all()


@pytest.mark.parametrize("fault,number", [
    ("recent", "select_miss_prefill"), ("topk_half", "missed_weight"), ("index_rope_off", "select_miss_decode"),
    ("index_norm_off", "select_miss_decode")])
def test_a_fault_planted_in_the_programs_indexer_is_refused_by_the_choices_numbers(fault, number, capsys):
    """The program traced with one of the reference's faults of the choice
    in its indexer (`dots._planted`): the number named lies past its limit,
    `cached_logits` hands back decode logits that are not numbers, and the
    program's modules are their own again after."""
    family, pc, mesh, params = built()
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, 3)
    before = (dots._rope_first, dots._layer_norm, keye.topk_rows_mask, attention.topk_rows, attention.topk_rows_mask)
    pre, dec = family.cached_logits(FILE, pc, params, mesh, s, False, indexer=fault)
    assert before == (dots._rope_first, dots._layer_norm, keye.topk_rows_mask, attention.topk_rows, attention.topk_rows_mask)
    assert not np.isfinite(np.asarray(dec)).any() and np.isfinite(np.asarray(pre)).all()
    assert f"[check] {number}=" in (out := capsys.readouterr().out)
    line = next(ln for ln in out.splitlines() if ln.startswith(f"[check] {number}="))
    assert line.endswith("EXCEEDED"), out
    with pytest.raises(ValueError, match="no fault 'rope_twice'"):
        family.cache_readings(FILE, pc, None, None, {}, False, indexer="rope_twice")
