"""Keye-VL-2.0's language model through the normal path: the program against
the plain reference (acpbench/families/keyevl_reference.py, which imports
nothing of the program) for `forward`, prefill, continuation over cached `kv` and `ik`
rows and decode through pages past `topk` (one gather of rows of K|V words, bit
for bit the two-leaf walk of PR 59), with the choice of rows and
experts free and with it given; a lane under `topk` beside one over it in one
step; three-axis rope on a grid span against the reference, and equal rows
equal to the one-position rope bit for bit; the threshold selection against
`jax.lax.top_k` with planted ties; the blocked attention's `keep` mask; the
eight shares of an expert layer summing to the uncut layer. The engine
serving it: `test_keye_engine.py`.

CPU, tiny sizes (3 layers, 4 query heads over 2 KV heads of 16, an indexer of
4 heads of 8 that chooses 8 rows, 16 experts top-2 of which 2 held), float32,
seeded weights. Budget: this file adds ~35 s to the tier-1 run (755 s of its
1,470 at PR 57), `test_keye_engine.py` ~25 s, `tests/acpbench/test_keyevl_spec.py` ~25 s.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acpbench import check, spec
from acpbench.families import keyevl as family_module
from acpbench.families import keyevl_reference
from agentcontrolplane_tpu.models import keye, preset
from agentcontrolplane_tpu.ops import attention, paged
from agentcontrolplane_tpu.ops.moe import routed_experts
from agentcontrolplane_tpu.ops.rope import apply_rope
from agentcontrolplane_tpu.parallel.mesh import make_mesh

FILE = spec.load_json(spec.os.path.join(spec.ROOT, "tests/acpbench/data/tiny-config-keye.json"))
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


# -- the program against the plain reference ---------------------------------------------------------


def test_forward_agrees_with_the_plain_reference_free_and_given():
    """Free, both sides choose the same rows and experts (float32: nothing
    for rounding to decide) and the logits agree; given the program's
    choices, the reference gives the same logits again, and given OTHER rows
    (the most recent 8) it does not: `select=` is read."""
    family, pc, mesh, params = built()
    tokens, rows = text_tokens()
    got, (chose, routed) = keye.forward(params, jnp.asarray(tokens), pc, tell=True)
    want = keyevl_reference.logits(params, sizes(), tokens, rows)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)
    free = keyevl_reference.choices(params, sizes(), tokens, against=chose)
    assert np.array_equal(free["select"], chose) and np.array_equal(free["route"], routed)
    t = np.arange(tokens.shape[1])
    assert np.array_equal(np.asarray(free["both"])[0, 0], np.minimum(t + 1, pc.index_topk))
    assert float(jnp.max(free["missed_weight"])) == 0.0
    given = keyevl_reference.logits(params, sizes(), tokens, rows, select=chose, route=routed)
    np.testing.assert_allclose(got, given, atol=3e-5, rtol=3e-5)
    recent = jnp.packbits((t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - pc.index_topk), axis=-1, bitorder="little")
    other = jnp.broadcast_to(recent, chose.shape)
    moved = keyevl_reference.logits(params, sizes(), tokens, rows, select=other, route=routed)
    assert float(jnp.abs(moved - want).max()) > 0.05
    # and the program given those rows follows them too
    np.testing.assert_allclose(keye.forward(params, jnp.asarray(tokens), pc, select=other, route=routed), moved,
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("seed", [11, 2**31 + 7], ids=["seed-11", "seed-over-31-bits"])
def test_program_agrees_with_the_plain_reference_through_the_pool(seed):
    """The family's cache check as every run of the cell makes it: the
    prompt's prefill and 8 decode steps through the pool's two leaves, free
    and telling; the longer prefills given; the reference given the same
    (prompts of 24-56 choose 8 rows of theirs, page 8). The choices agree
    with the free float32 reference's to the row."""
    family, pc, mesh, params = built(seed)
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, seed)
    pre, dec, chosen = family.cache_readings(FILE, pc, params, mesh, s, False)
    want = check.reference_logits(functools.partial(family.reference_logits, FILE, params), s)
    numbers = check.compare((pre, dec), want)
    assert numbers["finite"] and numbers["top1_agree"] == 1.0
    assert numbers["prefill_rel_rms"] < 2e-5 and numbers["decode_rel_rms"] < 2e-5, numbers
    assert set(chosen.values()) == {0.0} and "select_cache_miss" in chosen


def paged_setup(pc, B, M):
    cache = keye.init_paged_cache(pc, 1 + B * M, PAGE)
    tables = (1 + jnp.arange(B * M, dtype=jnp.int32)).reshape(B, M)
    return cache, tables


@pytest.mark.parametrize("interpret", [False, True], ids=["top_k", "kernel"])
def test_a_lane_under_topk_beside_one_over_it_in_one_step_and_the_counters(interpret):
    """One decode program either side of `topk`: a lane of 5 cached rows
    (all chosen, its list padded and masked) beside lanes of 20 and 33, each
    against `forward` at its own length; and what the counters count. With
    the choice `jax.lax.top_k`'s (what a CPU serves) and the chip's kernel's,
    interpreted (PR 62): the same logits and the same numbers. `lanes_tied`
    is 1: at this model's two indexer heads a quarter of a lane's rows score
    exactly 0 (ReLU), and one layer's eighth row of lane 1 is among them."""
    family, pc, mesh, params = built()
    tokens, _ = text_tokens(B=3, T=40, seed=2)
    full = keye.forward(params, jnp.asarray(tokens), pc)
    lengths = jnp.asarray([5, 20, 33], jnp.int32)
    cache, tables = paged_setup(pc, 3, 6)
    T = 40
    ids = jnp.where(jnp.arange(T // PAGE)[None] < -(-lengths // PAGE)[:, None], tables[:, : T // PAGE], 0)
    prompt = jnp.where(jnp.arange(T)[None] < lengths[:, None], tokens, 0)
    cache, logits = keye.prefill_paged_batch(params, cache, prompt, lengths, ids, pc)
    np.testing.assert_allclose(logits, full[jnp.arange(3), lengths - 1], atol=3e-5, rtol=3e-5)
    cache, logits, (rows, _experts) = keye.decode_step_paged(
        params, cache, jnp.asarray(tokens)[jnp.arange(3), lengths], lengths, tables, jnp.ones((3,), bool), pc, tell=True,
        interpret=interpret)
    np.testing.assert_allclose(logits, full[jnp.arange(3), lengths], atol=3e-5, rtol=3e-5)
    rows = np.asarray(rows)  # [layers, lanes, topk]
    assert sorted(rows[0, 0][rows[0, 0] >= 0]) == list(range(6)) and (rows[:, 0] >= 0).sum() == 6 * pc.n_layers
    assert ((rows[:, 1:] >= 0).sum(-1) == pc.index_topk).all()
    got = keye.describe_counters(pc, np.asarray(keye.counters(cache)))["sparse"]
    assert got["decode"] == {"steps": 1, "rows_scored": 3 * 6 * PAGE * 3, "rows_chosen": (6 + 8 + 8) * 3,
                             "rows_dense": (6 + 21 + 34) * 3, "lanes_past_topk": 2, "lanes_tied": 1}
    assert got["prefill"]["rows_dense"] == sum(n * (n + 1) // 2 for n in (5, 20, 33)) * 3
    assert (got["topk"], got["ik_row_bytes_stored"], got["layers"]) == (8, 128 * 4, 3)
    # the two leaves: a token's K row and V row (each its heads side by side) as ONE row of 32-bit words (float32
    # here: two words a value pair), the indexer's key on a whole lane tile, the rest zeros
    assert {n: (a.shape[2:], a.dtype) for n, a in paged.pool_leaves(cache).items()} == {
        "kv": ((PAGE, 64), jnp.uint32), "ik": ((PAGE, 128), jnp.float32)}
    ik = np.asarray(cache["ik"])
    assert np.abs(ik[:, 1, :, :8]).min() > 0 and not ik[..., 8:].any()


def two_leaf_walk(q, pool, block_tables, seq_lens, new, qi, wi, topk, given=None, interpret=False):
    """PR 59's `ops.paged.sparse_decode_attention_reference_cache_plus_new`,
    kept here as the double: K and V in two leaves, `fetch("k")` and
    `fetch("v")`, two gathers by the same row ids, the choice `jax.lax.top_k`'s
    (it counts no tied lane: PR 62's third result is all False)."""
    S, H, d = q.shape
    P = pool["k"].shape[1]
    C = block_tables.shape[1] * P
    pos = jnp.arange(C, dtype=jnp.int32)
    if given is None:
        rows = pool["ik"][block_tables].reshape(S, C, -1)
        cached = attention.index_scores(qi[:, None], wi[:, None], rows)[:, 0]
        own = attention.index_scores(qi[:, None], wi[:, None], new["ik"][:, None])[:, 0]
        scores = jnp.where(pos[None] == seq_lens[:, None], own, cached)
        chosen_pos, chosen = attention.topk_rows(scores, pos[None] <= seq_lens[:, None], topk)
    else:
        chosen_pos, chosen = jnp.maximum(given, 0), given >= 0
    hit = (chosen_pos // P)[:, :, None] == jnp.arange(block_tables.shape[1], dtype=chosen_pos.dtype)
    page = jnp.sum(jnp.where(hit, block_tables[:, None, :], 0), axis=-1)
    flat_row = page * P + chosen_pos % P
    is_new = (chosen_pos == seq_lens[:, None]) & chosen

    def fetch(name):
        leaf = pool[name]
        got = leaf.reshape((leaf.shape[0] * P,) + leaf.shape[2:])[flat_row]
        got = jnp.where(is_new[..., None], new[name].reshape(S, 1, -1).astype(got.dtype), got)
        return got.reshape(S, got.shape[1], -1, d)

    with jax.named_scope("sparse_walk"):
        k, v = fetch("k"), fetch("v")
    H_kv = k.shape[2]
    q4 = q.reshape(S, H_kv, H // H_kv, d)
    logits = jnp.einsum("skrd,snkd->skrn", q4, k, preferred_element_type=jnp.float32) * (d ** -0.5)
    logits = jnp.where(chosen[:, None, None, :], logits, paged.NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("skrn,snkd->skrd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return out.reshape(S, H, d).astype(q.dtype), jnp.where(chosen, chosen_pos, -1), jnp.zeros((S,), bool)


def through_two_leaves(q, pool, block_tables, seq_lens, new, qi, wi, topk, given=None, interpret=False):
    """The one-leaf pool handed to the double: the `kv` leaf and the new
    token's row taken apart, the K rows one leaf and the V rows another."""
    (k, v), (new_k, new_v) = paged.unpack_kv_rows(pool["kv"], q.dtype), paged.unpack_kv_rows(new["kv"], q.dtype)
    heads = lambda t: t.reshape(t.shape[0], -1, q.shape[-1])  # noqa: E731
    return two_leaf_walk(q, {"k": k, "v": v, "ik": pool["ik"]}, block_tables, seq_lens,
                         {"k": heads(new_k), "v": heads(new_v), "ik": new["ik"]}, qi, wi, topk, given)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, jnp.float16], ids=["bfloat16", "float32", "float16"])
def test_a_packed_row_gives_back_the_k_and_v_rows_bit_for_bit(dtype):
    """`pack_kv_rows`: 16 bits of K low and of V high in every word, a row
    of 2 KiB at 4 KV heads of 128 in bfloat16; any bit pattern comes back as
    it went in (NaNs, infinities and denormals among them: the words are
    made from bits, not values), under `jit` as outside it."""
    bits = jnp.uint16 if jnp.dtype(dtype).itemsize == 2 else jnp.uint32
    k, v = (jax.lax.bitcast_convert_type(jax.random.bits(jax.random.key(i), (3, 5, 512), bits), dtype) for i in (0, 1))
    for fn in (lambda f: f, jax.jit):
        words = fn(paged.pack_kv_rows)(k, v)
        assert words.dtype == jnp.uint32 and words.shape == (3, 5, 512 * jnp.dtype(dtype).itemsize // 2)
        back = fn(lambda w: paged.unpack_kv_rows(w, dtype))(words)
        for got, want in zip(back, (k, v)):
            assert got.dtype == want.dtype and got.shape == want.shape
            assert np.array_equal(jax.lax.bitcast_convert_type(got, bits), jax.lax.bitcast_convert_type(want, bits))
    if dtype == jnp.bfloat16:  # K is the low half: a word of K alone reads as K's bits
        assert np.array_equal(paged.pack_kv_rows(k, jnp.zeros_like(v)), jax.lax.bitcast_convert_type(k, jnp.uint16))


def prefilled_lanes(pc, params, dtype):
    """Lanes of 5, 20 and 33 cached rows (one under `topk`, two over it) after their prefill, in `dtype`."""
    pc = dataclasses.replace(pc, dtype=dtype)
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    tokens, _ = text_tokens(B=3, T=40, seed=2)
    lengths = jnp.asarray([5, 20, 33], jnp.int32)
    cache, tables = paged_setup(pc, 3, 6)
    ids = jnp.where(jnp.arange(40 // PAGE)[None] < -(-lengths // PAGE)[:, None], tables[:, : 40 // PAGE], 0)
    prompt = jnp.where(jnp.arange(40)[None] < lengths[:, None], tokens, 0)
    cache, _ = keye.prefill_paged_batch(params, cache, prompt, lengths, ids, pc)
    return pc, params, cache, tables, jnp.asarray(tokens)[jnp.arange(3), lengths], lengths


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("given", [False, True], ids=["free", "given"])
def test_a_decode_step_through_one_leaf_is_the_two_leaf_walk_bit_for_bit(monkeypatch, given, dtype):
    """The same step, the same pool, with the walk this family had at PR 59
    in the place of its own (`through_two_leaves`): the logits, the positions
    chosen and the pool after the commit are equal bit for bit, for a lane
    under `topk` beside two over it, with the choice free and with one handed
    in (`select=`: each lane's latest rows, another choice than its own)."""
    family, pc, mesh, params = built()
    pc, params, cache, tables, last, lengths = prefilled_lanes(pc, params, dtype)
    select = None
    if given:
        latest = lengths[:, None] - jnp.arange(pc.index_topk)[None]  # a lane's latest 8 of its rows and the new one
        select = jnp.broadcast_to(jnp.where(latest >= 0, latest, -1), (pc.n_layers, 3, pc.index_topk)).astype(jnp.int32)

    def step():
        return keye.decode_step_paged(params, dict(cache), last, lengths, tables, jnp.ones((3,), bool), pc,
                                      select=select, tell=True)

    one, logits, (rows, _experts) = step()
    monkeypatch.setattr(keye, "sparse_decode_attention_reference_cache_plus_new", through_two_leaves)
    two, want, (want_rows, _experts) = step()
    as_bits = lambda a: np.asarray(a if a.dtype == jnp.uint32 else a.astype(jnp.float32))  # noqa: E731
    assert np.array_equal(as_bits(logits), as_bits(want)) and np.isfinite(as_bits(logits)).all()
    # the positions chosen are a SET (PR 62: the chip's kernel hands them by position, `top_k` by score): sorted
    assert np.array_equal(np.sort(rows, -1), np.sort(want_rows, -1)) and (np.asarray(rows)[:, 0] >= 0).sum() == 6 * pc.n_layers
    assert all(np.array_equal(as_bits(one[name]), as_bits(two[name])) for name in ("kv", "ik"))
    if given:  # the rows handed in are the rows told, and another choice than the free one: `select=` is read
        assert np.array_equal(rows, select)
        assert not np.array_equal(rows, keye.decode_step_paged(params, dict(cache), last, lengths, tables,
                                                               jnp.ones((3,), bool), pc, tell=True)[2][0])


def equations(jaxpr, stack=""):
    """Every equation of a jaxpr and of the jaxprs its equations hold, each with the scopes it was traced under."""
    for eqn in jaxpr.eqns:
        under = f"{stack}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, under
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub, under)


def test_the_decode_program_holds_one_gather_under_sparse_walk():
    """Counted by primitive in the decode step's jaxpr: ONE gather in the
    scope `sparse_walk` (the chosen K|V rows) and one in `index_scores` (the
    lane's `ik` pages); the double's walk, in its place, holds two. A later
    edit that splits the row's fetch again is seen here, at no chip time."""
    family, pc, mesh, params = built()
    pc, params, cache, tables, last, lengths = prefilled_lanes(pc, params, jnp.float32)

    def gathers(walk):
        with pytest.MonkeyPatch.context() as mp:
            if walk is not None:
                mp.setattr(keye, "sparse_decode_attention_reference_cache_plus_new", walk)
            traced = jax.make_jaxpr(lambda p, ca: keye.decode_step_paged(p, ca, last, lengths, tables, jnp.ones((3,), bool), pc))(
                params, cache)
        found = [under for name, under in equations(traced.jaxpr) if name == "gather"]
        return {leaf: sum(leaf in under for under in found) for leaf in ("sparse_walk", "index_scores")}

    assert gathers(None) == {"sparse_walk": 1, "index_scores": 1}
    assert gathers(through_two_leaves)["sparse_walk"] == 2


def test_the_cells_page_costs_what_it_cost_in_two_leaves_fewer():
    """`models.page_bytes` of the cell's file: 2,304 B a token and layer
    (K and V 2 x 4 x 128 x 2 B as one row of 512 words, the indexer's key 256
    B) x 16 rows x 8 layers, what the three leaves of PR 59 cost; the pool is
    two leaves."""
    from agentcontrolplane_tpu import models

    conf = next(c for c in spec.benchmark()["configs"] if c["name"] == "keye-vl2-30b-a3b-bf16-v5e1-ep8")
    file = spec.load_json(spec.os.path.join(spec.ROOT, conf["file"]))
    program = spec.family(file).program_config(file)
    assert models.page_bytes(program, 16) == 2304 * 16 * 8
    shapes = jax.eval_shape(lambda: keye.init_paged_cache(program, 9, 16))
    assert {n: (a.shape, a.dtype) for n, a in paged.pool_leaves(shapes).items()} == {
        "kv": ((8, 9, 16, 512), jnp.uint32), "ik": ((8, 9, 16, 128), jnp.bfloat16)}
    assert paged.page_bytes(shapes, 9) == 2304 * 16 * 8


def test_a_continuation_reads_the_kv_and_ik_rows_it_did_not_write():
    """16 tokens whole, the rest continued over the gathered `kv` pages
    (taken apart into K and V) and `ik` rows: the logits of `forward`; with
    the cached `ik` rows zeroed the choice, and so the logits, change; with
    the K half of the cached `kv` words zeroed, or the V half, or the halves
    the other way round, the logits change: each half is read as what it was
    written as. The words the prefill wrote come apart into the K and the V
    of those tokens as written out here."""
    family, pc, mesh, params = built()
    tokens, _ = text_tokens(B=2, T=40, seed=3)
    full = keye.forward(params, jnp.asarray(tokens), pc)
    lengths = jnp.asarray([38, 27], jnp.int32)
    cache, tables = paged_setup(pc, 2, 6)
    first = jnp.full((2,), 16, jnp.int32)
    cache, _ = keye.prefill_paged_batch(params, cache, jnp.asarray(tokens[:, :16]), first, tables[:, :2], pc)
    rest = lengths - 16
    tail = jnp.where(jnp.arange(24)[None] < rest[:, None], tokens[:, 16:40], 0)
    ids = jnp.where(jnp.arange(3)[None] < -(-rest // PAGE)[:, None], tables[:, 2:5], 0)
    _cache, logits = keye.prefill_paged_continue(params, dict(cache), tail, rest, first, ids, tables, pc)
    np.testing.assert_allclose(logits, full[jnp.arange(2), lengths - 1], atol=3e-5, rtol=3e-5)
    kv = cache["kv"]
    for name, moved in (("ik", jnp.zeros_like(cache["ik"])), ("kv", (kv >> 16) << 16), ("kv", kv & 0xFFFF),
                        ("kv", (kv >> 16) | (kv << 16))):
        _cache, other = keye.prefill_paged_continue(params, {**cache, name: moved}, tail, rest, first, ids, tables, pc)
        assert float(jnp.abs(other - logits).max()) > 0.05
    # the first layer's rows as written: K (normed and roped) then V of the token's normed embedding
    w = jax.tree.map(lambda a: a[0], params["attn"])
    h = keye.rms_norm(params["embed"][jnp.asarray(tokens[:, :16])], w["ln1"], pc.norm_eps)
    k = keye.rms_norm((h @ w["wk"]).reshape(2, 16, pc.n_kv_heads, pc.head_dim), w["k_norm"], pc.norm_eps)
    k = apply_rope(k, jnp.broadcast_to(jnp.arange(16), (2, 16)), pc.rope_theta)
    wrote_k, wrote_v = paged.unpack_kv_rows(kv[0][tables[:, :2]].reshape(2, 16, -1), jnp.float32)  # [B, T, heads merged]
    np.testing.assert_allclose(wrote_k, k.reshape(2, 16, -1), atol=1e-5)
    np.testing.assert_allclose(wrote_v, h @ w["wv"], atol=1e-5)


@pytest.mark.parametrize("tier, block", [(16, 8), (48, 8), (24, 24), (20, 8)])
def test_a_prefill_in_tiers_of_blocks_of_query_rows_is_the_one_block_prefill(monkeypatch, tier, block):
    """With tiers of `MASK_TIER` rows in blocks of `MASK_BLOCK` the mask is
    made a block at a time against its tier's keys (three tiers of two
    blocks, one of six, two of one; a tier the 48 rows are not whole
    multiples of falls back to one), the tier wholly under `topk` keeps
    every causal key, and the logits and the packed choices are those of the
    one-block path."""
    family, pc, mesh, params = built()
    tokens, _ = text_tokens(T=48)
    want, (chose, _) = keye.forward(params, jnp.asarray(tokens), pc, tell=True)
    monkeypatch.setattr(keye, "MASK_TIER", tier)
    monkeypatch.setattr(keye, "MASK_BLOCK", block)
    got, (blocked, _) = keye.forward(params, jnp.asarray(tokens), pc, tell=True)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)
    assert np.array_equal(blocked, chose)


def test_a_buckets_mask_is_one_traced_body_a_tier_whatever_its_blocks(monkeypatch):
    """The mask of a whole prompt is a `lax.map` a tier: the index scores
    and the threshold search are in the program once a tier (three at 48
    rows in tiers of 16), not once a block of query rows (six), and a single
    tier over the whole width holds them once."""
    family, pc, mesh, params = built()
    tokens, _ = text_tokens(T=48)

    def searches(tier, block):
        monkeypatch.setattr(keye, "MASK_TIER", tier)
        monkeypatch.setattr(keye, "MASK_BLOCK", block)
        return str(jax.make_jaxpr(lambda p, t: keye.forward(p, t, pc))(params, jnp.asarray(tokens))).count("cond[")

    assert searches(48, 8) == 1 and searches(16, 8) == 3 and searches(16, 16) == 3


def test_causal_attention_with_keep_is_the_masked_softmax_and_the_blocked_attention_is_the_parents():
    """`causal_attention(keep=)` is the softmax over the kept causal keys,
    written out here; without `keep` its jaxpr is what it was; the blocked
    attention every other family's prefill calls takes no such argument."""
    import inspect

    B, T, H, Hkv, d = 2, 64, 4, 2, 16
    ks = jax.random.split(jax.random.key(0), 4)
    q, k, v = (jax.random.normal(ks[i], (B, T, h, d)) for i, h in enumerate((H, Hkv, Hkv)))
    mask = jax.random.bernoulli(ks[3], 0.5, (B, T, T)) | jnp.eye(T, dtype=bool)[None]
    got = attention.causal_attention(q, k, v, keep=mask)
    seen = mask & jnp.tril(jnp.ones((T, T), bool))[None]
    kk, vv = jnp.repeat(k, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, kk) * d ** -0.5
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1), vv)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert float(jnp.abs(got - attention.causal_attention(q, k, v)).max()) > 0.1
    plain = jax.make_jaxpr(lambda q, k, v: attention.causal_attention(q, k, v))(q, k, v)
    none = jax.make_jaxpr(lambda q, k, v: attention.causal_attention(q, k, v, keep=None))(q, k, v)
    assert str(plain) == str(none)
    assert "keep" not in inspect.signature(attention.blocked_causal_attention).parameters


def test_without_the_new_arguments_the_shared_ops_trace_to_the_parents_jaxprs():
    """`apply_rope` without `sections=`, `causal_attention` without `keep=`
    and `init_kv_pages` (which has no argument of this family's: its pool is
    `init_row_pages`') are what every other family calls: each traces to the
    jaxpr the function gave before the family came, by a digest of its text
    taken on that tree (PR 57's, jax 0.9.0); the latent family's pool is the
    one leaf it was."""
    import hashlib
    import inspect

    from agentcontrolplane_tpu.ops import rope

    x, pos = jnp.zeros((2, 24, 4, 16)), jnp.zeros((2, 24), jnp.int32)
    k = jnp.zeros((2, 24, 2, 16))
    traced = {
        "rope": jax.make_jaxpr(lambda x, p: rope.apply_rope(x, p, 1e6))(x, pos),
        "rope_llama3": jax.make_jaxpr(lambda x, p: rope.apply_rope(x, p, 5e5, scaling=(8.0, 1.0, 4.0, 8192)))(x, pos),
        "rope_yarn": jax.make_jaxpr(lambda x, p: rope.apply_rope(x, p, 1e6, yarn=(4.0, 4096, 32.0, 1.0, 1.2)))(x, pos),
        "causal": jax.make_jaxpr(lambda q, k, v: attention.causal_attention(q, k, v))(x, k, k),
        "causal_positions_window": jax.make_jaxpr(
            lambda q, k, v, p: attention.causal_attention(q, k, v, p, window=8))(x, k, k, pos),
        "kv_pages": jax.make_jaxpr(lambda: paged.init_kv_pages(2, 9, 16, 2, 16, jnp.bfloat16))(),
        "kv_pages_int8": jax.make_jaxpr(lambda: paged.init_kv_pages(2, 9, 16, 2, 16, jnp.bfloat16, quantize=True))(),
    }
    parents = {"rope": "4f8046572f147687", "rope_llama3": "d1149da6d9d7acac", "rope_yarn": "7aa3444606ae4881",
               "causal": "a14a73cf6b14e240", "causal_positions_window": "91cf105253f8c67b",
               "kv_pages": "1619fe8929d5a94e", "kv_pages_int8": "c1b42e2616f15e37"}
    assert {name: hashlib.sha256(str(j).encode()).hexdigest()[:16] for name, j in traced.items()} == parents
    assert "index_width" not in inspect.signature(paged.init_kv_pages).parameters
    latent = jax.eval_shape(lambda: paged.init_latent_pages(2, 9, 16, 640, jnp.bfloat16))
    assert {n: (a.shape, a.dtype) for n, a in latent.items()} == {"kv": ((2, 9, 16, 640), jnp.bfloat16)}


# -- the selection ---------------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 7, 16, 300])
def test_the_threshold_selection_is_lax_top_k_with_planted_ties(k):
    """Scores on a grid of quarter steps, so that the k-th largest is shared
    by several columns in most rows; rows with all, some, one and none of
    their columns valid. The mask (`topk_rows_mask`: threshold passes, no
    sort) and the list (`topk_rows`) choose the set `jax.lax.top_k` chooses:
    equal scores to the earlier column."""
    scores = jnp.round(jax.random.normal(jax.random.key(k), (7, 200)) * 4) / 4
    valid = jnp.arange(200)[None] < jnp.asarray([200, 150, 20, 9, 1, 0, 200])[:, None]
    valid = valid.at[6].set(jax.random.bernoulli(jax.random.key(1), 0.5, (200,)))
    mask = np.asarray(attention.topk_rows_mask(scores, valid, k))
    columns, chosen = (np.asarray(a) for a in attention.topk_rows(scores, valid, k))
    values, want = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), min(k, 200))
    for r in range(7):
        ref = set(np.asarray(want[r])[np.asarray(values[r]) > -np.inf].tolist())
        assert set(np.nonzero(mask[r])[0].tolist()) == ref, r
        assert set(columns[r][chosen[r]].tolist()) == ref and chosen[r].sum() == len(ref), r
    tied = sum(int((np.asarray(scores[r]) == float(values[r, -1])).sum() > 1) for r in (0, 1))
    assert k >= 300 or tied >= 1  # the threshold IS shared


def test_index_scores_over_a_stored_row_are_the_scores_of_the_key():
    ks = jax.random.split(jax.random.key(2), 3)
    qi, w, ki = jax.random.normal(ks[0], (2, 5, 4, 8)), jax.random.normal(ks[1], (2, 5, 4)), jax.random.normal(ks[2], (2, 11, 8))
    want = jnp.einsum("bth,bths->bts", w, jax.nn.relu(jnp.einsum("bthc,bsc->bths", qi, ki)))
    np.testing.assert_allclose(attention.index_scores(qi, w, ki), want, atol=1e-5)
    stored = jnp.pad(ki, ((0, 0), (0, 0), (0, 120)))
    assert np.array_equal(attention.index_scores(qi, w, stored), attention.index_scores(qi, w, ki))


# -- three-axis rope -------------------------------------------------------------------------------


def test_equal_rows_are_the_one_position_rope_bit_for_bit():
    x = jax.random.normal(jax.random.key(0), (2, 7, 4, 128), jnp.bfloat16)
    pos = jnp.arange(7)[None] + jnp.asarray([[3], [200_000]])
    three = jnp.broadcast_to(pos[:, None], (2, 3, 7))
    for fn in (lambda f: f, jax.jit):
        one = fn(lambda x, p: apply_rope(x, p, 1e7))(x, pos)
        got = fn(lambda x, p: apply_rope(x, p, 1e7, sections=(16, 24, 24)))(x, three)
        assert np.array_equal(np.asarray(one, np.float32), np.asarray(got, np.float32))
    with pytest.raises(ValueError, match="sections"):
        apply_rope(x, three, 1e7, sections=(16, 24, 25))


def test_a_grid_span_turns_each_section_by_its_own_axis_as_the_reference_does():
    """Text, then a 4 x 5 grid of patches at one time step (height and
    width count the grid), then text again: `forward` with the three
    positions against the reference with them; the temporal position alone
    (the reference's `one_axis`) is another result."""
    family, pc, mesh, params = built()
    tokens, rows = text_tokens()
    t = np.concatenate([np.arange(10), np.full(20, 10), 11 + np.arange(10)])
    h = np.concatenate([np.arange(10), 10 + np.repeat(np.arange(4), 5), 11 + np.arange(10)])
    w = np.concatenate([np.arange(10), 10 + np.tile(np.arange(5), 4), 11 + np.arange(10)])
    three = jnp.asarray(np.broadcast_to(np.stack([t, h, w])[None], (2, 3, 40)).astype(np.int32))
    got = keye.forward(params, jnp.asarray(tokens), pc, positions3=three, select=None)
    # the grid's rows share a temporal position: given the same choice of rows, the logits must agree
    chose = keyevl_reference.choices(params, sizes(), tokens, positions3=three)
    got = keye.forward(params, jnp.asarray(tokens), pc, positions3=three, select=chose["select"], route=chose["route"])
    want = keyevl_reference.logits(params, sizes(), tokens, rows, positions3=three, select=chose["select"], route=chose["route"])
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)
    flat = keyevl_reference.logits(params, sizes(), tokens, rows, positions3=three, lower="one_axis",
                                 select=chose["select"], route=chose["route"])
    assert float(jnp.abs(flat - want).max()) > 0.05


# -- the experts -----------------------------------------------------------------------------------


def test_the_eight_shares_of_an_expert_layer_sum_to_the_uncut_layer():
    """A layer's FF summed over eight chips' routed shares (each told which
    16 of 128 it holds, each routing over all 128 by the softmax, top 8
    renormalised) is the uncut reference's layer; nothing stands in for the
    absent chips in a share."""
    N, D, F, E, k = 24, 64, 32, 128, 8
    keys = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(keys[0], (N, D))
    layer = {"router": jax.random.normal(keys[1], (D, E)) * D ** -0.5,
             "w1": jax.random.normal(keys[2], (E, D, F)) * D ** -0.5,
             "w3": jax.random.normal(keys[3], (E, D, F)) * D ** -0.5,
             "w2": jax.random.normal(keys[4], (E, F, D)) * F ** -0.5}
    model = {"experts_per_token": k, "held": tuple(range(E)), "norm_topk_prob": True}
    whole = keyevl_reference._experts(x[None], layer, model, None)[0][0]
    total, landed = jnp.zeros_like(whole), 0
    for share in range(8):
        held = tuple(range(16 * share, 16 * share + 16))
        ids = np.array(held)
        y, counts = routed_experts(x, layer["router"], layer["w1"][ids], layer["w3"][ids], layer["w2"][ids], k,
                                   held=held, score="softmax", renormalize=True, interpret=share % 4 == 0)
        total, landed = total + y, landed + int(counts[1])
        assert float(jnp.abs(y - whole).max()) > 0.01  # a share is not the layer
    assert landed == N * k  # every (token, choice) pair landed on exactly one share
    np.testing.assert_allclose(total, whole, atol=3e-5)


# -- the controls ----------------------------------------------------------------------------------


@pytest.mark.parametrize("control,least", [
    ("int8", 5e-3), ("recent", 0.1), ("w_one", 0.1), ("topk_half", 0.1), ("index_rope_off", 0.05),
    ("index_norm_off", 0.05), ("dense", 0.1),
])
def test_each_reference_control_moves_the_logits(control, least):
    family, pc, mesh, params = built()
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, 3)
    want = keyevl_reference.logits(params, sizes(), s["tokens"], s["rows"])
    moved = check.compare(family.reference_logits(FILE, params, s["tokens"], s["rows"], lower=control), want)
    assert moved["logit_rel_rms"] > least, (control, moved["logit_rel_rms"])


def test_an_unknown_control_is_an_error_and_the_family_documents_its_own():
    family, pc, mesh, params = built()
    with pytest.raises(ValueError, match="no control 'fp4'"):
        family.reference_logits(FILE, params, [[0]], [[0]], lower="fp4")
    for name in keyevl_reference.CONTROLS:
        assert f'"{name}"' in family_module.__doc__ + keyevl_reference.__doc__, name
    assert preset("keye-vl-2.0-30b-a3b").index_topk == 2048 and preset("keye-tiny").index_topk == 8


@pytest.mark.parametrize("control,number,least", [({"ik_int8": True}, "select_cache_miss_all", 1e-3),
                                                  ({"kv_int8": True}, "cache_excess", 0.5),
                                                  ({"ik_crossed": True}, "select_cache_miss", 0.3)])
def test_each_cache_control_is_seen(monkeypatch, control, number, least):
    """int8 indexer keys choose other rows in the decode steps (the logits,
    compared with the choice given, do not see it: the choices' own number
    does: at 8 values a key and 8 rows of 40 a few rows in a thousand, on the chip's sizes PERF.md has it);
    int8 K and V show in the cache's excess. The family file's `kv_int8`
    names the leaves `k` and `v`, which the pool no longer has (PERF.md, Open
    after PR 60 (1)): here the `kv` leaf itself is rounded a row and head (2 x
    `n_kv_heads` heads, as `_as_int8` rounds) before each of the program's
    decode steps, that is between its prefill and them and after each."""
    family, pc, mesh, params = built()
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, 3)
    step = keye.decode_step_paged

    def over_int8_rows(p, cache, *a, **kw):
        k, v = paged.unpack_kv_rows(cache["kv"], pc.dtype)
        rounded = family_module._as_int8(jnp.concatenate([k, v], axis=-1), 2 * pc.n_kv_heads)
        return step(p, {**cache, "kv": paged.pack_kv_rows(*jnp.split(rounded, 2, axis=-1))}, *a, **kw)

    def reading(kv_int8=False, **kw):
        with monkeypatch.context() as mp:
            if kv_int8:
                mp.setattr(keye, "decode_step_paged", over_int8_rows)
            pre, dec, chosen = family.cache_readings(FILE, pc, params, mesh, s, False, **kw)
        want = family.reference_logits(FILE, params, s["tokens"], s["rows"])  # given what that program chose
        return {**check.compare((pre, dec), want), **chosen}

    sound, seen = reading(), reading(**control)
    assert seen[number] > least > abs(sound[number]), (sound[number], seen[number])


def test_past_a_limit_on_the_choices_the_decode_logits_are_not_numbers(capsys):
    family, pc, mesh, params = built()
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, 3)
    strict = dict(FILE, check=dict(FILE["check"], select_limits={"select_cache_miss_all": 0.001}))
    _pre, dec = family.cached_logits(strict, pc, params, mesh, s, False, ik_int8=True)
    assert not np.isfinite(np.asarray(dec)).any() and "select_cache_miss_all=" in capsys.readouterr().out
    _pre, dec = family.cached_logits(FILE, pc, params, mesh, s, False)
    assert np.isfinite(np.asarray(dec)).all()


@pytest.mark.parametrize("fault,number", [
    ("recent", "select_miss_prefill"), ("w_one", "select_miss_prefill"), ("topk_half", "missed_weight"),
    ("index_rope_off", "select_miss_decode"), ("index_norm_off", "select_miss_decode"), ("dense", "select_miss_prefill")])
def test_a_fault_planted_in_the_programs_indexer_is_refused_by_the_choices_numbers(fault, number, capsys):
    """The program traced with one of the reference's faults of the choice
    in its indexer (`keyevl._planted`): its logits still agree with the
    reference GIVEN its choices (the arithmetic is sound), the number named
    lies past its limit, `cached_logits` hands back decode logits that are
    not numbers, and the program's modules are their own again after."""
    family, pc, mesh, params = built()
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, 3)
    before = (keye.index_scores, keye.topk_rows_mask, keye.apply_rope, keye._layer_norm, attention.topk_rows)
    pre, dec = family.cached_logits(FILE, pc, params, mesh, s, False, indexer=fault)
    assert before == (keye.index_scores, keye.topk_rows_mask, keye.apply_rope, keye._layer_norm, attention.topk_rows)
    assert not np.isfinite(np.asarray(dec)).any() and np.isfinite(np.asarray(pre)).all()
    assert f"[check] {number}=" in (out := capsys.readouterr().out)
    line = next(ln for ln in out.splitlines() if ln.startswith(f"[check] {number}="))
    assert line.endswith("EXCEEDED"), out


def test_a_crossed_table_is_rated_far_under_the_requests_own_tokens():
    """`crossed_numbers`, the engine path's structural control: with the
    reference's own first choices standing for what an engine emitted (regret
    0 by construction), a context whose every second page holds another
    request's tokens emits tokens the reference rates far down."""
    family, pc, mesh, params = built()
    s = check.sample(FILE["check"], FILE["vocab_size"], PAGE, 3)
    R, lengths = 4, s["lengths"]
    tokens = np.array(s["tokens"])
    reference = lambda t, rows, lower=None: keyevl_reference.logits(params, sizes(), t, rows)  # noqa: E731
    emitted = [[] for _ in range(s["B"])]
    for j in range(R):  # greedy tokens of the free reference itself
        picked = np.asarray(jnp.argmax(reference(tokens, lengths[:, None] - 1 + j), -1))[:, 0]
        tokens[np.arange(s["B"]), lengths + j] = picked
        for b in range(s["B"]):
            emitted[b].append(int(picked[b]))
    path = {"returned": emitted}
    sound = check.engine_numbers(reference, s, {**path, "streamed": emitted, "finish": ["length"] * s["B"], "budget": R})
    crossed = family.crossed_numbers(reference, s, path)["pages_crossed"]
    assert sound["greedy_regret"] == 0.0
    assert crossed["greedy_regret"] > 1.0 and crossed["greedy_regret"] >= crossed["regret_median"] >= 0.0, crossed
    with pytest.raises(ValueError, match="two requests"):
        family.crossed_numbers(reference, {**s, "B": 1}, path)


# -- the prefill's kernel --------------------------------------------------------------------------


def test_the_masked_attention_kernel_is_the_masked_dense_attention():
    """`ops/pallas/masked_attention.py` interpreted: four query heads over two
    KV heads of 128 at 1,024 rows (four blocks of queries, two of keys, the
    key blocks past the diagonal skipped), a random mask under the causal
    one, a row that sees nothing among them (finite, read by nothing)."""
    from agentcontrolplane_tpu.ops.pallas import masked_attention as ma

    T, H, Hkv, d = 1024, 4, 2, 128
    ks = jax.random.split(jax.random.key(0), 4)
    q, k, v = (jax.random.normal(ks[i], (T, h, d)) for i, h in enumerate((H, Hkv, Hkv)))
    mask = (jax.random.bernoulli(ks[3], 0.2, (T, T)) | jnp.eye(T, dtype=bool)) & jnp.tril(jnp.ones((T, T), bool))
    mask = mask.at[700].set(False)
    got = ma.masked_attention(q, k, v, mask.astype(jnp.int8), interpret=True)
    want = attention.causal_attention(q[None], k[None], v[None], keep=mask[None])[0]
    seen = np.arange(T) != 700
    np.testing.assert_allclose(np.asarray(got)[seen], np.asarray(want)[seen], atol=2e-6)
    assert np.isfinite(np.asarray(got)).all()
    assert ma.serves(24576, 128, 128) and ma.serves(16384, 128, 128) and not ma.serves(40, 128, 128) and not ma.serves(1024, 16, 16)
    with pytest.raises(ValueError, match="whole blocks"):
        ma.masked_attention(q[:300], k[:300], v[:300], mask[:300, :300].astype(jnp.int8), interpret=True)


def test_a_prefill_through_the_kernel_is_the_prefill_through_xla(monkeypatch):
    """The same whole-prompt forward with the mask handed to the kernel
    (interpreted) and to XLA's plain masked attention: heads of 128, 512 rows
    in blocks of 256, 64 rows chosen; the packed choices are the same bits.
    A prompt that is not whole blocks of rows is refused by the kernel, not
    handed to another path."""
    pc = dataclasses.replace(preset("keye-tiny"), n_heads=2, n_kv_heads=1, head_dim=128, mrope_section=(16, 24, 24),
                             index_topk=64, n_layers=2)
    params = keye.init_params(pc, jax.random.key(0))
    tokens = jnp.asarray(np.random.default_rng(4).integers(0, 256, (2, 512)).astype(np.int32))
    monkeypatch.setattr(keye, "MASK_TIER", 256)
    monkeypatch.setattr(keye, "MASK_BLOCK", 128)
    want, (chose, _) = keye.forward(params, tokens, pc, tell=True)
    got, (through, _) = keye.forward(params, tokens, pc, tell=True, interpret=True)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)
    assert np.array_equal(through, chose)
    with pytest.raises(ValueError, match="whole blocks"):
        keye.forward(params, tokens[:, :40], pc, interpret=True)
