"""dots3-note-prev's OPERATORS and the reference's controls (`test_dots.py`
holds the programs through the caches): absorbed against
expanded attention; the sparse latent step over rows given; the masked kernel
at unequal key and value widths; the sixteen shares of an expert layer summing
to the uncut layer with the shared expert counted once; every `assumed`
control moving the logits, and what the family documents of itself.

CPU, `test_dots.py`'s tiny sizes, float32, seeded weights.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acpbench import check
from acpbench.families import dots as family_module
from acpbench.families import dots_reference
from agentcontrolplane_tpu.models import dots, experts, preset
from agentcontrolplane_tpu.ops import attention, paged
from agentcontrolplane_tpu.ops.moe import routed_experts

from ._dots_cases import FILE, PAGE, built, sizes, text_tokens


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
    y, _counts = experts.routed_ff(h, mine, stacks, jnp.int32(e), pc, jnp.ones((1, 12), bool), score="sigmoid", bias=True, scale=pc.routed_scaling_factor, chunk=True, shared=True)
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
