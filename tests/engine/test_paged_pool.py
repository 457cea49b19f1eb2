"""The programs over the paged pool stored as the walk reads it, and the
pool's helpers over a tree of page-shaped leaves."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from agentcontrolplane_tpu.ops.paged import TRASH_PAGE

# -- the programs over the pool stored as the walk reads it --------------------
#
# `[L, pages, P, H_kv * d]`, read and written through the pool flattened over
# its layers with page ids offset by the layer (ops/paged.py). Every page no
# block table names is NaN in every layer (int8 pages: its scales are), so a
# read through a wrong layer offset or a wrong page fails loudly, and a write
# that lands anywhere else is seen where the NaNs are counted afterwards. The
# reference is the model's plain causal forward over the whole sequence: it
# knows no pool.

_P, _M, _LAYERS, _POOL_PAGES = 8, 4, 3, 24
_MESHES = {"one-device": None, "tp2": {"tp": 2}, "sp2": {"sp": 2, "tp": 1}}


def _pool_case(mesh_axes, int8_pages):
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as Spec

    from agentcontrolplane_tpu.models import llama
    from agentcontrolplane_tpu.parallel.mesh import make_mesh, param_shardings

    c = dataclasses.replace(llama.PRESETS["tiny"], n_layers=_LAYERS)
    params = llama.init_params(c, jax.random.key(7))
    rng = np.random.default_rng(3)
    tokens = rng.integers(1, c.vocab_size, size=(2, _M * _P)).astype(np.int32)
    # scattered pages in table order; the second sequence never needs its fourth
    ids = rng.permutation(np.arange(1, _POOL_PAGES))[:7]
    tables = np.asarray([ids[:4], list(ids[4:7]) + [TRASH_PAGE]], dtype=np.int32)
    named = np.zeros(_POOL_PAGES, bool)
    named[tables.reshape(-1)] = True
    pool = llama.init_paged_cache(c, _POOL_PAGES, _P, quantize_kv=int8_pages)
    assert pool["k"].shape == (_LAYERS, _POOL_PAGES, _P, c.n_kv_heads * c.head_dim)
    poisoned = ("ks", "vs") if int8_pages else ("k", "v")
    for name in poisoned:
        pool[name] = pool[name].at[:, ~named].set(jnp.nan)
    mesh = None
    if mesh_axes is not None:
        n = int(np.prod(list(mesh_axes.values())))
        mesh = make_mesh(mesh_axes, devices=jax.devices()[:n])
        page_sh = NamedSharding(mesh, Spec(None, None, "sp" if "sp" in mesh_axes else None, "tp"))
        pool = {name: jax.device_put(a, page_sh) for name, a in pool.items()}
        params = jax.device_put(params, param_shardings(mesh, c, params))
    want = np.asarray(llama.forward(llama.init_params(c, jax.random.key(7)), jnp.asarray(tokens), c))
    return c, params, pool, tokens, tables, named, poisoned, mesh, want


def _rows(tokens, starts, lengths, T):
    out = np.zeros((len(starts), T), np.int32)
    for b, (s, n) in enumerate(zip(starts, lengths)):
        out[b, :n] = tokens[b, s:s + n]
    return jnp.asarray(out)


@pytest.mark.parametrize("walk", ["xla-gather", "pallas-interpret"])
@pytest.mark.parametrize("int8_pages", [False, True], ids=["f32-pages", "int8-pages"])
@pytest.mark.parametrize("mesh_axes", list(_MESHES.values()), ids=list(_MESHES))
def test_programs_through_the_merged_pool_match_the_plain_forward(mesh_axes, int8_pages, walk, monkeypatch):
    """Prefill, continuation, verify and two decode steps of two sequences,
    each program's logits against the plain forward at the same positions,
    on one device, tp=2 and sp=2, the decode walk by the XLA gather and by
    the kernel (interpret mode)."""
    import functools

    from agentcontrolplane_tpu.models import llama
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    name = "paged_decode_attention_cache_plus_new_sharded"  # the one entry the model calls, on any mesh
    monkeypatch.setattr(pa, name, functools.partial(getattr(pa, name), interpret=True))
    c, params, pool, tokens, tables, named, poisoned, mesh, want = _pool_case(mesh_axes, int8_pages)
    tb = jnp.asarray(tables)
    i32 = lambda *a: jnp.asarray(a, jnp.int32)  # noqa: E731
    # int8 pages round every K and V a row and head: the gate's tolerance, not the exact one
    close = functools.partial(np.testing.assert_allclose, rtol=0, atol=0.12 if int8_pages else 2e-4)

    # whole prompts of 16 and 8 tokens (rows padded to 16), two pages and one
    n0 = np.asarray([16, 8])
    page_ids = np.where(np.arange(2)[None, :] * _P < n0[:, None], tables[:, :2], TRASH_PAGE)
    pool, logits = jax.jit(lambda p, kv, *a: llama.prefill_paged_batch(p, kv, *a, c))(
        params, pool, _rows(tokens, [0, 0], n0, 16), i32(*n0), jnp.asarray(page_ids))
    close(np.asarray(logits), want[np.arange(2), n0 - 1], err_msg="prefill")

    # continuations from the page-aligned ends: 7 and 5 tokens, one page each
    n1 = np.asarray([7, 5])
    pool, logits = jax.jit(lambda p, kv, *a: llama.prefill_paged_continue(p, kv, *a, c))(
        params, pool, _rows(tokens, n0, n1, _P), i32(*n1), i32(*n0),
        jnp.asarray(tables[np.arange(2), n0 // _P][:, None]), tb)
    close(np.asarray(logits), want[np.arange(2), n0 + n1 - 1], err_msg="continuation")

    # a verify pass from mid-page (23 and 13): 3 and 2 tokens, a token-row commit
    s2, n2 = n0 + n1, np.asarray([3, 2])
    pool, logits = jax.jit(lambda p, kv, *a: llama.verify_paged_continue(p, kv, *a, c))(
        params, pool, _rows(tokens, s2, n2, 4), i32(*n2), i32(*s2), tb)
    for b in range(2):
        close(np.asarray(logits)[b, :n2[b]], want[b, s2[b]:s2[b] + n2[b]], err_msg=f"verify row {b}")

    # two decode steps; seq 0 crosses into its fourth page at 26 -> 24..31 is page 3
    seq = s2 + n2
    step = jax.jit(lambda p, kv, t, n, a: llama.decode_step_paged(
        p, kv, t, n, tb, a, c, use_pallas=walk == "pallas-interpret", mesh=mesh))
    for j in range(2):
        pool, logits = step(params, pool, jnp.asarray(tokens[np.arange(2), seq + j]), i32(*(seq + j)),
                            jnp.ones((2,), bool))
        close(np.asarray(logits), want[np.arange(2), seq + j], err_msg=f"decode step {j}")
    # an inactive lane writes the trash page and nothing else
    before = {name: np.asarray(a) for name, a in pool.items()}
    pool, _ = step(params, pool, jnp.asarray(tokens[np.arange(2), seq + 2]), i32(*(seq + 2)),
                   jnp.asarray([True, False]))
    for name, a in pool.items():
        a = np.asarray(a)
        lane1 = tables[1][tables[1] != TRASH_PAGE]
        np.testing.assert_array_equal(a[:, lane1], before[name][:, lane1], err_msg=f"{name}: an inactive lane's pages")
    # nothing was written to a page no table names, in any layer
    for name in poisoned:
        a = np.asarray(pool[name])
        assert np.isnan(a[:, ~named]).all(), f"{name}: a write landed on an unnamed page"
        assert np.isfinite(a[:, named & (np.arange(_POOL_PAGES) != TRASH_PAGE)]).all(), name


# -- the pool as a tree of page-shaped leaves: every helper takes them as they come ----------


def _pools():
    """A `k` / `v` pool, an int8 one with its scale twins, and a latent
    pool's one leaf: fresh rows for each, [L, B, T, heads, d]."""
    from agentcontrolplane_tpu.ops import paged

    L, NP, P, B, T = 3, 9, 4, 2, 8
    key = jax.random.key(5)
    rows = lambda i, heads, d: jax.random.normal(jax.random.fold_in(key, i), (L, B, T, heads, d), jnp.float32)  # noqa: E731
    return {
        "k_and_v": (paged.init_kv_pages(L, NP, P, 2, 8, jnp.float32), {"k": rows(1, 2, 8), "v": rows(2, 2, 8)}),
        "int8": (paged.init_kv_pages(L, NP, P, 2, 8, jnp.float32, quantize=True), {"k": rows(1, 2, 8), "v": rows(2, 2, 8)}),
        "one_leaf": (paged.init_latent_pages(L, NP, P, 24, jnp.float32), {"kv": rows(3, 1, 24)}),
    }, (L, NP, P, B, T)


@pytest.mark.parametrize("kind", ["k_and_v", "int8", "one_leaf"])
def test_the_pool_helpers_take_a_pools_leaves_as_they_come(kind):
    """`commit_whole_pages`, `commit_tokens`, `gather_pages` and `set_pages`
    over each kind of pool: what is committed is what is gathered (int8: to
    its rounding), no leaf is named by the helpers, and pages not written
    stay as they were."""
    from agentcontrolplane_tpu.ops import paged

    pools, (L, NP, P, B, T) = _pools()
    pool, new = pools[kind]
    ids = jnp.asarray([[1, 2], [5, 6]], jnp.int32)
    done = paged.commit_whole_pages(pool, new, ids)
    assert set(done) == set(pool) and all(done[name].shape == pool[name].shape for name in pool)
    tol = 0.05 if kind == "int8" else 0.0
    for name, rows in new.items():
        heads = rows.shape[-2]
        for layer in range(L):
            got = paged.gather_pages(done, name, paged.layer_tables(ids, layer, NP), jnp.float32, heads)
            np.testing.assert_allclose(got.reshape(B, T, heads, -1), rows[layer], atol=tol)
        untouched = np.asarray(done[name])[:, [0, 3, 4, 7, 8]]
        assert float(np.abs(untouched).max()) == 0.0
    # a token at a time: row 1 of pages 3 and 7, and nothing else moves
    one = {name: rows[:, :, 0] for name, rows in new.items()}
    after = paged.commit_tokens(done, one, jnp.asarray([3, 7], jnp.int32), jnp.asarray([1, 1], jnp.int32))
    for name, rows in one.items():
        heads = rows.shape[-2]
        got = paged.gather_pages(after, name, paged.layer_tables(jnp.asarray([[3], [7]]), 2, NP), jnp.float32, heads)
        np.testing.assert_allclose(got[:, 0, 1], rows[2], atol=tol)
        assert float(np.abs(np.asarray(got)[:, 0, [0, 2, 3]]).max()) == 0.0
        np.testing.assert_array_equal(np.asarray(after[name])[:, [1, 2, 5, 6]], np.asarray(done[name])[:, [1, 2, 5, 6]])
    # whole pages set leaf by leaf, as the engine's swap-in scatters a host entry's blocks
    blocks = {name: np.asarray(after[name])[:, [1, 2]] for name in after}
    moved = {name: paged.set_pages(after[name], jnp.asarray([7, 8]), jnp.asarray(blocks[name])) for name in after}
    for name in after:
        np.testing.assert_array_equal(np.asarray(moved[name])[:, [7, 8]], blocks[name])
    assert set(paged.pool_leaves({**pool, "state": {"x": 1}})) == set(pool)
