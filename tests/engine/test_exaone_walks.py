"""A verify step's attention, the kernels: a lane's rows in ONE query group of
the page walk and of the window walk (`ops/pallas/paged_attention.py
paged_verify_attention_cache_plus_new`), interpreted, against the gather of
`ops/paged.py`: contexts of 0 rows, under a page, on a page's edge and over
several turns; the two rows' edges in one page and in two; edges clipped at
0; a ring that has wrapped; new rows that are no key; bf16 and f32 pages;
heads of 64 two to a lane window; one row a lane bit for bit the decode
step's walk; and, from the jaxpr, what the call fetches. (A file of its own:
`test_exaone.py` holds the programs and stands at its budget of seconds.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentcontrolplane_tpu.ops import paged
from agentcontrolplane_tpu.ops.pallas import paged_attention
from agentcontrolplane_tpu.ops.pallas.paged_attention import paged_verify_attention_cache_plus_new


def _verify_case(seed, lens, *, R=2, H=4, Hkv=2, d=16, P=8, dtype=jnp.float32, ring=0):
    """Seeded rows of a verify step: ``R`` queries a lane over a pool in
    which every lane has its own pages (a table of `ring` entries: its ring)."""
    rng = np.random.default_rng(seed)
    S = len(lens)
    M = ring or max(1, -(-max(lens) // P))
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)  # noqa: E731
    q, kn, vn = draw(S, R, H, d), draw(S, R, Hkv, d), draw(S, R, Hkv, d)
    kp, vp = draw(1 + S * M, P, Hkv * d), draw(1 + S * M, P, Hkv * d)
    tables = (paged.ring_tables(jnp.arange(S, dtype=jnp.int32), ring) if ring
              else jnp.asarray(1 + rng.permutation(S * M).reshape(S, M), jnp.int32))
    return q, kp, vp, tables, jnp.asarray(lens, jnp.int32), kn, vn


def _edges(lens, R, window):
    return jnp.maximum(lens[:, None] + jnp.arange(R)[None] + 1 - window, 0)


_ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}
# (id, lens, the new rows that are keys, the case's geometry): contexts of 0 rows, under a page, on a page's edge and
# over several turns of the walk (16 pages of 8 float32 rows a turn, 8 of 16 bfloat16 rows: 128 rows)
_FULL_WALKS = [
    ("f32-empty-to-three-turns", [0, 5, 16, 300], [[1, 1], [1, 0], [1, 1], [0, 0]], {}),
    ("f32-every-lane-empty", [0, 0], [[1, 1], [0, 0]], {}),
    ("f32-a-turns-edge", [128, 129, 127], [[0, 1], [1, 1], [1, 0]], {}),
    ("bf16-pages-of-16", [0, 9, 32, 700], [[1, 1], [1, 1], [0, 1], [1, 0]], {"P": 16, "dtype": jnp.bfloat16}),
    ("f32-three-rows-a-lane", [3, 40, 260], [[1, 1, 1], [1, 0, 1], [0, 0, 0]], {"R": 3}),
    ("f32-one-row-a-lane", [0, 13, 141], [[1], [0], [1]], {"R": 1}),
    ("f32-heads-of-64-two-to-a-window", [0, 21, 150], [[1, 1], [1, 0], [1, 1]], {"d": 64, "H": 8, "Hkv": 4}),
    ("f32-a-group-of-one", [6, 140], [[1, 1], [1, 1]], {"H": 2}),
]


@pytest.mark.parametrize("lens,keys,geometry", [c[1:] for c in _FULL_WALKS], ids=[c[0] for c in _FULL_WALKS])
def test_the_interpreted_walks_of_a_verify_step_equal_the_xla_reference(lens, keys, geometry):
    """A lane's rows over its one table, the full layer: the kernel in
    interpret mode (every row of a lane in ONE query group) and the new rows
    folded outside it against the gather (`ops/paged.py`), rows that are no
    key left out."""
    q, kp, vp, tables, n, kn, vn = _verify_case(len(lens), lens, **geometry)
    valid = jnp.asarray(keys, bool)
    want = paged.paged_verify_attention_reference(q, kp, vp, tables, n, kn, vn, new_valid=valid)
    got = paged_verify_attention_cache_plus_new(q, kp, vp, tables, n, kn, vn, interpret=True, new_valid=valid)
    assert got.shape == want.shape and got.dtype == q.dtype
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), atol=_ATOL[q.dtype.type])


# (id, window, lens, keys, geometry). A window of 16 over pages of 8 is a ring of 3: at 22 rows the two rows' edges are
# 7 and 8, row 0's on a page's LAST row and row 1's in the next page; at 27 both lie in one page; under 16 both are
# clipped at 0; at 70 and 203 the ring has wrapped. bfloat16: the cell's own window of 128 over pages of 16 (a ring of
# 9, two turns of 8 pages), edges 15 and 16 at 142 rows. Windows of 1 and 2 rows: a query row that sees NO page row.
_RING_WALKS = [
    ("f32-edges-in-one-page-and-in-two", 16, [22, 27, 23], None, {}),
    ("f32-edges-clipped-at-0", 16, [0, 5, 14, 15, 16], [[1, 1], [1, 0], [1, 1], [0, 1], [1, 1]], {}),
    ("f32-a-ring-that-has-wrapped", 16, [70, 203, 64, 71], [[1, 1], [1, 1], [0, 0], [1, 0]], {}),
    ("bf16-the-cells-window", 128, [0, 100, 142, 1000, 2001], None, {"P": 16, "dtype": jnp.bfloat16}),
    ("f32-three-rows-a-lane", 16, [5, 22, 23, 70], None, {"R": 3}),
    ("f32-one-row-a-lane", 16, [5, 22, 70], None, {"R": 1}),
    ("f32-heads-of-64-two-to-a-window", 16, [0, 22, 27, 70], None, {"d": 64, "H": 8, "Hkv": 4}),
    ("f32-a-window-of-2-rows", 2, [0, 1, 8, 13, 40], [[1, 1], [0, 0], [0, 0], [1, 0], [0, 1]], {}),
    ("f32-a-window-of-1-row", 1, [0, 8, 13], [[0, 0], [0, 0], [1, 1]], {}),
]


@pytest.mark.parametrize("window,lens,keys,geometry", [c[1:] for c in _RING_WALKS], ids=[c[0] for c in _RING_WALKS])
def test_the_interpreted_window_walk_of_a_verify_step_masks_each_row_by_its_own_edge(window, lens, keys, geometry):
    """The same over a ring: the walk starts at the page of the lane's first
    edge and each query row sees from its OWN edge on, one position apart;
    a row with no visible page row adds nothing (with no new key either its
    output is the reference's zeros, not a mean of the rows it walked)."""
    P = geometry.get("P", 8)
    ring = -(-window // P) + 1  # `paged.ring_size`'s, and 2 for a window under a page
    q, kp, vp, rings, n, kn, vn = _verify_case(window + len(lens), lens, ring=ring, **geometry)
    starts = _edges(n, q.shape[1], window)
    valid = None if keys is None else jnp.asarray(keys, bool)
    want = paged.paged_verify_attention_reference(
        q, kp, vp, rings, n, kn, vn, new_valid=valid, row_positions=paged.ring_positions(n, ring, P), starts=starts)
    got = paged_verify_attention_cache_plus_new(
        q, kp, vp, rings, n, kn, vn, interpret=True, new_valid=valid, starts=starts, ring=ring)
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), atol=_ATOL[q.dtype.type])


@pytest.mark.parametrize("ring", [0, 3], ids=["full", "ring"])
def test_one_row_a_lane_is_the_decode_steps_walk_bit_for_bit(ring):
    """`decode_step_paged` goes through the verify step's wrapper with one
    row a lane: the decode step's attention, to the last bit."""
    q, kp, vp, tables, n, kn, vn = _verify_case(11, [0, 13, 41, 150], R=1, ring=ring)
    kw = {"starts": _edges(n, 1, 16), "ring": ring} if ring else {}
    got = paged_verify_attention_cache_plus_new(q, kp, vp, tables, n, kn, vn, interpret=True, **kw)
    if ring:
        kw["starts"] = kw["starts"][:, 0]
    want = paged_attention.paged_decode_attention_cache_plus_new(
        q[:, 0], kp, vp, tables, n, kn[:, 0], vn[:, 0], interpret=True, **kw)
    np.testing.assert_array_equal(got[:, 0], want)


@pytest.mark.parametrize("window", [False, True], ids=["pages", "ring"])
def test_the_verify_parity_case_the_chip_runs_holds_interpreted(window):
    """`kernel_parity.verify_walk_parity` is what `chip_smoke.py` and
    `test_tpu_hardware.py` run compiled: the same case (4 / 2 heads of 16,
    shorter lanes) through the interpreter, the pages outside the walk NaN."""
    from agentcontrolplane_tpu.engine.kernel_parity import make_verify_case, verify_walk_parity

    case = make_verify_case(3, H=4, H_kv=2, d=16, lens=(0, 9, 128, 142, 300, 700))
    assert all(bool(jnp.isnan(leaf).any()) for name in ("full", "win") for leaf in case[name]["pages"])
    got = verify_walk_parity(case, window=window, interpret=True)
    assert got["ok"] and got["shape"] == (6, 2, 4, 16), got


def _walk_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _walk_calls(inner)


@pytest.mark.parametrize("ring", [0, 3], ids=["full", "ring"])
def test_a_verify_steps_walk_is_one_call_of_S_lanes_and_a_group_of_all_their_rows(ring):
    """What is fetched is structural: ONE `pallas_call` whose tables and
    lengths are the lanes' own (`S` of them, not `S * R`) and whose query
    group holds `R * n_rep` rows a KV head; over a ring an edge a row."""
    S, R, H, Hkv, d = 4, 2, 8, 2, 16
    q, kp, vp, tables, n, kn, vn = _verify_case(3, [0, 13, 41, 150], H=H, ring=ring)
    kw = {"starts": _edges(n, R, 16), "ring": ring} if ring else {}
    jaxpr = jax.make_jaxpr(lambda *a: paged_verify_attention_cache_plus_new(*a, interpret=True, **kw))(
        q, kp, vp, tables, n, kn, vn)
    (call,) = _walk_calls(jaxpr.jaxpr)
    shapes = [v.aval.shape for v in call.invars]
    assert shapes[:2] == [tables.shape, (S,)]
    assert (S, Hkv, R * (H // Hkv), d) in shapes and [v.aval.shape for v in call.outvars] == [
        (S, Hkv, R * (H // Hkv), d), (S, Hkv, R * (H // Hkv), 1), (S, Hkv, R * (H // Hkv), 1)]
    assert ((S * R,) in shapes) == bool(ring)
    assert call.params["name"] == ("paged_window_walk" if ring else "paged_page_walk")
