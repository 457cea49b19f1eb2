"""What the files of the paged cache's tests share (`test_paged.py`: the
ops and the kernels against their references; `test_paged_stream.py`: the
walk as one stream of turns; `test_tpu_hardware.py`: that stream compiled):
a pool's row layout, the stream's cases and their check."""

import jax
import jax.numpy as jnp
import numpy as np

from agentcontrolplane_tpu.ops.paged import (
    TRASH_PAGE,
    paged_decode_attention_reference,
    paged_decode_attention_reference_cache_plus_new,
)
from agentcontrolplane_tpu.ops.pallas.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_cache_plus_new,
)


def merged(pages):
    """One layer's pages as a pool stores them: a row's KV heads side by
    side, ``[num_pages, P, H_kv * d]`` (what the sharded wrappers take)."""
    return pages.reshape(*pages.shape[:2], -1)


# -- the walk as one stream of turns over every slot ---------------------------
#
# The fetches run RING - 1 turns ahead of the fold from the kernel's first turn
# to its last, across slot boundaries: a slot's last turns are folded while the
# next slots' first are in flight, and slots with nothing to walk are stepped
# over. `DEPTH` turns in flight; a turn is `G` pages of 16 rows.

DEPTH, G = 3, 8
# pages a slot, by case: turn counts 0, 1, depth - 1, depth, depth + 1 and
# 3 x depth + 1 with the empty slot first, last and between two long ones
TURNS = {
    "empty-first": [0, 10, 1, 2, 3, 4],
    "empty-last": [10, 4, 3, 2, 1, 0],
    "empty-between-long": [10, 0, 10, 1, 0, 0, 4, 2, 0, 3],
    "every-slot-empty": [0, 0, 0],
    "one-slot-alone": [4],
}
PAGES = {k: [t * G for t in v] for k, v in TURNS.items()} | {
    "last-turns-of-one-page": [9 * G + 1, 1, 0, 3 * G + 1, G + 1],
    "last-turns-of-G-1-pages": [4 * G - 1, G - 1, 0, 10 * G - 1],
}


def stream_case(pages, dtype, seed=43, P=16, H=4, Hkv=2, d=8, int8=False):
    """One batch whose slot ``s`` walks ``pages[s]`` pages (its last one
    part-filled), scattered over a pool in which every page no block table
    names is NaN (int8 pools: its scales are): a fetch of any page that is
    not the walk's own fails loudly, and so does a row no fetch wrote
    (uninitialised scratch is NaN in interpret mode). The tables' padding
    names page 0, which is NaN too: nothing may read it."""
    from agentcontrolplane_tpu.ops.quant import kv_quantize

    rng = np.random.default_rng(seed)
    S, max_pages = len(pages), max(max(pages), 1) + 3
    seq_lens = np.asarray(
        [0 if n == 0 else (n - 1) * P + 1 + (5 * s + 3) % P for s, n in enumerate(pages)], np.int32)
    num_pages = sum(pages) + 7
    k_pages = np.full((num_pages, P, Hkv, d), np.nan, np.float32)
    v_pages = np.full((num_pages, P, Hkv, d), np.nan, np.float32)
    named = np.zeros(num_pages, bool)
    tables = np.full((S, max_pages), TRASH_PAGE, np.int32)
    free = list(rng.permutation(np.arange(1, num_pages)))
    order = [(s, j) for s in range(S) for j in range(pages[s])]
    rng.shuffle(order)  # no walk reads a contiguous run
    for s, j in order:
        page = int(free.pop())
        tables[s, j], named[page] = page, True
        # whole pages are written: rows past seq_len hold finite stale data
        k_pages[page] = rng.normal(size=(P, Hkv, d))
        v_pages[page] = rng.normal(size=(P, Hkv, d))
    as_dt = lambda x: jnp.asarray(x, dtype=dtype)  # noqa: E731
    case = dict(
        q=as_dt(rng.normal(size=(S, H, d))), k_pages=as_dt(k_pages), v_pages=as_dt(v_pages),
        tables=jnp.asarray(tables), seq_lens=jnp.asarray(seq_lens),
        k_new=as_dt(rng.normal(size=(S, Hkv, d))), v_new=as_dt(rng.normal(size=(S, Hkv, d))),
        scales={}, clean_scales={},
    )
    # the reference gathers whole tables: it gets the pool with the unnamed
    # pages zeroed (its mask then drops them exactly)
    clean = lambda x: jnp.nan_to_num(x.astype(jnp.float32)).astype(x.dtype)  # noqa: E731
    case["clean_k"], case["clean_v"] = clean(case["k_pages"]), clean(case["v_pages"])
    if int8:
        poison = jnp.where(jnp.asarray(named)[:, None, None], 1.0, jnp.nan)
        case["k_pages"], ks = kv_quantize(case["clean_k"])
        case["v_pages"], vs = kv_quantize(case["clean_v"])
        case["clean_k"], case["clean_v"] = case["k_pages"], case["v_pages"]
        case["scales"] = {"k_scales": ks * poison, "v_scales": vs * poison}
        case["clean_scales"] = {"k_scales": ks, "v_scales": vs}
    return case


def stream_parity(c, plus_new, atol, interpret=True, **kernel_kw):
    """The walk of one `stream_case` against the reference: interpreted in
    `test_paged_stream.py`, compiled in `test_tpu_hardware.py`."""
    args = [c["q"], c["k_pages"], c["v_pages"], c["tables"], c["seq_lens"]]
    ref_args = [c["q"], c["clean_k"], c["clean_v"], c["tables"], c["seq_lens"]]
    if plus_new:
        kernel, reference = paged_decode_attention_cache_plus_new, paged_decode_attention_reference_cache_plus_new
        args += [c["k_new"], c["v_new"]]
        ref_args += [c["k_new"], c["v_new"]]
    else:
        kernel, reference = paged_decode_attention, paged_decode_attention_reference
    out = np.asarray(kernel(*args, interpret=interpret, **c["scales"], **kernel_kw).astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference(*ref_args, **c["clean_scales"]).astype(jnp.float32))
    live = np.asarray(c["seq_lens"]) > 0
    if plus_new:
        live[:] = True  # the self term gives an empty slot its one token
    assert np.isfinite(out[live]).all(), "a walk read a page that is not its own, or a row no fetch wrote"
    np.testing.assert_allclose(out[live], ref[live], rtol=0, atol=atol)
    if not plus_new:
        # a slot with nothing to walk keeps the start state: acc 0 over the floor of l
        np.testing.assert_array_equal(out[~live], 0.0)
