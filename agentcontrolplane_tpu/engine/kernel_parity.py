"""Page-walk parity: the compiled kernel against the XLA gather reference.

One definition shared by ``chip_smoke.py`` and
``tests/engine/test_tpu_hardware.py`` (and usable in interpret mode on the
CPU): seeded ragged pages at a named geometry, the kernel and
``ops.paged``'s reference run on the default device, the largest absolute
difference judged against a tolerance set from the page dtype. The K/V walk
(:func:`page_walk_parity`), the latent walk over a pool of one leaf
(:func:`latent_walk_parity`) and a verify step's walks, several rows a lane
in one query group over pages or a ring (:func:`verify_walk_parity`); and
the routed experts' grouped matmul against ``jax.lax.ragged_dot`` over the
same plan (:func:`expert_matmul_parity`); and the indexer's choice of rows
against ``jax.lax.top_k``'s set (:func:`index_select_parity`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.moe import routed_experts
from ..ops.paged import (
    TRASH_PAGE,
    PageAllocator,
    latent_decode_attention_reference_cache_plus_new,
    paged_decode_attention_reference,
    paged_decode_attention_reference_cache_plus_new,
    paged_verify_attention_reference,
    ring_positions,
    ring_size,
    ring_tables,
)
from ..ops.pallas.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_cache_plus_new,
    paged_latent_attention_cache_plus_new,
    paged_verify_attention_cache_plus_new,
    pages_per_turn,
)
from ..ops.quant import kv_quantize

# Both sides accumulate in f32 (the kernel at f32 contract precision, the
# reference under default_matmul_precision("highest")); what differs is the
# order of reduction and, for bf16 q/pages, the final cast of the output.
TOLERANCE = {"float32": 2e-4, "bfloat16": 2e-2}


def make_paged_case(
    seed: int, *, S: int, H: int, H_kv: int, d: int, P: int = 16,
    max_pages: int = 8, num_pages: int = 128, dtype=jnp.float32,
    int8: bool = False,
) -> dict:
    """Seeded ragged sequences scattered over an allocator's pages (page 0
    stays the trash page). ``int8`` quantizes the pools with
    ``ops.quant.kv_quantize`` and adds the f32 scale twins."""
    rng = np.random.default_rng(seed)
    seq_lens = rng.integers(1, max_pages * P, size=S).astype(np.int32)
    k_pages = np.zeros((num_pages, P, H_kv, d), dtype=np.float32)
    v_pages = np.zeros((num_pages, P, H_kv, d), dtype=np.float32)
    alloc = PageAllocator(num_pages)
    tables = np.full((S, max_pages), TRASH_PAGE, dtype=np.int32)
    for s in range(S):
        n = -(-int(seq_lens[s]) // P)
        pages = alloc.alloc(n)
        tables[s, :n] = pages
        kv = rng.normal(size=(2, int(seq_lens[s]), H_kv, d)).astype(np.float32)
        for j, page in enumerate(pages):
            lo, hi = j * P, min((j + 1) * P, int(seq_lens[s]))
            k_pages[page, : hi - lo] = kv[0][lo:hi]
            v_pages[page, : hi - lo] = kv[1][lo:hi]
    case = {
        "q": jnp.asarray(rng.normal(size=(S, H, d)), dtype=dtype),
        "k_pages": jnp.asarray(k_pages, dtype=dtype),
        "v_pages": jnp.asarray(v_pages, dtype=dtype),
        "block_tables": jnp.asarray(tables),
        "seq_lens": jnp.asarray(seq_lens),
        "k_new": jnp.asarray(rng.normal(size=(S, H_kv, d)), dtype=dtype),
        "v_new": jnp.asarray(rng.normal(size=(S, H_kv, d)), dtype=dtype),
        "scales": {},
    }
    if int8:
        case["k_pages"], ks = kv_quantize(case["k_pages"])
        case["v_pages"], vs = kv_quantize(case["v_pages"])
        case["scales"] = {"k_scales": ks, "v_scales": vs}
    return case


def page_walk_parity(
    case: dict, *, plus_new: bool = True, interpret: bool = False
) -> dict:
    """Run the kernel (compiled unless ``interpret``) and the reference on
    the same operands; returns ``{max_abs_err, tolerance, ok, ...}``.
    ``plus_new`` selects the serving hot-path form (read-only pages + the
    new token's self term) over the classic written-pages-only form."""
    args = [
        case["q"], case["k_pages"], case["v_pages"],
        case["block_tables"], case["seq_lens"],
    ]
    if plus_new:
        args += [case["k_new"], case["v_new"]]
        kernel, reference = (
            paged_decode_attention_cache_plus_new,
            paged_decode_attention_reference_cache_plus_new,
        )
    else:
        kernel, reference = paged_decode_attention, paged_decode_attention_reference
    scales = case["scales"]
    out = jax.jit(
        lambda *a, **kw: kernel(*a, interpret=interpret, **kw)
    )(*args, **scales)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(reference)(*args, **scales)
    return _verdict(case, out, ref)


def _verdict(case: dict, out: jax.Array, ref: jax.Array) -> dict:
    """The kernel's output judged against the reference's: a NaN anywhere
    (a page read that is not the walk's own) is not ok whatever the rest."""
    out = np.asarray(out.astype(jnp.float32))
    ref = np.asarray(ref.astype(jnp.float32))
    err = float(np.max(np.abs(out - ref)))
    tol = TOLERANCE[jnp.dtype(case["q"].dtype).name]
    finite = bool(np.isfinite(out).all())
    return {
        "max_abs_err": err,
        "tolerance": tol,
        "finite": finite,
        "shape": tuple(out.shape),
        "seq_lens": [int(n) for n in np.asarray(case["seq_lens"])],
        "ok": finite and out.shape == ref.shape and err <= tol,
    }


# slots of 0 to 10 turns, empty ones first, between and last, a slot of one
# turn after the longest, last turns that hold one page and all but one
LATENT_TURNS = (0, 10, 1, 0, 0, 4, 2, 0, 3, 0)


def make_latent_case(seed: int, *, H: int = 32, width: int = 640, value_width: int = 512, P: int = 16,
                     turns: tuple = LATENT_TURNS, dtype=jnp.bfloat16) -> dict:
    """A pool of one leaf in which slot ``s`` holds ``turns[s]`` turns of the
    latent walk's own geometry (:func:`pages_per_turn` with ``leaves=1``),
    its last turn one page (odd slots) or a page short of whole (even), its
    last page part-filled; the pages scattered, and every page no slot's
    rows reach NaN (the table's padding names them): a walk that reads a
    page not its own, or scores a row no fetch wrote, fails loudly."""
    rng = np.random.default_rng(seed)
    G = pages_per_turn(P, dtype, 1, width, leaves=1)
    held = [0 if n == 0 else (n - 1) * G + 1 if s % 2 else n * G - 1 for s, n in enumerate(turns)]
    S, M = len(turns), max(1, max(held))
    order = 1 + rng.permutation(S * M).astype(np.int32).reshape(S, M)
    clean = rng.normal(size=(1 + S * M, P, width)).astype(np.float32)
    named = np.zeros((1 + S * M,), bool)
    for s, n in enumerate(held):
        named[order[s, :n]] = True
    return {
        "q": jnp.asarray(rng.normal(size=(S, H, width)) * 0.3, dtype=dtype),
        "pages": jnp.asarray(np.where(named[:, None, None], clean, np.nan), dtype=dtype),
        "clean": jnp.asarray(clean, dtype=dtype),
        "block_tables": jnp.asarray(order),
        "seq_lens": jnp.asarray([max(0, n * P - 3) for n in held], jnp.int32),
        "row_new": jnp.asarray(rng.normal(size=(S, width)), dtype=dtype),
        "value_width": value_width,
        "pages_per_turn": G,
    }


def latent_walk_parity(case: dict, *, score_dim: int = 192, interpret: bool = False) -> dict:
    """The latent walk (compiled unless ``interpret``) over the case's pool
    against the reference over the same pool without its NaN pages, in the
    serving form (read-only pages plus the new token's own term)."""
    tail = (case["block_tables"], case["seq_lens"], case["row_new"], case["value_width"], score_dim)
    out = jax.jit(lambda q, pages: paged_latent_attention_cache_plus_new(q, pages, *tail, interpret=interpret))(
        case["q"], case["pages"])
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda q, pages: latent_decode_attention_reference_cache_plus_new(q, pages, *tail))(
            case["q"], case["clean"])
    return {**_verdict(case, out, ref), "pages_per_turn": case["pages_per_turn"]}


# rows in a lane's pages: none, under a page, a window's worth, the two rows'
# edges 15 and 16 (row 0's on a page's last row, row 1's in the next page),
# and contexts of several turns whose rings have wrapped
VERIFY_LENS = (0, 9, 128, 142, 1000, 2001, 3800, 6000)


def make_verify_case(seed: int, *, R: int = 2, H: int = 64, H_kv: int = 8, d: int = 128, P: int = 16,
                     window: int = 128, lens: tuple = VERIFY_LENS, dtype=jnp.bfloat16) -> dict:
    """A verify step's attention at the geometry that runs it (``exaone``:
    64 / 8 heads of 128, two rows a lane, a window of 128 over pages of 16):
    a pool in which every lane's pages are scattered, and a ring a lane.
    Every page no lane's rows reach, and every page of a ring that lies
    outside its lane's window, is NaN where the kernel reads (``pages``,
    ``rings``) and seeded where the reference does: a walk that fetches a
    page not its own fails loudly."""
    rng = np.random.default_rng(seed)
    S, ring = len(lens), ring_size(window, P)
    held = [-(-n // P) for n in lens]
    M = max(1, max(held))
    order = 1 + rng.permutation(S * M).astype(np.int32).reshape(S, M)
    named = np.zeros((1 + S * M,), bool)
    in_window = np.zeros((S * ring,), bool)
    for s, n in enumerate(lens):
        named[order[s, :held[s]]] = True
        if n:
            in_window[s * ring + np.arange(max(n + 1 - window, 0) // P, (n - 1) // P + 1) % ring] = True
    draw = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    marked = lambda clean, live: jnp.asarray(np.where(live[:, None, None], clean, np.nan), dtype)  # noqa: E731
    pool, rings = draw(2, 1 + S * M, P, H_kv * d), draw(2, S * ring, P, H_kv * d)
    lens = jnp.asarray(lens, jnp.int32)
    return {
        "q": jnp.asarray(draw(S, R, H, d), dtype),
        "k_new": jnp.asarray(draw(S, R, H_kv, d), dtype), "v_new": jnp.asarray(draw(S, R, H_kv, d), dtype),
        # every fourth lane's first row is no key, and the lane's before it its second
        "new_valid": jnp.asarray((np.arange(S)[:, None] + np.arange(R)[None, :]) % 4 != 3),
        "seq_lens": lens, "ring": ring,
        "starts": jnp.maximum(lens[:, None] + jnp.arange(R)[None, :] + 1 - window, 0),
        "full": {"tables": jnp.asarray(order), "pages": [marked(x, named) for x in pool],
                 "clean": [jnp.asarray(x, dtype) for x in pool]},
        "win": {"tables": ring_tables(jnp.arange(S, dtype=jnp.int32), ring), "pages": [marked(x, in_window) for x in rings],
                "clean": [jnp.asarray(x, dtype) for x in rings]},
    }


def verify_walk_parity(case: dict, *, window: bool, interpret: bool = False) -> dict:
    """A verify step's walk (compiled unless ``interpret``) over the lanes'
    pages, or with ``window`` over their rings from each row's own edge on,
    against the gather over the same pool without its NaN pages."""
    pool = case["win" if window else "full"]
    n, new = case["seq_lens"], (case["k_new"], case["v_new"])
    kw = {"starts": case["starts"], "ring": case["ring"]} if window else {}
    out = jax.jit(lambda q, k, v: paged_verify_attention_cache_plus_new(
        q, k, v, pool["tables"], n, *new, interpret=interpret, new_valid=case["new_valid"], **kw))(
        case["q"], *pool["pages"])
    if window:
        kw = {"starts": case["starts"], "row_positions": ring_positions(n, case["ring"], pool["clean"][0].shape[1])}
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda q, k, v: paged_verify_attention_reference(
            q, k, v, pool["tables"], n, *new, new_valid=case["new_valid"], **kw))(case["q"], *pool["clean"])
    return _verdict(case, out, ref)


# the compiled kernels' cases: the widest layer served with most row tiles live (the selection leans to the held
# experts), then streams whose bound is mostly dead tiles at the two cells with the longest bounds (an unsteered router:
# the chip's share of the pairs lands) and one prefill (tiles of 128 rows, runs of several)
EXPERT_CASES = {
    "widest": dict(tokens=128, k=8, experts=128, held=16, hidden=6144, width=2048),
    "kexaone-decode": dict(tokens=128, k=8, experts=128, held=16, hidden=6144, width=2048, lean=0.0),  # ~17 of 80 tiles live
    "nemotron3s-decode": dict(tokens=128, k=22, experts=512, held=64, hidden=1024, width=2688, lean=0.0, gated=False),  # ~64 of 240
    "mellum2-prefill": dict(tokens=2048, k=8, experts=64, held=16, hidden=2304, width=896, lean=0.0),  # ~40 of 144 tiles of 128
}


def expert_matmul_parity(seed: int, *, tokens: int = 128, k: int = 8, experts: int = 128, held: int = 16,
                         hidden: int = 6144, width: int = 2048, layers: int = 2, lean: float = 2.0, gated: bool = True,
                         interpret: bool = False) -> dict:
    """``ops.moe.routed_experts`` through the ``moe_gmm`` kernels (compiled
    unless ``interpret``) against the same layer through ``ragged_dot``, by
    default at the geometry of the widest expert layer served (``exaone``'s
    decode step: 64 lanes x 2 rows x 8 choices over 16 of 128 experts, 6,144
    and 2,048 wide: sixteen column chunks an expert's gate and up), the
    second layer of a stack. ``lean`` is the selection's bias to the held
    experts: 2.0 so that most row tiles hold a row, 0.0 for the share an
    unsteered router lands here (``EXPERT_CASES``: a stream under a long
    dead bound). Held expert 1 is chosen by no token and every fifth token
    routes nowhere, so the plan has an idle expert and dead tiles; ``gated``
    False: experts of two matrices. The largest difference is judged against
    the largest output."""
    from ..ops.moe import row_tile

    keys = jax.random.split(jax.random.key(seed), 5)
    draw = lambda i, *shape, scale=1.0: jax.random.normal(keys[i], shape, jnp.bfloat16) * scale  # noqa: E731
    x, router = draw(0, tokens, hidden), draw(1, hidden, experts, scale=hidden ** -0.5)
    w1, w3 = (draw(i, layers * held, hidden, width, scale=hidden ** -0.5) for i in (2, 3))
    w2 = draw(4, layers * held, width, hidden, scale=width ** -0.5)
    bias = jnp.zeros((experts,), jnp.float32).at[:held].set(lean).at[1].set(-10.0)
    valid = jnp.arange(tokens) % 5 != 4
    run = lambda kernel: jax.jit(lambda x, router, w1, w3, w2: routed_experts(  # noqa: E731
        x, router, w1, w3 if gated else None, w2, k, held=tuple(range(held)), score="sigmoid", bias=bias, valid=valid,
        expert_base=(layers - 1) * held, kernel=kernel, interpret=interpret and kernel))(x, router, w1, w3, w2)
    (out, counts), (ref, _) = run(True), run(False)
    out, ref = np.asarray(out.astype(jnp.float32)), np.asarray(ref.astype(jnp.float32))
    err, top = float(np.max(np.abs(out - ref))), float(np.max(np.abs(ref)))
    finite, tol = bool(np.isfinite(out).all()), TOLERANCE["bfloat16"] * top
    landed = np.asarray(counts)[-held:].astype(np.int64)
    tm = row_tile(tokens * k, experts)
    routed = int(np.asarray(valid).sum()) * k
    return {
        "max_abs_err": err, "largest": top, "tolerance": tol, "finite": finite,
        "shape": tuple(out.shape), "pairs_by_expert": [int(n) for n in landed],
        "live_tiles": int(np.sum(-(-landed // tm))), "tiles": -(-tokens * k // tm) + held,
        "ok": finite and top > 0.1 and err <= tol and landed[1] == 0
              and int(landed.sum()) > (routed // 2 if lean else routed * held // experts // 2),
    }


def index_select_parity(seed: int, *, lanes: int = 16, columns: int = 26624, topk: int = 2048, coarse: bool = False,
                        interpret: bool = False) -> dict:
    """The indexer's choice (``ops.pallas.index_select``, compiled unless
    ``interpret``) against ``ops.attention.topk_rows`` (``jax.lax.top_k``)
    over the same scores: lanes of 0 rows, fewer than ``topk``, and up to the
    whole width; ``coarse`` rounds the scores to a few values, so that whole
    runs of columns tie at every threshold and the earlier must win. The
    sets are equal or they are not: no tolerance."""
    from ..ops.attention import topk_rows
    from ..ops.pallas.index_select import index_select

    rng = np.random.default_rng(seed)
    scores = rng.normal(size=(lanes, columns)).astype(np.float32)
    scores = np.round(scores * 2) / 2 if coarse else scores
    lens = rng.integers(topk, columns, lanes).astype(np.int32)
    lens[:3] = (0, topk // 2, columns - 1)[: min(3, lanes)]
    scores, lens = jnp.asarray(scores), jnp.asarray(lens)
    valid = jnp.arange(columns)[None] <= lens[:, None]
    want = jnp.minimum(topk, lens + 1)
    got, tied = jax.jit(lambda s, w: index_select(jnp.where(valid, s, -jnp.inf), w, topk, interpret=interpret))(scores, want)
    columns_ref, chosen = jax.jit(lambda s: topk_rows(s, valid, topk))(scores)
    got, columns_ref, chosen, want = (np.asarray(a) for a in (got, columns_ref, chosen, want))
    wrong = [b for b in range(lanes) if got[b, : want[b]].tolist() != sorted(columns_ref[b][chosen[b]].tolist())]
    return {"ok": not wrong, "lanes_wrong": wrong, "lanes_tied": int(np.asarray(tied).sum()), "seq_lens": np.asarray(lens).tolist()}
