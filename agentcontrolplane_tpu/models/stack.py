"""The plain parts every family's stack has: the token embedding, the last
norm and the head, the two matmul helpers, a row batch's positions, the
attention operators over plain K and V, and ``scan_layers``, which lays a list
of layer kinds out as loops. A mechanism module (``docs/serving-engine.md``,
"Adding a family"): families import it, it imports ``ops/`` alone, and an edit
here is an edit to every cell whose family names it in its header.
"""

from __future__ import annotations

import contextlib
import functools
import itertools

import jax
import jax.numpy as jnp

from ..observability import scopes
from ..ops.attention import continue_attention
from ..ops.norms import rms_norm
from ..ops.paged import flat_pages, gather_pages, layer_tables, paged_decode_attention_reference_cache_plus_new
from ..ops.rope import apply_rope


def mm(x, w):
    """``x @ w`` with the WEIGHT cast to the activation's dtype; the product
    accumulates and comes back as ``@`` has it, in the activation's dtype."""
    return x @ w.astype(x.dtype)


def mm_weight_dtype(x, w, out=None):
    """``x @ w`` with the ACTIVATION cast to the weight's dtype and the
    accumulator named: ``out`` (float32 where the caller reads the product
    unrounded), else the weight's dtype (``jamba``, ``nemotron_h``)."""
    return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=out or w.dtype)


def embed(params, tokens, c):
    with scopes.layer("embed"):
        return params["embed"][tokens].astype(c.dtype)


def final_norm(x, params, c):
    with scopes.layer("head"):
        return rms_norm(x, params["norm"], c.norm_eps)


def head_logits(x, params, c, last=None):
    """The output head; ``last`` [B] (true lengths) picks each row's last real
    token of ``x`` [B, T, D] first."""
    with scopes.layer("head"):
        if last is not None:
            x = x[jnp.arange(x.shape[0]), last - 1]
        head = params["embed"].T if c.tie_embeddings else params["lm_head"]
        return (x.astype(c.dtype) @ head.astype(c.dtype)).astype(jnp.float32)


def layer_row(tree, i):
    """Row ``i`` of every leaf: one layer's weights out of its kind's stack."""
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def kv_pool(cache: dict) -> dict:
    """The cache's pool: every leaf but the family's ``state``."""
    return {k: v for k, v in cache.items() if k != "state"}


def row_positions(lengths, starts, T):
    """-> (positions [B, T], -1 past a row's length; valid [B, T])."""
    ar = jnp.arange(T)
    valid = ar[None, :] < lengths[:, None]
    return jnp.where(valid, starts[:, None] + ar[None, :], -1), valid


def rows_ctx(lengths, starts, snap_at, T):
    """``row_positions`` for a family with state a slot: -> ({positions, valid,
    lengths, snap_rel: where in the row its snapshot is due}, whether one is)."""
    positions, valid = row_positions(lengths, starts, T)
    snap_rel = snap_at - starts
    ctx = {"positions": positions, "valid": valid, "lengths": lengths, "snap_rel": snap_rel}
    return ctx, (snap_rel >= 0) & (snap_rel <= lengths) & (lengths > 0)


def attention_op(h, layer, c, positions, attn_fn, yarn=None, walk="prefill_attention", rope=True):
    """GQA with an RMSNorm over each head of q and of k before rotary
    (``lfm2``, ``mellum``, ``exaone``) -> (Op output, k, v): k and v are the
    layer's new rows for the pool. ``yarn`` (``ops.rope.apply_rope``'s) turns
    q and k by YaRN's frequencies (``mellum``'s full layers). ``walk`` is the
    scope ``attn_fn`` runs under (a decode step's: ``page_walk``; None: it
    opens its own). ``rope`` False leaves q and k unturned (``exaone``'s full
    layers carry no position)."""
    B, T, _ = h.shape
    with jax.named_scope("attn_qkv"):
        q = mm(h, layer["wq"]).reshape(B, T, c.n_heads, c.head_dim)
        k = mm(h, layer["wk"]).reshape(B, T, c.n_kv_heads, c.head_dim)
        v = mm(h, layer["wv"]).reshape(B, T, c.n_kv_heads, c.head_dim)
        q = rms_norm(q, layer["q_norm"], c.norm_eps)
        k = rms_norm(k, layer["k_norm"], c.norm_eps)
        if rope:
            q = apply_rope(q, positions, c.rope_theta, yarn=yarn)
            k = apply_rope(k, positions, c.rope_theta, yarn=yarn)
    with jax.named_scope(walk) if walk else contextlib.nullcontext():
        out = attn_fn(q, k, v)
    with jax.named_scope("attn_out"):
        return mm(out.reshape(B, T, c.n_heads * c.head_dim), layer["wo"]), k, v


def plain_attention_op(h, layer, c, attn_fn, walk="prefill_attention"):
    """GQA with no norm and no position on q and k (``jamba``,
    ``nemotron_h``), its products by ``mm_weight_dtype`` -> (Op output, k, v).
    ``walk`` is the scope ``attn_fn`` runs under (a decode step's: ``page_walk``)."""
    B, T, _ = h.shape
    with jax.named_scope("attn_qkv"):
        q = mm_weight_dtype(h, layer["wq"]).reshape(B, T, c.n_heads, c.head_dim)
        k = mm_weight_dtype(h, layer["wk"]).reshape(B, T, c.n_kv_heads, c.head_dim)
        v = mm_weight_dtype(h, layer["wv"]).reshape(B, T, c.n_kv_heads, c.head_dim)
    with jax.named_scope(walk):
        out = attn_fn(q, k, v)
    with jax.named_scope("attn_out"):
        return mm_weight_dtype(out.reshape(B, T, c.n_heads * c.head_dim), layer["wo"]), k, v


def key_positions(starts, positions, held: int):
    """[B, held + T]: the position each key of a continuation holds, -1 none:
    a table's ``held`` cached rows before ``starts``, then the rows' own."""
    row_pos = jnp.arange(held)
    cache_pos = jnp.where(row_pos[None, :] < starts[:, None], row_pos[None, :], -1)
    return jnp.concatenate([cache_pos, positions], axis=1)


def over_pages(q, k, v, pool, tables, a, n_kv_heads: int, positions, key_pos, attend=continue_attention, **kw):
    """``q`` at ``positions`` over K/V layer ``a``'s (traced) gathered pages
    (``tables`` [B, M], the whole table's whatever the start) and the rows'
    own ``k`` and ``v``, the keys at ``key_pos``, by ``attend``
    (``ops.attention.continue_attention``'s form; ``kw``: its ``window``)."""
    B = q.shape[0]
    ids = layer_tables(tables, a, pool["k"].shape[1])
    k_rows = gather_pages(pool, "k", ids, k.dtype, n_kv_heads).reshape(B, -1, *k.shape[2:])
    v_rows = gather_pages(pool, "v", ids, v.dtype, n_kv_heads).reshape(B, -1, *v.shape[2:])
    return attend(q, jnp.concatenate([k_rows, k], axis=1), jnp.concatenate([v_rows, v], axis=1), positions, key_pos, **kw)


def prefix_attention(pool, block_tables, starts, positions, n_kv_heads: int, attend=continue_attention):
    """-> ``make_attn(a)``: a continuation's attention of K/V layer ``a``:
    rows that start at ``starts`` over their prefix pages plus themselves."""
    key_pos = key_positions(starts, positions, block_tables.shape[1] * pool["k"].shape[2])
    return lambda a: lambda q, k, v: over_pages(q, k, v, pool, block_tables, a, n_kv_heads, positions, key_pos, attend)


def page_walk(pool, block_tables, seq_lens, use_pallas: bool):
    """-> ``make_attn(a)``: a decode step's attention of K/V layer ``a``
    (traced), one query a lane over the lane's pages and the new token's own
    K and V. The walk takes the merged pool as it is; the XLA reference splits
    the heads on what it gathers (and reads int8 pages' scale twins)."""
    NP = pool["k"].shape[1]
    k_flat, v_flat = flat_pages(pool["k"]), flat_pages(pool["v"])
    scales = (flat_pages(pool["ks"]), flat_pages(pool["vs"])) if "ks" in pool else (None, None)

    def make_attn(a):
        def attn(q, k, v):
            args = (q[:, 0], k_flat, v_flat, layer_tables(block_tables, a, NP), seq_lens, k[:, 0], v[:, 0])
            if use_pallas:
                from ..ops.pallas.paged_attention import paged_decode_attention_cache_plus_new

                return paged_decode_attention_cache_plus_new(*args)[:, None]
            return paged_decode_attention_reference_cache_plus_new(*args, k_scales=scales[0], v_scales=scales[1])[:, None]

        return attn

    return make_attn


def segments(kinds: tuple[str, ...]) -> list[tuple[int, tuple[tuple[str, int], ...]]]:
    """``kinds`` in order as stretches ``(periods, runs)``: ``runs`` is one
    period as runs of one kind ``(kind, layers)``. The stretch that covers
    most layers by repeating a period at least twice (the shortest such
    period, the earliest such stretch) is taken first, then what stands
    before and after it in the same way; a layer that repeats nothing is a
    stretch of one period of one layer."""
    n = len(kinds)
    best = None  # (layers covered, -period, -start) the larger the better
    for span in range(1, n // 2 + 1):
        for start in range(n - 2 * span + 1):
            reps = 1
            while kinds[start + reps * span:start + (reps + 1) * span] == kinds[start:start + span]:
                reps += 1
            if reps > 1 and (best is None or (reps * span, -span, -start) > best[0]):
                best = ((reps * span, -span, -start), start, span, reps)
    if best is None:
        return [(1, ((kind, 1),)) for kind in kinds]
    _, start, span, reps = best
    runs = tuple((kind, len(list(group))) for kind, group in itertools.groupby(kinds[start:start + span]))
    return segments(kinds[:start]) + [(reps, runs)] + segments(kinds[start + reps * span:])


def _stack(parts: list):
    return jax.tree_util.tree_map(lambda *a: jnp.concatenate(a, axis=0), *parts)


def _run(layer, kind: str, n: int, carry, index, at):
    """``n`` layers of ``kind`` from place ``index`` and row ``at`` on: one
    written out, more as a scan -> (carry, the layers' ``out`` stacked)."""
    i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    if n == 1:
        carry, out = layer[kind](carry, i32(index), i32(at))
        return carry, jax.tree_util.tree_map(lambda a: a[None], out)
    return jax.lax.scan(lambda carry, j: layer[kind](carry, i32(index + j), i32(at + j)), carry,
                        jnp.arange(n, dtype=jnp.int32))


def _stretch(layer, reps: int, runs, carry, at: int, done: dict):
    """``reps`` periods of ``runs`` (``segments``) from place ``at`` on,
    ``done[kind]`` layers of each kind before them: one period written out,
    more as a scan over periods -> (carry, {kind: ``out`` stacked})."""
    span = sum(n for _, n in runs)
    each = {kind: sum(n for k, n in runs if k == kind) for kind, _ in runs}

    def period(carry, p):
        index, nth = at + p * span, {kind: done[kind] + p * each[kind] for kind in each}
        got: dict[str, list] = {}
        for kind, n in runs:
            carry, out = _run(layer, kind, n, carry, index, nth[kind])
            got.setdefault(kind, []).append(out)
            index, nth[kind] = index + n, nth[kind] + n
        return carry, {kind: _stack(parts) for kind, parts in got.items()}

    if reps == 1:
        return period(carry, 0)
    carry, out = jax.lax.scan(period, carry, jnp.arange(reps, dtype=jnp.int32))
    return carry, jax.tree_util.tree_map(lambda a: a.reshape((-1,) + a.shape[2:]), out)


def scan_layers(kinds: tuple[str, ...], carry, layer):
    """Run ``kinds`` in order as ``segments`` lays them out. ``layer(kind,
    carry, index, row) -> (carry, out)`` is one layer: ``index`` () int32
    its place in ``kinds`` and ``row`` its place among the layers of its
    kind, a loop's counters or constants. Every loop body has one kind. A
    kind's layer is traced and lowered ONCE a program however many loops and
    written-out places run it (a ``jax.jit`` a kind: the places call one
    function, which the compiler inlines; the published ``lfm2`` pattern has
    four places for two kinds, and a place costs its expert FF's trace). ->
    (carry, {kind: ``out`` stacked over the kind's layers in order}; a kind
    without layers is not there)."""
    outs: dict[str, list] = {}
    at, done = 0, dict.fromkeys(kinds, 0)
    bodies = {kind: jax.jit(functools.partial(layer, kind)) for kind in done}
    for reps, runs in segments(tuple(kinds)):
        carry, out = _stretch(bodies, reps, runs, carry, at, done)
        for kind, n in runs:
            done[kind] += reps * n
            at += reps * n
        for kind, part in out.items():
            outs.setdefault(kind, []).append(part)
    return carry, {kind: _stack(parts) for kind, parts in outs.items()}
