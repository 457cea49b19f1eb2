"""Paged KV cache: page-table layout + pure-XLA reference ops.

The north star calls for a paged KV cache: KV lives in fixed-size pages and
each sequence owns a page list (block table), so HBM is allocated
page-granular instead of max-context-granular — at 64 slots x 8k max context
the slot layout wastes whatever contexts don't use, the paged layout doesn't.

The pool is stored as the page walk reads it, for every model family:
``[L, num_pages, page_size, H_kv * d]`` per k/v, a page's row its KV heads
side by side (head-major). That is the walk's DMA source as it is; a pool
with the heads on an axis of their own is relaid on the chip's tiling every
time they are merged, a copy of a layer's whole pool a call (PERF.md, PR 31
and PR 33). A program hands the walk the whole pool flattened over its
layers (:func:`flat_pages`, a reshape of leading axes only) and block tables
offset by the layer (:func:`layer_tables`), and commits through the same
flat view (:func:`commit_whole_pages`, :func:`commit_tokens`): a scatter
windowed over the layer axis makes the compiler keep the pool layer-minor
and relay all of it.

Two families walk by another unit and keep a row of their own
(:func:`init_row_pages`), on the same page ids and through the same helpers.
Latent attention (``models/kanana.py``) keeps one leaf ``kv`` whose row is
key and value at once. Attention over rows CHOSEN by a learned indexer
(``models/keye.py``) keeps ``kv``, a token's K row and V row as ONE row of
32-bit words (:func:`pack_kv_rows`: a chosen position's K and V are one slice
of one gather, at the price of K's alone), and beside it ``ik``, the
indexer's key a token. ``models/dots.py`` keeps three leaves of three widths:
its full layers' latent row ``kv`` and their indexer's key ``ik`` on the page
list, and its sliding layers' latent row ``wkv`` in a ring a slot.

A layer that attends over a window keeps a **ring** a slot instead of a
page list (``ring_*`` below, ``models/mellum.py``): ``ring`` pages of a pool
of its own, fixed to the slot (slot ``s`` owns pages ``s * ring ..``, the
slot after the last is where padding lanes write), position ``p`` in ring
page ``(p // P) % ring``. With ``ring = window / P + 1`` the ``window`` rows
a query sees never touch one ring page twice, however long the context: the
cache of such a layer is ``window + P`` rows a slot, its table never changes
and nothing is allocated or freed while a request runs.

This module is the *reference* implementation (pure jnp gather/scatter,
exact); ``ops.pallas.paged_attention`` is the TPU kernel that walks block
tables with HBM->VMEM DMAs instead of materializing gathers. Page 0 is
reserved as the trash page: padded writes land there, nothing reads it.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..observability import scopes
from .attention import NEG_INF
from .quant import kv_dequantize, kv_quantize

TRASH_PAGE = 0


def init_kv_pages(
    n_layers: int, num_pages: int, page_size: int, n_kv_heads: int, head_dim: int,
    dtype, quantize: bool = False,
) -> dict:
    """Page pools [L, NP, P, H_kv * d] per k/v (the module's text). With
    ``quantize`` the values are int8 and per-row-per-head f32 scales ride
    page-shaped twins ("ks"/"vs", [L, NP, P, H_kv]) indexed by the SAME page
    ids — scale storage is allocated, shared, swapped, and freed with its
    pages."""
    rows = (n_layers, num_pages, page_size)
    width = n_kv_heads * head_dim
    if quantize:
        return {
            "k": jnp.zeros(rows + (width,), dtype=jnp.int8),
            "v": jnp.zeros(rows + (width,), dtype=jnp.int8),
            "ks": jnp.zeros(rows + (n_kv_heads,), dtype=jnp.float32),
            "vs": jnp.zeros(rows + (n_kv_heads,), dtype=jnp.float32),
        }
    return {"k": jnp.zeros(rows + (width,), dtype=dtype), "v": jnp.zeros(rows + (width,), dtype=dtype)}


def flat_pages(a: jax.Array) -> jax.Array:
    """A pool leaf ``[L, NP, ...]`` as ``[L * NP, ...]``: page ``p`` of layer
    ``l`` is page ``l * NP + p`` (:func:`layer_tables`)."""
    return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])


def layer_tables(page_ids: jax.Array, layer, num_pages: int) -> jax.Array:
    """Page ids of one layer's pool as ids of the flattened pool."""
    return page_ids + layer * num_pages


def set_pages(arr: jax.Array, page_ids: jax.Array, blocks: jax.Array) -> jax.Array:
    """``arr[:, page_ids] = blocks`` ([L, n, P, ...]) as one scatter of whole
    pages into the pool flattened over its layers."""
    L, NP = arr.shape[:2]
    with scopes.layer("commit"):
        ids = layer_tables(page_ids.reshape(-1)[None, :], jnp.arange(L)[:, None], NP).reshape(-1)
        flat = flat_pages(arr).at[ids].set(blocks.reshape((ids.shape[0],) + arr.shape[2:]))
        return flat.reshape(arr.shape)


def init_row_pages(n_layers: int, num_pages: int, page_size: int, **leaves: tuple) -> dict:
    """A pool of leaves ``{name: [L, NP, P, width]}`` for a family whose row
    is its own (``leaves``: ``name=(width, dtype)``): no ``"k"`` / ``"v"``
    pair, no scale twin. Every helper below takes a pool's leaves as they
    come, so these are committed, gathered, shared, swapped and restored as
    the ``k`` / ``v`` pools are."""
    return {name: jnp.zeros((n_layers, num_pages, page_size, width), dtype=dtype) for name, (width, dtype) in leaves.items()}


def pack_kv_rows(k: jax.Array, v: jax.Array) -> jax.Array:
    """A K row and a V row ``[..., n]`` of one dtype (2 or 4 bytes wide) as
    ONE row of 32-bit words ``[..., n * itemsize / 2]`` uint32: word ``j``
    holds the ``j``-th 16 bits of K's row in its low half and of V's row in
    its high half, bit for bit (:func:`unpack_kv_rows` gives both back). On
    the chip a bfloat16 array packs two ROWS a 32-bit sublane word, so a
    gather of one bfloat16 row of ``2n`` values touches ``2n / 128`` lane
    tiles and costs by them, twice a row of ``n``; this row is ``n / 128``
    tiles of whole words and is fetched at the price of K's alone (PERF.md,
    PR 60)."""
    def halves(t):  # [..., n] -> uint16 [..., n * itemsize / 2] (a wider dtype comes apart on a minor axis)
        return jax.lax.bitcast_convert_type(t, jnp.uint16).reshape(t.shape[:-1] + (-1,)).astype(jnp.uint32)

    return halves(k) | (halves(v) << 16)


def unpack_kv_rows(words: jax.Array, dtype) -> tuple[jax.Array, jax.Array]:
    """:func:`pack_kv_rows`' rows back: uint32 ``[..., m]`` -> (K, V), each
    ``[..., 2 * m / itemsize]`` of ``dtype``, the values that were packed."""
    per = jnp.dtype(dtype).itemsize // 2

    def whole(u):  # uint16 [..., m] -> dtype: `per` halves a value, joined on a minor axis
        return jax.lax.bitcast_convert_type(u.reshape(u.shape[:-1] + (-1, per)) if per > 1 else u, dtype)

    return whole((words & 0xFFFF).astype(jnp.uint16)), whole((words >> 16).astype(jnp.uint16))


def init_latent_pages(n_layers: int, num_pages: int, page_size: int, width: int, dtype) -> dict:
    """A pool of ONE leaf ``"kv"`` ``[L, NP, P, width]``: a row a token that
    is key and value at once (latent attention, ``models/kanana.py``: the
    normed latent and the roped shared key side by side, one head of
    ``width``)."""
    return init_row_pages(n_layers, num_pages, page_size, kv=(width, dtype))


def pool_leaves(cache: dict) -> dict:
    """The page-shaped leaves of a cache: all but what a family keeps beside
    them under ``"state"``."""
    return {name: a for name, a in cache.items() if name != "state"}


def page_bytes(cache: dict, num_pages: int) -> int:
    """Bytes one page costs over every cache layer and page-shaped leaf of
    ``cache`` (arrays or their shapes; scale twins among them): read off the
    leaves, whose axis 0 is the pool's depth whatever the config's layer
    count is. A leaf with another page count (a window ring's pool) is not
    the page list's."""
    return sum(a.size * a.dtype.itemsize for a in pool_leaves(cache).values() if a.shape[1] == num_pages) // num_pages


def kv_commit(pool: dict, new: dict, setter) -> dict:
    """Fresh rows ``{leaf: [L, ..., heads, d]}`` into the pool through
    ``setter(array, values)``, leaf by leaf: heads merged into the pool's
    row, a leaf with a scale twin (``name + "s"``: int8 pools) quantized
    here a row and head, its scales through the same setter."""
    merge = lambda t: t.reshape(t.shape[:-2] + (t.shape[-2] * t.shape[-1],))  # noqa: E731
    out = {}
    for name, rows in new.items():
        if name + "s" in pool:
            q, scales = kv_quantize(rows)
            out[name] = setter(pool[name], merge(q))
            out[name + "s"] = setter(pool[name + "s"], scales)
        else:
            out[name] = setter(pool[name], merge(rows).astype(pool[name].dtype))
    return out


def commit_whole_pages(pool: dict, new: dict, page_ids: jax.Array) -> dict:
    """``new`` ``{leaf: [L, B, T, heads, d]}`` into pages ``page_ids`` [B, T
    // P]: the one whole-page write of every prefill, continuation and mid
    chunk."""
    with scopes.layer("commit"):
        return kv_commit(pool, new, lambda arr, val: set_pages(arr, page_ids, val))


def commit_tokens(pool: dict, new: dict, pages: jax.Array, offsets: jax.Array) -> dict:
    """``new`` ``{leaf: [L, ..., heads, d]}`` a token at a time into row
    ``offsets`` of page ``pages`` (each [...]: a decode step's lanes, a
    verify pass's [B, T]): one scatter of token rows into the pool flattened
    over its layers. The within-page axis stays an axis of its own, so a
    pool that shards it (context-parallel serving) is not gathered to be
    written."""
    L, NP = next(iter(pool.values())).shape[:2]
    with scopes.layer("commit"):
        layer = jnp.arange(L).reshape((L,) + (1,) * pages.ndim)
        ids = layer_tables(pages[None], layer, NP)
        rows = jnp.broadcast_to(offsets[None], ids.shape)
        return kv_commit(pool, new,
                         lambda arr, val: flat_pages(arr).at[ids, rows].set(val).reshape(arr.shape))


def gather_pages(pool: dict, name: str, ids: jax.Array, dtype, n_kv_heads: int) -> jax.Array:
    """Pages ``ids`` (any shape; ids of the flattened pool) of leaf ``name``
    with the heads apart ``[..., P, H_kv, d]``: only what is gathered is
    split, and int8 pages are dequantized by their scale twins."""
    rows = flat_pages(pool[name])[ids]
    rows = rows.reshape(rows.shape[:-1] + (n_kv_heads, rows.shape[-1] // n_kv_heads))
    if name + "s" in pool:
        return kv_dequantize(rows, flat_pages(pool[name + "s"])[ids], dtype)
    return rows.astype(dtype)


def ring_size(window: int, page_size: int) -> int:
    """Pages of a slot's ring: the window's rows and one page of slack, so
    that the page being written is never one the window still reads."""
    if window % page_size:
        raise ValueError(f"page_size {page_size} must divide the window {window}")
    return window // page_size + 1


def ring_tables(slots: jax.Array, ring: int) -> jax.Array:
    """[B, ring] page ids of the window pool: slot ``s`` owns ``s * ring ..``."""
    return slots[:, None] * ring + jnp.arange(ring, dtype=jnp.int32)[None, :]


def ring_positions(written: jax.Array, ring: int, page_size: int) -> jax.Array:
    """[B, ring * P]: the position whose K/V each row of a slot's ring holds
    once rows ``0 .. written - 1`` of the sequence were committed (-1: none
    yet; rows at and past ``written`` in the newest page are stale and read
    as positions >= ``written``, which a caller masks as it masks any row
    not written)."""
    last = (written - 1) // page_size  # the newest page of the sequence, -1 for none
    j = jnp.arange(ring, dtype=jnp.int32)[None, :]
    page = last[:, None] - jnp.mod(last[:, None] - j, ring)  # newest page <= last that sits at j
    pos = page[:, :, None] * page_size + jnp.arange(page_size, dtype=jnp.int32)[None, None, :]
    pos = jnp.where((page >= 0)[:, :, None] & (written > 0)[:, None, None], pos, -1)
    return pos.reshape(written.shape[0], ring * page_size)


def ring_newest(slots: jax.Array, starts: jax.Array, lengths: jax.Array, T: int, page_size: int,
                ring: int, pad_slot: int):
    """What a prefill, a continuation or a mid chunk leaves of a window
    layer: the newest ``ring`` whole pages of rows ``starts .. starts +
    lengths`` (``starts`` page-aligned) of its ``T`` fresh rows. -> (``take``:
    a function from fresh K or V ``[B, T, ...]`` to those pages' rows ``[B,
    n * P, ...]``, to be applied inside the layer scan so that the scan
    stacks a ring's worth of rows a layer and not the bucket's; ``ids`` [B,
    n]: the pages of the window pool they go to, in the rows' slots' rings).
    Pages of a row that are older, or that hold no token, go to slot
    ``pad_slot``'s ring, which nothing reads."""
    P = page_size
    n = min(ring, T // P)
    last = (lengths - 1) // P  # [B] the row's newest page, -1 for an empty row
    local = last[:, None] - jnp.arange(n - 1, -1, -1, dtype=jnp.int32)[None, :]  # [B, n] oldest first
    live = local >= 0
    at = jnp.mod(starts[:, None] // P + local, ring)
    ids = jnp.where(live, slots[:, None], pad_slot) * ring + at
    chosen = jnp.clip(local, 0, T // P - 1)

    def take(t):
        B = t.shape[0]
        pages = t.reshape(B, T // P, P, *t.shape[2:])
        idx = chosen.reshape((B, n) + (1,) * (pages.ndim - 2))
        return jnp.take_along_axis(pages, idx, axis=1).reshape(B, n * P, *t.shape[2:])

    return take, ids


def write_prompt_to_pages(
    k_pages: jax.Array,  # [num_pages, P, ...] (one layer; any trailing axes)
    v_pages: jax.Array,
    page_ids: jax.Array,  # [max_prompt_pages] int32 — TRASH_PAGE beyond prompt
    k_new: jax.Array,  # [T, ...], T = max_prompt_pages * P (padded)
    v_new: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    P = k_pages.shape[1]
    T = k_new.shape[0]
    k_blocks = k_new.reshape(T // P, P, *k_new.shape[1:]).astype(k_pages.dtype)
    v_blocks = v_new.reshape(T // P, P, *v_new.shape[1:]).astype(v_pages.dtype)
    return k_pages.at[page_ids].set(k_blocks), v_pages.at[page_ids].set(v_blocks)


def write_token_to_pages(
    k_pages: jax.Array,  # [num_pages, P, ...]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [S, max_pages] int32
    positions: jax.Array,  # [S] int32 — token position per slot
    active: jax.Array,  # [S] bool — inactive slots write to the trash page
    k_new: jax.Array,  # [S, ...]
    v_new: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    P = k_pages.shape[1]
    S = positions.shape[0]
    page_idx = positions // P
    offset = positions % P
    pages = block_tables[jnp.arange(S), page_idx]
    pages = jnp.where(active, pages, TRASH_PAGE)
    k_pages = k_pages.at[pages, offset].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[pages, offset].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


def token_write_targets(
    block_tables: jax.Array,  # [S, max_pages] int32
    starts: jax.Array,  # [S] int32 — absolute position of each row's first token
    lengths: jax.Array,  # [S] int32 — valid tokens per row
    page_size: int,
    T: int,  # row width (padded token count)
) -> tuple[jax.Array, jax.Array]:
    """Per-token scatter targets for a multi-token write whose start is NOT
    page-aligned (speculative verify: the draft begins mid-page, inside a
    page that already holds live prefix KV — the page-granular commit of
    ``prefill_paged_continue`` would clobber it). Returns ``(pages [S, T],
    offsets [S, T])``; padded positions (beyond ``lengths``) land on the
    trash page, and page indexes are clamped so bucket padding can never
    gather out of bounds."""
    S = starts.shape[0]
    ar = jnp.arange(T)
    pos = starts[:, None] + ar[None, :]  # [S, T]
    valid = ar[None, :] < lengths[:, None]
    page_idx = jnp.minimum(pos // page_size, block_tables.shape[1] - 1)
    pages = jnp.take_along_axis(block_tables, page_idx, axis=1)
    pages = jnp.where(valid, pages, TRASH_PAGE)
    return pages, pos % page_size


def paged_decode_attention_reference(
    q: jax.Array,  # [S, H, d] — one new token per slot
    k_pages: jax.Array,  # [num_pages, P, H_kv * d], or the heads apart [.., H_kv, d]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [S, max_pages]
    seq_lens: jax.Array,  # [S] — valid tokens per slot (incl. the new one)
    k_scales: Optional[jax.Array] = None,  # [num_pages, P, H_kv] (int8 pools)
    v_scales: Optional[jax.Array] = None,
) -> jax.Array:
    """Exact paged attention by materializing each slot's pages (gather).
    O(S * max_pages * P) HBM traffic + a gathered copy — the thing the
    Pallas kernel avoids. The heads are split on what was gathered (a
    pool's row holds them side by side), never on the pool. With
    ``k_scales``/``v_scales`` the pools are int8 and dequantization happens
    AFTER the gather (only each slot's gathered rows ever exist in float;
    the pool stays int8).

    The (page, offset) axes stay UNMERGED through the whole reduction:
    under context-parallel serving the pools' within-page dim carries the
    mesh's 'sp' axis, and a merge-reshape of (replicated, sharded) axes is
    not GSPMD-representable — it would all-gather the cache. Unmerged, the
    softmax reductions compile to per-shard partials + tiny all-reduces,
    the same pattern as the slot layout's ctx-sharded cache."""
    S, H, d = q.shape
    P = k_pages.shape[1]
    max_pages = block_tables.shape[1]
    k = k_pages[block_tables].reshape(S, max_pages, P, -1, d)  # [S, M, P, H_kv, d]
    v = v_pages[block_tables].reshape(k.shape)
    H_kv = k.shape[3]
    if k_scales is not None:
        k = k.astype(jnp.float32) * k_scales[block_tables][..., None]
        v = v.astype(jnp.float32) * v_scales[block_tables][..., None]
    r = H // H_kv
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    q4 = q.reshape(S, H_kv, r, d).astype(jnp.float32)
    logits = jnp.einsum("skrd,smpkd->smpkr", q4, k.astype(jnp.float32)) * scale
    pos = jnp.arange(max_pages)[:, None] * P + jnp.arange(P)[None, :]  # [M, P]
    mask = pos[None, :, :, None, None] < seq_lens[:, None, None, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=(1, 2))  # [S, H_kv, r]
    p = jnp.exp(logits - m[:, None, None])
    denom = jnp.sum(p, axis=(1, 2))
    out = jnp.einsum("smpkr,smpkd->skrd", p, v.astype(jnp.float32))
    out = out / jnp.maximum(denom, 1e-30)[..., None]
    return out.reshape(S, H, d).astype(q.dtype)


def paged_decode_attention_reference_cache_plus_new(
    q: jax.Array,  # [S, H, d]
    k_pages: jax.Array,  # [num_pages, P, H_kv * d] (or [.., H_kv, d]) — WITHOUT the new token
    v_pages: jax.Array,
    block_tables: jax.Array,  # [S, max_pages]
    seq_lens: jax.Array,  # [S] — tokens valid in the pages (excl. new)
    k_new: jax.Array,  # [S, H_kv, d]
    v_new: jax.Array,
    k_scales: Optional[jax.Array] = None,  # [num_pages, P, H_kv] (int8 pools)
    v_scales: Optional[jax.Array] = None,
    row_positions: Optional[jax.Array] = None,  # [S, max_pages * P]: a ring's (ring_positions)
    starts: Optional[jax.Array] = None,  # [S]: with row_positions, the first position seen
) -> jax.Array:
    """Exact reference for the read-only-pages + self-term decode form (the
    hot-loop shape: pages stay a read-only operand, the new token attends
    via an explicit term, writes happen once per step outside the layer
    scan — see models/llama.py decode_step_paged). With scales, the int8
    pools dequantize after the gather (see
    :func:`paged_decode_attention_reference`); the NEW token's k/v stay
    exact — they are quantized only at the post-scan commit.

    (page, offset) axes stay unmerged — see
    :func:`paged_decode_attention_reference` for why (sp sharding)."""
    S, H, d = q.shape
    P = k_pages.shape[1]
    max_pages = block_tables.shape[1]
    H_kv = k_new.shape[1]
    r = H // H_kv
    k = k_pages[block_tables].reshape(S, max_pages, P, H_kv, d)
    v = v_pages[block_tables].reshape(k.shape)
    if k_scales is not None:
        k = k.astype(jnp.float32) * k_scales[block_tables][..., None]
        v = v.astype(jnp.float32) * v_scales[block_tables][..., None]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    q4 = q.reshape(S, H_kv, r, d).astype(jnp.float32)
    logits = jnp.einsum("skrd,smpkd->smpkr", q4, k.astype(jnp.float32)) * scale
    if row_positions is None:
        pos = jnp.arange(max_pages)[:, None] * P + jnp.arange(P)[None, :]  # [M, P]
        mask = pos[None, :, :, None, None] < seq_lens[:, None, None, None, None]
    else:  # the window walk: a ring's rows hold the positions given, seen from `starts` on
        pos = row_positions.reshape(S, max_pages, P)
        mask = ((pos >= starts[:, None, None]) & (pos < seq_lens[:, None, None]))[..., None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    self_logit = (
        jnp.sum(q4 * k_new.astype(jnp.float32)[:, :, None, :], axis=-1) * scale
    )  # [S, H_kv, r]
    m = jnp.maximum(jnp.max(logits, axis=(1, 2)), self_logit)
    p = jnp.exp(logits - m[:, None, None])
    p_self = jnp.exp(self_logit - m)
    denom = jnp.sum(p, axis=(1, 2)) + p_self
    out = jnp.einsum("smpkr,smpkd->skrd", p, v.astype(jnp.float32))
    out = out + p_self[..., None] * v_new.astype(jnp.float32)[:, :, None, :]
    out = out / jnp.maximum(denom, 1e-30)[..., None]
    return out.reshape(S, H, d).astype(q.dtype)


def paged_verify_attention_reference(
    q: jax.Array,  # [S, R, H, d]: R query rows a lane, row r at position seq_lens + r
    k_pages: jax.Array,  # [num_pages, P, H_kv * d] (or [.., H_kv, d]) — WITHOUT the new rows
    v_pages: jax.Array,
    block_tables: jax.Array,  # [S, max_pages]: one table for all of a lane's rows
    seq_lens: jax.Array,  # [S] — tokens valid in the pages (excl. the new rows)
    k_new: jax.Array,  # [S, R, H_kv, d]
    v_new: jax.Array,
    new_valid: Optional[jax.Array] = None,  # [S, R] bool: a new row that is no key (None: all are)
    row_positions: Optional[jax.Array] = None,  # [S, max_pages * P]: a ring's (ring_positions)
    starts: Optional[jax.Array] = None,  # [S, R]: with row_positions, the first position row r sees
) -> jax.Array:
    """Exact reference for a verify step's attention: the pages read-only,
    row ``r`` of a lane over the cached rows and the new rows ``0 .. r`` (the
    second row sees the first's K/V, which no page holds yet). One gather of
    a lane's pages serves all its rows. -> [S, R, H, d]."""
    S, R, H, d = q.shape
    P = k_pages.shape[1]
    max_pages = block_tables.shape[1]
    H_kv = k_new.shape[2]
    r = H // H_kv
    k = k_pages[block_tables].reshape(S, max_pages, P, H_kv, d)
    v = v_pages[block_tables].reshape(k.shape)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    q5 = q.reshape(S, R, H_kv, r, d).astype(jnp.float32)
    logits = jnp.einsum("sjkrd,smpkd->sjmpkr", q5, k.astype(jnp.float32)) * scale
    if row_positions is None:
        pos = jnp.arange(max_pages)[:, None] * P + jnp.arange(P)[None, :]  # [M, P]
        mask = jnp.broadcast_to((pos[None] < seq_lens[:, None, None])[:, None], (S, R, max_pages, P))
    else:
        pos = row_positions.reshape(S, 1, max_pages, P)
        mask = (pos >= starts[:, :, None, None]) & (pos < seq_lens[:, None, None, None])
    logits = jnp.where(mask[..., None, None], logits, NEG_INF)
    fresh = jnp.einsum("sjkrd,sikd->sjikr", q5, k_new.astype(jnp.float32)) * scale  # row j over new row i
    seen = jnp.arange(R)[None, :, None] >= jnp.arange(R)[None, None, :]  # [1, j, i]
    if new_valid is not None:
        seen = seen & new_valid[:, None, :]
    fresh = jnp.where(seen[..., None, None], fresh, NEG_INF)
    m = jnp.maximum(jnp.max(logits, axis=(2, 3)), jnp.max(fresh, axis=2))  # [S, R, H_kv, r]
    p = jnp.where(mask[..., None, None], jnp.exp(logits - m[:, :, None, None]), 0.0)
    p_new = jnp.where(seen[..., None, None], jnp.exp(fresh - m[:, :, None]), 0.0)
    denom = jnp.sum(p, axis=(2, 3)) + jnp.sum(p_new, axis=2)
    out = jnp.einsum("sjmpkr,smpkd->sjkrd", p, v.astype(jnp.float32))
    out = out + jnp.einsum("sjikr,sikd->sjkrd", p_new, v_new.astype(jnp.float32))
    out = out / jnp.maximum(denom, 1e-30)[..., None]
    return out.reshape(S, R, H, d).astype(q.dtype)


def latent_decode_attention_reference_cache_plus_new(
    q: jax.Array,  # [S, H, width]: every head's query against the whole row
    pages: jax.Array,  # [num_pages, P, width] — a latent pool's one leaf, WITHOUT the new token
    block_tables: jax.Array,  # [S, max_pages]
    seq_lens: jax.Array,  # [S] — tokens valid in the pages (excl. new)
    row_new: jax.Array,  # [S, width]: the new token's row
    value_width: int,  # the row's first columns are the value
    score_dim: int,  # softmax scale: score_dim ** -0.5
) -> jax.Array:
    """Exact reference of the latent walk (``ops.pallas.paged_attention``'s
    ``paged_latent_walk``) in the same absorbed form: a row a token shared
    by all heads, key as it stands and value in its first ``value_width``
    columns; the gathered rows are never expanded a head. -> [S, H,
    value_width] in q's dtype."""
    S, H, width = q.shape
    rows = pages[block_tables].reshape(S, -1, width).astype(jnp.float32)  # [S, M * P, width]
    qf, new = q.astype(jnp.float32), row_new.astype(jnp.float32)
    scale = score_dim ** -0.5
    prec = jax.lax.Precision.HIGHEST
    logits = jnp.einsum("shw,stw->sht", qf, rows, precision=prec) * scale
    mask = jnp.arange(rows.shape[1])[None, None, :] < seq_lens[:, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    self_logit = jnp.einsum("shw,sw->sh", qf, new, precision=prec) * scale
    m = jnp.maximum(jnp.max(logits, axis=-1), self_logit)
    p, p_self = jnp.exp(logits - m[..., None]), jnp.exp(self_logit - m)
    out = jnp.einsum("sht,stv->shv", p, rows[..., :value_width], precision=prec)
    out = out + p_self[..., None] * new[:, None, :value_width]
    return (out / (jnp.sum(p, axis=-1) + p_self)[..., None]).astype(q.dtype)


def chosen_rows(ik_pages, kv_rows_per_page: int, block_tables, seq_lens, new_ik, qi, wi, topk: int, given=None,
                interpret: bool = False):
    """What both decode steps over rows CHOSEN by a learned indexer share
    (:func:`sparse_decode_attention_reference_cache_plus_new`,
    :func:`sparse_latent_decode_attention_cache_plus_new`): every cached row
    of a lane scored through the ``ik`` leaf with the new token's own score in
    its place (``index_scores``), the ``topk`` of largest score chosen
    (``index_select``; a lane with fewer rows takes them all, its list padded
    and masked; ``given`` [S, topk] int32 positions, -1 none, is a choice
    handed in); where each chosen position lies in the pool flattened over
    pages and rows is :func:`chosen_flat_rows`'. The choice is a SET (the
    walk's softmax is over one): on a TPU (or ``interpret``: tests) the
    kernel ``ops.pallas.index_select`` finds it by a threshold and hands it in
    ascending position, no sort; off the TPU ``ops.attention.topk_rows``
    (``jax.lax.top_k``, by score) finds the same set, ties and all. ->
    (positions [S, topk] int32, chosen [S, topk] bool, tied [S] bool: lanes
    whose threshold had more rows at it than room, so that the tie rule, the
    earlier row, decided)."""
    from .attention import index_scores, topk_rows

    S, P = block_tables.shape[0], kv_rows_per_page
    C = block_tables.shape[1] * P
    if given is not None:
        return jnp.maximum(given, 0), given >= 0, jnp.zeros((S,), bool)
    pos = jnp.arange(C, dtype=jnp.int32)
    valid = pos[None] <= seq_lens[:, None]
    with jax.named_scope("index_scores"):
        rows = ik_pages[block_tables].reshape(S, C, -1)  # the lane's whole table, as the dense reference gathers
        cached = index_scores(qi[:, None], wi[:, None], rows)[:, 0]  # [S, C]
        own = index_scores(qi[:, None], wi[:, None], new_ik[:, None])[:, 0]  # [S, 1]
        scores = jnp.where(pos[None] == seq_lens[:, None], own, cached)
    with jax.named_scope("index_select"):
        k = min(topk, C)
        want = jnp.minimum(k, seq_lens + 1)
        if interpret or jax.default_backend() == "tpu":
            from .pallas.index_select import index_select

            chosen_pos, tied = index_select(jnp.where(valid, scores, -jnp.inf), want, k, interpret=interpret)
            chosen = jnp.arange(k, dtype=jnp.int32)[None] < want[:, None]
            chosen_pos = jnp.where(chosen, chosen_pos, 0)
        else:
            chosen_pos, chosen = topk_rows(scores, valid, topk)
            # the least score chosen is the threshold: more rows at or above it than the lane takes
            least = jnp.min(jnp.where(chosen, jnp.take_along_axis(scores, chosen_pos, axis=1), jnp.inf), axis=-1, keepdims=True)
            tied = jnp.sum(valid & (scores >= least), axis=-1) > want
    return chosen_pos, chosen, tied


def chosen_flat_rows(chosen_pos, chosen, block_tables, seq_lens, P: int):
    """(row of the pool flattened over pages and rows that holds each chosen
    position, which chosen position is the new token's own): the page by a
    compare against the table's own index and a masked sum, which fuse to one
    pass (a gather of 32,768 single ids took 0.29 ms a layer, two thirds of
    the choice itself)."""
    hit = (chosen_pos // P)[:, :, None] == jnp.arange(block_tables.shape[1], dtype=chosen_pos.dtype)
    page = jnp.sum(jnp.where(hit, block_tables[:, None, :], 0), axis=-1)
    return page * P + chosen_pos % P, (chosen_pos == seq_lens[:, None]) & chosen


def sparse_decode_attention_reference_cache_plus_new(
    q: jax.Array,  # [S, H, d]
    pool: dict,  # {"kv": [L * NP, P, words] uint32 (pack_kv_rows), "ik": [L * NP, P, width]} (:func:`flat_pages`), WITHOUT the new token
    block_tables: jax.Array,  # [S, max_pages]: ids of the flattened pool (:func:`layer_tables`)
    seq_lens: jax.Array,  # [S] — tokens valid in the pages (excl. new)
    new: dict,  # {"kv": [S, words] uint32 (pack_kv_rows), "ik": [S, index width as stored]}: the new token's rows
    qi: jax.Array,  # [S, Hi, c]: the indexer's queries, roped
    wi: jax.Array,  # [S, Hi]: its heads' weights
    topk: int,
    given: Optional[jax.Array] = None,  # [S, topk] int32 positions, -1 none: a choice given, not made
    interpret: bool = False,  # the choice's kernel interpreted (tests)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """A decode step of attention over rows CHOSEN by a learned indexer
    (``models/keye.py``), the new token's own row among the candidates:
    every cached row of a lane is scored through ``ik`` (``index_scores``: a
    gather of the lane's pages of the small leaf, 128 B a row where K and V
    are 2 KiB), the ``topk`` of largest score are chosen (``index_select``:
    :func:`chosen_rows`: a threshold held on the chip, no sort; a lane with
    fewer rows takes them all, its list padded and masked: one program either
    side of ``topk``), and K and
    V are fetched BY ROW through the block table (``sparse_walk``: a chosen
    position ``p`` is row ``table[p // P] * P + p % P`` of the pool
    flattened over pages and rows; at one row in six to thirteen chosen
    nearly every page holds one, so a walk by pages would read what the
    dense walk reads) and attended densely, grouped. The family's pool holds
    a token's K row and V row as ONE row of words of the leaf ``kv``
    (:func:`pack_kv_rows`): they are always chosen together, and XLA's gather
    costs by the slice and the lane tiles it touches, not by its bytes, so
    the walk is ONE gather of ``[S, topk, words]``, taken apart after the
    new token's row went in (PERF.md, PR 60). ``q``'s dtype is the rows'. ->
    (out [S, H, d], positions chosen [S, topk] int32 in no order a caller may
    count on, -1 where a lane had fewer, lanes whose choice the tie rule
    decided [S] bool)."""
    S, H, d = q.shape
    P = pool["kv"].shape[1]
    chosen_pos, chosen, tied = chosen_rows(pool["ik"], P, block_tables, seq_lens, new["ik"], qi, wi, topk, given, interpret)
    with jax.named_scope("sparse_walk"):
        flat_row, is_new = chosen_flat_rows(chosen_pos, chosen, block_tables, seq_lens, P)
        leaf = pool["kv"]
        got = leaf.reshape((leaf.shape[0] * P, leaf.shape[2]))[flat_row]  # [S, topk, words]
        got = jnp.where(is_new[..., None], new["kv"][:, None, :], got)
        k, v = (t.reshape(S, t.shape[1], -1, d) for t in unpack_kv_rows(got, q.dtype))
        H_kv = k.shape[2]
        q4 = q.reshape(S, H_kv, H // H_kv, d)
        logits = jnp.einsum("skrd,snkd->skrn", q4, k, preferred_element_type=jnp.float32) * (d ** -0.5)
        logits = jnp.where(chosen[:, None, None, :], logits, NEG_INF)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("skrn,snkd->skrd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return out.reshape(S, H, d).astype(q.dtype), jnp.where(chosen, chosen_pos, -1), tied


def sparse_latent_decode_attention_cache_plus_new(
    q: jax.Array,  # [S, H, width]: every head's absorbed query against the whole row
    pool: dict,  # {"kv": [L * NP, P, width] latent rows, "ik": [L * NP, P, index width]} (:func:`flat_pages`), WITHOUT the new token
    block_tables: jax.Array,  # [S, max_pages]: ids of the flattened pool (:func:`layer_tables`)
    seq_lens: jax.Array,  # [S] — tokens valid in the pages (excl. new)
    new: dict,  # {"kv": [S, width], "ik": [S, index width]}: the new token's rows
    qi: jax.Array,  # [S, Hi, c]: the indexer's queries, roped
    wi: jax.Array,  # [S, Hi]: its heads' weights
    topk: int,
    value_width: int,  # the row's first columns are the value
    score_dim: int,  # softmax scale: score_dim ** -0.5
    given: Optional[jax.Array] = None,  # [S, topk] int32 positions, -1 none: a choice given, not made
    interpret: bool = False,  # the choice's kernel interpreted (tests)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """A decode step of LATENT attention over rows chosen by a learned
    indexer (``models/dots.py``): :func:`sparse_decode_attention_reference_cache_plus_new`'s
    scoring and choice (``index_scores``, ``index_select``), then the chosen
    LATENT rows fetched by row through the block table and attended in
    absorbed form (``sparse_latent``): one row a token shared by all heads,
    key as it stands and value in its first ``value_width`` columns, never
    expanded a head (:func:`latent_decode_attention_reference_cache_plus_new`'s
    form over a list of rows). One gather of ``[S, topk, width]`` and two
    products whose operands are the rows' dtype, accumulated in float32. ->
    (out [S, H, value_width] in q's dtype, positions chosen [S, topk] int32,
    -1 where a lane had fewer, lanes whose choice the tie rule decided [S]
    bool)."""
    P = pool["kv"].shape[1]
    chosen_pos, chosen, tied = chosen_rows(pool["ik"], P, block_tables, seq_lens, new["ik"], qi, wi, topk, given, interpret)
    with jax.named_scope("sparse_latent"):
        flat_row, is_new = chosen_flat_rows(chosen_pos, chosen, block_tables, seq_lens, P)
        leaf = pool["kv"]
        got = leaf.reshape((leaf.shape[0] * P, leaf.shape[2]))[flat_row]  # [S, topk, width]
        got = jnp.where(is_new[..., None], new["kv"][:, None, :], got).astype(q.dtype)
        logits = jnp.einsum("shw,snw->shn", q, got, preferred_element_type=jnp.float32) * (score_dim ** -0.5)
        p = jax.nn.softmax(jnp.where(chosen[:, None, :], logits, NEG_INF), axis=-1)
        out = jnp.einsum("shn,snv->shv", p.astype(got.dtype), got[..., :value_width], preferred_element_type=jnp.float32)
    return out.astype(q.dtype), jnp.where(chosen, chosen_pos, -1), tied


def ring_latent_decode_attention_cache_plus_new(
    q: jax.Array,  # [S, H, width]: every head's absorbed query against the whole row
    pages: jax.Array,  # [L * NW, P, width]: the window pool's one leaf (:func:`flat_pages`), WITHOUT the new token
    ring_ids: jax.Array,  # [S, ring]: a slot's ring, ids of the flattened pool (:func:`ring_tables`, :func:`layer_tables`)
    seq_lens: jax.Array,  # [S] — tokens committed before the new one
    row_new: jax.Array,  # [S, width]: the new token's row
    value_width: int,
    score_dim: int,
    row_positions: jax.Array,  # [S, ring * P]: the position each row of the ring holds (:func:`ring_positions`)
    starts: jax.Array,  # [S]: the first position the query sees
) -> jax.Array:
    """A decode step of latent attention over a window kept as a RING of
    latent rows a slot (``models/dots.py``'s sliding layers): the slot's
    whole ring gathered (``ring`` pages, whatever the context), the rows
    whose positions lie in ``starts .. seq_lens - 1`` and the new token's
    own attended in absorbed form, operands in the rows' dtype and float32
    accumulators. -> [S, H, value_width] in q's dtype."""
    S, H, width = q.shape
    rows = pages[ring_ids].reshape(S, -1, width).astype(q.dtype)  # [S, ring * P, width]
    scale = score_dim ** -0.5
    logits = jnp.einsum("shw,stw->sht", q, rows, preferred_element_type=jnp.float32) * scale
    seen = (row_positions >= starts[:, None]) & (row_positions < seq_lens[:, None])
    logits = jnp.where(seen[:, None, :], logits, NEG_INF)
    new = row_new.astype(q.dtype)
    self_logit = jnp.einsum("shw,sw->sh", q, new, preferred_element_type=jnp.float32) * scale
    m = jnp.maximum(jnp.max(logits, axis=-1), self_logit)
    p, p_self = jnp.where(seen[:, None, :], jnp.exp(logits - m[..., None]), 0.0), jnp.exp(self_logit - m)
    out = jnp.einsum("sht,stv->shv", p.astype(rows.dtype), rows[..., :value_width], preferred_element_type=jnp.float32)
    out = out + p_self[..., None] * new[:, None, :value_width].astype(jnp.float32)
    return (out / (jnp.sum(p, axis=-1) + p_self)[..., None]).astype(q.dtype)


class PageAllocator:
    """Host-side page free list with reference counts (the engine thread
    owns it; no locking). Page 0 is the reserved trash page and is never
    handed out.

    Refcounts enable zero-copy prefix sharing: a cached prompt prefix keeps
    a reference on its (full, immutable) pages, and every sequence whose
    block table borrows them takes another — a page returns to the pool
    only when its last reference drops.

    With ``track_scales`` (quantized KV pools) the allocator additionally
    mirrors per-page SCALE-ROW ownership: a quantized page's f32 scale rows
    live in page-shaped twin arrays indexed by the same page id, so every
    allocated page must own exactly one set of scale rows and a freed page
    must relinquish them. The set is maintained incrementally (alloc adds,
    last-ref free removes) precisely so the invariant checker can cross-
    check it against the refcount truth — a future alloc/free path that
    forgets the scale side shows up as a scale-row leak instead of serving
    garbage dequantization."""

    def __init__(self, num_pages: int, track_scales: bool = False):
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))  # pop() yields 1,2,...
        self._refs: dict[int, int] = {}
        # pages with refcount >= 2 (cross-request shared-prefix dedup +
        # prefix-cache references), maintained incrementally so readers get
        # an atomic int instead of scanning the refcount dict
        self._shared = 0
        # quantized-page scale-row ownership (None = untracked bf16 pools)
        self._scale_pages: Optional[set[int]] = set() if track_scales else None

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def allocated_count(self) -> int:
        """Pages currently referenced (atomic len read, like free_count)."""
        return len(self._refs)

    @property
    def shared_count(self) -> int:
        """Pages currently referenced by MORE than one owner — the dedup
        payoff: each is one HBM page serving multiple sequences. Atomic
        int read (cross-thread safe, same contract as free_count)."""
        return self._shared

    def audit(self) -> tuple[list[int], dict[int, int]]:
        """Snapshot ``(free pages, {page: refcount})`` for the runtime
        invariant checker (engine/invariants.py): conservation demands the
        two partition {1..num_pages-1} exactly, and every refcount must be
        matched by that many live owners (slot tables, prefix-cache
        entries, fault-held pages). Copies, so the caller can audit without
        aliasing allocator internals."""
        return list(self._free), dict(self._refs)

    def scale_audit(self) -> Optional[set[int]]:
        """Snapshot the quantized-page scale-row ownership set (None when
        the pools are bf16 and scales aren't tracked). A copy, like
        :meth:`audit` — conservation demands it equal the allocated-page
        set exactly (see engine/invariants.py)."""
        return None if self._scale_pages is None else set(self._scale_pages)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise MemoryError(f"out of KV pages: need {n}, have {len(self._free)}")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        if self._scale_pages is not None:
            self._scale_pages.update(pages)
        return pages

    def share(self, pages: list[int]) -> None:
        """Take an additional reference on already-allocated pages."""
        for p in pages:
            if p != TRASH_PAGE:
                n = self._refs[p] + 1
                self._refs[p] = n
                if n == 2:
                    self._shared += 1

    def free(self, pages: list[int]) -> None:
        """Drop one reference per page; pool it when the last ref drops.
        Freeing a page with no live reference raises (KeyError) — a silent
        double-free would hand one page to two sequences later."""
        for p in pages:
            if p == TRASH_PAGE:
                continue
            left = self._refs[p] - 1
            if left == 1:
                self._shared -= 1
            if left <= 0:
                del self._refs[p]
                self._free.append(p)
                if self._scale_pages is not None:
                    # the page's scale rows return with it (stale values
                    # remain in the twin arrays but are never read: block
                    # tables only reference owned pages)
                    self._scale_pages.discard(p)
            else:
                self._refs[p] = left


@dataclass
class HostKVEntry:
    """Swapped-out KV resident in host RAM: the cache's leaves by the names
    the cache gives them, token-major, with the trailing axes of the cache
    they left (the engine's extract/restore paths convert to and from the
    slot rows ``[.., H_kv, d]`` or the page blocks ``[.., H_kv * d]`` of
    whichever KV layout is serving, generic over the leaves and over what
    follows the token axis; an entry restores into the layout and the
    family it was taken from).

    ``tokens`` is the exact token sequence whose KV the rows hold (rows
    ``[0, cut)`` of a request's prefill row), so an entry can be matched
    either by the rid it was swapped under (preempt -> resume) or by token
    -prefix equality (park expiry / mid-prefill deadline -> a later request
    re-sending the same conversation or persona prompt).

    ``rows`` holds whatever the pool holds: ``k`` and ``v`` ([L, cut, H_kv *
    d] paged, [L, cut, H_kv, d] slot); for a quantized-KV engine the int8
    bytes VERBATIM plus their per-row scale rows (``ks`` / ``vs``, [L, cut,
    H_kv] f32) — the host tier holds ~2x the tokens per byte, and a restore
    is bit-exact by construction (no requantization round trip); for a
    latent pool the one leaf ``kv`` [L, cut, width]; for the sparse
    family's ``kv`` [L, cut, words] uint32 (:func:`pack_kv_rows`) and ``ik``."""

    rid: str
    tokens: tuple
    rows: dict  # {leaf name: np.ndarray [L, cut, ...]}
    # families with per-slot state beside the pages: the state after
    # exactly ``cut`` tokens, the family's tree for one slot with numpy
    # leaves (one array or several, of whatever types); a restore resumes
    # from it, and an entry without one is a miss for such a family
    state: Optional[Any] = None

    @property
    def cut(self) -> int:
        return len(self.tokens)

    @property
    def nbytes(self) -> int:
        n = sum(int(a.nbytes) for a in self.rows.values())
        if self.state is not None:
            n += sum(int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(self.state))
        return n


class HostKVPool:
    """Bounded host-RAM KV tier (the offload side of the engine's memory
    hierarchy). Engine-thread owned, like :class:`PageAllocator` — no
    locking. Entries are LRU-evicted when a put would exceed ``max_bytes``;
    an entry that alone exceeds the budget is refused (the caller falls
    back to recompute-on-resume, today's behavior).

    ``audit()`` mirrors the allocator's: conservation here means the used-
    bytes counter equals the sum of live entries' bytes and never exceeds
    the budget — a swapped-out entry whose bytes vanished from accounting
    is a host-resident page leak (the invariant checker's new class)."""

    def __init__(self, max_bytes: int):
        self.max_bytes = int(max_bytes)
        self.used_bytes = 0
        self._entries: "OrderedDict[str, HostKVEntry]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def put(self, entry: HostKVEntry) -> bool:
        """Admit ``entry`` (keyed by rid; a re-put replaces), LRU-evicting
        until it fits. False when the entry alone exceeds the budget."""
        if entry.nbytes > self.max_bytes:
            return False
        old = self._entries.pop(entry.rid, None)
        if old is not None:
            self.used_bytes -= old.nbytes
        while self.used_bytes + entry.nbytes > self.max_bytes and self._entries:
            _, evicted = self._entries.popitem(last=False)
            self.used_bytes -= evicted.nbytes
        self._entries[entry.rid] = entry
        self.used_bytes += entry.nbytes
        return True

    def get(self, rid: str) -> Optional[HostKVEntry]:
        """Look up by rid without removing (reservation may still fail, so
        consumption is a separate :meth:`pop`). A hit refreshes recency —
        an attempted use is a use, or the LRU bound would really be FIFO
        and evict exactly the entries traffic keeps reaching for."""
        e = self._entries.get(rid)
        if e is not None:
            self._entries.move_to_end(rid)
        return e

    def match_prefix(self, row: list[int]) -> Optional[HostKVEntry]:
        """Longest entry whose tokens are a STRICT prefix of ``row`` (at
        least one suffix token must remain to produce logits) — the host
        tier acting as a second-level prefix cache for park-expired and
        deadline-dropped KV. A match refreshes the entry's recency (see
        :meth:`get`)."""
        best: Optional[HostKVEntry] = None
        for e in self._entries.values():
            if e.cut < len(row) and (best is None or e.cut > best.cut):
                if tuple(row[: e.cut]) == e.tokens:
                    best = e
        if best is not None:
            self._entries.move_to_end(best.rid)
        return best

    def pop(self, rid: str) -> Optional[HostKVEntry]:
        """Consume an entry (swap-in took it; its bytes return to budget)."""
        e = self._entries.pop(rid, None)
        if e is not None:
            self.used_bytes -= e.nbytes
        return e

    def clear(self) -> None:
        self._entries.clear()
        self.used_bytes = 0

    def audit(self) -> tuple[int, dict[str, int]]:
        """Snapshot ``(used_bytes, {rid: entry bytes})`` for the invariant
        checker. Copies, so auditors never alias pool internals."""
        return self.used_bytes, {r: e.nbytes for r, e in self._entries.items()}
