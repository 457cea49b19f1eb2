"""Attention ops for the serving engine and trainer.

Three entry points:

- ``causal_attention``       — full-sequence attention (prefill / training).
- ``decode_attention``       — one-token-per-slot attention over the slot KV
                               cache (the continuous-batching hot loop).
- ``write_kv`` / ``write_kv_token`` — cache updates.

The decode cache is a contiguous per-slot layout ``[S, max_ctx, H_kv, d]``:
on TPU a decode step must stream every live K/V byte from HBM regardless of
layout, so contiguous-slot reads beat a page-table gather (which would
materialize an extra copy in pure XLA); page-granular allocation is what a
Pallas kernel adds later (ops/pallas). GQA is handled by repeating KV heads.

All softmax math in float32; logits capped via stable max-subtraction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
CONTINUE_BLOCK = 512  # query rows a continuation attends at a time
KEY_BLOCK = 2048  # keys a block of queries under a mask folds at a time


def _softcap(logits: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 attention-logit soft-capping: cap * tanh(logits / cap),
    applied BEFORE masking (matching HF). cap == 0 disables (identity)."""
    if not cap:
        return logits
    capf = jnp.float32(cap)
    return capf * jnp.tanh(logits / capf)


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[..., H_kv, d] -> [..., H_kv * n_rep, d] (GQA)."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


def causal_attention(
    q: jax.Array,  # [B, T, H, d]
    k: jax.Array,  # [B, T, H_kv, d]
    v: jax.Array,  # [B, T, H_kv, d]
    positions: jax.Array | None = None,  # [B, T] for padded/packed inputs
    softcap: float = 0.0,
    window: int = 0,  # > 0: a query sees the last `window` positions, itself among them
    keep: jax.Array | None = None,  # [B, T, T] bool: the keys a query may see, beside the causal mask
) -> jax.Array:
    """Full causal self-attention. With ``positions`` given, tokens attend
    only to tokens with position <= their own AND valid (position >= 0)."""
    B, T, H, d = q.shape
    n_rep = H // k.shape[-2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = _softcap(
        jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale, softcap
    )
    if window and positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    if positions is None:
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))[None, None]
    else:
        valid = positions >= 0
        mask = (
            (positions[:, None, :, None] >= positions[:, None, None, :])
            & valid[:, None, :, None]
            & valid[:, None, None, :]
        )
        if window:
            mask = mask & (positions[:, None, None, :] > positions[:, None, :, None] - window)
    if keep is not None:
        mask = mask & keep[:, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def decode_attention(
    q: jax.Array,  # [S, H, d] — one new token per slot
    k_cache: jax.Array,  # [S, C, H_kv, d]
    v_cache: jax.Array,  # [S, C, H_kv, d]
    seq_lens: jax.Array,  # [S] int32 — tokens valid in each slot (incl. new)
    softcap: float = 0.0,
) -> jax.Array:
    """Single-step attention against the slot cache."""
    S, C, H_kv, d = k_cache.shape
    n_rep = q.shape[-2] // H_kv
    k = repeat_kv(k_cache, n_rep)  # [S, C, H, d]
    v = repeat_kv(v_cache, n_rep)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = _softcap(
        jnp.einsum("shd,schd->shc", q, k).astype(jnp.float32) * scale, softcap
    )
    mask = jnp.arange(C)[None, None, :] < seq_lens[:, None, None]  # [S,1,C]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("shc,schd->shd", probs, v)


def decode_attention_cache_plus_new(
    q: jax.Array,  # [S, H, d] — one new token per slot
    k_cache: jax.Array,  # [S, C, H_kv, d] — WITHOUT the new token
    v_cache: jax.Array,
    k_new: jax.Array,  # [S, H_kv, d] — the new token's K/V (not yet written)
    v_new: jax.Array,
    seq_lens: jax.Array,  # [S] int32 — tokens valid in the CACHE (excl. new)
    softcap: float = 0.0,
) -> jax.Array:
    """Decode attention over read-only cache rows plus an explicit
    self-attention term for the not-yet-written token.

    This split is the hot-loop enabler: the cache stays a READ-ONLY scan
    input through the layer stack (xs reads are free; in-place scatter
    inside a nested scan is not — XLA's copy insertion rewrites it into a
    full cache copy per layer, ~3x the whole step time at bench-1b/64x512),
    and the step commits every layer's new K/V with ONE scatter afterwards.
    GQA via q-reshape (no repeated-KV materialization)."""
    S, C, H_kv, d = k_cache.shape
    H = q.shape[1]
    r = H // H_kv
    q4 = q.reshape(S, H_kv, r, d).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = _softcap(
        jnp.einsum("skrd,sckd->sckr", q4, k_cache.astype(jnp.float32)) * scale,
        softcap,
    )  # [S, C, H_kv, r]
    mask = jnp.arange(C)[None, :, None, None] < seq_lens[:, None, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    self_logit = _softcap(
        jnp.sum(q4 * k_new.astype(jnp.float32)[:, :, None, :], axis=-1) * scale,
        softcap,
    )  # [S, H_kv, r]
    m = jnp.maximum(jnp.max(logits, axis=1), self_logit)
    p = jnp.exp(logits - m[:, None])
    p_self = jnp.exp(self_logit - m)
    denom = jnp.sum(p, axis=1) + p_self
    out = jnp.einsum("sckr,sckd->skrd", p, v_cache.astype(jnp.float32))
    out = out + p_self[..., None] * v_new.astype(jnp.float32)[:, :, None, :]
    out = out / denom[..., None]
    return out.reshape(S, H, d).astype(q.dtype)


def online_softmax_step(qf, kf, vf, mask, m, l, acc, scale, softcap=0.0):
    """One flash-style accumulation step over a K/V block: given f32 query
    [B,Tq,H,d], block keys/values [B,Tk,H,d] (kv heads already repeated),
    and a [B,1|H,Tq,Tk] mask, fold the block into the running (m, l, acc).
    The isfinite guards keep fully-masked-so-far rows at exactly zero; a
    previously-contaminated row (finite NEG_INF) is erased by the
    correction factor underflowing to 0 once a real key appears."""
    logits = _softcap(jnp.einsum("bthd,bshd->bhts", qf, kf) * scale, softcap)
    logits = jnp.where(mask, logits, NEG_INF)
    m_blk = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(jnp.isfinite(m_new)[..., None], p, 0.0)
    correction = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l = l * correction + jnp.sum(p, axis=-1)
    acc = acc * correction[..., None] + jnp.einsum("bhts,bshd->bhtd", p, vf)
    return m_new, l, acc


def online_softmax_finalize(l, acc, dtype):
    """(l, acc) -> [B, T, H, d] output in ``dtype``."""
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(dtype)


def blocked_causal_attention(
    q: jax.Array,  # [B, T, H, d]
    k: jax.Array,  # [B, T, H_kv, d]
    v: jax.Array,
    positions: jax.Array | None = None,  # [B, T] (-1 = padding)
    block_size: int = 512,
    softcap: float = 0.0,
    window: int = 0,
) -> jax.Array:
    """Flash-style blocked causal attention (single device): query blocks
    attend only their causal KEY PREFIX (q-block i scans key blocks 0..i
    with an online-softmax accumulator), so peak logits memory is
    [B, H, block, block]-ish instead of [B, H, T, T] AND roughly half the
    fully-masked block-pair FLOPs of a dense T x T computation are never
    issued. Exact vs :func:`causal_attention` up to f32 accumulation order.
    Requires right-padded rows (valid positions equal their indices — true
    for prefill); falls back to the dense path when T doesn't split into
    blocks (buckets are powers of two, so T > block implies divisibility).
    With ``window`` a query sees its last ``window`` positions only (a
    banded mask), and q-block i scans the key blocks the band touches,
    ``i - ceil(window / block) .. i``: the blocks wholly before the band are
    never issued. Keys and values may differ in width (latent attention
    expanded: keys of 192, values of 128); the scale is the keys'."""
    B, T, H, d = q.shape
    if T <= block_size or T % block_size:
        return causal_attention(q, k, v, positions, softcap=softcap, window=window)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    nb = T // block_size
    n_rep = H // k.shape[-2]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)

    band = -(-window // block_size) if window else nb  # key blocks before a q-block's own that it sees

    def kv_prefix(arrs, qi):
        return [a[:, max(0, qi - band) * block_size: (qi + 1) * block_size] for a in arrs]

    outs = []
    for qi in range(nb):  # unrolled: nb is small (T/512), shapes static per qi
        sl = slice(qi * block_size, (qi + 1) * block_size)
        qf = q[:, sl].astype(jnp.float32)
        q_pos = positions[:, sl]
        kp, vp, kvp = kv_prefix((k, v, positions), qi)
        nkb = qi + 1 - max(0, qi - band)
        k_blocks = jnp.moveaxis(kp.reshape(B, nkb, block_size, *k.shape[2:]), 1, 0)
        v_blocks = jnp.moveaxis(vp.reshape(B, nkb, block_size, *v.shape[2:]), 1, 0)
        pos_blocks = jnp.moveaxis(kvp.reshape(B, nkb, block_size), 1, 0)

        m = jnp.full((B, H, block_size), -jnp.inf, dtype=jnp.float32)
        l = jnp.zeros((B, H, block_size), dtype=jnp.float32)
        acc = jnp.zeros((B, H, block_size, v.shape[-1]), dtype=jnp.float32)  # v's width, which need not be k's

        def step(carry, blk, qf=qf, q_pos=q_pos):
            m, l, acc = carry
            kb, vb, kv_pos = blk
            kf = repeat_kv(kb, n_rep).astype(jnp.float32)
            vf = repeat_kv(vb, n_rep).astype(jnp.float32)
            mask = (
                (kv_pos[:, None, None, :] <= q_pos[:, None, :, None])
                & (q_pos[:, None, :, None] >= 0)
                & (kv_pos[:, None, None, :] >= 0)
            )
            if window:
                mask = mask & (kv_pos[:, None, None, :] > q_pos[:, None, :, None] - window)
            m, l, acc = online_softmax_step(
                qf, kf, vf, mask, m, l, acc, scale, softcap=softcap
            )
            return (m, l, acc), None

        (m, l, acc), _ = jax.lax.scan(step, (m, l, acc), (k_blocks, v_blocks, pos_blocks))
        outs.append(online_softmax_finalize(l, acc, q.dtype))
    return jnp.concatenate(outs, axis=1)


def continue_attention(
    q: jax.Array,  # [B, T, H, d] — suffix queries
    k_rows: jax.Array,  # [B, C, H_kv, d] — cache rows (and/or suffix keys)
    v_rows: jax.Array,
    positions: jax.Array,  # [B, T] absolute query positions (-1 = padding)
    key_positions: jax.Array | None = None,  # [B, C]; -1 = invalid key
    softcap: float = 0.0,
    window: int = 0,  # > 0: keys at positions > the query's - window only
) -> jax.Array:
    """Suffix-over-cache attention (prefix-cache continuation): each query
    attends to every key whose absolute position is <= its own — exactly
    causal. Without ``key_positions`` the keys are assumed to be cache rows
    at positions 0..C-1 (the write-then-attend form). With it, the caller
    supplies each key's position (-1 = invalid) — the read-only form passes
    [prefix-rows ++ own-suffix] with stale cache regions masked out."""
    B, T, H, d = q.shape
    C = k_rows.shape[1]
    n_rep = H // k_rows.shape[-2]
    k = repeat_kv(k_rows, n_rep)
    v = repeat_kv(v_rows, n_rep)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = _softcap(
        jnp.einsum("bthd,bchd->bhtc", q, k).astype(jnp.float32) * scale, softcap
    )
    if key_positions is None:
        key_positions = jnp.broadcast_to(jnp.arange(C)[None, :], (B, C))
    mask = (
        (key_positions[:, None, None, :] <= positions[:, None, :, None])
        & (key_positions >= 0)[:, None, None, :]
        & (positions >= 0)[:, None, :, None]
    )
    if window:
        mask = mask & (key_positions[:, None, None, :] > positions[:, None, :, None] - window)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhtc,bchd->bthd", probs, v)


def continue_attention_by_rows(q, k_rows, v_rows, positions, key_positions, block: int | None = None):
    """:func:`continue_attention`, ``block`` (``CONTINUE_BLOCK``) query rows
    at a time where ``T`` is whole blocks and more than one: dense over the
    keys, one ``lax.map`` over the blocks of rows, so that the scores held at
    once are a block's (3,072 rows of 32 heads against 8,192 keys are 3.2 GB).
    Keys and values may differ in width."""
    B, T = positions.shape
    R = block or CONTINUE_BLOCK
    if T <= R or T % R:
        return continue_attention(q, k_rows, v_rows, positions, key_positions)
    split = lambda t: jnp.moveaxis(t.reshape((B, T // R, R) + t.shape[2:]), 1, 0)  # noqa: E731
    out = jax.lax.map(lambda blk: continue_attention(blk[0], k_rows, v_rows, blk[1], key_positions),
                      (split(q), split(positions)))
    return jnp.moveaxis(out, 0, 1).reshape((B, T) + out.shape[3:])


def blocked_masked_attention(q, k, v, mask):
    """q [B, Tq, H, d] over keys [B, C, H_kv, d] and values [B, C, H_kv, dv]
    (``dv`` need not be ``d``: latent rows expanded) under ``mask`` [B, Tq,
    C]: dense where the keys are few, else ``KEY_BLOCK`` keys folded at a
    time into an online softmax (the scores of 512 rows against 51k keys
    would be 3.3 GB at once)."""
    B, Tq, H, d = q.shape
    C = k.shape[1]
    n_rep = H // k.shape[2]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    if C <= KEY_BLOCK or C % KEY_BLOCK:
        logits = jnp.einsum("bthd,bchd->bhtc", q, repeat_kv(k, n_rep)).astype(jnp.float32) * scale
        probs = jax.nn.softmax(jnp.where(mask[:, None], logits, NEG_INF), axis=-1).astype(q.dtype)
        return jnp.einsum("bhtc,bchd->bthd", probs, repeat_kv(v, n_rep))
    nb = C // KEY_BLOCK
    blocks = (jnp.moveaxis(k.reshape(B, nb, KEY_BLOCK, *k.shape[2:]), 1, 0),
              jnp.moveaxis(v.reshape(B, nb, KEY_BLOCK, *v.shape[2:]), 1, 0),
              jnp.moveaxis(mask.reshape(B, Tq, nb, KEY_BLOCK), 2, 0))
    qf = q.astype(jnp.float32)

    def step(carry, blk):
        kb, vb, seen = blk
        return online_softmax_step(qf, repeat_kv(kb, n_rep).astype(jnp.float32), repeat_kv(vb, n_rep).astype(jnp.float32),
                                   seen[:, None], *carry, scale), None

    init = (jnp.full((B, H, Tq), -jnp.inf, jnp.float32), jnp.zeros((B, H, Tq), jnp.float32),
            jnp.zeros((B, H, Tq, v.shape[-1]), jnp.float32))
    (_m, l, acc), _ = jax.lax.scan(step, init, blocks)
    return online_softmax_finalize(l, acc, q.dtype)


def write_kv(
    k_cache: jax.Array,  # [S, C, H_kv, d]
    v_cache: jax.Array,
    slot: jax.Array,  # scalar int32
    start: jax.Array,  # scalar int32 — first position to write
    k_new: jax.Array,  # [T, H_kv, d]
    v_new: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Write a prompt's K/V into one slot starting at ``start``."""
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k_new[None].astype(k_cache.dtype), (slot, start, 0, 0)
    )
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v_new[None].astype(v_cache.dtype), (slot, start, 0, 0)
    )
    return k_cache, v_cache


def write_kv_token(
    k_cache: jax.Array,  # [S, C, H_kv, d]
    v_cache: jax.Array,
    positions: jax.Array,  # [W] int32 — write position per slot, W <= S
    k_new: jax.Array,  # [W, H_kv, d]
    v_new: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Scatter one new token's K/V into slots 0..W-1 (decode step; W < S is
    the width-bucketed case — rows beyond W pass through untouched)."""
    slot_idx = jnp.arange(positions.shape[0])
    k_cache = k_cache.at[slot_idx, positions].set(k_new.astype(k_cache.dtype))
    v_cache = v_cache.at[slot_idx, positions].set(v_new.astype(v_cache.dtype))
    return k_cache, v_cache


# ---------------------------------------------------------------------------
# Rows chosen by a learned indexer (models/keye.py)
# ---------------------------------------------------------------------------


def index_scores(qi: jax.Array, w: jax.Array, ki: jax.Array) -> jax.Array:
    """The indexer's score of every key for every query, float32:
    ``I(t, s) = sum_j w[t, j] * ReLU(qi[t, j] . ki[s])`` over the indexer's
    heads ``j``, which share ONE key a row. ``qi`` [B, T, Hi, c], ``w`` [B, T,
    Hi], ``ki`` [B, C, c'] with ``c' >= c`` (a row stored on whole lane tiles:
    its first ``c`` values are the key, the rest zeros, and the query is
    padded to match so that the stored row is contracted as it lies) -> [B,
    T, C]. One product over all heads, then the heads' weighted sum as
    float32 products and adds, not a matmul (the chip's default matmul rounds
    float32 operands to bfloat16, which moved a decode step's scores by 0.3%
    and one chosen row in eleven against the same program's prefill). The
    compiler folds the sum into the product's epilogue: the per-head scores
    of 2,048 queries against 24k keys (3.2 GB) are never written, where a
    loop a head at a time wrote and read its running sum sixteen times
    (1.98 ms against 10.0: PR 58's builder's chip runs; PERF.md, PR 59)."""
    c = qi.shape[-1]
    if ki.shape[-1] != c:
        qi = jnp.pad(qi, ((0, 0), (0, 0), (0, 0), (0, ki.shape[-1] - c)))
    dots = jnp.einsum("bthc,bsc->bhts", qi, ki, preferred_element_type=jnp.float32)
    return jnp.sum(jnp.moveaxis(w.astype(jnp.float32), 2, 1)[..., None] * jax.nn.relu(dots), axis=1)


def topk_rows_mask(scores: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """[N, C] float32 scores, [N, C] bool -> [N, C] bool: a row's ``k`` valid
    columns of largest score (all of them where it has ``k`` or fewer),
    ties at the threshold to the EARLIER column, as ``jax.lax.top_k`` breaks
    them. The threshold is ``ops.sampling._topk_threshold``'s (~32
    compare-and-count passes, no sort), snapped to the smallest score it
    keeps so that the tie rule is exact: a block of 1,024 queries against
    24k keys is 25 M scores, of which a sort a row would be the whole
    prefill (PERF.md, PR 59)."""
    from .sampling import _topk_threshold

    least = jnp.min(jnp.where(valid, scores, jnp.inf), axis=-1, keepdims=True)
    least = jnp.where(jnp.isfinite(least), least, 0.0)  # a row with no valid column
    kk = jnp.minimum(k, jnp.sum(valid, axis=-1))
    floor = _topk_threshold(jnp.where(valid, scores, least), kk)
    thr = jnp.min(jnp.where(valid & (scores >= floor), scores, jnp.inf), axis=-1, keepdims=True)
    above = valid & (scores > thr)
    tied = valid & (scores == thr)
    room = kk[:, None] - jnp.sum(above, axis=-1, keepdims=True)
    # the running count that orders the tied columns is a dozen passes over the block on its own, and almost no
    # row needs it: where every row's tied columns all fit (one score at the threshold, room for one) it is skipped
    crowded = jnp.any(jnp.sum(tied, axis=-1, keepdims=True) > room)
    return above | jax.lax.cond(crowded, lambda: tied & (jnp.cumsum(tied, axis=-1) <= room), lambda: tied)


def topk_rows(scores: jax.Array, valid: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """[N, C] scores, [N, C] bool -> (columns [N, k] int32, chosen [N, k]
    bool): the same choice as :func:`topk_rows_mask` as a list, for a walk
    that fetches rows by their index. ``jax.lax.top_k`` gives equal values
    in index order, which is the tie rule; a row with fewer than ``k`` valid
    columns pads its list (``chosen`` False, column 0). At 16 lanes of 26k
    scores the chip takes 0.39 ms for this and 0.58 for the threshold and a
    compaction of its mask (PR 58's builder's chip runs; PERF.md, PR 59)."""
    k = min(k, scores.shape[-1])
    values, columns = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
    chosen = values > -jnp.inf
    return jnp.where(chosen, columns, 0).astype(jnp.int32), chosen
