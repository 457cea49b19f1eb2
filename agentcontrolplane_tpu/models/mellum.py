"""Mellum2 model family (``model_type: mellum``): attention layers of two
kinds in one model, every layer's FF routed experts.

Every layer is ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``:

- ``Attn`` is GQA with an RMSNorm over each head of q and of k before rotary.
  A ``full_attention`` layer sees every earlier token and turns by YaRN's
  frequencies (cos and sin times its attention factor); a
  ``sliding_attention`` layer sees the last ``window`` tokens, itself among
  them, and turns by the plain frequencies.
- ``MoE`` is ``ops.moe.routed_experts``: softmax scores over ``n_experts``,
  top-k renormalised, no shared expert, of which this chip holds
  ``experts_held``.

Layout for XLA: ``layer_types`` is a strict period, ``span - 1`` window
layers and then one full layer (``period``), so the stack is one scan over
the periods whose body is a scan over the period's window layers and then
its full layer. No conditional chooses a kind, so neither cache is an
operand of a switch (a switch's untaken branch copies a large operand it
hands through: PERF.md, PR 37), and the HLO holds one window layer and one
full layer whatever the depth. Weights are stacked by kind (``win``,
``full``: a layer reads its own row) and the expert FF over all layers in
order (``ff``; every layer's experts flattened to one axis and closed over,
indexed by the grouped matmul itself).

Serving state (paged layout only): two caches a slot, each ``[layers,
pages, P, H_kv * d]`` (``ops/paged.py``):

- ``k`` / ``v``: the full layers' pool, a page list a slot as every family
  has it, allocated by the engine as the context grows;
- ``wk`` / ``wv``: the window layers' pool, a **ring** a slot
  (``ops/paged.py``'s ``ring_*``): ``window / P + 1`` pages fixed to the
  slot, position ``p`` in ring page ``(p // P) % ring``, ``max_slots + 1``
  rings (the last is where padding lanes write). A window layer holds
  ``window + P`` rows a slot at any context; its table is the slot's number
  and is never uploaded;
- ``state["counts"]``: ``[2, 1 + COUNTS_HEAD + held + 4]`` uint32, row 0
  decode steps and row 1 prefills: the expert layers' counters as ``lfm2``
  keeps them, then the window layers' (dispatches, rows read a layer, rows
  there would be with no window, lanes past the window).

Keys are stored after rotary, so the walk of a window layer reads its ring
in table order and needs only the length to know what each page holds: the
pages before ``max(0, n + 1 - window)`` are skipped and not read, the rows
before it in the first page and the stale rows of the newest are masked
(``ops/pallas/paged_attention.py``, the walk named ``paged_window_walk``).
Every program reads both caches through its layer scan and commits after
it, so a continuation longer than the ring's one page of slack still reads
the rows its first queries need before it overwrites them.

The programs take ``lanes = (slots, snap_at)`` as every family with state a
slot does; ``snap_at`` is not used (nothing of the ring is snapshot: a
prefix entry, a park and a host swap are refused by the engine for this
family: ``models.programs``' ``refusals``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..observability import scopes
from ..ops.attention import blocked_causal_attention, causal_attention
from ..ops.moe import COUNTS_HEAD
from ..ops.norms import rms_norm
from ..ops.paged import (
    TRASH_PAGE, commit_tokens, commit_whole_pages, flat_pages, init_kv_pages, layer_tables,
    paged_decode_attention_reference_cache_plus_new, ring_newest, ring_positions, ring_size, ring_tables,
)
from .experts import describe_moe, routed_ff
from .stack import attention_op, embed, final_norm, head_logits, key_positions, layer_row, over_pages, row_positions
from .window import WINDOW_COUNTS, describe_window, slot_ring, window_counts


def _pattern(span: int, periods: int) -> tuple[str, ...]:
    return (("sliding_attention",) * (span - 1) + ("full_attention",)) * periods


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    dim: int = 2304
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    layer_types: tuple[str, ...] = _pattern(4, 7)
    window: int = 1024  # a sliding_attention layer's keys, the query's own among them
    ffn_dim: int = 7168  # published; no layer reads it (every layer is sparse)
    expert_ffn_dim: int = 896
    n_experts: int = 64  # the router's width
    experts_per_token: int = 8
    # global ids of the experts this chip holds, in the order of its
    # weights' leading axis; None holds all
    experts_held: Optional[tuple[int, ...]] = None
    norm_topk_prob: bool = True
    norm_eps: float = 1e-6
    rope_theta: float = 500000.0
    # the full layers' YaRN: (factor, original positions, beta_fast,
    # beta_slow, attention factor); None turns them by the plain frequencies
    yarn: Optional[tuple[float, int, float, float, float]] = (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    max_seq_len: int = 131072
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # what the engine asks of every config and this family has none of
    # (its window is `window`: served past it, not refused beyond it)
    attn_logit_softcap: float = 0.0
    post_norms: bool = False
    sliding_window: int = 0

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def span(self) -> int:
        """Layers a period: its window layers and the full layer after."""
        return self.layer_types.index("full_attention") + 1

    @property
    def n_full(self) -> int:
        return self.n_layers // self.span

    @property
    def n_window(self) -> int:
        return self.n_layers - self.n_full

    @property
    def held(self) -> tuple[int, ...]:
        return tuple(range(self.n_experts)) if self.experts_held is None else self.experts_held


PRESETS: dict[str, MellumConfig] = {
    # JetBrains/Mellum2-12B-A2.5B-Instruct whole: 24.3 GB of bfloat16, no single v5e
    "mellum2-12b-a2.5b": MellumConfig(),
    # one of four chips that share each layer: experts 0..15 of 64 held,
    # everything else whole (7.65 GB of weights)
    "mellum2-12b-a2.5b-ep4": MellumConfig(experts_held=tuple(range(16))),
    # CPU tests: two periods of three window layers and a full one
    "mellum-tiny": MellumConfig(
        vocab_size=256, dim=64, n_heads=4, n_kv_heads=2, head_dim=16, layer_types=_pattern(4, 2), window=32,
        ffn_dim=128, expert_ffn_dim=32, n_experts=8, experts_per_token=2, max_seq_len=512, rope_theta=10000.0,
        yarn=(4.0, 64, 32.0, 1.0, 1.1386294361119891), dtype=jnp.float32,
    ),
}


def period(c: MellumConfig) -> tuple[int, int]:
    """(layers a period, periods), after checking that ``layer_types`` is
    the strict period the layer scan is written for."""
    types = tuple(c.layer_types)
    bad = set(types) - {"sliding_attention", "full_attention"}
    if bad:
        raise ValueError(f"unknown layer types {sorted(bad)} (sliding_attention|full_attention)")
    if "full_attention" not in types or "sliding_attention" not in types:
        raise ValueError("the mellum family serves window layers beside full layers; layer_types has one kind only")
    span = c.span
    if len(types) % span or types != _pattern(span, len(types) // span):
        raise ValueError(
            f"layer_types is not a strict period of {span - 1} sliding_attention layers and one "
            "full_attention layer: the layer scan runs over whole periods")
    return span, len(types) // span


def init_params(config: MellumConfig, key: jax.Array) -> dict:
    """Random init in the served layout: the attention weights stacked by
    kind (``win`` over the window layers in order, ``full`` over the full
    layers), the FF's (norm, router, experts) over all layers (``ff``)."""
    c = config
    period(c)
    d, hd, eh, f, n = c.dim, c.head_dim, len(c.held), c.expert_ffn_dim, c.n_layers
    count = [0]

    def w(shape, scale):
        count[0] += 1
        return (jax.random.normal(jax.random.fold_in(key, count[0]), shape) * scale).astype(c.dtype)

    def attn(m):
        return {"ln1": jnp.ones((m, d), c.dtype),
                "wq": w((m, d, c.n_heads * hd), d ** -0.5), "wk": w((m, d, c.n_kv_heads * hd), d ** -0.5),
                "wv": w((m, d, c.n_kv_heads * hd), d ** -0.5), "wo": w((m, c.n_heads * hd, d), d ** -0.5),
                "q_norm": jnp.ones((m, hd), c.dtype), "k_norm": jnp.ones((m, hd), c.dtype)}

    return {
        "embed": w((c.vocab_size, d), d ** -0.5),
        "norm": jnp.ones((d,), c.dtype),
        "lm_head": w((d, c.vocab_size), d ** -0.5),
        "win": attn(c.n_window),
        "full": attn(c.n_full),
        "ff": {"ln2": jnp.ones((n, d), c.dtype), "router": w((n, d, c.n_experts), d ** -0.5),
               "w1": w((n, eh, d, f), d ** -0.5), "w3": w((n, eh, d, f), d ** -0.5),
               "w2": w((n, eh, f, d), f ** -0.5)},
    }


def _run_layers(params, c: MellumConfig, x, positions, valid, make_attn, route=None, keep=lambda t: t,
                walk="prefill_attention"):
    """The whole stack. ``make_attn(full, i)`` gives the attention function
    of window layer ``i`` or full layer ``i`` (a traced index among its own
    kind); ``route`` [n_layers, B, T, k] int32, where given, is every
    layer's choice of experts, taken as it is (an output check's
    teacher-forced routing; serving never gives one); ``keep`` is applied
    to a window layer's fresh K and V before the scan stacks them (a
    prefill keeps a ring's worth of its rows: ``ring_newest``); ``walk`` is
    the scope the attention functions run under (None: a decode step's open
    their own, ``page_walk`` and ``window_walk``). -> (x, new
    window k [n_window, B, kept rows, H_kv, d], new window v, new full k
    [n_full, B, T, H_kv, d], new full v, expert counters)."""
    span, periods = period(c)
    B, T, _ = x.shape
    dt = x.dtype
    norm = lambda x, w: rms_norm(x, w, c.norm_eps)  # noqa: E731
    ff = params["ff"]
    stacks = tuple(ff[name].reshape((-1,) + ff[name].shape[2:]) for name in ("w1", "w3", "w2"))
    small = {name: ff[name] for name in ("ln2", "router")}

    def layer(x, counts, stack, full: bool, i, index, chosen):
        with scopes.layer("attn"):
            weights = layer_row(stack, i)
            op, k, v = attention_op(norm(x, weights["ln1"]), weights, c, positions, make_attn(full, i),
                                     yarn=c.yarn if full else None, walk=walk)
            x = x + op
        with scopes.layer("ffn"):
            mine = layer_row(small, index)
            y, m = routed_ff(norm(x, mine["ln2"]), mine, stacks, index, c, valid, chosen, chunk=True)
            return x + y, counts + m, k.astype(dt), v.astype(dt)

    def one_period(carry, scanned):
        p, chosen = scanned  # chosen: [span, B, T, k] or None

        def window_layer(carry, scanned):
            j, given = scanned
            i = p * (span - 1) + j
            x, counts, k, v = layer(*carry, params["win"], False, i, p * span + j, given)
            with scopes.layer("commit"), jax.named_scope("window_commit"):
                return (x, counts), (keep(k), keep(v))

        carry, (wk, wv) = jax.lax.scan(
            window_layer, carry,
            (jnp.arange(span - 1, dtype=jnp.int32), None if chosen is None else chosen[:span - 1]))
        x, counts, fk, fv = layer(*carry, params["full"], True, p, p * span + span - 1,
                                  None if chosen is None else chosen[span - 1])
        return (x, counts), (wk, wv, fk, fv)

    counts = jnp.zeros((1 + COUNTS_HEAD + len(c.held),), jnp.uint32)
    by_period = None if route is None else route.reshape((periods, span) + route.shape[1:])
    (x, counts), (wk, wv, fk, fv) = jax.lax.scan(
        one_period, (x, counts), (jnp.arange(periods, dtype=jnp.int32), by_period))
    merge = lambda t: t.reshape((c.n_window,) + t.shape[2:])  # noqa: E731
    return x, merge(wk), merge(wv), fk, fv, counts


def forward(params: dict, tokens: jax.Array, config: MellumConfig) -> jax.Array:
    """Full-sequence causal forward -> logits [B, T, V] float32 (tests)."""
    c = config
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def make_attn(full, i):
        return lambda q, k, v: causal_attention(q, k, v, positions, window=0 if full else c.window)

    x, *_ = _run_layers(params, c, embed(params, tokens, c), positions, jnp.ones((B, T), bool), make_attn)
    return head_logits(final_norm(x, params, c), params, c)


# ---------------------------------------------------------------------------
# Serving: a page list a slot for the full layers, a ring a slot for the window layers
# ---------------------------------------------------------------------------


def init_paged_cache(config: MellumConfig, num_pages: int, page_size: int, quantize_kv: bool = False,
                     max_slots: int = 1) -> dict:
    c = config
    if quantize_kv:
        raise ValueError("the mellum family keeps its pages in the model's dtype (int8 window pages: ROADMAP M1)")
    ring = ring_size(c.window, page_size)
    full = init_kv_pages(c.n_full, num_pages, page_size, c.n_kv_heads, c.head_dim, c.dtype)
    # a ring a slot and one more, where padding lanes (slot `max_slots`) write
    win = init_kv_pages(c.n_window, (max_slots + 1) * ring, page_size, c.n_kv_heads, c.head_dim, c.dtype)
    return {
        "k": full["k"], "v": full["v"], "wk": win["k"], "wv": win["v"],
        "state": {"counts": jnp.zeros((2, 1 + COUNTS_HEAD + len(c.held) + WINDOW_COUNTS), jnp.uint32)},
    }


def _pools(cache: dict) -> tuple[dict, dict]:
    """(the full layers' pool, the window layers' pool), each ``{"k", "v"}``
    as ``ops/paged.py``'s helpers take a pool."""
    return {"k": cache["k"], "v": cache["v"]}, {"k": cache["wk"], "v": cache["wv"]}


def _committed(cache, full, win, counts, windowed, row):
    added = jnp.concatenate([counts, windowed])
    return {"k": full["k"], "v": full["v"], "wk": win["k"], "wv": win["v"],
            "state": {"counts": cache["state"]["counts"].at[row].add(added)}}


def prefill_paged_batch(params, cache, tokens, lengths, page_ids, lanes, config: MellumConfig, route=None):
    """B whole prompts in one dispatch: the full layers' K/V into each row's
    pages, the window layers' newest ``ring`` pages into its slot's ring.
    -> (cache, logits [B, V])."""
    c = config
    slots, _snap_at = lanes
    B, T = tokens.shape
    zero = jnp.zeros((B,), jnp.int32)
    positions, valid = row_positions(lengths, zero, T)

    def make_attn(full, i):
        return lambda q, k, v: blocked_causal_attention(q, k, v, positions, window=0 if full else c.window)

    full, win = _pools(cache)
    ring, pad = slot_ring(cache["wk"], c.window)
    keep, ring_ids = ring_newest(slots, zero, lengths, T, win["k"].shape[2], ring, pad)
    x, wk, wv, fk, fv, counts = _run_layers(
        params, c, embed(params, tokens, c), positions, valid, make_attn, route, keep)
    with scopes.layer("commit"):
        full = commit_whole_pages(full, {"k": fk, "v": fv}, page_ids)
        with jax.named_scope("window_commit"):
            win = commit_whole_pages(win, {"k": wk, "v": wv}, ring_ids)
        cache = _committed(cache, full, win, counts, window_counts(c.window, positions, valid), 1)
    x = final_norm(x, params, c)
    return cache, head_logits(x, params, c, last=lengths)


def _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, lanes, c):
    """Rows that start at ``starts`` (page-aligned): a full layer attends
    over its gathered prefix pages plus the rows themselves, a window layer
    over its slot's ring as it stands (the ``window`` rows before ``starts``
    are in it) plus the rows themselves. Nothing is written here. -> (x
    normed, the window layers' newest rows k, v and the ring pages they go
    to, new full k, v, counts, window counts)."""
    slots, _snap_at = lanes
    B, T = tokens.shape
    positions, valid = row_positions(lengths, starts, T)
    full, win = _pools(cache)
    P = full["k"].shape[2]
    ring, pad = slot_ring(cache["wk"], c.window)
    M = block_tables.shape[1]
    full_pos = key_positions(starts, positions, M * P)
    # the ring's rows hold the newest `ring` pages before `starts`; what lies
    # before a query's window is masked by `window`, as among the new rows
    ring_pos = ring_positions(starts, ring, P)
    ring_pos = jnp.where(ring_pos < starts[:, None], ring_pos, -1)
    win_pos = jnp.concatenate([ring_pos, positions], axis=1)
    rings = ring_tables(jnp.minimum(slots, pad), ring)

    def make_attn(is_full, i):
        pool, ids, key_pos, window = (full, block_tables, full_pos, 0) if is_full else (win, rings, win_pos, c.window)

        def attn(q, k, v):
            with jax.named_scope("full_gather" if is_full else "window_walk"):
                return over_pages(q, k, v, pool, ids, i, c.n_kv_heads, positions, key_pos, window=window)

        return attn

    keep, ring_ids = ring_newest(jnp.minimum(slots, pad), starts, lengths, T, P, ring, pad)
    x, wk, wv, fk, fv, counts = _run_layers(
        params, c, embed(params, tokens, c), positions, valid, make_attn, keep=keep)
    x = final_norm(x, params, c)
    with scopes.layer("commit"):
        return x, wk, wv, ring_ids, fk, fv, counts, window_counts(c.window, positions, valid)


def _continue_commit(cache, new, page_ids):
    wk, wv, ring_ids, fk, fv, counts, windowed = new
    full, win = _pools(cache)
    with scopes.layer("commit"):
        full = commit_whole_pages(full, {"k": fk, "v": fv}, page_ids)
        with jax.named_scope("window_commit"):
            win = commit_whole_pages(win, {"k": wk, "v": wv}, ring_ids)
        return _committed(cache, full, win, counts, windowed, 1)


def prefill_paged_continue(params, cache, tokens, lengths, starts, page_ids, block_tables, lanes,
                           config: MellumConfig):
    """Continuation (a later chunk of a long prompt, a resumed request's
    tail): -> (cache, last-token logits [B, V])."""
    x, *new = _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, lanes, config)
    cache = _continue_commit(cache, new, page_ids)
    return cache, head_logits(x, params, config, last=lengths)


def prefill_paged_continue_kv(params, cache, tokens, lengths, starts, page_ids, block_tables, lanes,
                              config: MellumConfig):
    """The continuation's writes without the head (a mid chunk)."""
    _x, *new = _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, lanes, config)
    return _continue_commit(cache, new, page_ids)


def decode_step_paged(params, cache, tokens, seq_lens, block_tables, active, config: MellumConfig,
                      use_pallas: bool = False, mesh=None, route=None, window_rows: Optional[int] = None):
    """One token for lanes 0..S-1 (lane b is slot b): a full layer walks the
    lane's pages, a window layer its ring from the window's edge on; an
    inactive lane's pages and ring are left as they were. ``window_rows``
    (an output check's control) walks another window than the model's."""
    c = config
    S = tokens.shape[0]
    full, win = _pools(cache)
    NP, P = full["k"].shape[1:3]
    NW = win["k"].shape[1]
    ring, pad = slot_ring(cache["wk"], c.window)
    k_flat, v_flat = flat_pages(full["k"]), flat_pages(full["v"])
    wk_flat, wv_flat = flat_pages(win["k"]), flat_pages(win["v"])
    positions = seq_lens[:, None]
    rings = ring_tables(jnp.arange(S, dtype=jnp.int32), ring)
    # the query at position n sees n + 1 - window .. n: from the ring the
    # rows from `first` on, the new token's own as the walk's self term
    first = jnp.maximum(seq_lens + 1 - (c.window if window_rows is None else window_rows), 0)

    def make_attn(is_full, i):
        def attn(q, k, v):
            with jax.named_scope("page_walk" if is_full else "window_walk"):
                if is_full:
                    args = (q[:, 0], k_flat, v_flat, layer_tables(block_tables, i, NP), seq_lens, k[:, 0], v[:, 0])
                    kw = {}
                else:
                    args = (q[:, 0], wk_flat, wv_flat, layer_tables(rings, i, NW), seq_lens, k[:, 0], v[:, 0])
                    kw = {"starts": first}
                if use_pallas:
                    from ..ops.pallas.paged_attention import paged_decode_attention_cache_plus_new

                    out = paged_decode_attention_cache_plus_new(*args, **kw, **({} if is_full else {"ring": ring}))
                else:
                    if not is_full:
                        kw["row_positions"] = ring_positions(seq_lens, ring, P)
                    out = paged_decode_attention_reference_cache_plus_new(*args, **kw)
            return out[:, None]

        return attn

    x, wk, wv, fk, fv, counts = _run_layers(
        params, c, embed(params, tokens[:, None], c), positions, active[:, None], make_attn, route, walk=None)
    with scopes.layer("commit"):
        target = jnp.where(active, block_tables[jnp.arange(S), seq_lens // P], TRASH_PAGE)
        full = commit_tokens(full, {"k": fk[:, :, 0], "v": fv[:, :, 0]}, target, seq_lens % P)
        with jax.named_scope("window_commit"):
            at = jnp.where(active, jnp.arange(S), pad) * ring + jnp.mod(seq_lens // P, ring)
            win = commit_tokens(win, {"k": wk[:, :, 0], "v": wv[:, :, 0]}, at, seq_lens % P)
        cache = _committed(cache, full, win, counts, window_counts(c.window, positions, active[:, None]), 0)
    x = final_norm(x[:, 0], params, c)
    return cache, head_logits(x, params, c)


def install_state(cache: dict, slot, state) -> dict:
    raise NotImplementedError(
        "the mellum family keeps no state a slot that can be copied in: the window layers' ring is rebuilt by a "
        "prefill (the engine refuses prefix entries, parks and host swaps for it)")


def saved_state(cache: dict, slot):
    raise NotImplementedError(
        "the mellum family saves no state a slot: a copy of the window layers' ring is 45 MB at the published "
        "widths (the engine refuses prefix entries, parks and host swaps for it)")


def counters(cache: dict) -> jax.Array:
    """The expert layers' and the window layers' counters as the programs keep them."""
    return cache["state"]["counts"]


def describe_counters(config: MellumConfig, total) -> dict:
    """``Engine.stats()``'s ``"moe"`` (``experts.describe_moe``) and
    ``"window"`` (``window.describe_window``) from the counters summed by the
    engine (``total`` [2, 1 + COUNTS_HEAD + held + WINDOW_COUNTS], None before
    the first dispatch)."""
    c = config
    cut = 1 + COUNTS_HEAD + len(c.held)
    if total is None:
        total = [[0] * (cut + WINDOW_COUNTS)] * 2
    return {**describe_moe(c, [r[:cut] for r in total]), **describe_window(total, cut, c.window, c.n_window, c.n_full)}
