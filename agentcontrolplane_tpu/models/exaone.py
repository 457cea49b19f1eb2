"""K-EXAONE model family (``model_type: exaone_moe``): window and full
attention layers over routed experts beside a shared expert, a dense first
layer, and the model's own multi-token-prediction (MTP) module, which this
family serves as its **drafter**.

Every layer is ``h = x + Attn(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``:

- ``Attn`` is GQA with an RMSNorm over each head of q and of k
  (``stack.attention_op``). A ``sliding_attention`` layer turns q and k by
  the one rope and sees the last ``window`` tokens, itself among them; a
  ``full_attention`` layer carries **no rotary** and sees every earlier
  token.
- ``FF`` of the first ``first_dense`` layers is a SwiGLU of ``ffn_dim``; of
  the others ``experts.routed_ff``: sigmoid scores, a selection bias in the
  choice only, top k renormalised and times ``routed_scaling_factor``, of
  which this chip holds ``experts_held``, plus the shared expert.
- The MTP module (DeepSeek-V3's form): with ``x_i`` the stack's output at
  position ``i`` before the last norm and ``t_{i+1}`` the token after it,
  ``u_i = W_eh [Norm_h(x_i) ; Norm_e(Emb(t_{i+1}))]``, one block as above
  (full attention over ``u_{<=i}``, no rotary, a dense SwiGLU), then
  ``Norm_m(.) W_head`` with the stack's own embedding and head: a
  distribution over ``t_{i+2}``.

Layout for XLA: the dense layers written out, the others laid out by
``lfm2.scan_layers`` (the pattern's periods one scan, what stands before and
after them their own short loops), every loop's body one kind of layer, so
neither cache is an operand of a switch. Weights are stacked by kind
(``win``, ``full``) and the expert FF over the sparse layers in order
(``ff``).

Serving state (paged layout only), as ``models/mellum.py`` keeps it:

- ``k`` / ``v``: the full layers' pool, ONE LAYER MORE than the stack has
  full layers: the last is the MTP block's;
- ``wk`` / ``wv``: the window layers' rings, ``window / P + 1`` pages a
  slot (9 pages, 144 rows at the published window of 128: smaller than a
  prefill bucket and barely larger than a decode block);
- ``state["counts"]``: ``mellum``'s row plus ``[proposed, accepted, steps,
  rows]`` for the drafter;
- ``state["hid"]`` ``[slots + 1, 2, D]``, ``state["pend"]``, ``state["prev"]``
  ``[slots + 1]``: what the drafter still owes a slot (below).

**The verify-and-draft step** (``verify_step_paged``, the family's decode
program; ``models.programs(...).draft_step``). A lane enters with its last
committed token ``t_n`` at position ``n``. One step:

1. *draft*: the MTP block runs the positions the last step committed (one or
   two: ``state["pend"]``), from the hidden states that step left
   (``state["hid"]``) and the tokens that followed them, writes its own K/V
   at exactly those positions, and its last row's logits are the drafted
   distribution ``q`` over ``t_{n+1}``; the engine's sampler draws the
   draft ``d`` from it;
2. *verify*: the stack runs the two rows ``[t_n, d]`` at positions ``n, n +
   1`` through both caches (the second row sees the first's new K/V; a
   window row sees ``i - window < j <= i``);
3. *accept*: the engine's sampler keeps ``d`` or replaces it
   (``ops.sampling.speculative_sample``) and the lane commits one or two
   tokens; the two rows' hidden states and the count are left for the next
   step's draft.

The MTP rows of a step are those whose next token is known when it starts,
so a prefill leaves nothing for the engine to finish: it runs the MTP block
over the prompt positions whose next token the prompt holds, and leaves the
last position's hidden state pending (its next token is the one the engine
samples after the prefill). Rollback is by count alone: a refused row's K/V
in the full pages, the MTP layer and the ring lies past the new length and
is overwritten in place by the next step (ring page ``(p // P) % ring``);
the ring's one page of slack holds the window a two-row step reads.

``decode_step_paged`` is the same stack one row a lane and no drafter: the
program a drafted run is held against.
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
    TRASH_PAGE, commit_tokens, commit_whole_pages, flat_pages, init_kv_pages, kv_commit, layer_tables,
    paged_verify_attention_reference, ring_newest, ring_positions, ring_size, ring_tables,
)
from .experts import describe_moe, routed_ff
from .stack import (
    attention_op, embed, final_norm, head_logits, key_positions, layer_row, mm, over_pages, row_positions, scan_layers,
)
from .window import WINDOW_COUNTS, describe_window, slot_ring, window_counts

DRAFT_COUNTS = 4  # proposed, accepted, steps, rows committed
ROWS = 2  # rows a verify step runs a lane: the committed token and the draft
KINDS = ("sliding_attention", "full_attention")


def _pattern(n: int) -> tuple[str, ...]:
    return (("sliding_attention",) * 3 + ("full_attention",)) * (n // 4) + ("sliding_attention",) * (n % 4)


@dataclass(frozen=True)
class ExaoneConfig:
    vocab_size: int = 153600
    dim: int = 6144
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    layer_types: tuple[str, ...] = _pattern(48)
    window: int = 128  # a sliding_attention layer's keys, the query's own among them
    first_dense: int = 1  # mlp_layer_types: the leading dense layers
    ffn_dim: int = 18432  # the dense layers' and the MTP block's SwiGLU
    expert_ffn_dim: int = 2048
    n_experts: int = 128  # the router's width
    experts_per_token: int = 8
    # global ids of the experts this chip holds, in the order of its
    # weights' leading axis; None holds all
    experts_held: Optional[tuple[int, ...]] = None
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_seq_len: int = 262144
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # what the engine asks of every config and this family has none of
    attn_logit_softcap: float = 0.0
    post_norms: bool = False
    sliding_window: int = 0

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_full(self) -> int:
        return sum(t == "full_attention" for t in self.layer_types)

    @property
    def n_window(self) -> int:
        return self.n_layers - self.n_full

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.expert_ffn_dim

    @property
    def held(self) -> tuple[int, ...]:
        return tuple(range(self.n_experts)) if self.experts_held is None else self.experts_held


PRESETS: dict[str, ExaoneConfig] = {
    # LGAI-EXAONE/K-EXAONE-236B-A23B whole: 473 GB of bfloat16, no single chip
    "k-exaone-236b-a23b": ExaoneConfig(),
    # CPU tests: a dense window layer, then window, window, full, window (the
    # benchmark's cut in small), a window of two pages of 8
    "exaone-tiny": ExaoneConfig(
        vocab_size=256, dim=64, n_heads=4, n_kv_heads=2, head_dim=16, layer_types=_pattern(5), window=16,
        ffn_dim=128, expert_ffn_dim=32, n_experts=16, experts_per_token=2, max_seq_len=512, rope_theta=10000.0,
        dtype=jnp.float32,
    ),
}


def plan(c: ExaoneConfig) -> dict:
    """The layer list as the leading dense layers' kinds (written out) and
    the sparse layers' (``scan_layers``), with the layers of each kind among
    the dense ones: a sparse layer's cache layer is its row among its kind
    plus those."""
    types = tuple(c.layer_types)
    bad = set(types) - set(KINDS)
    if bad:
        raise ValueError(f"unknown layer types {sorted(bad)} ({'|'.join(KINDS)})")
    if not c.n_full or not c.n_window:
        raise ValueError("the exaone family serves window layers beside full layers; layer_types has one kind only")
    pro, body = types[:c.first_dense], types[c.first_dense:]
    return {"prologue": pro, "body": body, "before": {kind: sum(t == kind for t in pro) for kind in KINDS}}


def init_params(config: ExaoneConfig, key: jax.Array) -> dict:
    """Random init in the served layout: ``pro`` a tuple of whole layer
    dicts (the leading dense layers), the sparse layers' attention stacked
    by kind (``win``, ``full``) and their FF stacked (``ff``), the MTP
    module (``mtp``: the two norms, ``eh_proj``, one whole dense layer, its
    last norm)."""
    c = config
    pl = plan(c)
    d, hd, f, eh = c.dim, c.head_dim, c.expert_ffn_dim, len(c.held)
    n = len(pl["body"])
    count = [0]

    def w(shape, scale):
        count[0] += 1
        return (jax.random.normal(jax.random.fold_in(key, count[0]), shape) * scale).astype(c.dtype)

    def attn(lead=()):
        return {"ln1": jnp.ones(lead + (d,), c.dtype),
                "wq": w(lead + (d, c.n_heads * hd), d ** -0.5), "wk": w(lead + (d, c.n_kv_heads * hd), d ** -0.5),
                "wv": w(lead + (d, c.n_kv_heads * hd), d ** -0.5), "wo": w(lead + (c.n_heads * hd, d), d ** -0.5),
                "q_norm": jnp.ones(lead + (hd,), c.dtype), "k_norm": jnp.ones(lead + (hd,), c.dtype)}

    def dense():
        return {"ln2": jnp.ones((d,), c.dtype), "w1": w((d, c.ffn_dim), d ** -0.5),
                "w3": w((d, c.ffn_dim), d ** -0.5), "w2": w((c.ffn_dim, d), c.ffn_dim ** -0.5)}

    sw = c.shared_width
    return {
        "embed": w((c.vocab_size, d), d ** -0.5),
        "norm": jnp.ones((d,), c.dtype),
        "lm_head": w((d, c.vocab_size), d ** -0.5),
        "pro": tuple({**attn(), **dense()} for _ in pl["prologue"]),
        "win": attn((sum(t == KINDS[0] for t in pl["body"]),)),
        "full": attn((sum(t == KINDS[1] for t in pl["body"]),)),
        "ff": {"ln2": jnp.ones((n, d), c.dtype), "router": w((n, d, c.n_experts), d ** -0.5),
               "router_bias": jnp.zeros((n, c.n_experts), jnp.float32),
               "w1": w((n, eh, d, f), d ** -0.5), "w3": w((n, eh, d, f), d ** -0.5),
               "w2": w((n, eh, f, d), f ** -0.5),
               "sw1": w((n, d, sw), d ** -0.5), "sw3": w((n, d, sw), d ** -0.5), "sw2": w((n, sw, d), sw ** -0.5)},
        "mtp": {"hnorm": jnp.ones((d,), c.dtype), "enorm": jnp.ones((d,), c.dtype),
                "eh_proj": w((2 * d, d), (2 * d) ** -0.5), "block": {**attn(), **dense()},
                "norm": jnp.ones((d,), c.dtype)},
    }


def _dense_ff(x, layer, c: ExaoneConfig):
    with scopes.layer("ffn"), jax.named_scope("ffn_dense"):
        h = rms_norm(x, layer["ln2"], c.norm_eps)
        return x + mm(jax.nn.silu(mm(h, layer["w1"])) * mm(h, layer["w3"]), layer["w2"])


def _run_layers(params, c: ExaoneConfig, x, positions, valid, make_attn, route=None, keep=lambda t: t,
                walk="prefill_attention"):
    """The whole stack. ``make_attn(full, i)`` gives the attention function
    of the ``i``-th window or full CACHE layer (a traced index among its
    kind, the dense layers' counted); ``route`` [sparse layers, B, T, k]
    int32, where given, is every sparse layer's choice of experts, taken as
    it is; ``keep`` is applied to a window layer's fresh K and V (a prefill
    keeps a ring's worth: ``ring_newest``); ``walk`` is the scope the
    attention functions run under (None: they open their own). -> (x, new
    window k [n_window, B, kept rows, H_kv, d], new window v, new full k
    [n_full, B, T, H_kv, d], new full v, expert counters)."""
    pl = plan(c)
    dt = x.dtype
    norm = lambda x, w: rms_norm(x, w, c.norm_eps)  # noqa: E731
    kept = {kind: ([], []) for kind in KINDS}

    def attention(kind, x, weights, at):
        full = kind == "full_attention"
        with scopes.layer("attn"):
            op, k, v = attention_op(norm(x, weights["ln1"]), weights, c, positions, make_attn(full, at),
                                     walk=walk, rope=not full)
            x = x + op
        if not full:
            with scopes.layer("commit"), jax.named_scope("window_commit"):
                k, v = keep(k), keep(v)
        return x, k.astype(dt), v.astype(dt)

    done = dict.fromkeys(KINDS, 0)
    for kind, layer in zip(pl["prologue"], params["pro"]):
        x, k, v = attention(kind, x, layer, jnp.int32(done[kind]))
        done[kind] += 1
        for part, new in zip(kept[kind], (k, v)):
            part.append(new[None])
        x = _dense_ff(x, layer, c)

    counts = jnp.zeros((1 + COUNTS_HEAD + len(c.held),), jnp.uint32)
    if pl["body"]:
        ff = params["ff"]
        stacks = tuple(ff[name].reshape((-1,) + ff[name].shape[2:]) for name in ("w1", "w3", "w2"))
        small = {name: ff[name] for name in ("ln2", "router", "router_bias", "sw1", "sw3", "sw2")}
        stack = {"sliding_attention": params["win"], "full_attention": params["full"]}

        def layer(kind, carry, index, at):
            x, counts = carry
            with scopes.layer("attn"):
                weights = layer_row(stack[kind], at)
            x, k, v = attention(kind, x, weights, pl["before"][kind] + at)
            with scopes.layer("ffn"):
                mine = layer_row(small, index)
                y, m = routed_ff(norm(x, mine["ln2"]), mine, stacks, index, c, valid,
                                 None if route is None else route[index], score="sigmoid", bias=True,
                                 scale=c.routed_scaling_factor, chunk=True, shared=True)
                return (x + y, counts + m), (k, v)

        (x, counts), outs = scan_layers(pl["body"], (x, counts), layer)
        for kind, (k, v) in outs.items():
            kept[kind][0].append(k)
            kept[kind][1].append(v)
    with scopes.layer("commit"):
        cat = lambda parts: jnp.concatenate(parts, axis=0)  # noqa: E731
        (wk, wv), (fk, fv) = (tuple(cat(p) for p in kept[kind]) for kind in KINDS)
        return x, wk, wv, fk, fv, counts


def _mtp_rows(params, c: ExaoneConfig, hidden, next_tokens, attn_fn, walk="prefill_attention"):
    """The MTP block over rows of (the stack's output before its last norm
    ``hidden`` [B, R, D], the token after it ``next_tokens`` [B, R]). ->
    (the block's output [B, R, D] before the module's last norm, its new K,
    V [B, R, H_kv, d])."""
    m = params["mtp"]
    with jax.named_scope("mtp_in_proj"):
        e = embed(params, next_tokens, c)
        with scopes.layer("ffn"):
            u = jnp.concatenate([rms_norm(hidden, m["hnorm"], c.norm_eps), rms_norm(e, m["enorm"], c.norm_eps)], -1)
            u = mm(u.astype(c.dtype), m["eh_proj"])
    with jax.named_scope("mtp_block"):
        layer = m["block"]
        with scopes.layer("attn"):
            op, k, v = attention_op(rms_norm(u, layer["ln1"], c.norm_eps), layer, c, None, attn_fn, walk=walk,
                                     rope=False)
            x = u + op
        return _dense_ff(x, layer, c), k.astype(u.dtype), v.astype(u.dtype)


def _mtp_logits(params, c: ExaoneConfig, x, last=None):
    """The drafted logits of MTP rows ``x`` [B, R, D] (``last`` [B]: of row
    ``last - 1`` alone): the module's own norm, the stack's head."""
    with jax.named_scope("mtp_head"):
        with scopes.layer("head"):
            x = rms_norm(x, params["mtp"]["norm"], c.norm_eps)
        return head_logits(x, params, c, last=last)


def forward(params: dict, tokens: jax.Array, config: ExaoneConfig) -> tuple[jax.Array, jax.Array]:
    """Full-sequence causal forward -> (logits [B, T, V], drafted logits [B,
    T - 1, V]: row ``i`` over token ``i + 2``), float32 (tests)."""
    c = config
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def make_attn(full, i):
        return lambda q, k, v: causal_attention(q, k, v, positions, window=0 if full else c.window)

    x, *_ = _run_layers(params, c, embed(params, tokens, c), positions, jnp.ones((B, T), bool), make_attn)
    y, _k, _v = _mtp_rows(params, c, x[:, :-1], tokens[:, 1:],
                          lambda q, k, v: causal_attention(q, k, v, positions[:, :-1]))
    return head_logits(final_norm(x, params, c), params, c), _mtp_logits(params, c, y)


# ---------------------------------------------------------------------------
# Serving: the full layers' and the MTP block's pages, a ring a slot, the drafter's pending rows
# ---------------------------------------------------------------------------


def init_paged_cache(config: ExaoneConfig, num_pages: int, page_size: int, quantize_kv: bool = False,
                     max_slots: int = 1) -> dict:
    c = config
    if quantize_kv:
        raise ValueError("the exaone family keeps its pages in the model's dtype (int8 pages: ROADMAP M1)")
    ring = ring_size(c.window, page_size)
    full = init_kv_pages(c.n_full + 1, num_pages, page_size, c.n_kv_heads, c.head_dim, c.dtype)  # the MTP block's last
    win = init_kv_pages(c.n_window, (max_slots + 1) * ring, page_size, c.n_kv_heads, c.head_dim, c.dtype)
    return {
        "k": full["k"], "v": full["v"], "wk": win["k"], "wv": win["v"],
        "state": {
            "counts": jnp.zeros((2, 1 + COUNTS_HEAD + len(c.held) + WINDOW_COUNTS + DRAFT_COUNTS), jnp.uint32),
            # what the drafter owes a slot: the stack's output at the last
            # one or two committed positions, how many, and the token at the
            # first of two (the second's is the lane's own)
            "hid": jnp.zeros((max_slots + 1, ROWS, c.dim), c.dtype),
            "pend": jnp.zeros((max_slots + 1,), jnp.int32),
            "prev": jnp.zeros((max_slots + 1,), jnp.int32),
        },
    }


def _pools(cache: dict) -> tuple[dict, dict]:
    return {"k": cache["k"], "v": cache["v"]}, {"k": cache["wk"], "v": cache["wv"]}


def _committed(cache, full, win, counts, windowed, row, state=None, draft_counts=None):
    zero = jnp.zeros((DRAFT_COUNTS,), jnp.uint32)
    added = jnp.concatenate([counts, windowed, zero if draft_counts is None else draft_counts])
    return {"k": full["k"], "v": full["v"], "wk": win["k"], "wv": win["v"],
            "state": {**cache["state"], **(state or {}), "counts": cache["state"]["counts"].at[row].add(added)}}


def _pending(cache, x, lengths, slots, pad):
    """The drafter's state after a prefill of ``lengths`` rows: the last
    row's hidden state owed, one row, in the row's slot (an empty row's goes
    to the slot nothing reads)."""
    st = cache["state"]
    B = x.shape[0]
    at = jnp.where(lengths > 0, jnp.minimum(slots, pad), pad)
    last = x[jnp.arange(B), jnp.maximum(lengths - 1, 0)]
    return {"hid": st["hid"].at[at, 0].set(last.astype(st["hid"].dtype)), "pend": st["pend"].at[at].set(1)}


def prefill_paged_batch(params, cache, tokens, lengths, page_ids, lanes, config: ExaoneConfig, route=None):
    """B whole prompts in one dispatch: the full layers' K/V into each row's
    pages, the window layers' newest ``ring`` pages into its slot's ring,
    the MTP block's K/V for every position whose next token the prompt
    holds, the last position's hidden state left pending. -> (cache, logits
    [B, V])."""
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
    # MTP row i: position i, the token after it the prompt's own; the last
    # position's next token is not the prompt's to give
    drafted = jnp.where(positions + 1 < lengths[:, None], positions, -1)
    _y, mk, mv = _mtp_rows(params, c, x, jnp.roll(tokens, -1, axis=1),
                           lambda q, k, v: blocked_causal_attention(q, k, v, drafted))
    with scopes.layer("commit"):
        new = {"k": jnp.concatenate([fk, mk[None]]), "v": jnp.concatenate([fv, mv[None]])}
        full = commit_whole_pages(full, new, page_ids)
        with jax.named_scope("window_commit"):
            win = commit_whole_pages(win, {"k": wk, "v": wv}, ring_ids)
        cache = _committed(cache, full, win, counts, window_counts(c.window, positions, valid), 1,
                           _pending(cache, x, lengths, slots, pad))
    x = final_norm(x, params, c)
    return cache, head_logits(x, params, c, last=lengths)


def _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, lanes, c):
    """Rows that start at ``starts`` (page-aligned): a full layer attends
    over its gathered prefix pages plus the rows themselves, a window layer
    over its slot's ring as it stands plus the rows themselves; the MTP
    block runs the slot's pending row (position ``starts - 1``: its next
    token is these rows' first) and then the rows whose next token the rows
    hold. Nothing is written here. -> (x normed, what `_continue_commit`
    writes)."""
    slots, _snap_at = lanes
    B, T = tokens.shape
    positions, valid = row_positions(lengths, starts, T)
    full, win = _pools(cache)
    P = full["k"].shape[2]
    ring, pad = slot_ring(cache["wk"], c.window)
    slots = jnp.minimum(slots, pad)
    M = block_tables.shape[1]
    row_pos = jnp.arange(M * P)
    full_pos = key_positions(starts, positions, M * P)
    ring_pos = ring_positions(starts, ring, P)
    ring_pos = jnp.where(ring_pos < starts[:, None], ring_pos, -1)
    win_pos = jnp.concatenate([ring_pos, positions], axis=1)
    rings = ring_tables(slots, ring)

    def make_attn(is_full, i):
        def attn(q, k, v):
            with jax.named_scope("full_gather" if is_full else "window_walk"):
                if is_full:
                    return over_pages(q, k, v, full, block_tables, i, c.n_kv_heads, positions, full_pos)
                return over_pages(q, k, v, win, rings, i, c.n_kv_heads, positions, win_pos, window=c.window)

        return attn

    keep, ring_ids = ring_newest(slots, starts, lengths, T, P, ring, pad)
    x, wk, wv, fk, fv, counts = _run_layers(
        params, c, embed(params, tokens, c), positions, valid, make_attn, keep=keep)
    # the MTP rows: T + 1 of them, row 0 the slot's pending row, row j + 1
    # position `starts + j`; row i's next token is tokens[i] (row T's none)
    owed = (starts > 0) & (lengths > 0) & (cache["state"]["pend"][slots] > 0)
    hidden = jnp.concatenate([cache["state"]["hid"][slots, :1].astype(x.dtype), x], axis=1)
    nxt = jnp.concatenate([tokens, jnp.zeros((B, 1), tokens.dtype)], axis=1)
    ar = jnp.arange(T + 1)
    drafted = jnp.where((ar[None, :] < lengths[:, None]) & ((ar[None, :] > 0) | owed[:, None]),
                        starts[:, None] - 1 + ar[None, :], -1)
    mtp_pos = jnp.concatenate([jnp.where(row_pos[None, :] < starts[:, None] - 1, row_pos[None, :], -1), drafted], 1)

    def mtp_attn(q, k, v):
        with jax.named_scope("full_gather"):
            return over_pages(q, k, v, full, block_tables, c.n_full, c.n_kv_heads, drafted, mtp_pos)

    _y, mk, mv = _mtp_rows(params, c, hidden, nxt, mtp_attn, walk=None)
    with scopes.layer("commit"):
        new = {"k": jnp.concatenate([fk, mk[None, :, 1:]]), "v": jnp.concatenate([fv, mv[None, :, 1:]])}
        before = jnp.maximum(starts - 1, 0)
        owed_at = (jnp.where(owed, block_tables[jnp.arange(B), before // P], TRASH_PAGE), before % P)
        return (final_norm(x, params, c), wk, wv, ring_ids, new, (mk[:, 0], mv[:, 0], owed_at), counts,
                window_counts(c.window, positions, valid), _pending(cache, x, lengths, slots, pad))


def _continue_commit(cache, got, page_ids, c):
    wk, wv, ring_ids, new, (ok, ov, (owed_page, owed_row)), counts, windowed, state = got
    full, win = _pools(cache)
    NP = full["k"].shape[1]
    with scopes.layer("commit"):
        full = commit_whole_pages(full, new, page_ids)
        # the pending row's K/V, in the page before these rows' first: the
        # MTP layer alone (its page of the flattened pool)
        merge = lambda t: t.reshape(t.shape[0], -1)  # noqa: E731
        at = layer_tables(owed_page, c.n_full, NP)
        full = {"k": flat_pages(full["k"]).at[at, owed_row].set(merge(ok).astype(full["k"].dtype)).reshape(full["k"].shape),
                "v": flat_pages(full["v"]).at[at, owed_row].set(merge(ov).astype(full["v"].dtype)).reshape(full["v"].shape)}
        with jax.named_scope("window_commit"):
            win = commit_whole_pages(win, {"k": wk, "v": wv}, ring_ids)
        return _committed(cache, full, win, counts, windowed, 1, state)


def prefill_paged_continue(params, cache, tokens, lengths, starts, page_ids, block_tables, lanes,
                           config: ExaoneConfig):
    """Continuation (a later chunk of a long prompt, a resumed request's
    tail): -> (cache, last-token logits [B, V])."""
    x, *got = _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, lanes, config)
    cache = _continue_commit(cache, got, page_ids, config)
    return cache, head_logits(x, params, config, last=lengths)


def prefill_paged_continue_kv(params, cache, tokens, lengths, starts, page_ids, block_tables, lanes,
                              config: ExaoneConfig):
    """The continuation's writes without the head (a mid chunk)."""
    _x, *got = _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, lanes, config)
    return _continue_commit(cache, got, page_ids, config)


def _step_attention(cache, c: ExaoneConfig, seq_lens, block_tables, use_pallas: bool, window_rows=None):
    """``attend(layer, i, q, k, v, lens, new_valid=None)`` for a step's rows
    (``q`` [S, R, H, d] at positions ``lens + r``): a full or MTP layer
    (``layer`` "full") walks the lane's pages, a window layer its ring from
    each row's own edge on; row ``r`` sees the new rows ``0 .. r``."""
    S = seq_lens.shape[0]
    full, win = _pools(cache)
    NP, P = full["k"].shape[1:3]
    NW = win["k"].shape[1]
    ring, _pad = slot_ring(cache["wk"], c.window)
    flat = {"full": (flat_pages(full["k"]), flat_pages(full["v"])), "win": (flat_pages(win["k"]), flat_pages(win["v"]))}
    rings = ring_tables(jnp.arange(S, dtype=jnp.int32), ring)
    window = c.window if window_rows is None else window_rows

    def attend(layer, i, q, k, v, lens, new_valid=None):
        R = q.shape[1]
        is_full = layer == "full"
        tables = layer_tables(block_tables, i, NP) if is_full else layer_tables(rings, i, NW)
        kw = {"new_valid": new_valid}
        if not is_full:
            # the query at position p sees p + 1 - window .. p
            kw["starts"] = jnp.maximum(lens[:, None] + jnp.arange(R)[None, :] + 1 - window, 0)
        with jax.named_scope("page_walk" if is_full else "window_walk"):
            if use_pallas:
                from ..ops.pallas.paged_attention import paged_verify_attention_cache_plus_new

                return paged_verify_attention_cache_plus_new(
                    q, *flat[layer], tables, lens, k, v, **kw, **({} if is_full else {"ring": ring}))
            if not is_full:
                kw["row_positions"] = ring_positions(lens, ring, P)
            return paged_verify_attention_reference(q, *flat[layer], tables, lens, k, v, **kw)

    return attend


def _commit_step(cache, c: ExaoneConfig, new, seq_lens, block_tables, live, mtp=None):
    """A step's new rows into both pools, one scatter a pool: the stack's
    (``new``: wk, wv [n_window, S, R, H_kv, d], fk, fv [n_full, ...]) at
    positions ``seq_lens + r`` of each lane's pages and ring, and the MTP
    block's (``mtp``: k, v [S, R, H_kv, d], the length its rows start at,
    which of them are owed) into the full pool's last layer. A row that is
    not ``live`` [S, R], and the MTP layer's where ``mtp`` is None, goes
    where nothing reads."""
    wk, wv, fk, fv = new
    full, win = _pools(cache)
    S, R = live.shape
    NP, P = full["k"].shape[1:3]
    rows = jnp.arange(R)[None, :]

    def paged(at, ok):
        page = jnp.minimum(at // P, block_tables.shape[1] - 1)
        return jnp.where(ok, jnp.take_along_axis(block_tables, page, axis=1), TRASH_PAGE), at % P

    pages, offsets = paged(seq_lens[:, None] + rows, live)
    if mtp is None:
        mk = mv = jnp.zeros_like(fk[0])
        m_pages, m_offsets = jnp.zeros_like(pages), jnp.zeros_like(offsets)
    else:
        mk, mv, base, owed = mtp
        m_pages, m_offsets = paged(base[:, None] + rows, owed)
    tile = lambda t: jnp.broadcast_to(t[None], (c.n_full,) + t.shape)  # noqa: E731
    ids = layer_tables(jnp.concatenate([tile(pages), m_pages[None]]), jnp.arange(c.n_full + 1)[:, None, None], NP)
    at = jnp.concatenate([tile(offsets), m_offsets[None]])
    full = kv_commit(full, {"k": jnp.concatenate([fk, mk[None]]), "v": jnp.concatenate([fv, mv[None]])},
                     lambda arr, val: flat_pages(arr).at[ids, at].set(val).reshape(arr.shape))
    with jax.named_scope("window_commit"):
        ring, pad = slot_ring(cache["wk"], c.window)
        where = seq_lens[:, None] + rows
        target = jnp.where(live, jnp.arange(S)[:, None], pad) * ring + jnp.mod(where // P, ring)
        win = commit_tokens(win, {"k": wk, "v": wv}, target, where % P)
    return full, win


def _stack_step(params, cache, rows, seq_lens, block_tables, live, c, use_pallas, route, window_rows):
    """The stack over ``rows`` [S, R] tokens at positions ``seq_lens + r``
    through both caches; nothing is written. -> (x [S, R, D] before the last
    norm, its new rows (wk, wv, fk, fv), expert counters, window counters)."""
    R = rows.shape[1]
    positions = seq_lens[:, None] + jnp.arange(R)[None, :]
    attend = _step_attention(cache, c, seq_lens, block_tables, use_pallas, window_rows)

    def make_attn(is_full, i):
        return lambda q, k, v: attend("full" if is_full else "win", i, q, k, v, seq_lens)

    x, *new, counts = _run_layers(params, c, embed(params, rows, c), positions, live, make_attn, route, walk=None)
    return x, new, counts, window_counts(c.window, positions, live)


def decode_step_paged(params, cache, tokens, seq_lens, block_tables, active, config: ExaoneConfig,
                      use_pallas: bool = False, mesh=None, route=None, window_rows: Optional[int] = None):
    """One token for lanes 0..S-1 and NO drafter (the MTP layer's pages and
    the pending rows are left as they were): the program a drafted run is
    held against, token for token. -> (cache, logits [S, V])."""
    c = config
    live = active[:, None]
    x, new, counts, windowed = _stack_step(
        params, cache, tokens[:, None], seq_lens, block_tables, live, c, use_pallas, route, window_rows)
    with scopes.layer("commit"):
        full, win = _commit_step(cache, c, new, seq_lens, block_tables, live)
        cache = _committed(cache, full, win, counts, windowed, 0)
    return cache, head_logits(final_norm(x[:, 0], params, c), params, c)


def verify_step_paged(params, cache, tokens, seq_lens, block_tables, active, sampler, config: ExaoneConfig,
                      use_pallas: bool = False, mesh=None, route=None, window_rows: Optional[int] = None):
    """One verify-and-draft step for lanes 0..S-1 (lane b is slot b; module
    text). ``sampler`` is the engine's: ``propose(q_logits [S, V]) ->
    (draft [S], q_logits as drawn from)`` and ``accept(logits [S, 2, V],
    draft, q_logits) -> (tokens [S, 2] with -1 where none, emitted [S] in
    0..2, kept [S] bool)``. An inactive lane's pages, ring and pending rows
    are left as they were. -> (cache, tokens [S, 2], emitted [S], {"logits":
    [S, 2, V], "draft_logits": [S, V]} for an output check)."""
    c = config
    S = tokens.shape[0]
    st = cache["state"]
    pend = jnp.where(active, jnp.clip(st["pend"][:S], 1, ROWS), 1)
    # 1. draft: the MTP block over the rows the last step committed
    base = jnp.maximum(seq_lens - pend, 0)  # the MTP layer's rows in the pages
    two = pend == ROWS
    nxt = jnp.stack([jnp.where(two, st["prev"][:S], tokens), tokens], axis=1)
    owed = jnp.stack([active, active & two], axis=1)
    attend = _step_attention(cache, c, seq_lens, block_tables, use_pallas)
    y, mk, mv = _mtp_rows(params, c, st["hid"][:S].astype(c.dtype), nxt,
                          lambda q, k, v: attend("full", c.n_full, q, k, v, base, new_valid=owed), walk=None)
    q_logits = _mtp_logits(params, c, y, last=pend)
    draft, q_logits = sampler.propose(q_logits)
    # 2. verify: the stack over [t_n, d] at positions n, n + 1
    live = jnp.broadcast_to(active[:, None], (S, ROWS))
    x, new, counts, windowed = _stack_step(
        params, cache, jnp.stack([tokens, draft], axis=1), seq_lens, block_tables, live, c, use_pallas, route,
        window_rows)
    logits = head_logits(final_norm(x, params, c).reshape(S * ROWS, -1), params, c).reshape(S, ROWS, -1)
    # 3. accept: one or two tokens a lane
    out, emitted, kept = sampler.accept(logits, draft, q_logits)
    with scopes.layer("commit"):
        full, win = _commit_step(cache, c, new, seq_lens, block_tables, live, (mk, mv, base, owed))
        did = active & (emitted > 0)
        state = {"hid": st["hid"].at[:S].set(jnp.where(did[:, None, None], x.astype(st["hid"].dtype), st["hid"][:S])),
                 "pend": st["pend"].at[:S].set(jnp.where(did, emitted, st["pend"][:S])),
                 "prev": st["prev"].at[:S].set(jnp.where(did, out[:, 0], st["prev"][:S]))}
        u32 = lambda v: jnp.sum(v).astype(jnp.uint32)  # noqa: E731
        drafted = jnp.stack([u32(active), u32(active & kept), jnp.ones((), jnp.uint32),
                             u32(jnp.where(active, emitted, 0))])
        cache = _committed(cache, full, win, counts, windowed, 0, state, drafted)
    return cache, out, emitted, {"logits": logits, "draft_logits": q_logits}


def install_state(cache: dict, slot, state) -> dict:
    raise NotImplementedError(
        "the exaone family keeps no state a slot that can be copied in: the window layers' ring and the drafter's "
        "pending rows are rebuilt by a prefill (the engine refuses prefix entries, parks and host swaps for it)")


def saved_state(cache: dict, slot):
    raise NotImplementedError(
        "the exaone family saves no state a slot (the engine refuses prefix entries, parks and host swaps for it)")


def counters(cache: dict) -> jax.Array:
    """The expert layers', the window layers' and the drafter's counters as the programs keep them."""
    return cache["state"]["counts"]


def describe_counters(config: ExaoneConfig, total) -> dict:
    """``Engine.stats()``'s ``"moe"`` (``kanana``'s keys) and ``"window"``
    (``mellum``'s) and ``"drafter"`` from the counters summed by the engine (None before
    the first dispatch). ``drafter``: ``steps`` verify-and-draft steps,
    ``proposed`` drafts put to a live lane (one a lane and step),
    ``accepted`` those kept, ``tokens`` what the lanes committed (a kept
    draft that a budget, a stop token or the context's edge cut short
    commits one), ``tokens_per_step`` a live lane's mean."""
    c = config
    cut = 1 + COUNTS_HEAD + len(c.held) + WINDOW_COUNTS
    if total is None:
        total = [[0] * (cut + DRAFT_COUNTS)] * 2
    proposed, accepted, steps, rows = (int(v) for v in total[0][cut:cut + DRAFT_COUNTS])
    moe = describe_moe(c, [r[:cut - WINDOW_COUNTS] for r in total])["moe"]
    return {
        "moe": {**moe, "shared_width": c.shared_width},
        **describe_window(total, cut - WINDOW_COUNTS, c.window, c.n_window, c.n_full),
        "drafter": {"proposed": proposed, "accepted": accepted, "steps": steps, "tokens": rows,
                    "tokens_per_step": rows / proposed if proposed else 0.0},
    }
