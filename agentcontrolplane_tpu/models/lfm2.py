"""LFM2-MoE model family (``model_type: lfm2_moe``): more than one kind of
layer in one model.

Every layer is ``h = x + Op(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))`` with

- ``Op`` a gated short convolution (``conv``): ``[B, C, u] = split3(x W_in)``,
  ``s_t = B_t * u_t``, ``c_t = sum_j w[:, j] * s_{t-(taps-1)+j}`` (depthwise,
  causal, no bias, no activation), ``Op = (C_t * c_t) W_out``; or GQA
  attention (``attention``) with an RMSNorm over each head of q and of k
  before rotary;
- ``FF`` a dense SwiGLU (the first ``num_dense_layers`` layers) or routed
  experts (``ops.moe.routed_experts``: sigmoid scores, a selection bias,
  top-k renormalised, of which this chip holds ``experts_held``).

Layout for XLA: the leading dense layers are written out; weights are
stacked by kind (each layer reading its own row) and the experts of all
layers flattened to one axis, closed over and indexed by the grouped matmul
itself. The expert layers run in one of two layouts (``_run_layers``):

- the decode step, whose cost is the serving rate: a layer's kind is a fact
  of the trace, never a value on the chip. ``stack.segments`` reads
  ``layer_types``: the longest stretch that repeats a period is a
  ``lax.scan`` over periods whose body holds one layer body a run of one
  kind (a run longer than one layer is an inner scan), what stands before
  and after it likewise, and a layer that repeats nothing is written out
  (the published pattern: ``(attention, conv x 3) x 9`` as a scan of nine,
  then ``attention, conv``). No loop is handed a stack it reads one row of,
  and a kind's layer is traced once however many places run it;
- rows of tokens (prefill, continuation, ``forward``): ONE scan whose body is
  one layer, a switch on its kind between the two operators and the expert
  FF once, so the HLO holds each piece once whatever the pattern. The switch
  makes every stack an operand of every layer, which the chip answers by
  moving ``wk`` and the conv state once a layer: 1.9 ms of a 14.7 ms decode
  step, a few percent of a prefill. A body a kind holds the expert FF once a
  body, and the cell's 27 prefill programs with it took half as long again
  to trace, load and compile (PERF.md, PR 41).

Serving state (paged layout only): the KV pool holds the attention layers
alone, in the one layout every family stores and the page walk reads,
``[n_attention, pages, P, H_kv * d]`` (``ops/paged.py``'s module text says
why; int8 pages keep a scale a row and head, ``[.., P, H_kv]``), and beside
it ``cache["state"]``:

- ``conv``  ``[n_conv, slots, taps-1, D]`` in the model's dtype: the last
  ``taps-1`` values of ``s`` of every slot and conv layer, what a decode
  step reads and shifts (0.25 MB a slot at the published widths);
- ``snap``  the same shape: a copy taken inside a prefill at the one
  page-aligned length the engine names (``snap_at``), the state a prefix
  hit, a park or a host swap at that length resumes from;
- ``moe``   ``[2, 1 + COUNTS_HEAD + held]`` uint32 counters of the expert
  layers (row 0 decode steps, row 1 prefills), wrapping; ``Engine.stats``
  sums their differences.

Every program takes ``lanes = (slots, snap_at)`` beside the page ids: which
slot's state each row reads and writes, and where (absolute tokens, -1:
nowhere) its snapshot is due. A row that starts at 0 starts from a zero
state; one that starts later reads ``conv[:, slot]``, which the engine has
set (the previous chunk left it, or ``install_state`` copied it in).

The decode walk reads the whole pool flattened to ``[L * pages, P, ...]``
through block tables offset by the layer, and every commit goes through the
same flat view, so no layer of the pool is sliced out or relaid per step:
``ops/paged.py``'s ``flat_pages``, ``layer_tables``, ``commit_whole_pages``,
``commit_tokens`` and ``gather_pages``, the one copy ``models/llama.py``
uses too.
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
from ..ops.paged import TRASH_PAGE, commit_tokens, commit_whole_pages, init_kv_pages
from .experts import describe_moe as describe_counters  # noqa: F401  the seam's: ``Engine.stats()["moe"]``
from .experts import routed_ff
from .stack import (
    attention_op, embed, final_norm, head_logits, kv_pool, layer_row, mm, page_walk, prefix_attention, rows_ctx, scan_layers,
)

PERIOD = ("attention", "conv", "conv", "conv")


def _pattern(prologue: int, periods: int, tail: tuple[str, ...]) -> tuple[str, ...]:
    return ("conv",) * prologue + PERIOD * periods + tail


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    dim: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    layer_types: tuple[str, ...] = _pattern(2, 9, ("attention", "conv"))
    num_dense_layers: int = 2
    ffn_dim: int = 11776  # the dense layers' SwiGLU
    expert_ffn_dim: int = 1536
    n_experts: int = 64  # the router's width
    experts_per_token: int = 4
    # global ids of the experts this chip holds, in the order of its
    # weights' leading axis; None holds all
    experts_held: Optional[tuple[int, ...]] = None
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    conv_taps: int = 3  # conv_L_cache
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_seq_len: int = 128000
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    # what the engine asks of every config and this family has none of
    attn_logit_softcap: float = 0.0
    post_norms: bool = False
    sliding_window: int = 0

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_attention(self) -> int:
        return sum(t == "attention" for t in self.layer_types)

    @property
    def n_conv(self) -> int:
        return sum(t == "conv" for t in self.layer_types)

    @property
    def held(self) -> tuple[int, ...]:
        return tuple(range(self.n_experts)) if self.experts_held is None else self.experts_held


PRESETS: dict[str, Lfm2Config] = {
    # LiquidAI/LFM2-24B-A2B whole: 47.7 GB of bfloat16, no single chip
    "lfm2-24b-a2b": Lfm2Config(),
    # one of eight chips that share each layer: experts 0..7 of 64 held,
    # everything else whole (7.5 GB of weights)
    "lfm2-24b-a2b-ep8": Lfm2Config(experts_held=tuple(range(8))),
    # CPU tests: every kind of layer, prologue, two periods and the tail
    "lfm2-tiny": Lfm2Config(
        vocab_size=256, dim=64, n_heads=4, n_kv_heads=2, head_dim=16,
        layer_types=_pattern(2, 2, ("attention", "conv")), num_dense_layers=2,
        ffn_dim=128, expert_ffn_dim=32, n_experts=8, experts_per_token=2,
        max_seq_len=256, rope_theta=10000.0, dtype=jnp.float32,
    ),
}


def plan(c: Lfm2Config) -> dict:
    """The layer list as the leading dense layers' kinds (written out) and
    the expert layers' (one scan), with each expert layer's index among the
    attention or the conv layers of the scan."""
    import numpy as np

    types = tuple(c.layer_types)
    bad = set(types) - {"conv", "attention"}
    if bad:
        raise ValueError(f"unknown layer types {sorted(bad)} (conv|attention)")
    pro, body = types[:c.num_dense_layers], types[c.num_dense_layers:]
    is_attn = np.array([k == "attention" for k in body], dtype=bool)
    return {
        "prologue": pro, "body": body, "is_attn": is_attn,
        # an expert layer's row in the stack of its own kind (0 for the other's)
        "attn_row": np.where(is_attn, np.cumsum(is_attn) - 1, 0).astype(np.int32),
        "conv_row": np.where(~is_attn, np.cumsum(~is_attn) - 1, 0).astype(np.int32),
    }


def init_params(config: Lfm2Config, key: jax.Array) -> dict:
    """Random init in the served layout: ``pro`` a tuple of whole layer
    dicts (the leading dense layers), and the expert layers' weights stacked
    by what they are: ``attn`` and ``conv`` (the operators, each over its
    own layers in order) and ``ff`` (norm, router and experts, over all
    expert layers)."""
    c, pl_ = config, plan(config)
    d, hd, eh, f = c.dim, c.head_dim, len(c.held), c.expert_ffn_dim
    count = [0]

    def w(shape, scale):
        count[0] += 1
        return (jax.random.normal(jax.random.fold_in(key, count[0]), shape) * scale).astype(c.dtype)

    def conv(lead=()):
        return {"ln1": jnp.ones(lead + (d,), c.dtype), "conv_in": w(lead + (d, 3 * d), d ** -0.5),
                "conv_w": w(lead + (d, c.conv_taps), 0.5), "conv_out": w(lead + (d, d), d ** -0.5)}

    def attn(lead=()):
        return {"ln1": jnp.ones(lead + (d,), c.dtype),
                "wq": w(lead + (d, c.n_heads * hd), d ** -0.5), "wk": w(lead + (d, c.n_kv_heads * hd), d ** -0.5),
                "wv": w(lead + (d, c.n_kv_heads * hd), d ** -0.5), "wo": w(lead + (c.n_heads * hd, d), d ** -0.5),
                "q_norm": jnp.ones(lead + (hd,), c.dtype), "k_norm": jnp.ones(lead + (hd,), c.dtype)}

    def dense():
        return {"ln2": jnp.ones((d,), c.dtype), "w1": w((d, c.ffn_dim), d ** -0.5),
                "w3": w((d, c.ffn_dim), d ** -0.5), "w2": w((c.ffn_dim, d), c.ffn_dim ** -0.5)}

    n_body, n_attn = len(pl_["body"]), int(pl_["is_attn"].sum())
    params = {
        "embed": w((c.vocab_size, d), d ** -0.5),
        "norm": jnp.ones((d,), c.dtype),
        "pro": tuple({**(conv() if k == "conv" else attn()), **dense()} for k in pl_["prologue"]),
        "attn": attn((n_attn,)),
        "conv": conv((n_body - n_attn,)),
        "ff": {"ln2": jnp.ones((n_body, d), c.dtype), "router": w((n_body, d, c.n_experts), d ** -0.5),
               "router_bias": jnp.zeros((n_body, c.n_experts), jnp.float32),
               "w1": w((n_body, eh, d, f), d ** -0.5), "w3": w((n_body, eh, d, f), d ** -0.5),
               "w2": w((n_body, eh, f, d), f ** -0.5)},
    }
    if not c.tie_embeddings:
        params["lm_head"] = w((d, c.vocab_size), d ** -0.5)
    return params


def _conv_op(h, layer, c: Lfm2Config, state_in, lengths, snap_rel):
    """h [B, T, D] normed input; state_in [B, taps-1, D] (s before the
    row's first token). -> (Op output [B, T, D], state at each row's end
    [B, taps-1, D], state at ``snap_rel`` tokens into the row)."""
    with jax.named_scope("short_conv"):
        B, T, D = h.shape
        n = c.conv_taps - 1
        # the gates and the taps in float32 inside the operator, straight
        # from the projection's accumulator; `s` itself in the model's dtype,
        # the one the state keeps it in, so that a decode step that reads two
        # values back convolves what the prefill convolved
        with jax.named_scope("conv_in_proj"):
            bcu = jnp.matmul(h, layer["conv_in"].astype(h.dtype), preferred_element_type=jnp.float32)
        b_, c_, u_ = bcu[..., :D], bcu[..., D:2 * D], bcu[..., 2 * D:]
        s = (b_ * u_).astype(h.dtype)
        s_ext = jnp.concatenate([state_in.astype(h.dtype), s], axis=1).astype(jnp.float32)  # [B, n + T, D]
        taps = layer["conv_w"].astype(jnp.float32)  # [D, taps]
        conv = sum(s_ext[:, j:j + T] * taps[:, j] for j in range(c.conv_taps))
        with jax.named_scope("conv_out_proj"):
            out = mm((c_ * conv).astype(h.dtype), layer["conv_out"])

        def state_at(rel):  # the n values of s before token `rel` of the row
            idx = jnp.clip(rel, 0, T)[:, None] + jnp.arange(n)[None, :]
            return jnp.take_along_axis(s_ext, idx[:, :, None], axis=1).astype(h.dtype)

        return out, state_at(lengths), state_at(snap_rel)


def _run_layers(params, c: Lfm2Config, x, ctx, conv_state, make_attn, route=None, by_kind=False):
    """The whole stack. ``conv_state`` [n_conv, B, taps-1, D] is each conv
    layer's state before the rows; ``make_attn(a)`` gives attention layer
    ``a``'s (traced index) attention function; ``route``
    [n_expert_layers, B, T, k] int32, where given, is every expert layer's
    choice of experts, taken as it is (an output check's teacher-forced
    routing; serving never gives one). -> (x, conv ends
    [n_conv, B, taps-1, D], conv snaps, new k [n_attention, B, T, H_kv, d],
    new v, expert counters).

    The leading dense layers are written out. The expert layers run in one
    of two layouts, the same operations on the same values in the same
    order (module text): ``by_kind`` (the decode step) as ``scan_layers``
    lays the pattern out, each loop's body one kind of layer that reads its
    own row of its kind's stack, of the state and of the router by the
    loops' counters; otherwise (rows of tokens) as ONE scan whose body is
    one layer, a switch on the kind between the two operators and the expert
    FF once."""
    pl_ = plan(c)
    B, T, D = x.shape
    n = c.conv_taps - 1
    kv_shape = (B, T, c.n_kv_heads, c.head_dim)
    ends, snaps, ks, vs = [], [], [], []
    # the residual stream in the model's dtype, as the source serves it:
    # 80 sublayers' sums rounded to bfloat16 each are the largest part of
    # what the program loses against its float32 reference (PERF.md, PR 31)
    dt = x.dtype
    norm = lambda x, w: rms_norm(x, w, c.norm_eps)  # noqa: E731

    def operator(kind, x, layer, at):
        """``Op(RMSNorm(x))`` with ``layer`` the weights of the ``at``-th
        layer of ``kind`` after those ``done`` -> (Op, (end, snap) or (k, v))."""
        with scopes.layer("mixer" if kind == "conv" else "attn"):
            h = norm(x, layer["ln1"])
            at = done[kind] + at
            if kind == "conv":
                op, *out = _conv_op(h, layer, c, conv_state[at], ctx["lengths"], ctx["snap_rel"])
            else:
                op, *out = attention_op(h, layer, c, ctx["positions"], make_attn(at),
                                         walk="page_walk" if by_kind else "prefill_attention")
            return op, tuple(o.astype(dt) for o in out)

    done = {"conv": 0, "attention": 0}  # layers of each kind so far
    kept = {"conv": (ends, snaps), "attention": (ks, vs)}
    for kind, layer in zip(pl_["prologue"], params["pro"]):
        op, out = operator(kind, x, layer, 0)
        for part, o in zip(kept[kind], out):
            part.append(o[None])
        done[kind] += 1
        with scopes.layer("ffn"), jax.named_scope("ffn_dense"):
            x = x + op
            h = norm(x, layer["ln2"])
            x = x + mm(jax.nn.silu(mm(h, layer["w1"])) * mm(h, layer["w3"]), layer["w2"])

    counts = jnp.zeros((1 + COUNTS_HEAD + len(c.held),), jnp.uint32)
    if pl_["body"]:
        ff = params["ff"]
        stacks = tuple(ff[name].reshape((-1,) + ff[name].shape[2:]) for name in ("w1", "w3", "w2"))
        small = {name: ff[name] for name in ("ln2", "router", "router_bias")}
        stack = {"attention": params["attn"], "conv": params["conv"]}

        def expert_layer(carry, op, mine, index, chosen):
            """``mine``: the layer's norm and router."""
            x, counts = carry
            with scopes.layer("ffn"):
                x = x + op
                y, m = routed_ff(norm(x, mine["ln2"]), mine, stacks, index, c, ctx["valid"], chosen, score="sigmoid",
                                 bias=c.use_expert_bias, scale=c.routed_scaling_factor)
                return x + y, counts + m

        if by_kind:
            def layer(kind, carry, index, at):
                with scopes.layer("mixer" if kind == "conv" else "attn"):
                    weights = layer_row(stack[kind], at)
                op, out = operator(kind, carry[0], weights, at)
                with scopes.layer("ffn"):
                    mine, chosen = layer_row(small, index), None if route is None else route[index]
                return expert_layer(carry, op, mine, index, chosen), out

            (x, counts), outs = scan_layers(pl_["body"], (x, counts), layer)
            for kind, out in outs.items():
                for part, o in zip(kept[kind], out):
                    part.append(o)
        else:
            def branch(kind):
                def run(x, at):  # -> (Op, end, snap, k, v), zeros for the other kind's
                    with scopes.layer("mixer" if kind == "conv" else "attn"):
                        weights = layer_row(stack[kind], at[kind])
                    op, out = operator(kind, x, weights, at[kind])
                    zero = jnp.zeros(kv_shape if kind == "conv" else (B, n, D), dt)
                    return (op, *out, zero, zero) if kind == "conv" else (op, zero, zero, *out)

                return run

            def body(carry, scanned):
                mine, index, is_attn, at, chosen = scanned
                if len(set(pl_["body"])) == 1:  # one kind only: its stack alone has rows
                    op, *out = branch(pl_["body"][0])(carry[0], at)
                else:
                    op, *out = jax.lax.cond(is_attn, branch("attention"), branch("conv"), carry[0], at)
                return expert_layer(carry, op, mine, index, chosen), tuple(out)

            n_body = len(pl_["body"])
            (x, counts), (e, s_, kk, vv) = jax.lax.scan(
                body, (x, counts),
                (small, jnp.arange(n_body, dtype=jnp.int32), jnp.asarray(pl_["is_attn"]),
                 {"attention": jnp.asarray(pl_["attn_row"]), "conv": jnp.asarray(pl_["conv_row"])}, route))
            attn_at, conv_at = pl_["is_attn"].nonzero()[0], (~pl_["is_attn"]).nonzero()[0]
            with scopes.layer("commit"):  # each kind's rows out of what the one body stacked for both
                for part, o in zip((ends, snaps, ks, vs), (e[conv_at], s_[conv_at], kk[attn_at], vv[attn_at])):
                    part.append(o)

    cat = lambda parts, shape, dtype: (  # noqa: E731
        jnp.concatenate(parts, axis=0) if parts else jnp.zeros((0,) + shape, dtype))
    with scopes.layer("commit"):
        return (x, cat(ends, (B, n, D), dt), cat(snaps, (B, n, D), dt), cat(ks, kv_shape, dt),
                cat(vs, kv_shape, dt), counts)


def _zero_state(c: Lfm2Config, B: int) -> jax.Array:
    """Every conv layer's state before a sequence starts."""
    return jnp.zeros((c.n_conv, B, c.conv_taps - 1, c.dim), c.dtype)


def forward(params: dict, tokens: jax.Array, config: Lfm2Config) -> jax.Array:
    """Full-sequence causal forward -> logits [B, T, V] float32 (tests)."""
    c = config
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    ctx = {"positions": positions, "valid": jnp.ones((B, T), bool),
           "lengths": jnp.full((B,), T, jnp.int32), "snap_rel": jnp.zeros((B,), jnp.int32)}
    x, *_ = _run_layers(params, c, embed(params, tokens, c), ctx, _zero_state(c, B),
                        lambda a: lambda q, k, v: causal_attention(q, k, v, positions))
    return head_logits(final_norm(x, params, c), params, c)


# ---------------------------------------------------------------------------
# Serving: pages for the attention layers, state beside them
# ---------------------------------------------------------------------------


def init_paged_cache(config: Lfm2Config, num_pages: int, page_size: int, quantize_kv: bool = False,
                     max_slots: int = 1) -> dict:
    c = config
    cache = init_kv_pages(c.n_attention, num_pages, page_size, c.n_kv_heads, c.head_dim, c.dtype,
                          quantize=quantize_kv)
    shape = (c.n_conv, max_slots, c.conv_taps - 1, c.dim)
    cache["state"] = {
        "conv": jnp.zeros(shape, c.dtype),
        "snap": jnp.zeros(shape, c.dtype),
        "moe": jnp.zeros((2, 1 + COUNTS_HEAD + len(c.held)), jnp.uint32),
    }
    return cache


def _commit_state(cache, pages, slots, ends, snaps, snap_ok, counts, row):
    """The cache with its pages replaced and the rows' state written: a
    row's end state always, its snapshot where one fell inside the row."""
    st = cache["state"]
    with scopes.layer("commit"):
        conv = st["conv"].at[:, slots].set(ends.astype(st["conv"].dtype), mode="drop")
        old = st["snap"][:, jnp.clip(slots, 0, st["snap"].shape[1] - 1)]
        snap = st["snap"].at[:, slots].set(
            jnp.where(snap_ok[None, :, None, None], snaps.astype(old.dtype), old), mode="drop")
        return {**pages, "state": {"conv": conv, "snap": snap, "moe": st["moe"].at[row].add(counts)}}


def _state_in(cache, slots, starts):
    """[n_conv, B, taps-1, D]: zeros for a row that starts the sequence,
    the slot's state otherwise. A padding lane's slot is out of range: it
    reads any slot's and writes nowhere (``mode="drop"``)."""
    conv = cache["state"]["conv"]
    with scopes.layer("commit"):
        got = conv[:, jnp.clip(slots, 0, conv.shape[1] - 1)]
        return jnp.where((starts > 0)[None, :, None, None], got, 0)


def prefill_paged_batch(params, cache, tokens, lengths, page_ids, lanes, config: Lfm2Config, route=None):
    """B whole prompts in one dispatch: K/V into each row's pages, the conv
    state at the prompt's end into its slot. -> (cache, logits [B, V])."""
    c = config
    slots, snap_at = lanes
    B, T = tokens.shape
    zero = jnp.zeros((B,), jnp.int32)
    ctx, snap_ok = rows_ctx(lengths, zero, snap_at, T)
    positions = ctx["positions"]
    x, ends, snaps, new_k, new_v, counts = _run_layers(
        params, c, embed(params, tokens, c), ctx, _zero_state(c, B),
        lambda a: lambda q, k, v: blocked_causal_attention(q, k, v, positions), route)
    pages = commit_whole_pages(kv_pool(cache), {"k": new_k, "v": new_v}, page_ids)
    cache = _commit_state(cache, pages, slots, ends, snaps, snap_ok, counts, 1)
    x = final_norm(x, params, c)
    return cache, head_logits(x, params, c, last=lengths)


def _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, lanes, c):
    """Rows that start at ``starts`` (page-aligned), attending over their
    gathered prefix pages plus themselves, conv layers carried on from the
    slots' state. -> (x normed, new k, new v uncommitted, ends, snaps,
    snap_ok, counts)."""
    slots, snap_at = lanes
    B, T = tokens.shape
    ctx, snap_ok = rows_ctx(lengths, starts, snap_at, T)
    positions = ctx["positions"]
    make_attn = prefix_attention(kv_pool(cache), block_tables, starts, positions, c.n_kv_heads)
    x, ends, snaps, new_k, new_v, counts = _run_layers(
        params, c, embed(params, tokens, c), ctx, _state_in(cache, slots, starts), make_attn)
    return final_norm(x, params, c), new_k, new_v, ends, snaps, snap_ok, counts


def prefill_paged_continue(params, cache, tokens, lengths, starts, page_ids, block_tables, lanes,
                           config: Lfm2Config):
    """Continuation (a prefix hit's suffix, a later chunk of a long
    prompt): -> (cache, last-token logits [B, V])."""
    x, new_k, new_v, ends, snaps, snap_ok, counts = _paged_continue_forward(
        params, cache, tokens, lengths, starts, block_tables, lanes, config)
    pages = commit_whole_pages(kv_pool(cache), {"k": new_k, "v": new_v}, page_ids)
    cache = _commit_state(cache, pages, lanes[0], ends, snaps, snap_ok, counts, 1)
    return cache, head_logits(x, params, config, last=lengths)


def prefill_paged_continue_kv(params, cache, tokens, lengths, starts, page_ids, block_tables, lanes,
                              config: Lfm2Config):
    """The continuation's writes without the head (a mid chunk)."""
    _x, new_k, new_v, ends, snaps, snap_ok, counts = _paged_continue_forward(
        params, cache, tokens, lengths, starts, block_tables, lanes, config)
    pages = commit_whole_pages(kv_pool(cache), {"k": new_k, "v": new_v}, page_ids)
    return _commit_state(cache, pages, lanes[0], ends, snaps, snap_ok, counts, 1)


def decode_step_paged(params, cache, tokens, seq_lens, block_tables, active, config: Lfm2Config,
                      use_pallas: bool = False, mesh=None, route=None):
    """One token for lanes 0..S-1 (lane b is slot b): attention layers walk
    the pages, conv layers read and shift their slot's state; an inactive
    lane's state and pages are left as they were."""
    c = config
    S = tokens.shape[0]
    pool = kv_pool(cache)
    P = pool["k"].shape[2]
    make_attn = page_walk(pool, block_tables, seq_lens, use_pallas)
    ctx = {"positions": seq_lens[:, None], "valid": active[:, None],
           "lengths": jnp.ones((S,), jnp.int32), "snap_rel": jnp.zeros((S,), jnp.int32)}
    st = cache["state"]
    x, ends, _snaps, new_k, new_v, counts = _run_layers(
        params, c, embed(params, tokens[:, None], c), ctx, st["conv"][:, :S], make_attn, route, by_kind=True)
    with scopes.layer("commit"):
        target = jnp.where(active, block_tables[jnp.arange(S), seq_lens // P], TRASH_PAGE)
        pages = commit_tokens(pool, {"k": new_k[:, :, 0], "v": new_v[:, :, 0]}, target, seq_lens % P)
        conv = st["conv"].at[:, :S].set(
            jnp.where(active[None, :, None, None], ends.astype(st["conv"].dtype), st["conv"][:, :S]))
        cache = {**pages, "state": {"conv": conv, "snap": st["snap"], "moe": st["moe"].at[0].add(counts)}}
    x = final_norm(x[:, 0], params, c)
    return cache, head_logits(x, params, c)


def install_state(cache: dict, slot, state: jax.Array) -> dict:
    """``conv[:, slot] = state`` [n_conv, taps-1, D]: what a continuation
    that starts past 0 in ``slot`` resumes from (a prefix entry's, a parked
    turn's or a host entry's saved state)."""
    st = cache["state"]
    conv = jax.lax.dynamic_update_slice(st["conv"], state.astype(st["conv"].dtype)[:, None], (0, slot, 0, 0))
    return {**cache, "state": {**st, "conv": conv}}


def saved_state(cache: dict, slot) -> jax.Array:
    """A copy of ``snap[:, slot]``: the state at the slot's ``snap_at``."""
    snap = cache["state"]["snap"]
    return jax.lax.dynamic_slice(snap, (0, slot, 0, 0), (snap.shape[0], 1) + snap.shape[2:])[:, 0]


def counters(cache: dict) -> jax.Array:
    """The expert layers' counters as the programs keep them (``moe``)."""
    return cache["state"]["moe"]
