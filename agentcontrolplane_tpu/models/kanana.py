"""Kanana-2 model family (``model_type: deepseek_v3`` as
``kakaocorp/kanana-2-30b-a3b-instruct-2601`` publishes it): latent attention
(MLA) whose cache is ONE row a token, a leading dense layer, then layers of
routed experts beside a shared expert.

Every layer is ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``:

- ``Attn`` as published: ``q = W_q u`` -> heads of ``nope + rope`` (128 + 64);
  ``[c ; k_pe] = W_kva u`` -> 512 + 64; ``c <- RMSNorm_kv(c)``; rotary over
  the 64 rope values of every head's ``q_pe`` and of the ONE ``k_pe`` all
  heads share; ``[k_nope_h ; v_h] = W_kvb c``; scores over ``sqrt(192)``;
  ``W_o`` over the heads' 128 values. No bias, no q norm (``q_lora_rank``
  null), no ``mscale`` (``rope_scaling`` null).
- ``FFN`` of the first ``first_dense`` layers: a SwiGLU of ``ffn_dim``. Of
  the others: ``ops.moe.routed_experts`` (sigmoid scores, a selection bias
  in the choice only, top k renormalised and times ``routed_scaling_factor``;
  one group, so ``n_group`` / ``topk_group`` mask nothing) of which this chip
  holds ``experts_held``, PLUS the shared expert, a SwiGLU of
  ``n_shared_experts x expert_ffn_dim`` every token passes through, which
  every chip computes alike.

**What is cached** is the row ``[c ; k_pe]`` after the norm and after rotary,
``row_width`` = 576 values in the model's dtype, and nothing a head, where
32 heads of K and V would be 10,240 values. The pool is ``{"kv": [n_layers,
pages, P, row_stored]}`` (``ops/paged.py`` ``init_latent_pages``) with
``row_stored`` = 640: the row on whole 128-lane tiles, its last 64 columns
zeros. That is what the chip's tiling makes of a 576-wide row anyway (the
compiled pool is ``[.., 16, 640]`` in HBM whichever is asked for: 1,280 B a
token and layer, not 1,152), and the kernel's fetch needs it said: Mosaic
slices no HBM operand whose minor axis is not whole lane tiles ("Slice shape
along dimension 2 must be aligned to tiling (128), but is 576": the compile
rehearsal, PERF.md PR 44). Two leaves (``c`` 512, ``k_pe`` 64) would store
``k_pe`` on 128 lanes, the same 640, and fetch twice a page. The config
answers the engine's questions for the row as the pool holds it:
``n_kv_heads`` 1, ``head_dim`` ``row_stored``.

Two attention paths in one family, equal in exact arithmetic
(``tests/engine/test_kanana.py`` holds them together in float32):

- rows of tokens (prefill, continuation, ``forward``) **expand**: the rows'
  latents through ``W_UK`` and ``W_UV`` to per-head K of 192 and V of 128,
  then blocked causal attention (``mla_expand``, ``prefill_attention``). A
  continuation gathers the latent rows it did not write and expands them
  with its own (20 KB a row and layer) and attends densely;
- the decode step **absorbs**: ``q~_h = W_UK_h^T q_nope_h`` (512), scores
  ``[q~_h ; q_pe_h] . row / sqrt(192)``, ``o~_h = sum p row[:512]``, ``o_h =
  W_UV_h o~_h`` (``mla_absorb`` on either side of ``latent_walk``: the kernel
  ``paged_latent_walk`` of ``ops/pallas/paged_attention.py``, or the XLA
  reference of ``ops/paged.py`` in the same form). No per-head K or V of the
  context is ever made: ``stats()["latent"]["decode"]["rows_expanded"]`` is 0.

Departures from the source's layout, made where weights are made or loaded
(``from_published``) and changing no result. Each projection whose columns
the program reads apart is kept as the parts it reads, so that no step
relays a weight to slice it (as one matrix each, a decode block copied 1.7
GB of them before its first step: the compile rehearsal, PERF.md PR 44):
``q_proj`` as ``wq_nope`` [H * 128, D] and ``wq_pe`` [H * 64, D], outputs
first as the source stores it: the decode step's compiler lays the queries
out a head at a time for the absorbed product and takes the projection that
way round. Stored inputs first it copied both stacks transposed, 1.18 GB,
once a decode block before its first step (0.23 ms a step, 1.2 GB of
temporaries, 1% of the cell's tokens a second: my chip runs, PR 44);
``kv_a_proj_with_mqa`` as ``wkv_c`` [D, 512] and ``wk_pe`` [D, 64];
``kv_b_proj`` as ``wuk`` [H, 128, 512] and ``wuv`` [H, 512, 128], a head's
``W_UK`` and ``W_UV`` as the absorbed products contract them. The 64 rope
columns of ``wq_pe`` (a head) and of ``wk_pe`` are **de-interleaved**: the
source rotates pairs ``(2k, 2k + 1)`` (``rope_interleave``), this program
keeps ``ops.rope.apply_rope``'s halves ``(k, k + 32)``, and ``q_pe . k_pe``
is unchanged by one permutation of both. The renormalisation adds ``route_scores``' 1e-6 to the chosen scores'
sum where the source adds 1e-20: 3e-7 of a weight. Read by nothing: the
source's ``head_dim`` 64 (the rope width, here ``qk_rope_head_dim``),
``num_key_value_heads`` 32, ``moe_layer_freq`` 1.

Layout for XLA: the dense layers written out, then ONE scan over the expert
layers, all of one kind; the pool never passes through a conditional
(PERF.md, PR 37). Every program reads the pool through its layer scan and
commits all layers' new rows by one scatter after it.

The family keeps no state a slot (``has_state`` False: its programs take the
page ids alone, as the dense family's), and counts on the device:
``cache["state"]["counts"]`` ``[2, 1 + COUNTS_HEAD + held + 3]`` uint32, row 0
decode steps and row 1 prefills: the expert layers' counters as ``lfm2``
keeps them, then dispatches, rows the attention covered (a layer) and rows
expanded to per-head K and V (a layer).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..observability import scopes
from ..ops.attention import blocked_causal_attention, causal_attention, continue_attention_by_rows
from ..ops.moe import COUNTS_HEAD
from ..ops.norms import rms_norm
from ..ops.paged import (
    TRASH_PAGE, commit_tokens, commit_whole_pages, flat_pages, gather_pages, init_latent_pages, layer_tables,
    latent_decode_attention_reference_cache_plus_new, pool_leaves,
)
from ..ops.rope import apply_rope, deinterleave_pairs
from .experts import describe_moe, routed_ff
from .stack import embed, final_norm, head_logits, key_positions, mm, row_positions

LATENT_COUNTS = 4  # dispatches, rows covered, rows expanded, rows fetched


@dataclass(frozen=True)
class KananaConfig:
    vocab_size: int = 128256
    dim: int = 2048
    n_heads: int = 32
    kv_lora_rank: int = 512  # the latent's width
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_layers: int = 48
    first_dense: int = 1  # first_k_dense_replace
    ffn_dim: int = 6144  # the dense layers' SwiGLU
    expert_ffn_dim: int = 768
    n_experts: int = 128  # the router's width
    experts_per_token: int = 6
    # global ids of the experts this chip holds, in the order of its
    # weights' leading axis; None holds all
    experts_held: Optional[tuple[int, ...]] = None
    n_shared_experts: int = 2  # one SwiGLU of n_shared_experts x expert_ffn_dim
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_seq_len: int = 32768
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # what the engine asks of every config and this family has none of
    attn_logit_softcap: float = 0.0
    post_norms: bool = False
    sliding_window: int = 0

    @property
    def row_width(self) -> int:
        """Values a token and layer keeps: the latent and the shared roped key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_stored(self) -> int:
        """Columns of the pool's row: ``row_width`` on whole 128-lane tiles
        (module text), the rest zeros."""
        return -(-self.row_width // 128) * 128

    # the cache as the engine asks after it: one "head" of the row's width
    @property
    def n_kv_heads(self) -> int:
        return 1

    @property
    def head_dim(self) -> int:
        return self.row_stored

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.expert_ffn_dim

    @property
    def held(self) -> tuple[int, ...]:
        return tuple(range(self.n_experts)) if self.experts_held is None else self.experts_held


PRESETS: dict[str, KananaConfig] = {
    # kakaocorp/kanana-2-30b-a3b-instruct-2601 whole: 61.3 GB of bfloat16, no single chip
    "kanana-2-30b-a3b": KananaConfig(),
    # one of sixteen chips that share each layer: experts 0..7 of 128 held,
    # everything else whole (8.12 GB of weights)
    "kanana-2-30b-a3b-ep16": KananaConfig(experts_held=tuple(range(8))),
    # CPU tests: a dense layer and three expert layers
    "kanana-tiny": KananaConfig(
        vocab_size=256, dim=64, n_heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_layers=4, first_dense=1, ffn_dim=128, expert_ffn_dim=32, n_experts=16, experts_per_token=2,
        n_shared_experts=1, max_seq_len=512, rope_theta=10000.0, dtype=jnp.float32,
    ),
}


def init_params(config: KananaConfig, key: jax.Array) -> dict:
    """Random init in the served layout: ``pro`` a tuple of whole layer dicts
    (the leading dense layers), the expert layers' attention stacked
    (``attn``) and their FF (norm, router, bias, held experts, shared
    expert) stacked (``ff``)."""
    c = config
    d, H, f, eh = c.dim, c.n_heads, c.expert_ffn_dim, len(c.held)
    n = c.n_layers - c.first_dense
    count = [0]

    def w(shape, scale):
        count[0] += 1
        return (jax.random.normal(jax.random.fold_in(key, count[0]), shape) * scale).astype(c.dtype)

    def attn(lead=()):
        r = c.kv_lora_rank
        return {"ln1": jnp.ones(lead + (d,), c.dtype),
                "wq_nope": w(lead + (H * c.qk_nope_head_dim, d), d ** -0.5),
                "wq_pe": w(lead + (H * c.qk_rope_head_dim, d), d ** -0.5),
                "wkv_c": w(lead + (d, r), d ** -0.5),
                "wk_pe": w(lead + (d, c.qk_rope_head_dim), d ** -0.5),
                "kv_norm": jnp.ones(lead + (r,), c.dtype),
                "wuk": w(lead + (H, c.qk_nope_head_dim, r), r ** -0.5),
                "wuv": w(lead + (H, r, c.v_head_dim), r ** -0.5),
                "wo": w(lead + (H * c.v_head_dim, d), (H * c.v_head_dim) ** -0.5)}

    def dense():
        return {"ln2": jnp.ones((d,), c.dtype), "w1": w((d, c.ffn_dim), d ** -0.5),
                "w3": w((d, c.ffn_dim), d ** -0.5), "w2": w((c.ffn_dim, d), c.ffn_dim ** -0.5)}

    sw = c.shared_width
    return {
        "embed": w((c.vocab_size, d), d ** -0.5),
        "norm": jnp.ones((d,), c.dtype),
        "lm_head": w((d, c.vocab_size), d ** -0.5),
        "pro": tuple({**attn(), **dense()} for _ in range(c.first_dense)),
        "attn": attn((n,)),
        "ff": {"ln2": jnp.ones((n, d), c.dtype), "router": w((n, d, c.n_experts), d ** -0.5),
               "router_bias": jnp.zeros((n, c.n_experts), jnp.float32),
               "w1": w((n, eh, d, f), d ** -0.5), "w3": w((n, eh, d, f), d ** -0.5),
               "w2": w((n, eh, f, d), f ** -0.5),
               "sw1": w((n, d, sw), d ** -0.5), "sw3": w((n, d, sw), d ** -0.5), "sw2": w((n, sw, d), sw ** -0.5)},
    }


def from_published(q_proj: jax.Array, kv_a_proj: jax.Array, kv_b_proj: jax.Array, config: KananaConfig) -> dict:
    """The source's ``q_proj`` [D, H * 192], ``kv_a_proj_with_mqa`` [D, 576]
    and ``kv_b_proj`` [512, H * 256] (inputs first) in the served layout
    (module text): each as the parts the program reads, the rope columns
    de-interleaved. -> ``{"wq_nope", "wq_pe", "wkv_c", "wk_pe", "wuk", "wuv"}``."""
    c = config
    H, nope, r = c.n_heads, c.qk_nope_head_dim, c.kv_lora_rank
    q = q_proj.reshape(q_proj.shape[:-1] + (H, c.qk_head_dim))
    kvb = kv_b_proj.reshape(kv_b_proj.shape[:-1] + (H, nope + c.v_head_dim))  # [512, H, 256]
    flat = lambda t: t.reshape(t.shape[:-2] + (t.shape[-2] * t.shape[-1],))  # noqa: E731
    return {"wq_nope": flat(q[..., :nope]).T, "wq_pe": flat(deinterleave_pairs(q[..., nope:])).T,
            "wkv_c": kv_a_proj[..., :r], "wk_pe": deinterleave_pairs(kv_a_proj[..., r:]),
            "wuk": jnp.moveaxis(kvb[..., :nope], -3, -1),  # [H, 128, 512]
            "wuv": jnp.moveaxis(kvb[..., nope:], -3, -2)}  # [H, 512, 128]


def _expand(rows, w, c: KananaConfig):
    """Latent rows [B, T, row_stored] to per-head K [B, T, H, 192] (``k_nope_h`` from
    ``W_UK``, the shared roped key beside it) and V [B, T, H, 128]. -> (K, V,
    the rows it made them for: ``B * T``, padding among them), the count
    every attention path hands on to ``rows_expanded``."""
    with jax.named_scope("mla_expand"):
        B, T, _ = rows.shape
        lat, k_pe = rows[..., :c.kv_lora_rank], rows[..., c.kv_lora_rank:c.row_width]
        k_nope = jnp.einsum("btc,hnc->bthn", lat, w["wuk"].astype(lat.dtype))
        v = jnp.einsum("btc,hcv->bthv", lat, w["wuv"].astype(lat.dtype))
        k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (B, T, c.n_heads, c.qk_rope_head_dim))
        return jnp.concatenate([k_nope, k_pe], axis=-1), v, B * T


def _attention_op(h, w, c: KananaConfig, positions, attend):
    """-> (Op output [B, T, D], the layer's new rows [B, T, row_stored] for
    the pool, the rows the path expanded). ``attend(q_nope [B, T, H, 128],
    q_pe [B, T, H, 64] roped, rows, w) -> ([B, T, H, 128], rows it put
    through ``_expand``)`` is the path: expanded or absorbed."""
    B, T, _ = h.shape
    with jax.named_scope("attn_qkv"):
        # outputs first, as the source stores a projection: the layout the
        # decode step's compiler asks for (module text)
        q_nope = jnp.einsum("btd,nd->btn", h, w["wq_nope"].astype(h.dtype)).reshape(
            B, T, c.n_heads, c.qk_nope_head_dim)
        q_pe = jnp.einsum("btd,nd->btn", h, w["wq_pe"].astype(h.dtype)).reshape(B, T, c.n_heads, c.qk_rope_head_dim)
        q_pe = apply_rope(q_pe, positions, c.rope_theta)
        lat = rms_norm(mm(h, w["wkv_c"]), w["kv_norm"], c.norm_eps)
        # one key for all heads: rotated as one head
        k_pe = apply_rope(mm(h, w["wk_pe"])[..., None, :], positions, c.rope_theta)[..., 0, :]
        pad = jnp.zeros((B, T, c.row_stored - c.row_width), h.dtype)
        rows = jnp.concatenate([lat.astype(h.dtype), k_pe.astype(h.dtype), pad], axis=-1)
    out, expanded = attend(q_nope, q_pe, rows, w)
    with jax.named_scope("attn_out"):
        return mm(out.reshape(B, T, c.n_heads * c.v_head_dim), w["wo"]), rows, expanded


def _run_layers(params, c: KananaConfig, x, positions, valid, make_attend, route=None):
    """The whole stack. ``make_attend(i)`` gives layer ``i``'s (traced index)
    attention path; ``route`` [n_expert_layers, B, T, k] int32, where given,
    is every expert layer's choice of experts, taken as it is (an output
    check's teacher-forced routing; serving never gives one). -> (x, every
    layer's new rows [n_layers, B, T, row_stored], expert counters, the rows
    a layer's attention path expanded)."""
    dt = x.dtype
    norm = lambda x, w: rms_norm(x, w, c.norm_eps)  # noqa: E731
    pro_rows = []
    expanded = jnp.zeros((), jnp.uint32)  # summed over the layers, as each layer's path reports it
    for i, layer in enumerate(params["pro"]):
        with scopes.layer("attn"):
            op, rows, n = _attention_op(norm(x, layer["ln1"]), layer, c, positions, make_attend(jnp.int32(i)))
            x, expanded = x + op, expanded + jnp.uint32(n)
        pro_rows.append(rows.astype(dt)[None])
        with scopes.layer("ffn"), jax.named_scope("ffn_dense"):
            h = norm(x, layer["ln2"])
            x = x + mm(jax.nn.silu(mm(h, layer["w1"])) * mm(h, layer["w3"]), layer["w2"])

    counts = jnp.zeros((1 + COUNTS_HEAD + len(c.held),), jnp.uint32)
    n = c.n_layers - c.first_dense
    if n:
        ff = params["ff"]
        stacks = tuple(ff[name].reshape((-1,) + ff[name].shape[2:]) for name in ("w1", "w3", "w2"))
        small = {name: ff[name] for name in ("ln2", "router", "router_bias", "sw1", "sw3", "sw2")}

        def body(carry, scanned):
            x, counts, expanded = carry
            weights, mine, index, chosen = scanned
            with scopes.layer("attn"):
                op, rows, n = _attention_op(norm(x, weights["ln1"]), weights, c, positions,
                                            make_attend(c.first_dense + index))
                x = x + op
            with scopes.layer("ffn"):
                y, m = routed_ff(norm(x, mine["ln2"]), mine, stacks, index, c, valid, chosen, score="sigmoid", bias=True,
                                 scale=c.routed_scaling_factor, chunk=True, shared=True)
                return (x + y, counts + m, expanded + jnp.uint32(n)), rows.astype(dt)

        (x, counts, expanded), rows = jax.lax.scan(
            body, (x, counts, expanded), (params["attn"], small, jnp.arange(n, dtype=jnp.int32), route))
        pro_rows.append(rows)
    with scopes.layer("commit"):
        return x, jnp.concatenate(pro_rows, axis=0), counts, expanded // jnp.uint32(c.n_layers)


def _expanded(c: KananaConfig, attention):
    """The published path: ``attention(q, k, v)`` over the rows' own K and V."""
    def attend(q_nope, q_pe, rows, w):
        k, v, n = _expand(rows, w, c)
        with jax.named_scope("prefill_attention"):
            return attention(jnp.concatenate([q_nope, q_pe], axis=-1), k, v), n

    return attend


def forward(params: dict, tokens: jax.Array, config: KananaConfig) -> jax.Array:
    """Full-sequence causal forward -> logits [B, T, V] float32 (tests)."""
    c = config
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    attend = _expanded(c, lambda q, k, v: causal_attention(q, k, v, positions))
    x, *_ = _run_layers(params, c, embed(params, tokens, c), positions, jnp.ones((B, T), bool), lambda i: attend)
    return head_logits(final_norm(x, params, c), params, c)


# ---------------------------------------------------------------------------
# Serving: a latent row a token in the paged pool
# ---------------------------------------------------------------------------


def init_paged_cache(config: KananaConfig, num_pages: int, page_size: int, quantize_kv: bool = False,
                     max_slots: int = 1) -> dict:
    c = config
    if quantize_kv:
        raise ValueError("the kanana family keeps its latent rows in the model's dtype: a latent row has no scale "
                         "twin (int8 or fp8 latent rows: ROADMAP M5)")
    cache = init_latent_pages(c.n_layers, num_pages, page_size, c.row_stored, c.dtype)
    cache["state"] = {"counts": jnp.zeros((2, 1 + COUNTS_HEAD + len(c.held) + LATENT_COUNTS), jnp.uint32)}
    return cache


def _committed(cache, pool, counts, covered, expanded, row, fetched=0):
    """The cache with its pages replaced and the dispatch counted."""
    u32 = lambda v: jnp.asarray(v).astype(jnp.uint32)  # noqa: E731
    added = jnp.concatenate([counts, jnp.stack([jnp.ones((), jnp.uint32), u32(covered), u32(expanded), u32(fetched)])])
    return {**pool, "state": {"counts": cache["state"]["counts"].at[row].add(added)}}


def prefill_paged_batch(params, cache, tokens, lengths, page_ids, config: KananaConfig, route=None):
    """B whole prompts in one dispatch, expanded: each row's latent rows into
    its pages. -> (cache, logits [B, V])."""
    c = config
    B, T = tokens.shape
    positions, valid = row_positions(lengths, jnp.zeros((B,), jnp.int32), T)
    attend = _expanded(c, lambda q, k, v: blocked_causal_attention(q, k, v, positions))
    x, rows, counts, expanded = _run_layers(
        params, c, embed(params, tokens, c), positions, valid, lambda i: attend, route)
    with scopes.layer("commit"):
        pool = commit_whole_pages(pool_leaves(cache), {"kv": rows[..., None, :]}, page_ids)
        cache = _committed(cache, pool, counts, jnp.sum(lengths), expanded, 1)
    x = final_norm(x, params, c)
    return cache, head_logits(x, params, c, last=lengths)


def _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, c: KananaConfig):
    """Rows that start at ``starts`` (page-aligned) attend over their
    gathered prefix pages plus themselves: the latent rows gathered (the
    whole table's, whatever the start) are expanded with the rows' own and
    attended densely. Nothing is written here. -> (x normed, new rows,
    counts, rows covered, rows expanded, rows fetched)."""
    B, T = tokens.shape
    positions, valid = row_positions(lengths, starts, T)
    pool = pool_leaves(cache)
    NP, P = pool["kv"].shape[1:3]
    M = block_tables.shape[1]
    key_pos = key_positions(starts, positions, M * P)

    def make_attend(i):
        def attend(q_nope, q_pe, rows, w):
            with jax.named_scope("latent_gather"):
                got = gather_pages(pool, "kv", layer_tables(block_tables, i, NP), rows.dtype, 1)
                ctx = jnp.concatenate([got.reshape(B, M * P, c.row_stored), rows], axis=1)
            k, v, n = _expand(ctx, w, c)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            with jax.named_scope("prefill_attention"):
                # dense over the keys, a block of query rows at a time: the
                # scores of 3,072 rows against 8,192 keys are 3.2 GB at once
                return continue_attention_by_rows(q, k, v, positions, key_pos), n

        return attend

    x, rows, counts, expanded = _run_layers(params, c, embed(params, tokens, c), positions, valid, make_attend)
    live = jnp.where(lengths > 0, starts, 0)
    return final_norm(x, params, c), rows, counts, jnp.sum(lengths + live), expanded, B * M * P


def _continue_commit(cache, new, page_ids):
    rows, counts, covered, expanded, fetched = new
    with scopes.layer("commit"):
        pool = commit_whole_pages(pool_leaves(cache), {"kv": rows[..., None, :]}, page_ids)
        return _committed(cache, pool, counts, covered, expanded, 1, fetched)


def prefill_paged_continue(params, cache, tokens, lengths, starts, page_ids, block_tables, config: KananaConfig):
    """Continuation (a prefix hit's suffix, a later chunk of a long prompt, a
    resumed request's tail): -> (cache, last-token logits [B, V])."""
    x, *new = _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, config)
    return _continue_commit(cache, new, page_ids), head_logits(x, params, config, last=lengths)


def prefill_paged_continue_kv(params, cache, tokens, lengths, starts, page_ids, block_tables,
                              config: KananaConfig):
    """The continuation's writes without the head (a mid chunk)."""
    _x, *new = _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, config)
    return _continue_commit(cache, new, page_ids)


def decode_step_paged(params, cache, tokens, seq_lens, block_tables, active, config: KananaConfig,
                      use_pallas: bool = False, mesh=None, route=None, interpret: bool = False):
    """One token for lanes 0..S-1 (lane b is slot b), absorbed: every layer
    walks the lane's latent rows as they lie in the pool."""
    c = config
    S = tokens.shape[0]
    pool = pool_leaves(cache)
    NP, P = pool["kv"].shape[1:3]
    flat = flat_pages(pool["kv"])
    r, H = c.kv_lora_rank, c.n_heads
    if use_pallas or interpret:
        from ..ops.pallas.paged_attention import paged_latent_attention_cache_plus_new, pages_per_turn

        # the walk fetches whole turns: a slot's last turn reads its last page again
        turn = P * pages_per_turn(P, flat.dtype, 1, c.row_stored, leaves=1)
        fetched = jnp.sum((seq_lens + turn - 1) // turn * turn)
    else:
        fetched = S * block_tables.shape[1] * P  # the reference gathers a lane's whole table

    def make_attend(i):
        def attend(q_nope, q_pe, rows, w):
            dt = rows.dtype
            with jax.named_scope("mla_absorb"):
                # q~_h = W_UK_h^T q_nope_h: the key's expansion folded into the query
                q_lat = jnp.einsum("shn,hnc->shc", q_nope[:, 0], w["wuk"].astype(dt))
                pad = jnp.zeros((S, H, c.row_stored - c.row_width), dt)
                q_row = jnp.concatenate([q_lat.astype(dt), q_pe[:, 0], pad], axis=-1)  # [S, H, row_stored]
            with jax.named_scope("latent_walk"):
                args = (q_row, flat, layer_tables(block_tables, i, NP), seq_lens, rows[:, 0], r, c.qk_head_dim)
                if use_pallas or interpret:
                    o_lat = paged_latent_attention_cache_plus_new(*args, interpret=interpret)
                else:
                    o_lat = latent_decode_attention_reference_cache_plus_new(*args)
            with jax.named_scope("mla_absorb"):
                # o_h = W_UV_h o~_h: the value's expansion applied after the softmax
                out = jnp.einsum("shc,hcv->shv", o_lat, w["wuv"].astype(dt))
            return out.astype(dt)[:, None], 0  # the absorbed path puts no row through `_expand`

        return attend

    x, rows, counts, expanded = _run_layers(
        params, c, embed(params, tokens[:, None], c), seq_lens[:, None], active[:, None], make_attend, route)
    with scopes.layer("commit"):
        target = jnp.where(active, block_tables[jnp.arange(S), seq_lens // P], TRASH_PAGE)
        pool = commit_tokens(pool, {"kv": rows[:, :, 0, None, :]}, target, seq_lens % P)
        cache = _committed(cache, pool, counts, jnp.sum(jnp.where(active, seq_lens + 1, 0)), expanded, 0, fetched)
    x = final_norm(x[:, 0], params, c)
    return cache, head_logits(x, params, c)


def counters(cache: dict) -> jax.Array:
    """The expert layers' and the latent cache's counters as the programs keep them."""
    return cache["state"]["counts"]


def describe_counters(config: KananaConfig, total) -> dict:
    """``Engine.stats()``'s ``"moe"`` (the keys ``lfm2`` gives, and
    ``shared_width``) and ``"latent"`` from the counters summed by the engine
    (``total`` [2, 1 + COUNTS_HEAD + held + LATENT_COUNTS], None before the
    first dispatch), decode steps and prefills apart. ``latent``: ``steps``
    dispatches, ``rows_read`` the rows one layer's attention covered (a
    decode step: the rows the walks covered, each lane's own among them),
    ``rows_expanded`` the rows one layer put through ``_expand`` into
    per-head K and V, as the attention path that ran reports them (a
    bucket's padding rows and a continuation's whole gathered table among
    them): 0 in decode, or the absorbed path is not what runs;
    ``rows_fetched`` the cached rows one layer's attention fetched to cover
    them (a decode step: the walk's whole turns, so the tail a wider turn
    costs is ``rows_fetched`` over ``rows_read``; the XLA reference and a
    continuation gather a lane's whole table; a whole prompt fetches none)."""
    c = config
    cut = 1 + COUNTS_HEAD + len(c.held)
    if total is None:
        total = [[0] * (cut + LATENT_COUNTS)] * 2

    def latent(r):
        return {"steps": int(r[cut]), "rows_read": int(r[cut + 1]), "rows_expanded": int(r[cut + 2]),
                "rows_fetched": int(r[cut + 3])}

    moe = describe_moe(c, [r[:cut] for r in total])["moe"]
    return {
        "moe": {**moe, "shared_width": c.shared_width},
        "latent": {"row_values": c.row_width, "row_bytes_stored": c.row_stored * jnp.dtype(c.dtype).itemsize,
                   "layers": c.n_layers, "decode": latent(total[0]), "prefill": latent(total[1])},
    }


def refusals(asked: dict) -> list[tuple[bool, str]]:
    """What the engine was asked for that this family does not serve, in
    words (``models.programs``): the row is shared by all heads, so a tensor-
    parallel mesh would only replicate it, and ``parallel/mesh.py`` knows
    the dense family's leaves alone."""
    return [
        (asked["kv_layout"] != "paged", "kv_layout='slot': its latent rows live in the paged pool; serve it with kv_layout='paged'"),
        (asked["spec_len"] > 0, "spec_len > 0: it has no verify program over latent rows"),
        (asked["tp"] > 1 or asked["sp"] > 1, "tensor or context parallelism: a latent row is shared by all heads and its weights have no sharding here; serve it on a tp=1 mesh"),
        (asked["quantize_weights"], "weight-only int8: its matrices are served in the dtype they were made in"),
        (asked["quantize_kv"], "quantize_kv: a latent row has no scale twin; its rows are kept in the model's dtype"),
        (asked["coordination"], "multi-host lockstep serving"),
    ]
