"""dots3 model family, the language model of ``dots-studio/dots3-note-prev``
(``model_type: dots3_note``): latent attention (MLA) at TWO ranks in one
model. Full layers keep a latent row of 576 values a token and attend over
the 2,048 rows a learned indexer chooses for every query; sliding layers
keep a latent row of 1,088 values and attend over the last 513 rows, the
query's own among them; both multiply each head's output by a gate. A
leading dense layer, then routed experts beside a shared one.

Every layer is ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``.
With ``u = RMSNorm(x_t)`` for the token at position ``t``, and a layer's
sizes (``DotsConfig.full`` / ``.swa``: heads ``H``, ``nope``, ``rope``, ``v``,
``q_rank``, ``kv_rank``, ``theta``):

- ``c^Q = a_q RMSNorm(W_qa u)`` (``q_rank``); ``[q^N_h ; q^R_h] = W_qb c^Q``
  (``H`` heads of ``nope + rope``), ``q^R`` roped; ``[c' ; k^R] = W_kva u``
  (``kv_rank + rope``), ``c = a_kv RMSNorm(c')``, ``k^R`` roped and shared by
  every head. The row kept is ``[c ; rope(k^R)]``. ``k_{h,s} = [W^UK_h c_s ;
  k^R_s]``, ``v_{h,s} = W^UV_h c_s``; scores over ``sqrt(nope + rope)``.
  ``a_q = sqrt(dim / q_rank)``, ``a_kv = sqrt(dim / kv_rank)``
  (``apply_mla_qkv_lora_rescale``: the LongCat-Flash report's scale
  correction of a latent narrower than the stream).
- **a full layer's indexer** (the published DeepSeek-V3.2-Exp form): ``q^I =
  W^I_q c^Q`` (``index_heads`` of ``index_head_dim``, from the QUERY's
  latent), ``k^I_s = LayerNorm(W^I_k u_s)`` (ONE head), the first ``rope``
  values of both roped at the layer's theta, ``w = W^I_w u``; ``I(t, s) =
  sum_j w_j ReLU(q^I_j . k^I_s)`` in float32 (``ops.attention.index_scores``),
  ``S_t`` the ``min(t + 1, index_topk)`` rows of largest score, ties to the
  earlier row. Attention is the softmax over ``s in S_t`` alone. A positive
  factor on ``I(t, .)`` changes no choice, so the source's scaling of ``w``
  is left out, as its Hadamard rotation and its fp8 keys are
  (``models/keye.py``).
- **a sliding layer** attends over ``t - (window - 1) <= s <= t``
  (``sliding_window_size`` 513 rows with the token's own) and has no indexer.
- **the gate** (``attention_gate_type: headwise``, arXiv:2505.06708): ``g =
  sigmoid(W_g u)``, one scalar a head, times that head's attention output
  before ``W_o``.
- ``FFN`` of the first ``first_dense`` layers: a SwiGLU of ``ffn_dim``. Of
  the others: ``ops.moe.routed_experts`` (float32 router over ``n_experts``,
  sigmoid, the ``experts_per_token`` largest of ``score + bias``, the unbiased
  scores renormalised, times ``routed_scaling_factor``) of which this chip
  holds ``experts_held``, plus ONE shared SwiGLU expert over every token;
  nothing stands in for the experts held elsewhere.

**What is cached**, a pool of three leaves of three widths
(``ops.paged.init_row_pages``): on the page list a slot, ``kv`` ``[full
layers, pages, P, 640]``, a full layer's latent row (576 values on five
128-lane tiles, the rest zeros: what the chip stores of a 576-wide row
anyway, ``models/kanana.py``), and ``ik`` ``[full layers, pages, P, 128]``,
its indexer's key after norm and rope (a whole lane tile, no padding); and
``wkv`` ``[sliding layers, (slots + 1) * ring, P, 1152]``, a sliding layer's
latent row (1,088 values on nine tiles) in a **ring** a slot
(``ops/paged.py``'s ``ring_*``, as ``models/mellum.py``'s but of ONE leaf):
``ring`` pages fixed to the slot, position ``p`` in ring page ``(p // P) %
ring``, ``window`` (the 513 rows rounded up to whole pages of 16: 528) ``/ P
+ 1`` pages, 34 at 16 rows a page; the slot after the last is where padding
lanes write. ``ops.paged.kv_commit`` and the engine's page helpers take the
leaves as they come.

Attention paths, equal in exact arithmetic (``tests/engine/test_dots.py``):

- rows of tokens (prefill, continuation, ``forward``) **expand** rows to
  per-head K and V (``mla_expand``). A full layer makes its mask a block of
  queries at a time (``models/keye.py``'s ``prompt_mask``: index scores, the
  ``index_topk``-th largest a row) and attends under it ``HEAD_GROUP`` heads
  at a time (128 heads of a 16,384-row prompt's q, K, V and output are 3.2
  GB at once): on a TPU by the kernel of ``ops/pallas/masked_attention.py``
  with keys of 192 padded to 256 beside values of 128, elsewhere by the plain
  ``causal_attention(keep=)``. A sliding layer attends a block of 512 queries
  at a time over the slice of keys its window reaches (``_banded``).
- the decode step **absorbs**: ``q~_h = [W^UK_h^T q^N_h ; q^R_h]`` against
  the row as it lies, values its first ``kv_rank`` columns, ``W^UV_h`` after
  the softmax (``mla_absorb``). A full layer scores every cached ``ik`` row,
  chooses (``ops.paged.chosen_rows``: on a TPU the kernel
  ``ops/pallas/index_select.py``, a threshold held on the chip and no sort;
  off it ``jax.lax.top_k``, the same set), fetches the chosen LATENT rows by
  row and attends over them (``ops.paged.sparse_latent_decode_attention_cache_plus_new``:
  ``index_scores``, ``index_select``, ``sparse_latent``); ONE program either
  side of ``index_topk`` rows (a lane under it chooses all its rows, its
  list padded and masked). A sliding layer gathers its slot's ring and
  attends over the rows inside the window (``ring_latent``:
  ``ops.paged.ring_latent_decode_attention_cache_plus_new``). Both walks are
  XLA: no per-head K or V of the context is ever made, and no kernel walks either
  (``ops/pallas/paged_attention.py``'s latent walk reads a block table from
  row 0 and every row: ROADMAP M1, M5, M10).

Departures from the source's layout, made where weights are made or loaded
and changing no result (``models/kanana.py`` says why for each):
``q_b_proj`` as ``wq_nope`` [H * nope, q_rank] and ``wq_pe`` [H * rope,
q_rank], outputs first; ``kv_a_proj_with_mqa`` as ``wkv_c`` and ``wk_pe``;
``kv_b_proj`` as ``wuk`` [H, nope, kv_rank] and ``wuv`` [H, kv_rank, v]; the
rope columns de-interleaved where the published layout is the interleaved
one (``ops.rope.deinterleave_pairs``).

Layout for XLA: three kinds of layer (``dense_full``, ``full``,
``sliding``) run as loops whose body has one kind
(``models.lfm2.scan_layers``); weights stacked by kind (``dense``, ``full``,
``swa``) and the expert FF over all expert layers (``ff``); the pool never
passes through a conditional (PERF.md, PR 37); every program commits all
layers' new rows after the loops.

The programs take ``lanes = (slots, snap_at)`` as every family with state a
slot does (the ring is the slot's); nothing of the ring is saved or
installed, so prefix entries, parks and host swaps are refused by the engine
(``models.programs``). Counted on the device: ``cache["state"]["counts"]``
``[2, 1 + COUNTS_HEAD + held + SPARSE_COUNTS + WINDOW_COUNTS]`` uint32, row 0
decode steps and row 1 prefills: the expert layers' counters as ``lfm2``
keeps them, the full layers' as ``keye`` keeps them, the sliding layers' as
``mellum`` keeps them.
"""

from __future__ import annotations

from dataclasses import dataclass
from types import SimpleNamespace
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..observability import scopes
from ..ops.attention import CONTINUE_BLOCK, blocked_masked_attention, causal_attention, continue_attention
from ..ops.moe import COUNTS_HEAD
from ..ops.norms import rms_norm
from ..ops.paged import (
    TRASH_PAGE, commit_tokens, commit_whole_pages, flat_pages, gather_pages, init_row_pages, layer_tables,
    ring_latent_decode_attention_cache_plus_new, ring_newest, ring_positions, ring_size, ring_tables,
    sparse_latent_decode_attention_cache_plus_new,
)
from ..ops.rope import apply_rope
from .experts import describe_moe, routed_ff
# the one family this file imports (`tests/engine/test_model_seam.py`): the chosen-rows machinery stays in `keye.py`
# because the benchmark plants its controls of the choice on that module's globals by name (`index_scores`,
# `topk_rows_mask`, `apply_rope`, `_layer_norm`: `acpbench/families/dots.py _planted`), and on `_layer_norm` and
# `_rope_first` here: a function that read them from another module would silently no longer be planted on
from .keye import SPARSE_COUNTS, _layer_norm, causal_ok, chosen_mask, packed, prompt_mask, row_blocks, unpacked
from .stack import embed, final_norm, head_logits, key_positions, layer_row, mm, row_positions, scan_layers
from .window import WINDOW_COUNTS, describe_window, slot_ring, window_counts

HEAD_GROUP = 32  # heads a full layer's rows of tokens expand and attend at a time (module text)
FULL, SLIDING = "full_attention", "sliding_attention"


def _pattern(n: int) -> tuple[str, ...]:
    """The published list's first ``n``: full, full, then (sliding x 3, full) repeated."""
    return ((FULL, FULL) + (SLIDING, SLIDING, SLIDING, FULL) * n)[:n]


@dataclass(frozen=True)
class DotsConfig:
    vocab_size: int = 152064
    dim: int = 5120
    # a full layer's latent attention
    n_heads: int = 128
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    rope_theta: float = 8e7
    # a sliding layer's, at its own sizes
    swa_n_heads: int = 64
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_rope_theta: float = 5e4
    sliding_window_size: int = 513  # a sliding layer's keys, the query's own among them
    index_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    layer_types: tuple[str, ...] = _pattern(46)
    first_dense: int = 1  # first_k_dense_replace
    ffn_dim: int = 13824  # the dense layers' SwiGLU
    expert_ffn_dim: int = 1536
    n_experts: int = 256  # the router's width
    experts_per_token: int = 8
    # global ids of the experts this chip holds, in the order of its
    # weights' leading axis; None holds all
    experts_held: Optional[tuple[int, ...]] = None
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    max_seq_len: int = 524288
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # what the engine asks of every config and this family has none of
    # (its window is `sliding_window_size`: served past it, not refused beyond it)
    attn_logit_softcap: float = 0.0
    post_norms: bool = False
    sliding_window: int = 0

    def _latent(self, H, nope, rope, v, q_rank, kv_rank, theta) -> SimpleNamespace:
        width = kv_rank + rope
        return SimpleNamespace(
            n_heads=H, nope=nope, rope=rope, v=v, q_rank=q_rank, kv_rank=kv_rank, theta=theta, qk_head_dim=nope + rope,
            row_width=width, row_stored=-(-width // 128) * 128,
            a_q=(self.dim / q_rank) ** 0.5, a_kv=(self.dim / kv_rank) ** 0.5)

    @property
    def full(self) -> SimpleNamespace:
        """A full layer's sizes; ``row_width`` the values a token keeps,
        ``row_stored`` that on whole 128-lane tiles, ``a_q`` / ``a_kv`` the
        latents' rescale."""
        return self._latent(self.n_heads, self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
                            self.q_lora_rank, self.kv_lora_rank, self.rope_theta)

    @property
    def swa(self) -> SimpleNamespace:
        return self._latent(self.swa_n_heads, self.swa_qk_nope_head_dim, self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                            self.swa_q_lora_rank, self.swa_kv_lora_rank, self.swa_rope_theta)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_full(self) -> int:
        return sum(t == FULL for t in self.layer_types)

    @property
    def n_sliding(self) -> int:
        return self.n_layers - self.n_full

    @property
    def window(self) -> int:
        """The rows a slot's ring is sized for (``ops.paged.ring_size``, the
        engine's ``stats()``): the window on whole pages of 16 rows, which
        pages of 4, 8 and 16 rows divide."""
        return -(-self.sliding_window_size // 16) * 16

    # the page list's cache as the engine asks after it: one "head" of the full layers' row
    @property
    def n_kv_heads(self) -> int:
        return 1

    @property
    def head_dim(self) -> int:
        return self.full.row_stored

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.expert_ffn_dim

    @property
    def held(self) -> tuple[int, ...]:
        return tuple(range(self.n_experts)) if self.experts_held is None else self.experts_held


PRESETS: dict[str, DotsConfig] = {
    # dots-studio/dots3-note-prev's language model whole: 559 GB of bfloat16, no single chip
    "dots3-note-prev": DotsConfig(),
    # CPU tests: a dense full layer, an expert full layer and three sliding
    # layers; contexts to 64 choose 8 rows and see 9
    "dots-tiny": DotsConfig(
        vocab_size=256, dim=64, n_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=32,
        kv_lora_rank=24, rope_theta=10000.0, swa_n_heads=2, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
        swa_v_head_dim=16, swa_q_lora_rank=32, swa_kv_lora_rank=40, swa_rope_theta=1000.0, sliding_window_size=9,
        index_heads=4, index_head_dim=16, index_topk=8, layer_types=_pattern(5), first_dense=1, ffn_dim=128,
        expert_ffn_dim=32, n_experts=16, experts_per_token=2, max_seq_len=512, dtype=jnp.float32,
    ),
}


def layer_kinds(c: DotsConfig) -> tuple[str, ...]:
    """Every layer's kind for the layer loops (``dense_full``, ``full``,
    ``sliding``), after checking that the list is one this family is written
    for: the dense layers lead and are full layers."""
    types = tuple(c.layer_types)
    bad = set(types) - {FULL, SLIDING}
    if bad:
        raise ValueError(f"unknown layer types {sorted(bad)} ({SLIDING}|{FULL})")
    if any(t != FULL for t in types[:c.first_dense]):
        raise ValueError("the dots family's leading dense layers are full_attention layers (the published list's are)")
    return tuple("dense_full" if i < c.first_dense else ("full" if t == FULL else "sliding") for i, t in enumerate(types))


def init_params(config: DotsConfig, key: jax.Array) -> dict:
    """Random init in the served layout, every leaf stacked over the layers
    of its kind in order: ``dense`` (the leading dense layers: a full layer's
    attention and a SwiGLU), ``full`` and ``swa`` (the expert layers'
    attention by kind), ``ff`` (norm, router, bias, held experts, shared
    expert over all expert layers)."""
    c = config
    kinds = layer_kinds(c)
    d, f, eh, sw = c.dim, c.expert_ffn_dim, len(c.held), c.shared_width
    count = [0]

    def w(shape, scale):
        count[0] += 1
        return (jax.random.normal(jax.random.fold_in(key, count[0]), shape) * scale).astype(c.dtype)

    def attn(g, n, indexer: bool):
        H, qr, r = g.n_heads, g.q_rank, g.kv_rank
        out = {"ln1": jnp.ones((n, d), c.dtype),
               "wq_a": w((n, d, qr), d ** -0.5), "q_norm": jnp.ones((n, qr), c.dtype),
               "wq_nope": w((n, H * g.nope, qr), qr ** -0.5), "wq_pe": w((n, H * g.rope, qr), qr ** -0.5),
               "wkv_c": w((n, d, r), d ** -0.5), "wk_pe": w((n, d, g.rope), d ** -0.5),
               "kv_norm": jnp.ones((n, r), c.dtype),
               "wuk": w((n, H, g.nope, r), r ** -0.5), "wuv": w((n, H, r, g.v), r ** -0.5),
               "wg": w((n, d, H), d ** -0.5), "wo": w((n, H * g.v, d), (H * g.v) ** -0.5)}
        if indexer:
            Hi, ci = c.index_heads, c.index_head_dim
            out.update({"iq": w((n, qr, Hi * ci), qr ** -0.5), "ik": w((n, d, ci), d ** -0.5),
                        "iw": w((n, d, Hi), d ** -0.5), "ik_norm": jnp.ones((n, ci), c.dtype),
                        "ik_bias": jnp.zeros((n, ci), c.dtype)})
        return out

    nd, n = c.first_dense, c.n_layers - c.first_dense
    return {
        "embed": w((c.vocab_size, d), d ** -0.5),
        "norm": jnp.ones((d,), c.dtype),
        "lm_head": w((d, c.vocab_size), d ** -0.5),
        "dense": {**attn(c.full, nd, True), "ln2": jnp.ones((nd, d), c.dtype), "w1": w((nd, d, c.ffn_dim), d ** -0.5),
                  "w3": w((nd, d, c.ffn_dim), d ** -0.5), "w2": w((nd, c.ffn_dim, d), c.ffn_dim ** -0.5)},
        "full": attn(c.full, kinds.count("full"), True),
        "swa": attn(c.swa, kinds.count("sliding"), False),
        "ff": {"ln2": jnp.ones((n, d), c.dtype), "router": w((n, d, c.n_experts), d ** -0.5),
               "router_bias": jnp.zeros((n, c.n_experts), jnp.float32),
               "w1": w((n, eh, d, f), d ** -0.5), "w3": w((n, eh, d, f), d ** -0.5), "w2": w((n, eh, f, d), f ** -0.5),
               "sw1": w((n, d, sw), d ** -0.5), "sw3": w((n, d, sw), d ** -0.5), "sw2": w((n, sw, d), sw ** -0.5)},
    }


def _expand(rows, wuk, wuv, g):
    """Latent rows [B, T, row_stored] to per-head K [B, T, H, nope + rope]
    (``k_nope_h`` from ``W_UK``, the shared roped key beside it) and V [B, T,
    H, v], for the ``H`` heads ``wuk`` [H, nope, kv_rank] and ``wuv`` hold."""
    with jax.named_scope("mla_expand"):
        B, T, _ = rows.shape
        lat, k_pe = rows[..., :g.kv_rank], rows[..., g.kv_rank:g.row_width]
        k_nope = jnp.einsum("btc,hnc->bthn", lat, wuk.astype(lat.dtype))
        v = jnp.einsum("btc,hcv->bthv", lat, wuv.astype(lat.dtype))
        k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (B, T, wuk.shape[0], g.rope))
        return jnp.concatenate([k_nope, k_pe], axis=-1), v


def _rope_first(x, positions, theta, n):
    """``x`` [B, T, H, d] with its first ``n`` values turned, the rest as they are."""
    return jnp.concatenate([apply_rope(x[..., :n], positions, theta), x[..., n:]], axis=-1)


def _attention_op(h, w, c: DotsConfig, g, positions, attend):
    """-> (Op output [B, T, D], the layer's new rows for the pool ``{"kv":
    [B, T, row_stored]}`` and, of a full layer, ``"ik"`` [B, T,
    index_head_dim], whatever ``attend`` hands on, the queries whose choice
    the tie rule decided as it counts them). ``attend(q_nope [B, T, H,
    nope], q_pe [B, T, H, rope] roped, row [B, T, row_stored], w, index) ->
    ([B, T, H, v], extra, tied)`` is the path: expanded or absorbed; ``index`` is a
    full layer's ``(qi [B, T, Hi, c], wi [B, T, Hi] float32, ik [B, T, c])``,
    None of a sliding layer."""
    B, T, _ = h.shape
    H = g.n_heads
    with jax.named_scope("attn_qkv"):
        cq = rms_norm(mm(h, w["wq_a"]), w["q_norm"], c.norm_eps) * jnp.asarray(g.a_q, h.dtype)
        # outputs first, as the source stores a projection: the layout the decode step's compiler asks for (kanana)
        q_nope = jnp.einsum("btr,nr->btn", cq, w["wq_nope"].astype(h.dtype)).reshape(B, T, H, g.nope)
        q_pe = jnp.einsum("btr,nr->btn", cq, w["wq_pe"].astype(h.dtype)).reshape(B, T, H, g.rope)
        q_pe = apply_rope(q_pe, positions, g.theta)
        lat = rms_norm(mm(h, w["wkv_c"]), w["kv_norm"], c.norm_eps) * jnp.asarray(g.a_kv, h.dtype)
        k_pe = apply_rope(mm(h, w["wk_pe"])[..., None, :], positions, g.theta)[..., 0, :]  # one key for all heads
        pad = jnp.zeros((B, T, g.row_stored - g.row_width), h.dtype)
        row = jnp.concatenate([lat.astype(h.dtype), k_pe.astype(h.dtype), pad], axis=-1)
    index, new = None, {"kv": row}
    if "iq" in w:
        with jax.named_scope("index_proj"):
            qi = _rope_first(mm(cq, w["iq"]).reshape(B, T, c.index_heads, c.index_head_dim), positions, g.theta, g.rope)
            ki = _layer_norm(mm(h, w["ik"]), w["ik_norm"], w["ik_bias"], c.norm_eps)
            ik = _rope_first(ki[:, :, None, :], positions, g.theta, g.rope)[:, :, 0, :].astype(h.dtype)
            wi = jnp.matmul(h, w["iw"].astype(h.dtype), preferred_element_type=jnp.float32)  # the accumulator, unrounded
        index, new = (qi, wi, ik), {"kv": row, "ik": ik}
    out, extra, tied = attend(q_nope, q_pe, row, w, index)
    with jax.named_scope("attn_gate"):
        out = out * jax.nn.sigmoid(mm(h, w["wg"]).astype(jnp.float32)).astype(out.dtype)[..., None]
    with jax.named_scope("attn_out"):
        return mm(out.reshape(B, T, H * g.v), w["wo"]), new, extra, tied


def _run_layers(params, c: DotsConfig, x, positions, valid, paths, route=None, select=None, keep=lambda t: t,
                tell=False):
    """The whole stack. ``paths(full: bool, i, given)`` gives the attention
    path of full-type layer ``i`` (a traced index among the full-type layers,
    dense ones first, which is its layer of ``kv`` / ``ik``) or of sliding
    layer ``i`` (its layer of ``wkv``); ``given`` is the layer's row of
    ``select`` [full-type layers, ...] (rows chosen by the caller, in the
    path's own form) or None. ``route`` [expert layers, B, T, k] int32, where
    given, is every expert layer's choice of experts, taken as it is (an
    output check's; serving gives neither). ``keep`` is applied to a sliding
    layer's fresh rows before the loop stacks them (a prefill keeps a ring's
    worth: ``ring_newest``). -> (x, the full-type layers' new rows ``{"kv",
    "ik"}`` each [layers, B, T, width], the sliding layers' ``wkv`` [layers,
    B, kept rows, width], expert counters and after them the queries whose
    choice the tie rule decided (a decode step's lanes, over the full layers:
    ``lanes_tied``), and with ``tell`` what the layers chose: ``(rows [full-type layers, ...], experts [expert layers, B, T,
    k])``, else None)."""
    kinds = layer_kinds(c)
    nd = c.first_dense
    dt = x.dtype
    norm = lambda x, w: rms_norm(x, w, c.norm_eps)  # noqa: E731
    ff = params["ff"]
    stacks = tuple(ff[name].reshape((-1,) + ff[name].shape[2:]) for name in ("w1", "w3", "w2"))
    small = {name: ff[name] for name in ("ln2", "router", "router_bias", "sw1", "sw3", "sw2")}
    stack_of = {"dense_full": "dense", "full": "full", "sliding": "swa"}

    def layer(kind, carry, index, row):
        x, counts = carry
        full = kind != "sliding"
        weights = layer_row(params[stack_of[kind]], row)
        at = row + (nd if kind == "full" else 0)  # the layer of its leaves
        given = select[at] if full and select is not None else None
        with scopes.layer("attn"):
            op, new, told, tied = _attention_op(norm(x, weights["ln1"]), weights, c, c.full if full else c.swa, positions,
                                                paths(full, at, given))
            x, counts = x + op, counts.at[-1].add(jnp.asarray(tied, jnp.uint32))
        out = {"rows": {name: (t if full else keep(t)).astype(dt) for name, t in new.items()}}
        with scopes.layer("ffn"):
            h = norm(x, weights["ln2"] if kind == "dense_full" else small["ln2"][index - nd])
            if kind == "dense_full":
                with jax.named_scope("ffn_dense"):
                    x = x + mm(jax.nn.silu(mm(h, weights["w1"])) * mm(h, weights["w3"]), weights["w2"])
            else:
                e = index - nd
                mine = layer_row(small, e)
                chosen = None if route is None else route[e]
                if tell:
                    scores = jax.nn.sigmoid(h.astype(jnp.float32) @ mine["router"].astype(jnp.float32))
                    out["experts"] = (jax.lax.top_k(scores + mine["router_bias"], c.experts_per_token)[1]
                                      if chosen is None else chosen)
                y, m = routed_ff(h, mine, stacks, e, c, valid, chosen, score="sigmoid", bias=True,
                                 scale=c.routed_scaling_factor, chunk=True, shared=True)
                x, counts = x + y, counts.at[:-1].add(m)
        if tell and full:
            out["chose"] = told
        return (x, counts), out

    counts = jnp.zeros((1 + COUNTS_HEAD + len(c.held) + 1,), jnp.uint32)
    (x, counts), outs = scan_layers(kinds, (x, counts), layer)
    full_kinds = [k for k in ("dense_full", "full") if k in outs]
    rows = {name: jnp.concatenate([outs[k]["rows"][name] for k in full_kinds], axis=0) for name in ("kv", "ik")}
    ring = outs["sliding"]["rows"]["kv"] if "sliding" in outs else None
    told = None
    if tell:
        seen = dict.fromkeys(kinds, 0)
        experts = []
        for kind in kinds:  # the expert layers in the list's order, whatever their kind
            if kind != "dense_full":
                experts.append(outs[kind]["experts"][seen[kind]])
            seen[kind] += 1
        told = (jnp.concatenate([outs[k]["chose"] for k in full_kinds], axis=0), jnp.stack(experts))
    return x, rows, ring, counts, told


def _queries(q_nope, q_pe):
    return jnp.concatenate([q_nope, q_pe], axis=-1)


def _head_groups(H: int):
    """(heads a group, a function that puts an array's axis of ``H`` heads as
    groups first, for a ``lax.map`` over them)."""
    G = HEAD_GROUP if H % HEAD_GROUP == 0 else H

    def grouped(t, axis):
        return jnp.moveaxis(t.reshape(t.shape[:axis] + (H // G, G) + t.shape[axis + 1:]), axis, 0)

    return G, grouped


def _attend_under(c: DotsConfig, g, mask, q_nope, q_pe, row, w, interpret: bool):
    """A full layer's rows of tokens under ``mask`` [B, T, T] bool, expanded.
    On a TPU (or ``interpret``: tests) ``HEAD_GROUP`` heads at a time, each
    group's K and V made from the rows and attended by the kernel of
    ``ops/pallas/masked_attention.py``, keys padded with zeros to whole lane
    tiles (the scale the unpadded width's); it refuses a ``T`` it does not
    serve. Elsewhere the plain ``causal_attention(keep=)``, all heads and
    whole scores at once, what the CPU runs at its tiny sizes."""
    B, T, H, _ = q_nope.shape
    if not (interpret or jax.default_backend() == "tpu"):
        k, v = _expand(row, w["wuk"], w["wuv"], g)
        return causal_attention(_queries(q_nope, q_pe), k, v, keep=mask)
    from ..ops.pallas.masked_attention import masked_attention

    _G, grouped = _head_groups(H)
    seen = mask.astype(jnp.int8)
    widen = lambda t: jnp.pad(t, ((0, 0),) * (t.ndim - 1) + ((0, -t.shape[-1] % 128),))  # noqa: E731  to whole lane tiles

    def group(xs):
        qn, qp, wuk, wuv = xs
        k, v = _expand(row, wuk, wuv, g)
        q, k, v = widen(_queries(qn, qp)), widen(k), widen(v)  # the published values are a lane tile wide already
        return jnp.stack([masked_attention(q[b], k[b], v[b], seen[b], scale=g.qk_head_dim ** -0.5, interpret=interpret)
                          for b in range(B)])[..., :g.v]

    out = jax.lax.map(group, (grouped(q_nope, 2), grouped(q_pe, 2), grouped(w["wuk"], 0), grouped(w["wuv"], 0)))
    return jnp.moveaxis(out, 0, 2).reshape(B, T, H, g.v)


def _whole_rows(c: DotsConfig, positions, tell, interpret: bool = False):
    """The paths of rows that attend over themselves alone (a whole prompt,
    ``forward``). A full layer: ``given`` [B, T, ceil(T / 8)] uint8 is a
    choice of rows handed in, packed; with ``tell`` the path hands its own
    on, so packed. A sliding layer: the banded attention over its expanded
    rows (``_banded``)."""

    def paths(full, i, given):
        def attend_full(q_nope, q_pe, row, w, index):
            T = positions.shape[1]
            with jax.named_scope("prefill_attention"):
                with jax.named_scope("sparse_mask"):
                    if given is None:
                        # ONE tier: the masks are 3 of a prefill's 54 ms a 1,000 tokens here, and a tier of its own
                        # is 2 s of this sandbox's compiler a program (4 tiers 19.3 s, one 13.1: PERF.md, PR 61)
                        mask = prompt_mask(c, positions, *index, tier=T)
                    else:
                        mask = causal_ok(positions, positions) & unpacked(given, T)
                out = _attend_under(c, c.full, mask, q_nope, q_pe, row, w, interpret)
            return out, packed(mask) if tell else None, 0

        def attend_sliding(q_nope, q_pe, row, w, index):
            k, v = _expand(row, w["wuk"], w["wuv"], c.swa)
            with jax.named_scope("prefill_attention"):
                return _banded(_queries(q_nope, q_pe), k, v, positions, c.sliding_window_size), None, 0

        return attend_full if full else attend_sliding

    return paths


def forward(params: dict, tokens: jax.Array, config: DotsConfig, select=None, route=None, tell: bool = False,
            interpret: bool = False, rows: jax.Array | None = None):
    """Full-sequence causal forward -> logits [B, T, V] float32 (tests and
    the output check), or with ``rows`` [B, R] those rows' alone [B, R, V].
    With ``tell`` -> (logits, (rows chosen packed [full-type layers, B, T,
    ceil(T / 8)], experts chosen [expert layers, B, T, k])). ``interpret``
    runs the full layers' attention kernel interpreted (tests)."""
    c = config
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x, _rows_, _ring, _counts, told = _run_layers(
        params, c, embed(params, tokens, c), positions, jnp.ones((B, T), bool), _whole_rows(c, positions, tell, interpret),
        route, select, tell=tell)
    if rows is not None:
        x = x[jnp.arange(B)[:, None], rows]
    logits = head_logits(final_norm(x, params, c), params, c)
    return (logits, told) if tell else logits


# ---------------------------------------------------------------------------
# Serving: latent rows and indexer keys on the page list, a ring of latent rows a slot
# ---------------------------------------------------------------------------


def init_paged_cache(config: DotsConfig, num_pages: int, page_size: int, quantize_kv: bool = False,
                     max_slots: int = 1) -> dict:
    c = config
    if quantize_kv:
        raise ValueError("the dots family keeps its latent rows and the indexer's keys in the model's dtype: a latent "
                         "row has no scale twin, and a rounded key of the indexer mis-chooses rows (ROADMAP M5, M10)")
    ring = ring_size(c.window, page_size)
    cache = init_row_pages(c.n_full, num_pages, page_size, kv=(c.full.row_stored, c.dtype), ik=(c.index_head_dim, c.dtype))
    # a ring a slot and one more, where padding lanes (slot `max_slots`) write
    cache.update(init_row_pages(c.n_sliding, (max_slots + 1) * ring, page_size, wkv=(c.swa.row_stored, c.dtype)))
    cache["state"] = {"counts": jnp.zeros((2, 1 + COUNTS_HEAD + len(c.held) + SPARSE_COUNTS + WINDOW_COUNTS), jnp.uint32)}
    return cache


def _pools(cache: dict) -> tuple[dict, dict]:
    """(the page list's pool ``{"kv", "ik"}``, the rings' ``{"wkv"}``)."""
    return {"kv": cache["kv"], "ik": cache["ik"]}, {"wkv": cache["wkv"]}


def _committed(cache, full, win, counts, c: DotsConfig, row, scored, positions, valid):
    """The cache with its leaves replaced and the dispatch counted: the
    expert layers' counters, then ``keye``'s of A full layer (``scored`` the
    ``ik`` rows it read or made to score; a query at position ``p`` could see
    ``p + 1`` rows), then ``mellum``'s of a sliding layer."""
    u32 = lambda v: jnp.asarray(v).astype(jnp.uint32)  # noqa: E731
    live = jnp.where(valid, positions + 1, 0).reshape(-1)
    sparse = jnp.stack([jnp.ones((), jnp.uint32), u32(scored), u32(jnp.sum(jnp.minimum(live, c.index_topk))),
                        u32(jnp.sum(live)), u32(jnp.sum(live > c.index_topk))])
    window = window_counts(c.sliding_window_size, positions, valid)
    added = jnp.concatenate([counts[:-1], sparse, counts[-1:], window])  # `_run_layers` counts the tied lanes after the experts
    return {**full, **win, "state": {"counts": cache["state"]["counts"].at[row].add(added)}}


def _paged(rows: dict) -> dict:
    """New rows ``{leaf: [L, B, T, width]}`` as the commits take them: one "head" of the row's width."""
    return {name: t[..., None, :] for name, t in rows.items()}


def prefill_paged_batch(params, cache, tokens, lengths, page_ids, lanes, config: DotsConfig, route=None, select=None,
                        tell: bool = False, interpret: bool = False):
    """B whole prompts in one dispatch: the full layers' latent rows and
    indexer keys into each row's pages, the sliding layers' newest ``ring``
    pages into its slot's ring. -> (cache, logits [B, V]), and with ``tell``
    what every layer chose (``forward``'s)."""
    c = config
    slots, _snap_at = lanes
    B, T = tokens.shape
    zero = jnp.zeros((B,), jnp.int32)
    positions, valid = row_positions(lengths, zero, T)
    full, win = _pools(cache)
    ring, pad = slot_ring(cache["wkv"], c.window)
    keep, ring_ids = ring_newest(slots, zero, lengths, T, win["wkv"].shape[2], ring, pad)
    x, rows, fresh, counts, told = _run_layers(
        params, c, embed(params, tokens, c), positions, valid, _whole_rows(c, positions, tell, interpret), route, select,
        keep, tell)
    with scopes.layer("commit"):
        full = commit_whole_pages(full, _paged(rows), page_ids)
        with jax.named_scope("window_commit"):
            win = commit_whole_pages(win, {"wkv": fresh[..., None, :]}, ring_ids)
        # a whole prompt's block of queries scores its causal keys' block columns: counted as the pairs it needs
        cache = _committed(cache, full, win, counts, c, 1, jnp.sum(lengths * (lengths + 1) // 2), positions, valid)
    logits = head_logits(final_norm(x, params, c), params, c, last=lengths)
    return (cache, logits, told) if tell else (cache, logits)


def _banded(q, k, v, positions, window: int, ring=None):
    """A sliding layer's rows of tokens: queries [B, T, H, d] over the rows'
    own K and V inside the window and, of a continuation, over its slot's
    ring as it stands (``ring`` = K and V expanded and the position a row
    holds, -1 none). Dense where the rows are few; else ``CONTINUE_BLOCK``
    queries at a time, one ``lax.map`` whose body is traced once, over the
    ring and the slice of the rows' own keys the window can reach (the scores
    of 16,384 rows against themselves are 69 GB at once, and XLA's blocked
    attention written out a block of queries at a time was half of a
    16,384-row prefill's compile: PERF.md, PR 61)."""
    B, T = positions.shape
    R = CONTINUE_BLOCK
    beside = lambda t, part: t if ring is None else jnp.concatenate([part, t], axis=1)  # noqa: E731
    ring_k, ring_v, ring_pos = ring if ring is not None else (None, None, None)
    if T <= R or T % R:
        return continue_attention(q, beside(k, ring_k), beside(v, ring_v), positions, beside(positions, ring_pos),
                                  window=window)
    span = min(T, -(-(window - 1) // R) * R + R)  # the keys a block of queries can see, the block's own among them

    def block(i):
        cut = lambda t, lo, n: jax.lax.dynamic_slice_in_dim(t, lo, n, axis=1)  # noqa: E731
        lo = jnp.clip((i + 1) * R - span, 0, T - span)
        return continue_attention(cut(q, i * R, R), beside(cut(k, lo, span), ring_k), beside(cut(v, lo, span), ring_v),
                                  cut(positions, i * R, R), beside(cut(positions, lo, span), ring_pos), window=window)

    out = jax.lax.map(block, jnp.arange(T // R, dtype=jnp.int32))
    return jnp.moveaxis(out, 0, 1).reshape((B, T) + out.shape[3:])


def _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, lanes, c: DotsConfig):
    """Rows that start at ``starts`` (page-aligned). A full layer attends
    over the rows chosen among its gathered prefix pages plus the rows
    themselves: the latent rows and the indexer's keys gathered (the whole
    table's, whatever the start), every query's mask made a block at a time,
    then ``HEAD_GROUP`` heads at a time expanded and attended a block of
    queries at a time. A sliding layer attends over its slot's ring as it
    stands (the rows before ``starts`` are in it) plus the rows themselves.
    Nothing is written here. -> (x normed, the full layers' new rows, the
    sliding layers' newest rows and the ring pages they go to, counts, ik
    rows scored, positions, valid)."""
    slots, _snap_at = lanes
    B, T = tokens.shape
    positions, valid = row_positions(lengths, starts, T)
    full, win = _pools(cache)
    NP, P = full["kv"].shape[1:3]
    NW = win["wkv"].shape[1]
    ring, pad = slot_ring(cache["wkv"], c.window)
    M = block_tables.shape[1]
    key_pos = key_positions(starts, positions, M * P)
    ring_pos = ring_positions(starts, ring, P)
    ring_pos = jnp.where(ring_pos < starts[:, None], ring_pos, -1)
    rings = ring_tables(jnp.minimum(slots, pad), ring)
    blocked = T > CONTINUE_BLOCK and T % CONTINUE_BLOCK == 0

    def paths(is_full, i, given):
        def attend_full(q_nope, q_pe, row, w, index):
            g = c.full
            qi, wi, ik = index
            with jax.named_scope("full_gather"):
                ids = layer_tables(block_tables, i, NP)
                ctx = jnp.concatenate([gather_pages(full, "kv", ids, row.dtype, 1).reshape(B, M * P, -1), row], axis=1)
                keys = jnp.concatenate([gather_pages(full, "ik", ids, row.dtype, 1).reshape(B, M * P, -1), ik], axis=1)
            with jax.named_scope("sparse_mask"):
                if blocked:
                    seen = jax.lax.map(lambda blk: chosen_mask(c, blk[0], blk[1], keys, blk[2], key_pos),
                                       tuple(row_blocks(t, CONTINUE_BLOCK) for t in (qi, wi, positions)))
                    mask = jnp.moveaxis(seen, 0, 1).reshape(B, T, -1)
                else:
                    mask = chosen_mask(c, qi, wi, keys, positions, key_pos)
            H = g.n_heads
            G, grouped = _head_groups(H)

            def group(xs):
                q, wuk, wuv = xs
                k, v = _expand(ctx, wuk, wuv, g)
                with jax.named_scope("prefill_attention"):
                    if not blocked:
                        return blocked_masked_attention(q, k, v, mask)
                    out = jax.lax.map(lambda blk: blocked_masked_attention(blk[0], k, v, blk[1]),
                                      tuple(row_blocks(t, CONTINUE_BLOCK) for t in (q, mask)))
                    return jnp.moveaxis(out, 0, 1).reshape(B, T, G, g.v)

            out = jax.lax.map(group, (grouped(_queries(q_nope, q_pe), 2), grouped(w["wuk"], 0), grouped(w["wuv"], 0)))
            return jnp.moveaxis(out, 0, 2).reshape(B, T, H, g.v), None, 0

        def attend_sliding(q_nope, q_pe, row, w, index):
            g = c.swa
            with jax.named_scope("ring_latent"):
                got = gather_pages(win, "wkv", layer_tables(rings, i, NW), row.dtype, 1).reshape(B, ring * P, -1)
            ring_k, ring_v = _expand(got, w["wuk"], w["wuv"], g)
            k, v = _expand(row, w["wuk"], w["wuv"], g)
            with jax.named_scope("prefill_attention"):
                return _banded(_queries(q_nope, q_pe), k, v, positions, c.sliding_window_size, (ring_k, ring_v, ring_pos)), None, 0

        return attend_full if is_full else attend_sliding

    keep, ring_ids = ring_newest(jnp.minimum(slots, pad), starts, lengths, T, P, ring, pad)
    x, rows, fresh, counts, _ = _run_layers(params, c, embed(params, tokens, c), positions, valid, paths, keep=keep)
    return final_norm(x, params, c), rows, fresh, ring_ids, counts, jnp.sum(lengths) * (M * P + T), positions, valid


def _continue_commit(cache, new, page_ids, c: DotsConfig):
    rows, fresh, ring_ids, counts, scored, positions, valid = new
    full, win = _pools(cache)
    with scopes.layer("commit"):
        full = commit_whole_pages(full, _paged(rows), page_ids)
        with jax.named_scope("window_commit"):
            win = commit_whole_pages(win, {"wkv": fresh[..., None, :]}, ring_ids)
        return _committed(cache, full, win, counts, c, 1, scored, positions, valid)


def prefill_paged_continue(params, cache, tokens, lengths, starts, page_ids, block_tables, lanes, config: DotsConfig):
    """Continuation (a later chunk of a long prompt, a resumed request's
    tail): -> (cache, last-token logits [B, V])."""
    x, *new = _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, lanes, config)
    return _continue_commit(cache, new, page_ids, config), head_logits(x, params, config, last=lengths)


def prefill_paged_continue_kv(params, cache, tokens, lengths, starts, page_ids, block_tables, lanes,
                              config: DotsConfig):
    """The continuation's writes without the head (a mid chunk)."""
    _x, *new = _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, lanes, config)
    return _continue_commit(cache, new, page_ids, config)


def decode_step_paged(params, cache, tokens, seq_lens, block_tables, active, config: DotsConfig,
                      use_pallas: bool = False, mesh=None, route=None, select=None, tell: bool = False,
                      window_rows: Optional[int] = None, interpret: bool = False):
    """One token for lanes 0..S-1 (lane b is slot b), absorbed: a full layer
    scores the lane's cached rows through ``ik``, chooses, and attends over
    the chosen latent rows fetched by row; a sliding layer over its ring from
    the window's edge on; an inactive lane's pages and ring are left as they
    were. ``use_pallas`` and ``mesh`` are what the engine hands every
    family's step; neither changes anything here (module text): the choice's
    kernel (``ops/pallas/index_select.py``) runs wherever the backend is a
    TPU, and ``interpret`` runs it interpreted (tests). ``select``
    [full-type layers, S, index_topk] int32 positions (-1 none) is a choice
    handed in; with ``tell`` -> (cache, logits, (positions chosen [full-type
    layers, S, index_topk] in no order a caller may count on, experts chosen
    [expert layers, S, 1, k])).
    ``window_rows`` (an output check's control) sees another window than the
    model's."""
    c = config
    S = tokens.shape[0]
    full, win = _pools(cache)
    NP, P = full["kv"].shape[1:3]
    NW = win["wkv"].shape[1]
    ring, pad = slot_ring(cache["wkv"], c.window)
    flat = {name: flat_pages(a) for name, a in full.items()}
    wflat = flat_pages(win["wkv"])
    rings = ring_tables(jnp.arange(S, dtype=jnp.int32), ring)
    ring_pos = ring_positions(seq_lens, ring, P)
    # the query at position n sees n + 1 - window .. n: from the ring the rows from `first` on, its own as the self term
    first = jnp.maximum(seq_lens + 1 - (c.sliding_window_size if window_rows is None else window_rows), 0)

    def absorbed(q_nope, q_pe, w, g):
        with jax.named_scope("mla_absorb"):
            dt = q_pe.dtype
            # q~_h = W_UK_h^T q_nope_h: the key's expansion folded into the query
            q_lat = jnp.einsum("shn,hnc->shc", q_nope[:, 0], w["wuk"].astype(dt))
            zeros = jnp.zeros((S, g.n_heads, g.row_stored - g.row_width), dt)
            return jnp.concatenate([q_lat.astype(dt), q_pe[:, 0], zeros], axis=-1)  # [S, H, row_stored]

    def values(o_lat, w):
        with jax.named_scope("mla_absorb"):
            # o_h = W_UV_h o~_h: the value's expansion applied after the softmax
            return jnp.einsum("shc,hcv->shv", o_lat, w["wuv"].astype(o_lat.dtype)).astype(o_lat.dtype)[:, None]

    def paths(is_full, i, given):
        def attend_full(q_nope, q_pe, row, w, index):
            g = c.full
            qi, wi, ik = index
            o_lat, chosen, tied = sparse_latent_decode_attention_cache_plus_new(
                absorbed(q_nope, q_pe, w, g), flat, layer_tables(block_tables, i, NP), seq_lens,
                {"kv": row[:, 0], "ik": ik[:, 0]}, qi[:, 0], wi[:, 0], c.index_topk, g.kv_rank, g.qk_head_dim, given,
                interpret)
            return values(o_lat, w), chosen, jnp.sum(tied & active)

        def attend_sliding(q_nope, q_pe, row, w, index):
            g = c.swa
            q_row = absorbed(q_nope, q_pe, w, g)
            with jax.named_scope("ring_latent"):
                o_lat = ring_latent_decode_attention_cache_plus_new(
                    q_row, wflat, layer_tables(rings, i, NW), seq_lens, row[:, 0], g.kv_rank, g.qk_head_dim, ring_pos, first)
            return values(o_lat, w), None, 0

        return attend_full if is_full else attend_sliding

    positions = seq_lens[:, None]
    x, rows, fresh, counts, told = _run_layers(params, c, embed(params, tokens[:, None], c), positions, active[:, None],
                                               paths, route, select, tell=tell)
    with scopes.layer("commit"):
        target = jnp.where(active, block_tables[jnp.arange(S), seq_lens // P], TRASH_PAGE)
        full = commit_tokens(full, {name: t[:, :, 0, None, :] for name, t in rows.items()}, target, seq_lens % P)
        with jax.named_scope("window_commit"):
            at = jnp.where(active, jnp.arange(S), pad) * ring + jnp.mod(seq_lens // P, ring)
            win = commit_tokens(win, {"wkv": fresh[:, :, 0, None, :]}, at, seq_lens % P)
        cache = _committed(cache, full, win, counts, c, 0, jnp.sum(active) * block_tables.shape[1] * P, positions,
                           active[:, None])
    logits = head_logits(final_norm(x[:, 0], params, c), params, c)
    return (cache, logits, told) if tell else (cache, logits)


def install_state(cache: dict, slot, state) -> dict:
    raise NotImplementedError(
        "the dots family keeps no state a slot that can be copied in: the sliding layers' ring is rebuilt by a "
        "prefill (the engine refuses prefix entries, parks and host swaps for it)")


def saved_state(cache: dict, slot):
    raise NotImplementedError(
        "the dots family saves no state a slot: a copy of the sliding layers' ring is 41 MB at the published widths "
        "(the engine refuses prefix entries, parks and host swaps for it)")


def counters(cache: dict) -> jax.Array:
    """The expert layers', the indexer's and the sliding layers' counters as the programs keep them."""
    return cache["state"]["counts"]


def describe_counters(config: DotsConfig, total) -> dict:
    """``Engine.stats()``'s ``"moe"`` (the keys ``lfm2`` gives, and
    ``shared_width``), ``"sparse"`` (``models/keye.py``'s keys, each over the
    FULL layers: the device counts a layer and every full layer sees the same
    rows) and ``"window"`` (``models/mellum.py``'s keys, a sliding layer's)
    from the counters summed by the engine (``total`` [2, 1 + COUNTS_HEAD +
    held + SPARSE_COUNTS + WINDOW_COUNTS], None before the first dispatch),
    decode steps and prefills apart."""
    c = config
    cut = 1 + COUNTS_HEAD + len(c.held)
    if total is None:
        total = [[0] * (cut + SPARSE_COUNTS + WINDOW_COUNTS)] * 2
    itemsize = jnp.dtype(c.dtype).itemsize

    def sparse(r):
        n = c.n_full
        return {"steps": int(r[cut]), "rows_scored": int(r[cut + 1]) * n, "rows_chosen": int(r[cut + 2]) * n,
                "rows_dense": int(r[cut + 3]) * n, "lanes_past_topk": int(r[cut + 4]), "lanes_tied": int(r[cut + 5])}

    moe = describe_moe(c, [r[:cut] for r in total])["moe"]
    return {
        "moe": {**moe, "shared_width": c.shared_width},
        "sparse": {"topk": c.index_topk, "index_heads": c.index_heads, "index_values": c.index_head_dim,
                   "ik_row_bytes_stored": c.index_head_dim * itemsize, "row_values": c.full.row_width,
                   "row_bytes_stored": c.full.row_stored * itemsize, "layers": c.n_full,
                   "decode": sparse(total[0]), "prefill": sparse(total[1])},
        **describe_window(total, cut + SPARSE_COUNTS, c.sliding_window_size, c.n_sliding, c.n_full,
                          row_values=c.swa.row_width, row_bytes_stored=c.swa.row_stored * itemsize),
    }
