"""Keye-VL-2.0 model family, the language model of
``Kwai-Keye/Keye-VL-2.0-30B-A3B`` (``model_type: KeyeVL2``): grouped-query
attention over rows CHOSEN by a learned indexer (the published DeepSeek
sparse attention, whose five sizes ``sa_config`` gives), three-axis rotary
positions, and routed experts with no shared one in every layer.

Every layer is ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``.
With ``u = RMSNorm(x_t)`` for the token at position ``t``:

- ``q = W_q u`` (``n_heads`` of ``head_dim``), ``k = W_k u``, ``v = W_v u``
  (``n_kv_heads``), no bias; an RMSNorm with a weight over each head of ``q``
  and of ``k``; rotary over three axes: a token carries ``(p_t, p_h, p_w)``
  and frequency ``i`` of the ``head_dim / 2`` turns by the position of the
  axis whose section of ``mrope_section`` holds ``i`` (``ops.rope.apply_rope``
  ``sections=``), halves rotated. A text token's three are equal, which is
  the plain rope bit for bit; the serving programs carry one position a
  token (the engine serves token ids; the vision tower is not part of this
  family) and ``forward`` takes all three.
- **the indexer**: ``q^I = W^I_q u`` (``index_heads`` of ``index_head_dim``),
  ``k^I = LayerNorm(W^I_k u)`` (ONE head, shared by all, weight and bias),
  ``w = W^I_w u`` (a value a head); ``q^I`` and ``k^I`` turn by the plain
  rope at the temporal position over their ``index_head_dim`` values. The
  index score of an earlier row ``s <= t`` is ``I(t, s) = sum_j w_j ReLU(q^I_j
  . k^I_s)`` in float32 (``ops.attention.index_scores``) and ``S_t`` is the
  ``min(t + 1, index_topk)`` rows of largest score, ties to the earlier row.
  A positive factor on ``I(t, .)`` changes no choice, so the source's scaling
  of ``w`` (by ``index_heads ** -0.5`` and the softmax scale) is left out. Its
  Hadamard rotation of ``q^I`` and ``k^I`` is left out too (one orthogonal
  map of both changes no product), and ``k^I`` is kept in the model's dtype,
  not the source's fp8.
- attention is the softmax of ``q . k_s / sqrt(head_dim)`` over ``s in S_t``
  ALONE, ``n_heads / n_kv_heads`` query heads to a KV head, then ``W_o``.
- ``MoE``: ``ops.moe.routed_experts`` (float32 router over ``n_experts``,
  softmax, the ``experts_per_token`` largest renormalised), of which this chip
  holds ``experts_held``; nothing stands in for the others.

**What is cached**, a pool of the family's own (``ops.paged.init_row_pages``):
``kv`` ``[n_layers, pages, P, n_kv_heads * head_dim]`` uint32 in bfloat16
(twice as wide in float32), a token's K row (its heads side by side) and its
V row as ONE row of 32-bit words, K's 16 bits low and V's high
(``ops.paged.pack_kv_rows``): 2 KiB at the published sizes, the bfloat16
values bit for bit. A chosen position's K and V are always read together,
and XLA's gather of rows costs by the slice and the lane tiles it touches,
not by its bytes: one gather of four tiles of whole words where two leaves
took two gathers of four tiles of packed bfloat16 rows, and the halves come
apart where the gathered rows are regrouped by head, a pass the two-leaf
walk made too (side by side as ONE bfloat16 row of eight tiles the gather
cost 1.43 of a 1 KiB row's and the split a pass of its own: PERF.md, PR 60).
And ``ik`` ``[n_layers, pages, P, ik_stored]``: the
indexer's key a token and layer after its norm and rope, ``index_head_dim``
(64) values stored on a whole 128-lane tile (``ik_stored`` = 128, the rest
zeros): 256 B a row beside K and V's 2,048. Stored 64 wide the chip pads the
row to its tile anyway and the gather that scores a lane's rows runs 5.5
times slower (2.65 ms against 0.48 a layer at 16 lanes of 26,624 rows: PR
58's builder's chip run). Both leaves ride one list of page ids:
``ops.paged.kv_commit`` writes them, and the engine's page helpers move
them, with no line of their own.

Two attention paths, equal in exact arithmetic
(``tests/engine/test_keye.py``):

- rows of tokens (prefill, continuation, ``forward``): dense operations,
  the sparse result. A block of ``MASK_BLOCK`` query rows at a time, its
  index scores against the keys of its tier of ``MASK_TIER`` rows (one
  ``lax.map`` a tier: ``prompt_mask``), each query's ``index_topk``-th
  largest (``topk_rows_mask``: a threshold found by compare-and-count, no
  sort, as the decode step's 16 rows find theirs inside one kernel,
  ``ops/pallas/index_select.py``: the two give one choice, ties and all),
  and that mask under the causal rule. The attention under the whole ``[T, T]`` mask
  is, on a TPU, the kernel of ``ops/pallas/masked_attention.py``, which
  keeps a block's scores in VMEM and reads the mask a tile at a time (XLA's
  blocked attention writes them to HBM three times: 190.6 ms a layer at
  24,576 rows against 64.6, PR 58's builder's chip runs); it skips no key
  the mask hides (ROADMAP M10 (b)) and refuses a ``T`` that is not whole
  blocks. Off the TPU it is the plain ``causal_attention(keep=)``, at the
  tiny sizes a CPU runs. A continuation gathers a slot's ``ik`` pages as it
  gathers its ``kv`` pages, once, and splits them.
- the decode step: ``ops.paged.sparse_decode_attention_reference_cache_plus_new``:
  every cached row scored through ``ik``, the ``index_topk`` of largest
  score found by a threshold held on the chip (``ops.paged.chosen_rows``: on
  a TPU the kernel ``ops/pallas/index_select.py``, 32 compare-and-count
  passes over a lane's scores in VMEM, the tie rule and the compaction, no
  sort; off it ``jax.lax.top_k``, the same set), the chosen ``kv`` rows
  fetched by row, once. ONE program for lanes under and over
  ``index_topk`` rows: a lane under it chooses all its rows and its list is
  padded and masked.

Layout for XLA: ONE scan over the layers, all of one kind; the pool never
passes through a conditional (PERF.md, PR 37); every program commits all
layers' new rows by one scatter after the scan.

The family keeps no state a slot and counts on the device:
``cache["state"]["counts"]`` ``[2, 1 + COUNTS_HEAD + held + SPARSE_COUNTS]``
uint32, row 0 decode steps and row 1 prefills: the expert layers' counters
as ``lfm2`` keeps them, then dispatches, and A LAYER's rows scored, rows
chosen, rows a dense walk would read, lanes past ``index_topk``, and (over
all layers) the decode lanes whose choice the tie rule decided.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..observability import scopes
from ..ops.attention import CONTINUE_BLOCK, blocked_masked_attention, causal_attention, index_scores, topk_rows_mask
from ..ops.moe import COUNTS_HEAD
from ..ops.norms import rms_norm
from ..ops.paged import (
    TRASH_PAGE, commit_tokens, commit_whole_pages, flat_pages, gather_pages, init_row_pages, layer_tables,
    pack_kv_rows, pool_leaves, sparse_decode_attention_reference_cache_plus_new, unpack_kv_rows,
)
from ..ops.rope import apply_rope
from .experts import describe_moe, routed_ff
from .stack import embed, final_norm, head_logits, key_positions, mm, row_positions

SPARSE_COUNTS = 6  # dispatches, rows scored, rows chosen, rows a dense walk would read, lanes past topk, lanes tied
# query rows of a whole prompt whose index scores and threshold are made at once: 50 MB of float32 scores against
# 24,576 keys, which the ~32 passes of the threshold find in the chip's near memory (at 2,048 rows, 200 MB, they took
# 10.8 ms where two blocks of 1,024 take 3.5: PR 58's builder's chip runs; at 512 a 24,576-row prefill of 8 layers
# is 974 ms where blocks of 1,024 are 1,024 ms: my chip run, PR 59)
MASK_BLOCK = 512
# rows of a tier, whose blocks are one `lax.map` over the tier's keys (`prompt_mask`): every bucket is whole tiers.
# The whole prefill at 24,576 rows, compile and run (my chip run, PR 59): blocks written out against their own keys
# 30.9 s and 992 ms, tiers of 4,096 11.3 s and 974 ms, of 8,192 9.2 s and 1,060 ms, one map 5.8 s and 1,132 ms
MASK_TIER = 4096


@dataclass(frozen=True)
class KeyeConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    n_layers: int = 48
    expert_ffn_dim: int = 768
    n_experts: int = 128  # the router's width
    experts_per_token: int = 8
    # global ids of the experts this chip holds, in the order of its
    # weights' leading axis; None holds all
    experts_held: Optional[tuple[int, ...]] = None
    norm_topk_prob: bool = True
    norm_eps: float = 1e-6
    rope_theta: float = 1e7
    mrope_section: tuple[int, ...] = (16, 24, 24)  # frequencies turned by time, height, width
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    max_seq_len: int = 262144
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # what the engine asks of every config and this family has none of
    attn_logit_softcap: float = 0.0
    post_norms: bool = False
    sliding_window: int = 0

    @property
    def ik_stored(self) -> int:
        """Columns of the pool's ``ik`` row: the key on whole 128-lane tiles
        (module text), the rest zeros."""
        return -(-self.index_head_dim // 128) * 128

    @property
    def held(self) -> tuple[int, ...]:
        return tuple(range(self.n_experts)) if self.experts_held is None else self.experts_held


PRESETS: dict[str, KeyeConfig] = {
    # Kwai-Keye/Keye-VL-2.0-30B-A3B's language model whole: 61.3 GB of bfloat16, no single chip
    "keye-vl-2.0-30b-a3b": KeyeConfig(),
    # CPU tests: contexts to 48 choose 8 rows
    "keye-tiny": KeyeConfig(
        vocab_size=256, dim=64, n_heads=4, n_kv_heads=2, head_dim=16, n_layers=3, expert_ffn_dim=32, n_experts=16,
        experts_per_token=2, mrope_section=(2, 3, 3), index_heads=4, index_head_dim=8, index_topk=8, max_seq_len=512,
        rope_theta=10000.0, dtype=jnp.float32,
    ),
}


def init_params(config: KeyeConfig, key: jax.Array) -> dict:
    """Random init in the served layout: every layer's attention and
    indexer stacked (``attn``) and its FF (norm, router, held experts)
    stacked (``ff``)."""
    c = config
    d, H, KV, hd, f, eh, n = c.dim, c.n_heads, c.n_kv_heads, c.head_dim, c.expert_ffn_dim, len(c.held), c.n_layers
    Hi, ci = c.index_heads, c.index_head_dim
    count = [0]

    def w(shape, scale):
        count[0] += 1
        return (jax.random.normal(jax.random.fold_in(key, count[0]), shape) * scale).astype(c.dtype)

    return {
        "embed": w((c.vocab_size, d), d ** -0.5),
        "norm": jnp.ones((d,), c.dtype),
        "lm_head": w((d, c.vocab_size), d ** -0.5),
        "attn": {"ln1": jnp.ones((n, d), c.dtype),
                 "wq": w((n, d, H * hd), d ** -0.5), "wk": w((n, d, KV * hd), d ** -0.5),
                 "wv": w((n, d, KV * hd), d ** -0.5), "wo": w((n, H * hd, d), (H * hd) ** -0.5),
                 "q_norm": jnp.ones((n, hd), c.dtype), "k_norm": jnp.ones((n, hd), c.dtype),
                 "iq": w((n, d, Hi * ci), d ** -0.5), "ik": w((n, d, ci), d ** -0.5), "iw": w((n, d, Hi), d ** -0.5),
                 "ik_norm": jnp.ones((n, ci), c.dtype), "ik_bias": jnp.zeros((n, ci), c.dtype)},
        "ff": {"ln2": jnp.ones((n, d), c.dtype), "router": w((n, d, c.n_experts), d ** -0.5),
               "w1": w((n, eh, d, f), d ** -0.5), "w3": w((n, eh, d, f), d ** -0.5), "w2": w((n, eh, f, d), f ** -0.5)},
    }


def _layer_norm(x, weight, bias, eps):
    """LayerNorm with weight and bias over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def _attention_op(h, w, c: KeyeConfig, positions, attend, positions3=None):
    """-> (Op output [B, T, D], the layer's new rows ``{"kv": [B, T, 1,
    words] uint32 (``pack_kv_rows`` of K's and V's heads side by side), "ik":
    [B, T, 1, ik_stored]}`` for the pool, whatever ``attend`` hands on, the
    queries whose choice the tie rule decided as it counts them).
    ``attend(q, k, v, qi [B, T, Hi, c], wi [B, T, Hi] float32, ik
    [B, T, ik_stored]) -> ([B, T, H, d], extra, tied)`` is the path. ``positions``
    [B, T] are the temporal ones; ``positions3`` [B, 3, T], where given, turn
    q and k an axis a section."""
    B, T, _ = h.shape
    with jax.named_scope("attn_qkv"):
        q = rms_norm(mm(h, w["wq"]).reshape(B, T, c.n_heads, c.head_dim), w["q_norm"], c.norm_eps)
        k = rms_norm(mm(h, w["wk"]).reshape(B, T, c.n_kv_heads, c.head_dim), w["k_norm"], c.norm_eps)
        v = mm(h, w["wv"]).reshape(B, T, c.n_kv_heads, c.head_dim)
        if positions3 is None:
            q, k = apply_rope(q, positions, c.rope_theta), apply_rope(k, positions, c.rope_theta)
        else:
            q = apply_rope(q, positions3, c.rope_theta, sections=c.mrope_section)
            k = apply_rope(k, positions3, c.rope_theta, sections=c.mrope_section)
    with jax.named_scope("index_proj"):
        qi = apply_rope(mm(h, w["iq"]).reshape(B, T, c.index_heads, c.index_head_dim), positions, c.rope_theta)
        ki = _layer_norm(mm(h, w["ik"]), w["ik_norm"], w["ik_bias"], c.norm_eps)
        ki = apply_rope(ki[:, :, None, :], positions, c.rope_theta)[:, :, 0, :]  # one key for all heads: one head
        ik = jnp.pad(ki.astype(h.dtype), ((0, 0), (0, 0), (0, c.ik_stored - c.index_head_dim)))
        wi = jnp.matmul(h, w["iw"].astype(h.dtype), preferred_element_type=jnp.float32)  # the accumulator, unrounded
    out, extra, tied = attend(q, k, v, qi, wi, ik)
    with jax.named_scope("attn_out"):
        op = mm(out.reshape(B, T, c.n_heads * c.head_dim), w["wo"])
    kv = pack_kv_rows(*(t.reshape(B, T, -1).astype(h.dtype) for t in (k, v)))
    return op, {"kv": kv[:, :, None, :], "ik": ik[:, :, None, :]}, extra, tied


def _run_layers(params, c: KeyeConfig, x, positions, valid, make_attend, route=None, select=None, positions3=None,
                tell=False):
    """The whole stack, one scan. ``make_attend(i, given)`` gives layer
    ``i``'s (traced index) attention path, ``given`` its row of ``select``
    (rows chosen by the caller, in the path's own form) or None; ``route``
    [n_layers, B, T, k] int32, where given, is every layer's choice of
    experts, taken as it is (an output check's; serving gives neither).
    -> (x, every layer's new rows ``{leaf: [n_layers, B, T, ...]}``, expert
    counters and after them the queries whose choice the tie rule decided
    (a decode step's lanes, over all layers: ``lanes_tied``), and with
    ``tell`` what each layer chose: ``(rows, experts)`` stacked over the
    layers, else None)."""
    norm = lambda x, w: rms_norm(x, w, c.norm_eps)  # noqa: E731
    ff = params["ff"]
    stacks = tuple(ff[name].reshape((-1,) + ff[name].shape[2:]) for name in ("w1", "w3", "w2"))
    small = {name: ff[name] for name in ("ln2", "router")}
    n = c.n_layers

    def body(carry, scanned):
        x, counts = carry
        weights, mine, index, chosen, given = scanned
        with scopes.layer("attn"):
            op, rows, told, tied = _attention_op(norm(x, weights["ln1"]), weights, c, positions,
                                                 make_attend(index, given), positions3)
            x = x + op
        with scopes.layer("ffn"):
            h = norm(x, mine["ln2"])
            experts = None
            if tell:
                logits = h.astype(jnp.float32) @ mine["router"].astype(jnp.float32)
                experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), c.experts_per_token)[1] if chosen is None else chosen
            y, m = routed_ff(h, mine, stacks, index, c, valid, chosen, chunk=True)
            counts = counts + jnp.concatenate([m, jnp.asarray(tied, jnp.uint32)[None]])
            return (x + y, counts), (rows, (told, experts) if tell else None)

    counts = jnp.zeros((1 + COUNTS_HEAD + len(c.held) + 1,), jnp.uint32)
    (x, counts), (rows, told) = jax.lax.scan(
        body, (x, counts), (params["attn"], small, jnp.arange(n, dtype=jnp.int32), route, select))
    return x, rows, counts, told


def causal_ok(q_pos, k_pos):
    """[B, Tq], [B, Tk] -> [B, Tq, Tk]: key at or before the query, both real."""
    return (k_pos[:, None, :] <= q_pos[:, :, None]) & (k_pos[:, None, :] >= 0) & (q_pos[:, :, None] >= 0)


def chosen_mask(c: KeyeConfig, qi, wi, ik, q_pos, k_pos):
    """[B, Tq, Tk] bool: each query's ``index_topk`` causal keys of largest
    index score."""
    B, Tq, Tk = qi.shape[0], qi.shape[1], ik.shape[1]
    with jax.named_scope("index_scores"):
        scores = index_scores(qi, wi, ik)
    with jax.named_scope("index_select"):
        ok = causal_ok(q_pos, k_pos)
        return topk_rows_mask(scores.reshape(B * Tq, Tk), ok.reshape(B * Tq, Tk), c.index_topk).reshape(B, Tq, Tk)


def packed(mask):
    """[..., Tk] bool -> [..., ceil(Tk / 8)] uint8, key ``s`` bit ``s % 8`` of byte ``s // 8``."""
    return jnp.packbits(mask, axis=-1, bitorder="little")


def unpacked(bits, n):
    return jnp.unpackbits(bits, axis=-1, count=n, bitorder="little").astype(bool)


def row_blocks(t, rows: int):
    """[B, T, ...] -> [T / rows, B, rows, ...]: blocks of rows first, for a ``lax.map`` over them."""
    B, T = t.shape[:2]
    return jnp.moveaxis(t.reshape((B, T // rows, rows) + t.shape[2:]), 1, 0)


def prompt_mask(c: KeyeConfig, positions, qi, wi, ik, tier: int | None = None):
    """[B, T, T] bool: the causal keys each query of a whole prompt chooses,
    ``MASK_BLOCK`` query rows at a time (index scores, then each row's
    ``index_topk``-th largest by ``topk_rows_mask``). The blocks of one TIER,
    ``MASK_TIER`` rows whose keys end with the tier, are one ``lax.map``:
    its body is traced and compiled once and scores the block against the
    tier's keys, not the block's own, so a tier at a time the triangle of
    causal pairs is rounded up to rectangles (21 of 36 squares of 4,096 at
    24,576 rows, where the pairs are 18; one map over the whole width would
    score all 36). A ``T`` that is not whole tiers (the CPU's tiny sizes) is
    one tier, and one that is not whole blocks one block. ``tier`` is the
    tier's rows where they are not ``MASK_TIER`` (``models/dots.py`` asks for
    one tier, the whole prompt: its masks are a twentieth of its prefill and
    each tier is a body to compile)."""
    B, T = positions.shape
    tier = tier or MASK_TIER
    step = tier if T % tier == 0 else T
    R = MASK_BLOCK if step % MASK_BLOCK == 0 else step
    tiers = []
    for hi in range(step, T + 1, step):
        keys, key_pos = ik[:, :hi], positions[:, :hi]
        blocks = tuple(row_blocks(t[:, hi - step: hi], R) for t in (qi, wi, positions))
        seen = jax.lax.map(lambda blk: chosen_mask(c, blk[0], blk[1], keys, blk[2], key_pos), blocks)  # noqa: B023
        tiers.append(jnp.pad(jnp.moveaxis(seen, 0, 1).reshape(B, step, hi), ((0, 0), (0, 0), (0, T - hi))))
    return tiers[0] if len(tiers) == 1 else jnp.concatenate(tiers, axis=1)


def _whole_rows(c: KeyeConfig, positions, tell, interpret: bool = False):
    """The path of rows that attend over themselves alone (a whole prompt,
    ``forward``). ``given`` [B, T, ceil(T / 8)] uint8 is a choice of rows
    handed in, packed; with ``tell`` the path hands its own on, so packed.
    The mask (``prompt_mask``, or the choice given under the causal rule) is
    handed whole (``[T, T]``: 604 MB of int8 at 24,576 tokens, a layer at a
    time) to the attention under it: on a TPU (or ``interpret``: tests) the
    kernel of ``ops/pallas/masked_attention.py``, which refuses a ``T`` it
    does not serve (whole blocks of rows: every bucket is); elsewhere the
    plain ``causal_attention(keep=)``, whole scores at once, what the CPU
    runs at its tiny sizes."""
    def make_attend(i, given):
        def attend(q, k, v, qi, wi, ik):
            B, T = positions.shape
            with jax.named_scope("prefill_attention"):
                with jax.named_scope("sparse_mask"):
                    if given is None:
                        mask = prompt_mask(c, positions, qi, wi, ik)
                    else:
                        mask = causal_ok(positions, positions) & unpacked(given, T)
                if interpret or jax.default_backend() == "tpu":
                    from ..ops.pallas.masked_attention import masked_attention

                    seen = mask.astype(jnp.int8)  # keys and values both `head_dim` wide, the scale the keys' own
                    out = jnp.stack([masked_attention(q[b], k[b], v[b], seen[b], scale=c.head_dim ** -0.5,
                                                      interpret=interpret) for b in range(B)])
                else:
                    out = causal_attention(q, k, v, keep=mask)
            return out, packed(mask) if tell else None, 0

        return attend

    return make_attend


def forward(params: dict, tokens: jax.Array, config: KeyeConfig, positions3: jax.Array | None = None,
            select=None, route=None, tell: bool = False, interpret: bool = False, rows: jax.Array | None = None):
    """Full-sequence causal forward -> logits [B, T, V] float32 (tests and
    the output check), or with ``rows`` [B, R] those rows' alone [B, R, V]
    (a 16,384-token prompt's whole logits are 1.2 GB). ``positions3`` [B, 3,
    T] are a token's three positions (default: all its index, a text
    token's). With ``tell`` -> (logits, (rows chosen packed [L, B, T,
    ceil(T / 8)], experts chosen [L, B, T, k])). ``interpret`` runs the
    attention's kernel interpreted (tests)."""
    c = config
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T)) if positions3 is None else positions3[:, 0]
    x, _rows, _counts, told = _run_layers(params, c, embed(params, tokens, c), positions, jnp.ones((B, T), bool),
                                          _whole_rows(c, positions, tell, interpret), route, select, positions3, tell)
    if rows is not None:
        x = x[jnp.arange(B)[:, None], rows]
    logits = head_logits(final_norm(x, params, c), params, c)
    return (logits, told) if tell else logits


# ---------------------------------------------------------------------------
# Serving: a token's K|V row and the indexer's key in the paged pool
# ---------------------------------------------------------------------------


def init_paged_cache(config: KeyeConfig, num_pages: int, page_size: int, quantize_kv: bool = False,
                     max_slots: int = 1) -> dict:
    c = config
    if quantize_kv:
        raise ValueError("the keye family keeps K, V and the indexer's keys in the model's dtype: an int8 key of the "
                         "indexer needs a check that sees the rows it mis-chooses (ROADMAP M10 (c))")
    words = c.n_kv_heads * c.head_dim * jnp.dtype(c.dtype).itemsize // 2
    cache = init_row_pages(c.n_layers, num_pages, page_size, kv=(words, jnp.uint32), ik=(c.ik_stored, c.dtype))
    cache["state"] = {"counts": jnp.zeros((2, 1 + COUNTS_HEAD + len(c.held) + SPARSE_COUNTS), jnp.uint32)}
    return cache


def _committed(cache, pool, counts, c: KeyeConfig, row, scored, live):
    """The cache with its pages replaced and the dispatch counted. ``live``
    [N] are the rows each query could see (0: none, a padding row);
    ``scored`` the ``ik`` rows a layer read or made to score them."""
    u32 = lambda v: jnp.asarray(v).astype(jnp.uint32)  # noqa: E731
    live = live.reshape(-1)
    sparse = jnp.stack([jnp.ones((), jnp.uint32), u32(scored), u32(jnp.sum(jnp.minimum(live, c.index_topk))),
                        u32(jnp.sum(live)), u32(jnp.sum(live > c.index_topk))])
    added = jnp.concatenate([counts[:-1], sparse, counts[-1:]])  # `_run_layers` counts the tied lanes after the experts
    return {**pool, "state": {"counts": cache["state"]["counts"].at[row].add(added)}}


def prefill_paged_batch(params, cache, tokens, lengths, page_ids, config: KeyeConfig, route=None, select=None,
                        tell: bool = False, interpret: bool = False):
    """B whole prompts in one dispatch: each row's K|V rows and indexer keys
    into its pages. -> (cache, logits [B, V]), and with ``tell`` what every
    layer chose (``forward``'s)."""
    c = config
    B, T = tokens.shape
    positions, valid = row_positions(lengths, jnp.zeros((B,), jnp.int32), T)
    x, rows, counts, told = _run_layers(params, c, embed(params, tokens, c), positions, valid,
                                        _whole_rows(c, positions, tell, interpret), route, select, tell=tell)
    with scopes.layer("commit"):
        pool = commit_whole_pages(pool_leaves(cache), rows, page_ids)
        # a whole prompt's block of queries scores its causal keys' block columns: counted as the pairs it needs
        cache = _committed(cache, pool, counts, c, 1, jnp.sum(lengths * (lengths + 1) // 2), positions + 1)
    logits = head_logits(final_norm(x, params, c), params, c, last=lengths)
    return (cache, logits, told) if tell else (cache, logits)


def _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, c: KeyeConfig):
    """Rows that start at ``starts`` (page-aligned) attend over the rows
    chosen among their gathered prefix pages plus themselves: the ``kv`` pages
    (split into K and V) and the indexer's keys gathered (the whole table's,
    whatever the start), the queries' scores made against cache and own rows
    together. Nothing is written here. -> (x normed, new rows, counts, ik
    rows scored, live rows a query)."""
    B, T = tokens.shape
    positions, valid = row_positions(lengths, starts, T)
    pool = pool_leaves(cache)
    NP, P = pool["kv"].shape[1:3]
    M = block_tables.shape[1]
    key_pos = key_positions(starts, positions, M * P)

    def make_attend(i, given):
        def attend(q, k, v, qi, wi, ik):
            with jax.named_scope("full_gather"):
                ids = layer_tables(block_tables, i, NP)
                got = unpack_kv_rows(flat_pages(pool["kv"])[ids].reshape(B, M * P, -1), q.dtype)
                keys, values = (jnp.concatenate([t.reshape((B, M * P) + k.shape[2:]), new], axis=1)
                                for t, new in zip(got, (k, v)))
                index_keys = jnp.concatenate([gather_pages(pool, "ik", ids, q.dtype, 1).reshape(B, M * P, -1), ik], axis=1)

            def block(blk):
                q_b, qi_b, wi_b, pos_b = blk
                mask = chosen_mask(c, qi_b, wi_b, index_keys, pos_b, key_pos)
                with jax.named_scope("prefill_attention"):
                    return blocked_masked_attention(q_b, keys, values, mask)

            if T <= CONTINUE_BLOCK or T % CONTINUE_BLOCK:
                return block((q, qi, wi, positions)), None, 0
            out = jax.lax.map(block, tuple(row_blocks(t, CONTINUE_BLOCK) for t in (q, qi, wi, positions)))
            return jnp.moveaxis(out, 0, 1).reshape(B, T, c.n_heads, c.head_dim), None, 0

        return attend

    x, rows, counts, _ = _run_layers(params, c, embed(params, tokens, c), positions, valid, make_attend)
    return final_norm(x, params, c), rows, counts, jnp.sum(lengths) * (M * P + T), positions + 1


def _continue_commit(cache, new, page_ids, c: KeyeConfig):
    rows, counts, scored, live = new
    with scopes.layer("commit"):
        pool = commit_whole_pages(pool_leaves(cache), rows, page_ids)
        return _committed(cache, pool, counts, c, 1, scored, live)


def prefill_paged_continue(params, cache, tokens, lengths, starts, page_ids, block_tables, config: KeyeConfig):
    """Continuation (a prefix hit's suffix, a later chunk of a long prompt, a
    resumed request's tail): -> (cache, last-token logits [B, V])."""
    x, *new = _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, config)
    return _continue_commit(cache, new, page_ids, config), head_logits(x, params, config, last=lengths)


def prefill_paged_continue_kv(params, cache, tokens, lengths, starts, page_ids, block_tables, config: KeyeConfig):
    """The continuation's writes without the head (a mid chunk)."""
    _x, *new = _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, config)
    return _continue_commit(cache, new, page_ids, config)


def decode_step_paged(params, cache, tokens, seq_lens, block_tables, active, config: KeyeConfig,
                      use_pallas: bool = False, mesh=None, route=None, select=None, tell: bool = False,
                      interpret: bool = False):
    """One token for lanes 0..S-1 (lane b is slot b): every layer scores the
    lane's cached rows through ``ik``, chooses, and attends over the chosen
    ``kv`` rows fetched by row. ``use_pallas`` and ``mesh`` are what the
    engine hands every family's step; neither changes anything here: the
    walk by rows is XLA's gather (module text), and the choice's kernel runs
    wherever the backend is a TPU (``interpret`` runs it interpreted:
    tests). ``select`` [n_layers, S, index_topk] int32 positions (-1 none)
    is a choice handed in; with ``tell`` -> (cache, logits, (positions chosen
    [L, S, index_topk] in no order a caller may count on, experts chosen [L,
    S, 1, k]))."""
    c = config
    S = tokens.shape[0]
    pool = pool_leaves(cache)
    NP, P = pool["kv"].shape[1:3]
    flat = {name: flat_pages(a) for name, a in pool.items()}

    def make_attend(i, given):
        def attend(q, k, v, qi, wi, ik):
            out, chosen, tied = sparse_decode_attention_reference_cache_plus_new(
                q[:, 0], flat, layer_tables(block_tables, i, NP), seq_lens,
                {"kv": pack_kv_rows(k.reshape(S, -1), v.reshape(S, -1)), "ik": ik[:, 0]}, qi[:, 0], wi[:, 0],
                c.index_topk, given, interpret)
            return out[:, None], chosen, jnp.sum(tied & active)

        return attend

    x, rows, counts, told = _run_layers(params, c, embed(params, tokens[:, None], c), seq_lens[:, None],
                                        active[:, None], make_attend, route, select, tell=tell)
    with scopes.layer("commit"):
        target = jnp.where(active, block_tables[jnp.arange(S), seq_lens // P], TRASH_PAGE)
        pool = commit_tokens(pool, {name: r[:, :, 0] for name, r in rows.items()}, target, seq_lens % P)
        live = jnp.where(active, seq_lens + 1, 0)
        cache = _committed(cache, pool, counts, c, 0, jnp.sum(active) * block_tables.shape[1] * P, live)
    logits = head_logits(final_norm(x[:, 0], params, c), params, c)
    return (cache, logits, told) if tell else (cache, logits)


def counters(cache: dict) -> jax.Array:
    """The expert layers' and the indexer's counters as the programs keep them."""
    return cache["state"]["counts"]


def describe_counters(config: KeyeConfig, total) -> dict:
    """``Engine.stats()``'s ``"moe"`` (the keys ``lfm2`` gives) and
    ``"sparse"`` from the counters summed by the engine (``total`` [2, 1 +
    COUNTS_HEAD + held + SPARSE_COUNTS], None before the first dispatch),
    decode steps and prefills apart. ``sparse``, each over ALL layers (the
    device counts a layer; every layer sees the same rows): ``steps``
    dispatches; ``rows_scored`` rows of ``ik`` the indexer read or made to
    score (a decode step: the lanes' whole tables, as the gather reads them;
    a whole prompt: its causal pairs); ``rows_chosen`` rows attention was
    taken over, ``min(rows it could see, index_topk)`` a query;
    ``rows_dense`` rows a dense walk would have read, all a query could see;
    ``lanes_past_topk`` queries (a decode step: lanes) that could see more
    than ``index_topk`` and so left some out; ``lanes_tied`` decode lanes, a
    layer each, whose ``index_topk``-th score had more rows at it than room,
    so that the tie rule (the earlier row) decided the set."""
    c = config
    cut = 1 + COUNTS_HEAD + len(c.held)
    if total is None:
        total = [[0] * (cut + SPARSE_COUNTS)] * 2

    def sparse(r):
        n = c.n_layers
        return {"steps": int(r[cut]), "rows_scored": int(r[cut + 1]) * n, "rows_chosen": int(r[cut + 2]) * n,
                "rows_dense": int(r[cut + 3]) * n, "lanes_past_topk": int(r[cut + 4]), "lanes_tied": int(r[cut + 5])}

    return {
        "moe": describe_moe(c, [r[:cut] for r in total])["moe"],
        "sparse": {"topk": c.index_topk, "index_heads": c.index_heads, "index_values": c.index_head_dim,
                   "ik_row_bytes_stored": c.ik_stored * jnp.dtype(c.dtype).itemsize, "layers": c.n_layers,
                   "decode": sparse(total[0]), "prefill": sparse(total[1])},
    }


def refusals(asked: dict) -> list[tuple[bool, str]]:
    """What the engine was asked for that this family does not serve, in
    words (``models.programs``): selection runs over a lane's whole context,
    so a mesh that splits heads or context would have to agree on one choice
    (ROADMAP M10 (d)), and ``parallel/mesh.py`` knows the dense family's leaves
    alone."""
    return [
        (asked["kv_layout"] != "paged", "kv_layout='slot': the indexer's keys live in the paged pool beside K and V; serve it with kv_layout='paged'"),
        (asked["spec_len"] > 0, "spec_len > 0: it has no verify program over chosen rows"),
        (asked["tp"] > 1 or asked["sp"] > 1, "tensor or context parallelism: every head attends over one choice of rows and its weights have no sharding here; serve it on a tp=1 mesh"),
        (asked["quantize_weights"], "weight-only int8: its matrices are served in the dtype they were made in"),
        (asked["quantize_kv"], "quantize_kv: K, V and the indexer's keys are kept in the model's dtype"),
        (asked["coordination"], "multi-host lockstep serving"),
    ]
