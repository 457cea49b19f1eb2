"""Llama model family, TPU-first pure-JAX implementation.

No reference analogue (humanlayer/agentcontrolplane runs no models —
SURVEY.md §0); this is the compute core of the in-tree ``provider: tpu``
backend (north star: Llama-3-8B serving on v5e-8).

Design choices for TPU/XLA:

- Params are a plain pytree with **stacked layer weights** (leading dim =
  n_layers) so the transformer body is one ``lax.scan`` — O(1) HLO size and
  compile time in depth, and XLA pipelines the layer loop.
- bf16 params/activations (MXU-native), float32 for norms/softmax/rope.
- GQA (n_kv_heads <= n_heads), SwiGLU MLP, RMSNorm, RoPE — weight layout
  matches HF ``LlamaForCausalLM`` so checkpoints load without surgery.
- Three entry points: ``forward`` (full sequence — training/prefill/tests),
  ``prefill`` (writes a slot KV cache), ``decode_step`` (one token for all
  slots of the continuous batch).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops.attention import (
    blocked_causal_attention,
    causal_attention,
    continue_attention,
    decode_attention_cache_plus_new,
)
from ..observability import scopes
from ..ops.norms import rms_norm
from ..ops.paged import (
    TRASH_PAGE,
    commit_tokens,
    commit_whole_pages,
    flat_pages,
    gather_pages,
    init_kv_pages,
    layer_tables,
    paged_decode_attention_reference_cache_plus_new,
    token_write_targets,
)
from ..ops.quant import kv_dequantize, kv_quantize
from ..ops.rope import apply_rope


@dataclass(frozen=True)
class LlamaConfig:
    """Covers the Llama-architecture family: Llama-3/3.x, Mistral (same
    block; sliding window unused at our context lengths), Qwen2/2.5
    (``qkv_bias=True``), and Gemma-1 (``hidden_act="gelu_tanh"``,
    ``norm_plus_one``, ``embed_scale``, explicit ``head_dim`` — MQA)."""

    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    # Llama-3.1-style RoPE frequency rescale (HF rope_scaling.rope_type
    # "llama3"): factor > 1 enables (8.0 for 3.1, 32.0 for 3.2); the other
    # three follow the checkpoint config. Real 3.1/3.2 checkpoints are
    # TRAINED with these — serving them unscaled is a different function.
    rope_scaling_factor: float = 1.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_seq: int = 8192
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    qkv_bias: bool = False  # Qwen2-style attention input bias
    hidden_act: str = "silu"  # "silu" (llama/mistral/qwen) | "gelu_tanh" (gemma)
    norm_plus_one: bool = False  # gemma RMSNorm multiplies by (1 + weight)
    embed_scale: bool = False  # gemma scales embeddings by sqrt(dim)
    head_dim_override: Optional[int] = None  # gemma: head_dim != dim/n_heads
    # Gemma-2 additions (all default-off => prior families unchanged):
    attn_logit_softcap: float = 0.0  # tanh-cap attention logits (g2: 50.0)
    final_logit_softcap: float = 0.0  # tanh-cap lm_head logits (g2: 30.0)
    post_norms: bool = False  # extra RMSNorms on sublayer OUTPUTS pre-residual
    query_pre_attn_scalar: float = 0.0  # q scale denominator; 0 = head_dim
    # Gemma-2 alternates local (sliding-window) and global layers. Within
    # one window sliding == full causal, so serving is EXACT for contexts
    # <= window (4096) and the engine refuses longer for this family: it
    # keeps one cache for every layer. The windowed KV path is
    # models/mellum.py's (a ring of window pages a slot beside the full
    # layers' pages, the window walk); this family does not take it yet.
    sliding_window: int = 0
    # Mixture-of-Experts (Mixtral architecture): n_experts > 0 replaces the
    # dense FFN with top-k routed SwiGLU experts (ops/moe.py routed_experts:
    # no capacity, no dropped token). The expert axis shards over the mesh's
    # 'ep' axis (expert parallelism).
    n_experts: int = 0
    experts_per_token: int = 2
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.dim // self.n_heads


# Presets: llama3-8b matches meta-llama/Meta-Llama-3-8B(-Instruct);
# llama3.2-1b matches meta-llama/Llama-3.2-1B(-Instruct).
PRESETS: dict[str, LlamaConfig] = {
    "llama3-8b": LlamaConfig(),
    # 3.1 = the 3-8B architecture + llama3 rope scaling to 128k context
    "llama3.1-8b": LlamaConfig(
        rope_scaling_factor=8.0,
        rope_low_freq_factor=1.0,
        rope_high_freq_factor=4.0,
        rope_original_max_seq=8192,
        max_seq_len=131072,
    ),
    "llama3.2-1b": LlamaConfig(
        vocab_size=128256,
        dim=2048,
        n_layers=16,
        n_heads=32,
        n_kv_heads=8,
        ffn_dim=8192,
        rope_theta=500000.0,
        tie_embeddings=True,
        rope_scaling_factor=32.0,
        rope_original_max_seq=8192,
        max_seq_len=131072,
    ),
    "llama3.2-3b": LlamaConfig(
        vocab_size=128256,
        dim=3072,
        n_layers=28,
        n_heads=24,
        n_kv_heads=8,
        ffn_dim=8192,
        tie_embeddings=True,
        rope_scaling_factor=32.0,
        rope_original_max_seq=8192,
        max_seq_len=131072,
    ),
    # ~1.1B params — sized to fill a single v5e chip nicely at batch 64
    "bench-1b": LlamaConfig(
        vocab_size=32768,
        dim=2048,
        n_layers=16,
        n_heads=16,
        n_kv_heads=8,
        ffn_dim=8192,
    ),
    "mistral-7b": LlamaConfig(
        vocab_size=32000,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        ffn_dim=14336,
        rope_theta=10000.0,
        max_seq_len=8192,
    ),
    "qwen2.5-7b": LlamaConfig(
        vocab_size=152064,
        dim=3584,
        n_layers=28,
        n_heads=28,
        n_kv_heads=4,
        ffn_dim=18944,
        rope_theta=1000000.0,
        qkv_bias=True,
    ),
    "qwen2.5-0.5b": LlamaConfig(
        vocab_size=151936,
        dim=896,
        n_layers=24,
        n_heads=14,
        n_kv_heads=2,
        ffn_dim=4864,
        rope_theta=1000000.0,
        qkv_bias=True,
        tie_embeddings=True,
    ),
    # google/gemma-2b: MQA (1 kv head), GeGLU, (1+w) norms, scaled embeddings
    "gemma-2b": LlamaConfig(
        vocab_size=256000,
        dim=2048,
        n_layers=18,
        n_heads=8,
        n_kv_heads=1,
        ffn_dim=16384,
        rope_theta=10000.0,
        norm_eps=1e-6,
        tie_embeddings=True,
        hidden_act="gelu_tanh",
        norm_plus_one=True,
        embed_scale=True,
        head_dim_override=256,
    ),
    "gemma-7b": LlamaConfig(
        vocab_size=256000,
        dim=3072,
        n_layers=28,
        n_heads=16,
        n_kv_heads=16,
        ffn_dim=24576,
        rope_theta=10000.0,
        norm_eps=1e-6,
        tie_embeddings=True,
        hidden_act="gelu_tanh",
        norm_plus_one=True,
        embed_scale=True,
        head_dim_override=256,
    ),
    # google/gemma-2-2b: four-norm blocks, tanh soft-caps, GQA,
    # query_pre_attn_scalar = head_dim, alternating 4096-token local layers
    # (serve with max_ctx <= 4096; see LlamaConfig.sliding_window)
    "gemma2-2b": LlamaConfig(
        vocab_size=256000,
        dim=2304,
        n_layers=26,
        n_heads=8,
        n_kv_heads=4,
        ffn_dim=9216,
        rope_theta=10000.0,
        norm_eps=1e-6,
        tie_embeddings=True,
        hidden_act="gelu_tanh",
        norm_plus_one=True,
        embed_scale=True,
        head_dim_override=256,
        attn_logit_softcap=50.0,
        final_logit_softcap=30.0,
        post_norms=True,
        query_pre_attn_scalar=256.0,
        sliding_window=4096,
    ),
    "gemma2-9b": LlamaConfig(
        vocab_size=256000,
        dim=3584,
        n_layers=42,
        n_heads=16,
        n_kv_heads=8,
        ffn_dim=14336,
        rope_theta=10000.0,
        norm_eps=1e-6,
        tie_embeddings=True,
        hidden_act="gelu_tanh",
        norm_plus_one=True,
        embed_scale=True,
        head_dim_override=256,
        attn_logit_softcap=50.0,
        final_logit_softcap=30.0,
        post_norms=True,
        query_pre_attn_scalar=256.0,
        sliding_window=4096,
    ),
    # mistralai/Mixtral-8x7B(-Instruct): Mistral block + 8 top-2 experts
    "mixtral-8x7b": LlamaConfig(
        vocab_size=32000,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        ffn_dim=14336,
        rope_theta=1000000.0,
        max_seq_len=32768,
        n_experts=8,
        experts_per_token=2,
    ),
    # tiny MoE for CPU tests (4 experts, top-2)
    "moe-tiny": LlamaConfig(
        vocab_size=256,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_dim=128,
        max_seq_len=128,
        rope_theta=10000.0,
        n_experts=4,
        experts_per_token=2,
        dtype=jnp.float32,
    ),
    # tiny config for CPU tests (matches an HF config in tests)
    "tiny": LlamaConfig(
        vocab_size=256,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_dim=128,
        max_seq_len=128,
        rope_theta=10000.0,
        dtype=jnp.float32,
    ),
}


def init_params(config: LlamaConfig, key: jax.Array) -> dict:
    """Random init (serving benchmarks / tests); layout mirrors HF names."""
    c = config
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    d, hd = c.dim, c.head_dim

    def norm_init(shape, scale):
        # truncated-normal-ish init; exact init only matters for training
        return (
            jax.random.normal(jax.random.fold_in(k_layers, hash(shape) % 2**31), shape)
            * scale
        ).astype(c.dtype)

    def stacked(shape, scale):
        return (
            jax.random.normal(
                jax.random.fold_in(k_layers, (hash(shape) + 1) % 2**31),
                (c.n_layers, *shape),
            )
            * scale
        ).astype(c.dtype)

    scale = d**-0.5
    if c.n_experts > 0:
        ffn = {
            "router": stacked((d, c.n_experts), scale),
            "w1": stacked((c.n_experts, d, c.ffn_dim), scale),
            "w3": stacked((c.n_experts, d, c.ffn_dim), scale),
            "w2": stacked((c.n_experts, c.ffn_dim, d), c.ffn_dim**-0.5),
        }
    else:
        ffn = {
            "w1": stacked((d, c.ffn_dim), scale),  # gate_proj
            "w3": stacked((d, c.ffn_dim), scale),  # up_proj
            "w2": stacked((c.ffn_dim, d), c.ffn_dim**-0.5),  # down_proj
        }
    params = {
        "embed": (jax.random.normal(k_embed, (c.vocab_size, d)) * scale).astype(c.dtype),
        "layers": {
            "ln1": jnp.ones((c.n_layers, d), dtype=c.dtype),
            "ln2": jnp.ones((c.n_layers, d), dtype=c.dtype),
            "wq": stacked((d, c.n_heads * hd), scale),
            "wk": stacked((d, c.n_kv_heads * hd), scale),
            "wv": stacked((d, c.n_kv_heads * hd), scale),
            "wo": stacked((c.n_heads * hd, d), scale),
            **ffn,
        },
        "norm": jnp.ones((d,), dtype=c.dtype),
    }
    if c.qkv_bias:
        params["layers"]["bq"] = jnp.zeros((c.n_layers, c.n_heads * hd), dtype=c.dtype)
        params["layers"]["bk"] = jnp.zeros((c.n_layers, c.n_kv_heads * hd), dtype=c.dtype)
        params["layers"]["bv"] = jnp.zeros((c.n_layers, c.n_kv_heads * hd), dtype=c.dtype)
    if c.post_norms:  # gemma-2 sublayer-output norms
        params["layers"]["ln1_post"] = jnp.ones((c.n_layers, d), dtype=c.dtype)
        params["layers"]["ln2_post"] = jnp.ones((c.n_layers, d), dtype=c.dtype)
    if not c.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (d, c.vocab_size)) * scale
        ).astype(c.dtype)
    return params



def embed(params: dict, tokens: jax.Array, c: LlamaConfig) -> jax.Array:
    with scopes.layer("embed"):
        x = params["embed"][tokens].astype(c.dtype)
        if c.embed_scale:  # gemma normalizes embeddings by sqrt(dim)
            x = x * jnp.asarray(c.dim**0.5, dtype=c.dtype)
        return x


def final_norm_w(params: dict, c: LlamaConfig) -> jax.Array:
    return params["norm"] + 1.0 if c.norm_plus_one else params["norm"]


def _final_norm(x: jax.Array, params: dict, c: LlamaConfig) -> jax.Array:
    with scopes.layer("head"):
        return rms_norm(x, final_norm_w(params, c), c.norm_eps)


def head_logits(x: jax.Array, params: dict, c: LlamaConfig, last: Optional[jax.Array] = None) -> jax.Array:
    """lm_head projection -> float32 logits; applies gemma-2's final logit
    soft-capping when configured (cap * tanh(logits / cap)). ``last`` [B]
    (true lengths) picks each row's last real token of ``x`` [B, T, D] first."""
    with scopes.layer("head"):
        if last is not None:
            x = x[jnp.arange(x.shape[0]), last - 1]
        head = params["embed"].T if c.tie_embeddings else params["lm_head"]
        logits = (x @ head.astype(c.dtype)).astype(jnp.float32)
        if c.final_logit_softcap:
            cap = jnp.float32(c.final_logit_softcap)
            logits = cap * jnp.tanh(logits / cap)
        return logits


def attn_mlp(
    x: jax.Array,  # [B, T, D]
    layer: dict,  # one layer's params (unstacked)
    config: LlamaConfig,
    positions: jax.Array,  # [B, T]
    attn_fn,
    walk: str = "prefill_attention",  # the attention operator's scope: a decode step's is "page_walk"
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Shared block body: returns (output, k, v) where k/v are this layer's
    new key/value tensors (for cache writes). The device scopes of every
    llama program are opened here (observability/scopes.py)."""
    from ..ops.quant import matmul as mm  # transparent int8 dequant

    c = config
    B, T, D = x.shape
    norm_w = (lambda w: w + 1.0) if c.norm_plus_one else (lambda w: w)
    if c.hidden_act == "silu":
        act = jax.nn.silu
    elif c.hidden_act == "gelu_tanh":
        act = partial(jax.nn.gelu, approximate=True)
    else:  # fail at trace time, not silently compute the wrong function
        raise ValueError(f"unsupported hidden_act {c.hidden_act!r} (silu|gelu_tanh)")
    with scopes.layer("attn"):
        with jax.named_scope("attn_qkv"):
            h = rms_norm(x, norm_w(layer["ln1"]), c.norm_eps)
            q = mm(h, layer["wq"])
            k = mm(h, layer["wk"])
            v = mm(h, layer["wv"])
            if c.qkv_bias:
                q = q + layer["bq"]
                k = k + layer["bk"]
                v = v + layer["bv"]
            # q and k exist as [B, T, heads * head_dim] before they are split to
            # heads. Without the barrier the chip's compiler folds the reshape
            # below into the two products, wants each weight heads-major, and so
            # in every decode program cuts the layer's wq / wk out of its stack
            # into fast memory as an op of its own, relays it there, only then
            # multiplies, and copies the whole stacks once a block into the
            # layout the cut wants (`constant_dynamic-slice_fusion.9
            # s8[1,3584,3584]`, `.8 s8[1,3584,512]`, `copy.44 s8[28,3584,3584]`,
            # `copy.43` on the 7B: 0.80 ms of a 13.27 ms step; PERF.md, PR 48).
            # The identity on values; v is not roped and was never staged.
            q, k = jax.lax.optimization_barrier((q, k))
            q = q.reshape(B, T, c.n_heads, c.head_dim)
            k = k.reshape(B, T, c.n_kv_heads, c.head_dim)
            v = v.reshape(B, T, c.n_kv_heads, c.head_dim)
            scaling = (
                (c.rope_scaling_factor, c.rope_low_freq_factor,
                 c.rope_high_freq_factor, c.rope_original_max_seq)
                if c.rope_scaling_factor != 1.0
                else None
            )
            q = apply_rope(q, positions, c.rope_theta, scaling=scaling)
            k = apply_rope(k, positions, c.rope_theta, scaling=scaling)
            if c.query_pre_attn_scalar:
                # gemma-2 scales attention by 1/sqrt(query_pre_attn_scalar) instead
                # of 1/sqrt(head_dim); pre-scaling q here keeps every attention
                # implementation's internal 1/sqrt(head_dim) untouched
                q = q * jnp.asarray(
                    (c.head_dim ** 0.5) / (c.query_pre_attn_scalar ** 0.5), dtype=q.dtype
                )
        with jax.named_scope(walk):
            attn = attn_fn(q, k, v)
        with jax.named_scope("attn_out"):
            attn_out = mm(attn.reshape(B, T, c.n_heads * c.head_dim), layer["wo"])
            if c.post_norms:  # gemma-2: norm the sublayer OUTPUT before residual
                attn_out = rms_norm(attn_out, norm_w(layer["ln1_post"]), c.norm_eps)
            x = x + attn_out
    with scopes.layer("ffn"):
        if c.n_experts > 0:
            from ..ops.moe import routed_experts

            h = rms_norm(x, norm_w(layer["ln2"]), c.norm_eps)
            # kernel=False: jax.lax.ragged_dot, which GSPMD partitions over the
            # mesh's 'ep' and 'tp' axes (an opaque kernel it would replicate)
            y, _counts = routed_experts(
                h.reshape(B * T, D), layer["router"], layer["w1"], layer["w3"], layer["w2"],
                c.experts_per_token, score="softmax", act=act, kernel=False,
            )
            x = x + y.reshape(B, T, D)
        else:
            with jax.named_scope("ffn_dense"):
                h = rms_norm(x, norm_w(layer["ln2"]), c.norm_eps)
                y = mm(act(mm(h, layer["w1"])) * mm(h, layer["w3"]), layer["w2"])
                if c.post_norms:
                    y = rms_norm(y, norm_w(layer["ln2_post"]), c.norm_eps)
                x = x + y
    return x, k, v


def forward(
    params: dict,
    tokens: jax.Array,  # [B, T] int32
    config: LlamaConfig,
    positions: Optional[jax.Array] = None,  # [B, T]; default arange
    attn_impl=None,  # callable(q, k, v, positions) -> out; default dense causal
    remat: bool = False,
) -> jax.Array:
    """Full-sequence causal forward -> logits [B, T, V] (float32).

    ``attn_impl`` swaps the attention op — e.g. ring attention for
    sequence-parallel training (parallel.ring_attention). ``remat``
    rematerializes each layer in the backward pass (``jax.checkpoint`` on
    the scan body): activation memory drops from O(n_layers · B · T ·
    state) to one layer's worth at ~1/3 extra FLOPs — what lets an 8B
    train step fit HBM at real sequence lengths. Gradients are
    numerically identical (tested); inference paths leave it off (no
    backward = nothing to save)."""
    c = config
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    if attn_impl is not None:
        if c.attn_logit_softcap:
            # refuse, don't mis-serve: a swapped-in attention op (ring
            # attention etc.) has no soft-cap path, and silently dropping
            # the cap trains/evaluates a DIFFERENT model than configured
            raise ValueError(
                "attn_logit_softcap is configured but a custom attn_impl "
                "cannot apply it — use the default dense attention (or a "
                "soft-cap-aware implementation) for gemma-2-style models"
            )
        attn = attn_impl
    else:
        attn = partial(causal_attention, softcap=c.attn_logit_softcap)

    def body(x, layer):
        out, _, _ = attn_mlp(
            x,
            layer,
            c,
            positions,
            lambda q, k, v: attn(q, k, v, positions),
        )
        return out, None

    if remat:
        # prevent_cse=False: safe and faster under scan (the loop already
        # isolates iterations; CSE prevention only matters for unrolled use)
        body = jax.checkpoint(body, prevent_cse=False)

    x = embed(params, tokens, c)

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _final_norm(x, params, c)
    return head_logits(x, params, c)


# ---------------------------------------------------------------------------
# Serving: KV quantization plumbing (shared by the slot and paged layouts)
# ---------------------------------------------------------------------------
#
# A quantized cache is the SAME dict with int8 "k"/"v" plus per-row-per-head
# f32 scale arrays "ks"/"vs", one a row and KV head ([L, S, C, H_kv] slot /
# [L, NP, P, H_kv] paged). Presence of "ks" is the trace-time switch: every
# slot-layout program below commits through _kv_commit (quantize-on-commit,
# same single scatter) and reads through _kv_rows (dequantize-after-gather);
# the paged programs go through ops/paged.py's kv_commit and gather_pages,
# the same discipline over the pool's merged rows. So all compiled shapes —
# prefill, continuation, KV-only megastep chunks, decode, spec verify — serve
# quantized without a second code path. Scale scatters reuse the value
# scatter's leading indices, so scale storage is owned/freed with its pages
# by construction.


def _kv_scan_xs(cache: dict) -> tuple:
    """The read-only KV xs a layer scan carries: ``((k, ks?), (v, vs?))``
    tuples so quantized caches ride the same scan discipline."""
    if "ks" in cache:
        return (cache["k"], cache["ks"]), (cache["v"], cache["vs"])
    return (cache["k"],), (cache["v"],)


def _kv_rows(kv: tuple, idx, dtype) -> jax.Array:
    """Gather rows/pages from one layer's scanned KV leaf group and
    dequantize when quantized. ``idx`` is any indexer valid on the value
    array's leading dims (slice, gather array, block table)."""
    if len(kv) == 2:
        return kv_dequantize(kv[0][idx], kv[1][idx], dtype)
    return kv[0][idx].astype(dtype)


def _kv_commit(cache: dict, new_k: jax.Array, new_v: jax.Array, setter) -> dict:
    """Commit fresh K/V through ``setter(array, values)`` — the SAME
    scatter applied to the value arrays ([..., H_kv, d]) and, for a
    quantized cache, to the scale arrays ([..., H_kv]); quantization
    happens here, once per dispatch, on the already-stacked commit."""
    with scopes.layer("commit"):
        if "ks" in cache:
            qk, sk = kv_quantize(new_k)
            qv, sv = kv_quantize(new_v)
            return {
                "k": setter(cache["k"], qk),
                "v": setter(cache["v"], qv),
                "ks": setter(cache["ks"], sk),
                "vs": setter(cache["vs"], sv),
            }
        return {
            "k": setter(cache["k"], new_k.astype(cache["k"].dtype)),
            "v": setter(cache["v"], new_v.astype(cache["v"].dtype)),
        }


# ---------------------------------------------------------------------------
# Serving: slot KV cache
# ---------------------------------------------------------------------------


def init_kv_cache(
    config: LlamaConfig, max_slots: int, max_ctx: int, quantize_kv: bool = False
) -> dict:
    """[L, S, C, H_kv, d] per k/v, bf16 — or int8 plus [L, S, C, H_kv] f32
    scale rows with ``quantize_kv`` (see the KV quantization plumbing)."""
    c = config
    shape = (c.n_layers, max_slots, max_ctx, c.n_kv_heads, c.head_dim)
    if quantize_kv:
        return {
            "k": jnp.zeros(shape, dtype=jnp.int8),
            "v": jnp.zeros(shape, dtype=jnp.int8),
            "ks": jnp.zeros(shape[:-1], dtype=jnp.float32),
            "vs": jnp.zeros(shape[:-1], dtype=jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, dtype=c.dtype),
        "v": jnp.zeros(shape, dtype=c.dtype),
    }


def prefill_batch(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [B, T] int32 (each row padded)
    lengths: jax.Array,  # [B] int32 — true prompt lengths
    slots: jax.Array,  # [B] int32 — distinct target slots
    config: LlamaConfig,
) -> tuple[dict, jax.Array]:
    """Run B prompts through the model in one dispatch, writing each row's
    K/V into its slot. Batching prefills is how burst admissions avoid
    serializing (one compiled program per (B, T) bucket pair; the engine
    splits admission groups into power-of-two B). Returns
    (cache, logits_at_last_token [B, V])."""
    c = config
    T = tokens.shape[1]
    ar = jnp.arange(T)
    positions = jnp.where(ar[None, :] < lengths[:, None], ar[None, :], -1)  # [B,T]
    x = embed(params, tokens, c)  # [B, T, D]

    def body(carry, layer):
        x = carry
        out, k, v = attn_mlp(
            x,
            layer,
            c,
            positions,
            lambda q, k, v: blocked_causal_attention(
                q, k, v, positions, softcap=c.attn_logit_softcap
            ),
        )
        return out, (k, v)

    # prompt attention never reads the cache, so the cache stays OUT of the
    # scan entirely: stack the per-layer K/V (ys) and commit with one
    # scatter — writing inside the scan would copy the whole cache per layer
    # (see decode_step)
    x, (new_k, new_v) = jax.lax.scan(body, x, params["layers"])
    cache = _kv_commit(
        cache, new_k, new_v, lambda arr, val: arr.at[:, slots, :T].set(val)
    )
    # (padded tail is garbage but never read: decode masks by seq_len)
    x = _final_norm(x, params, c)
    logits = head_logits(x, params, c, last=lengths)
    return cache, logits


def prefill(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [T] int32 (padded)
    length: jax.Array,  # scalar int32 — true prompt length
    slot: jax.Array,  # scalar int32
    config: LlamaConfig,
) -> tuple[dict, jax.Array]:
    """Single-prompt prefill (B=1 view of :func:`prefill_batch`)."""
    cache, logits = prefill_batch(
        params, cache, tokens[None], length[None], slot[None], config
    )
    return cache, logits[0]


def _continue_forward(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [B, T] int32 — SUFFIX tokens (rows padded)
    lengths: jax.Array,  # [B] int32 — true suffix lengths
    starts: jax.Array,  # [B] int32 — absolute position of each suffix start
    slots: jax.Array,  # [B] int32
    config: LlamaConfig,
) -> tuple[dict, jax.Array]:
    """Shared continuation body (slot layout): the first ``starts[b]``
    positions of each slot's KV rows are already populated; run only the
    suffix through the model, attending over prefix + suffix, and commit the
    suffix K/V. Returns ``(cache, x_normed [B, T, D])`` — the final-norm
    hidden states at EVERY suffix position, so callers choose the head:
    :func:`prefill_continue` projects only the last token (prefix-cache
    hits / chunked prefill), :func:`verify_continue` projects all positions
    (speculative verification)."""
    c = config
    B, T = tokens.shape
    ar = jnp.arange(T)
    positions = jnp.where(ar[None, :] < lengths[:, None], starts[:, None] + ar[None, :], -1)
    x = embed(params, tokens, c)
    C = cache["k"].shape[2]
    # scatter indices for the suffix writes; clamped so bucket padding can
    # never write past the row (clamped garbage lands at C-1, which is
    # never readable: attention masks at seq_len, and a slot finishes
    # before its seq_len reaches C)
    write_pos = jnp.minimum(starts[:, None] + ar[None, :], C - 1)  # [B, T]

    # keys = [prefix rows (read-only, positions < start) ++ own suffix];
    # the cache's stale suffix region is masked via key position -1
    cache_pos = jnp.where(
        jnp.arange(C)[None, :] < starts[:, None], jnp.arange(C)[None, :], -1
    )  # [B, C]
    key_pos = jnp.concatenate([cache_pos, positions], axis=1)  # [B, C+T]

    def body(carry, scanned):
        x = carry
        layer, k_kv, v_kv = scanned  # read-only (value + optional scales)

        def attn(q, k, v):
            k_full = jnp.concatenate(
                [_kv_rows(k_kv, slots, k.dtype), k], axis=1
            )
            v_full = jnp.concatenate(
                [_kv_rows(v_kv, slots, v.dtype), v], axis=1
            )
            out = continue_attention(
                q, k_full, v_full, positions, key_pos,
                softcap=c.attn_logit_softcap,
            )
            attn.new_kv = (k, v)
            return out

        out, _, _ = attn_mlp(x, layer, c, positions, attn)
        return out, attn.new_kv

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], *_kv_scan_xs(cache))
    )
    # one scatter commits the suffix K/V for every layer
    cache = _kv_commit(
        cache, new_k, new_v,
        lambda arr, val: arr.at[:, slots[:, None], write_pos].set(val),
    )
    x = _final_norm(x, params, c)
    return cache, x


def prefill_continue(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [B, T] int32 — SUFFIX tokens (rows padded)
    lengths: jax.Array,  # [B] int32 — true suffix lengths
    starts: jax.Array,  # [B] int32 — absolute position of each suffix start
    slots: jax.Array,  # [B] int32
    config: LlamaConfig,
) -> tuple[dict, jax.Array]:
    """Prefix-cache continuation: the first ``starts[b]`` positions of each
    slot's KV rows were already populated (copied from the prefix cache);
    run only the suffix through the model, attending over prefix + suffix.
    Costs O(suffix) model FLOPs instead of O(full prompt) — the win that
    makes multi-turn agent conversations cheap (each turn's prompt extends
    the previous one). Returns (cache, last-token logits [B, V])."""
    cache, x = _continue_forward(params, cache, tokens, lengths, starts, slots, config)
    logits = head_logits(x, params, config, last=lengths)
    return cache, logits


def prefill_continue_kv(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [B, T] int32 — chunk tokens (rows padded)
    lengths: jax.Array,  # [B] int32 — true chunk lengths (0 = padding lane)
    starts: jax.Array,  # [B] int32 — absolute chunk start per row
    slots: jax.Array,  # [B] int32
    config: LlamaConfig,
) -> dict:
    """KV-only continuation (the fused megastep's mid-chunk phase): the
    exact cache writes of :func:`prefill_continue` with the lm_head
    projection dropped — non-final chunks never sample, so the split
    path's discarded logits were pure waste. A padding lane (length 0,
    start = max_ctx) clamps its garbage write to the never-readable last
    row (see ``_continue_forward``'s write clamp)."""
    cache, _x = _continue_forward(
        params, cache, tokens, lengths, starts, slots, config
    )
    return cache


def verify_continue(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [B, T] int32 — last sampled token + draft (rows padded)
    lengths: jax.Array,  # [B] int32 — 1 + draft length per row
    starts: jax.Array,  # [B] int32 — seq_len per row (first unwritten KV position)
    config: LlamaConfig,
) -> tuple[dict, jax.Array]:
    """Speculative-decode verify pass (slot layout): score EVERY draft
    position in one dispatch. Row ``b`` IS decode lane/slot ``b`` (the spec
    path always dispatches the compacted width, so no slot indirection is
    needed). Same attention/KV-write semantics as :func:`prefill_continue`;
    the only difference is the head: logits at ALL positions [B, T, V], so
    ``logits[b, i]`` scores the token following ``tokens[b, i]`` — exactly
    what :func:`agentcontrolplane_tpu.ops.sampling.speculative_accept`
    consumes. KV for the whole row is written optimistically; a rejected
    tail needs no rollback because the engine only advances ``seq_len`` over
    the accepted prefix and attention never reads beyond it."""
    B = tokens.shape[0]
    cache, x = _continue_forward(
        params, cache, tokens, lengths, starts, jnp.arange(B), config
    )
    return cache, head_logits(x, params, config)


# ---------------------------------------------------------------------------
# Serving: paged KV cache (page tables; ops.paged + ops.pallas)
# ---------------------------------------------------------------------------


def init_paged_cache(
    config: LlamaConfig, num_pages: int, page_size: int, quantize_kv: bool = False
) -> dict:
    """``{"k", "v": [L, num_pages, P, H_kv * d]}`` (+ int8 scale twins
    ``[L, num_pages, P, H_kv]``): the layout the page walk reads, shared by
    every family (``ops/paged.py``)."""
    return init_kv_pages(
        config.n_layers, num_pages, page_size, config.n_kv_heads, config.head_dim,
        config.dtype, quantize=quantize_kv,
    )


def prefill_paged_batch(
    params: dict,
    pages: dict,  # {"k": [L, num_pages, P, H_kv * d], "v": ...}
    tokens: jax.Array,  # [B, T] int32 (rows padded to a multiple of page_size)
    lengths: jax.Array,  # [B] int32
    page_ids: jax.Array,  # [B, T // P] int32 (TRASH_PAGE beyond each prompt)
    config: LlamaConfig,
) -> tuple[dict, jax.Array]:
    """B prompts forward in one dispatch, each writing K/V into its own
    pages. Rows' trash-page writes may collide — unordered garbage into the
    never-read page 0."""
    c = config
    T = tokens.shape[1]
    ar = jnp.arange(T)
    positions = jnp.where(ar[None, :] < lengths[:, None], ar[None, :], -1)
    x = embed(params, tokens, c)

    def body(carry, layer):
        x = carry
        out, k, v = attn_mlp(
            x, layer, c, positions,
            lambda q, k, v: blocked_causal_attention(
                q, k, v, positions, softcap=c.attn_logit_softcap
            ),
        )
        return out, (k, v)

    # pages stay out of the scan (prompt attention never reads them); one
    # scatter of whole pages commits all layers' blocks through the pool
    # flattened over its layers (ops/paged.py) — see prefill_batch/decode_step
    x, (new_k, new_v) = jax.lax.scan(body, x, params["layers"])
    pages = commit_whole_pages(pages, {"k": new_k, "v": new_v}, page_ids)
    x = _final_norm(x, params, c)
    logits = head_logits(x, params, c, last=lengths)
    return pages, logits


def prefill_paged(
    params: dict,
    pages: dict,
    tokens: jax.Array,  # [T] int32 (padded to a multiple of page_size)
    length: jax.Array,  # scalar int32
    page_ids: jax.Array,  # [T // P] int32 (TRASH_PAGE beyond the prompt)
    config: LlamaConfig,
) -> tuple[dict, jax.Array]:
    """Single-prompt paged prefill (B=1 view of :func:`prefill_paged_batch`)."""
    pages, logits = prefill_paged_batch(
        params, pages, tokens[None], length[None], page_ids[None], config
    )
    return pages, logits[0]


def _paged_continue_forward(
    params: dict,
    pages: dict,  # {"k": [L, num_pages, P, H_kv * d], "v": ...}
    tokens: jax.Array,  # [B, T] int32 — new tokens (rows padded)
    lengths: jax.Array,  # [B] int32 — true token counts
    starts: jax.Array,  # [B] int32 — absolute position of each row's first token
    block_tables: jax.Array,  # [B, max_pages] int32
    config: LlamaConfig,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Shared paged continuation body: run each row's new tokens through the
    model attending over its gathered prefix pages (positions < start) plus
    the new tokens themselves. Returns ``(new_k, new_v, x_normed)`` with
    ``new_k/new_v`` [L, B, T, H_kv, d] UNCOMMITTED — the callers commit
    differently: :func:`prefill_paged_continue` writes whole (page-aligned,
    fresh) pages, :func:`verify_paged_continue` scatters per token because a
    draft starts mid-page, inside a page holding live prefix KV."""
    c = config
    B, T = tokens.shape
    ar = jnp.arange(T)
    positions = jnp.where(ar[None, :] < lengths[:, None], starts[:, None] + ar[None, :], -1)
    x = embed(params, tokens, c)
    max_pages = block_tables.shape[1]

    NP, P = pages["k"].shape[1:3]
    # keys = [gathered prefix pages (positions < start) ++ own suffix]; the
    # suffix pages referenced by the block table are not yet written, so
    # their gathered rows are stale — masked via key position -1.
    # OFFSET-MAJOR row order: gathered pages are transposed to [P, M]
    # before the merge so the within-page axis — which carries the mesh's
    # 'sp' axis under context-parallel serving — stays OUTERMOST. Merging
    # with the sharded axis inner is not GSPMD-representable and would
    # all-gather the page pool; outermost, the merged ctx dim stays
    # contiguously sp-sharded (same shape as the slot path's sharded C).
    r_idx = jnp.arange(P * max_pages)
    row_pos = (r_idx % max_pages) * P + r_idx // max_pages  # abs ctx position
    cache_pos = jnp.where(
        row_pos[None, :] < starts[:, None], row_pos[None, :], -1
    )  # [B, P*M]
    key_pos = jnp.concatenate([cache_pos, positions], axis=1)

    def body(carry, scanned):
        x = carry
        layer, index = scanned
        # the pool stays out of the scan's xs (a slice of a stacked operand
        # is a copy of the layer's pool): the layer's pages are gathered from
        # the whole pool by tables offset by the layer, and only the
        # gathered rows have their heads split (and are dequantized)
        with scopes.layer("attn"), jax.named_scope("prefill_attention"):
            tables = layer_tables(block_tables, index, NP)

        def attn(q, k, v):
            k_gath = gather_pages(pages, "k", tables, k.dtype, c.n_kv_heads)  # [B, M, P, H, d]
            v_gath = gather_pages(pages, "v", tables, v.dtype, c.n_kv_heads)
            # transpose to the offset-major row order described above
            k_rows = jnp.swapaxes(k_gath, 1, 2).reshape(
                B, P * max_pages, *k_gath.shape[3:]
            )
            v_rows = jnp.swapaxes(v_gath, 1, 2).reshape(
                B, P * max_pages, *v_gath.shape[3:]
            )
            k_full = jnp.concatenate([k_rows, k], axis=1)
            v_full = jnp.concatenate([v_rows, v], axis=1)
            out = continue_attention(
                q, k_full, v_full, positions, key_pos,
                softcap=c.attn_logit_softcap,
            )
            attn.new_kv = (k, v)
            return out

        out, _, _ = attn_mlp(x, layer, c, positions, attn)
        return out, attn.new_kv

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(c.n_layers, dtype=jnp.int32))
    )
    x = _final_norm(x, params, c)
    return new_k, new_v, x


def prefill_paged_continue(
    params: dict,
    pages: dict,  # {"k": [L, num_pages, P, H_kv * d], "v": ...}
    tokens: jax.Array,  # [B, T] int32 — SUFFIX tokens (rows padded)
    lengths: jax.Array,  # [B] int32 — true suffix lengths
    starts: jax.Array,  # [B] int32 — absolute suffix start (page-aligned)
    page_ids: jax.Array,  # [B, T // P] int32 — the SUFFIX pages
    block_tables: jax.Array,  # [B, max_pages] int32 — prefix + suffix pages
    config: LlamaConfig,
) -> tuple[dict, jax.Array]:
    """Paged prefix-cache continuation: the prefix pages referenced by each
    row's block table are already populated (SHARED with the cache entry —
    never written here; starts are page-aligned so suffix writes only touch
    fresh pages). Runs the suffix through the model, attending over the
    gathered prefix+suffix pages. Returns (pages, last-token logits [B, V])."""
    new_k, new_v, x = _paged_continue_forward(
        params, pages, tokens, lengths, starts, block_tables, config
    )
    # one scatter commits the suffix blocks for every layer
    pages = commit_whole_pages(pages, {"k": new_k, "v": new_v}, page_ids)
    logits = head_logits(x, params, config, last=lengths)
    return pages, logits


def prefill_paged_continue_kv(
    params: dict,
    pages: dict,  # {"k": [L, num_pages, P, H_kv * d], "v": ...}
    tokens: jax.Array,  # [B, T] int32 — chunk tokens (rows padded)
    lengths: jax.Array,  # [B] int32 — true chunk lengths (0 = padding lane)
    starts: jax.Array,  # [B] int32 — absolute chunk start (page-aligned)
    page_ids: jax.Array,  # [B, T // P] int32 — the chunk's pages (TRASH pads)
    block_tables: jax.Array,  # [B, max_pages] int32
    config: LlamaConfig,
) -> dict:
    """Paged KV-only continuation (the fused megastep's mid-chunk phase):
    :func:`prefill_paged_continue`'s whole-page commit without the lm_head
    projection. Padding lanes route every page write to the trash page."""
    new_k, new_v, _x = _paged_continue_forward(
        params, pages, tokens, lengths, starts, block_tables, config
    )
    return commit_whole_pages(pages, {"k": new_k, "v": new_v}, page_ids)


def verify_paged_continue(
    params: dict,
    pages: dict,  # {"k": [L, num_pages, P, H_kv * d], "v": ...}
    tokens: jax.Array,  # [B, T] int32 — last sampled token + draft (rows padded)
    lengths: jax.Array,  # [B] int32 — 1 + draft length per row
    starts: jax.Array,  # [B] int32 — seq_len per row (NOT page-aligned)
    block_tables: jax.Array,  # [B, max_pages] int32
    config: LlamaConfig,
) -> tuple[dict, jax.Array]:
    """Speculative-decode verify pass (paged layout): score every draft
    position in one dispatch over the gathered block-table pages. Unlike
    :func:`prefill_paged_continue`, the rows start MID-PAGE (``starts`` is
    the slot's live seq_len), so the commit scatters per token via
    :func:`agentcontrolplane_tpu.ops.paged.token_write_targets` — a page-
    granular write would clobber the live prefix KV sharing the first page.
    Padded positions land on the trash page. Returns (pages, logits
    [B, T, V]); the rejected tail's KV needs no rollback (attention masks
    by seq_len, which the engine only advances over the accepted prefix)."""
    B, T = tokens.shape
    P = pages["k"].shape[2]
    new_k, new_v, x = _paged_continue_forward(
        params, pages, tokens, lengths, starts, block_tables, config
    )
    with scopes.layer("commit"):
        target, offset = token_write_targets(block_tables, starts, lengths, P, T)
        pages = commit_tokens(pages, {"k": new_k, "v": new_v}, target, offset)
    return pages, head_logits(x, params, config)


def decode_step_paged(
    params: dict,
    pages: dict,
    tokens: jax.Array,  # [S] int32
    seq_lens: jax.Array,  # [S] int32 (length before this token)
    block_tables: jax.Array,  # [S, max_pages] int32
    active: jax.Array,  # [S] bool
    config: LlamaConfig,
    use_pallas: bool = False,
    mesh=None,  # required for the pallas path when the mesh has tp > 1
) -> tuple[dict, jax.Array]:
    """One decode step for all slots against the paged cache.

    Same HBM discipline as :func:`decode_step`: the pages are READ-ONLY
    through the layer scan, the new token attends via a self term (folded
    outside the Pallas kernel from its unnormalized (acc, m, l) output), and
    one scatter after the scan commits every layer's new K/V to the pages.
    The pool is not among the scan's xs: the scan carries the layer's index,
    and every layer's walk is handed the WHOLE pool flattened over its
    layers with block tables offset by the layer, so a step moves no page
    it does not read (a slice of the stacked pool handed to an opaque kernel
    is a copy of the layer's pool; ops/paged.py)."""
    c = config
    S = tokens.shape[0]
    positions = seq_lens[:, None]
    x = embed(params, tokens[:, None], c)
    quantized = "ks" in pages
    NP, P = pages["k"].shape[1:3]
    k_flat, v_flat = flat_pages(pages["k"]), flat_pages(pages["v"])
    # int8 pages carry f32 scale twins; the Pallas path DMAs them with each
    # page fetch and applies them in VMEM (the same f32 math as the
    # reference up to rounding order; parity pinned at 1e-5). The kernel
    # reads them head-major and lane-padded: laid out once here for the
    # whole pool, not once a layer inside the scan (walk_scale_rows)
    scales = {}
    if quantized:
        scales = {"k_scales": flat_pages(pages["ks"]), "v_scales": flat_pages(pages["vs"])}
        if use_pallas:
            from ..ops.pallas.paged_attention import walk_scale_rows

            scales = {name: walk_scale_rows(a, mesh) for name, a in scales.items()}
            scales["scales_laid"] = True

    def body(carry, scanned):
        x = carry
        layer, index = scanned
        with scopes.layer("attn"), jax.named_scope("page_walk"):
            tables = layer_tables(block_tables, index, NP)

        def attn(q, k, v):
            args = (q[:, 0], k_flat, v_flat, tables, seq_lens, k[:, 0], v[:, 0])
            if use_pallas:
                # one chip runs the kernel as it is; tp>1 under shard_map
                # over each chip's heads, sp>1 through the cross-rank
                # (acc, m, l) flash merge
                from ..ops.pallas.paged_attention import (
                    paged_decode_attention_cache_plus_new_sharded,
                )

                out = paged_decode_attention_cache_plus_new_sharded(mesh, *args, **scales)
            else:
                # the XLA reference splits the heads on what it gathers
                out = paged_decode_attention_reference_cache_plus_new(*args, **scales)
            attn.new_kv = (k[:, 0], v[:, 0])
            return out[:, None]

        out, _, _ = attn_mlp(x, layer, c, positions, attn, walk="page_walk")
        return out, attn.new_kv

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(c.n_layers, dtype=jnp.int32))
    )
    # one scatter of token rows commits all layers: (l, page(slot),
    # offset(slot)); inactive slots land on the trash page
    with scopes.layer("commit"):
        target = block_tables[jnp.arange(S), seq_lens // P]
        target = jnp.where(active, target, TRASH_PAGE)
        pages = commit_tokens(pages, {"k": new_k, "v": new_v}, target, seq_lens % P)
    x = _final_norm(x[:, 0], params, c)
    logits = head_logits(x, params, c)
    return pages, logits


def decode_step(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [W] int32 — last sampled token per slot, W <= max_slots
    seq_lens: jax.Array,  # [W] int32 — current length per slot (before this token)
    config: LlamaConfig,
    active: Optional[jax.Array] = None,  # [W] bool; inactive lanes write to C-1
) -> tuple[dict, jax.Array]:
    """One decode step for slots 0..W-1 (the continuous-batching hot loop).
    W may be narrower than the cache's slot count — width bucketing: at low
    occupancy the engine dispatches a power-of-two W covering the active
    slots, so one live request doesn't pay max_slots of compute. Inactive
    slots inside W compute garbage that is never read; cache rows beyond W
    pass through untouched. Returns (cache, logits [W, V]).

    ``active`` masks the K/V WRITE for inactive lanes to the never-readable
    row C-1 (attention masks at seq_len, and a lane deactivates before its
    seq_len reaches C — the same clamp the verify dispatch uses for its
    absent lanes). Without it an inactive lane writes garbage at its stale
    uploaded ``seq_lens`` — harmless for a free lane (row 0, overwritten by
    the next prefill) but CORRUPTING for a mid-prefill slot below the
    dispatch width, whose chunk loop has already written real prompt KV at
    that position. The split dispatch path mostly dodged this by accident
    (chunking slots usually sit above the active width; finals re-upload
    lanes before the block); the fused megastep's decode phase runs on
    pre-final lanes and hit it deterministically. Paged decode always had
    the equivalent mask (inactive targets -> TRASH_PAGE).

    HBM discipline (measured on v5e through the hot loop): the cache rides
    the layer scan as READ-ONLY xs, the new token attends via an explicit
    self term (decode_attention_cache_plus_new), and all L layers' new K/V
    commit in ONE scatter after the scan. Writing inside the scan — whether
    as stacked ys or as a scatter on a carried cache — makes XLA's copy
    insertion duplicate the entire cache every step (44ms/step vs 13.5 for
    this form at bench-1b 64x512)."""
    c = config
    W = tokens.shape[0]
    positions = seq_lens[:, None]  # the new token's position, [W, 1]
    x = embed(params, tokens[:, None], c)  # [W, 1, D]

    def body(carry, scanned):
        x = carry
        layer, k_kv, v_kv = scanned  # cache rows: read-only (+ scales)

        def attn(q, k, v):
            out = decode_attention_cache_plus_new(
                q[:, 0],
                _kv_rows(k_kv, slice(0, W), k.dtype),
                _kv_rows(v_kv, slice(0, W), v.dtype),
                k[:, 0], v[:, 0], seq_lens,
                softcap=c.attn_logit_softcap,
            )
            attn.new_kv = (k[:, 0], v[:, 0])
            return out[:, None]

        out, _, _ = attn_mlp(x, layer, c, positions, attn, walk="decode_attention")
        return out, attn.new_kv

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], *_kv_scan_xs(cache))
    )
    # one scatter commits every layer's token: rows (l, s, seq_lens[s]);
    # inactive lanes clamp to the never-read last row
    slot_idx = jnp.arange(W)
    C = cache["k"].shape[2]
    write_rows = (
        jnp.where(active, seq_lens, C - 1) if active is not None else seq_lens
    )
    cache = _kv_commit(
        cache, new_k, new_v,
        lambda arr, val: arr.at[:, slot_idx, write_rows].set(val),
    )
    x = _final_norm(x[:, 0], params, c)  # [S, D]
    logits = head_logits(x, params, c)
    return cache, logits
