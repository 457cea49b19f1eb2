"""Nemotron-H model family (``model_type: nemotron_h``; Nemotron 3 Super):
blocks of ONE mixer each, Mamba-2, latent mixture-of-experts or attention.

Every block is ``x + Mixer(RMSNorm(x))``; there is no attention-plus-feed-
forward pair. The layer list is the published ``hybrid_override_pattern``, a
character a block (``M`` Mamba-2, ``E`` experts, ``*`` attention), and is not
periodic (``MEMEMEM*EME...``). A final RMSNorm, an untied head, no bias but
the conv's, no position encoding of any kind (the Mamba layers carry
position, as in ``models/jamba.py``). With ``D`` the hidden width:

- ``mamba`` (Mamba-2: ``H`` heads of ``P`` channels, ``G`` groups, state
  ``N``; ``d_inner = H P``): ``[z (d_inner), xBC (d_inner + 2 G N), dt (H)] =
  x W_in``; ``xBC' = silu(b + sum_j w[j] xBC_{t-3+j})`` (depthwise, causal,
  ``d_conv`` taps, with bias, over all of ``xBC``); ``xBC' -> x [H, P], B [G,
  N], C [G, N]``; ``dt = softplus(dt + dt_bias)`` [H]; ``A = -exp(A_log)``
  [H], ONE scalar a head; for head ``h`` of group ``g = h // (H / G)``:
  ``S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]`` (``S``
  is ``P x N``), ``y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]`` (``ops/pallas/
  ssd.py``); ``y = RMSNorm_groups(y * silu(z)) * w``, the norm over each of
  the ``G`` groups of ``d_inner / G`` channels, gate first; ``Mixer = y
  W_out``;
- ``attention``: grouped-query attention, causal, no rotary, no bias;
- ``moe`` (LatentMoE): router ``s = sigmoid(x W_r)`` over all ``E`` experts
  at FULL width in float32; the choice is the top ``k`` of ``s + bias``, the
  weights ``s`` at the chosen over their sum, times ``routed_scaling_factor``;
  ``u = x W_down`` (``D -> latent_dim``); expert ``e``: ``W2_e relu(W1_e
  u)^2``, two matrices and NOT gated; ``routed = (sum_chosen w_e expert_e(u))
  W_up`` (``latent_dim -> D``); one shared expert at full width, ``W2_s
  relu(W1_s x)^2``; ``Mixer = routed + shared``. Of the ``E`` experts the
  chip holds ``experts_held`` (``ops.moe.routed_experts``: what absent
  experts would add is left out, nothing stands in for the other chips).

Departures from the published model, each the configuration's to state: the
multi-token-prediction module (``num_nextn_predict_layers`` 1) is not served
(it is no part of the next-token pass, and a draft verified over recurrent
state needs a rollback the engine refuses: ``models/__init__.py``
``_stateful_refusals``); the order of ``z, xBC, dt`` in ``W_in``'s columns,
the latent projections without bias or norm and no ``time_step_limit`` are
assumed (the catalog gives none of them).

Precision: the residual stream and every matmul's inputs in the model's
dtype; float32 inside the recurrence (``dt``, the decays, ``S``, ``x``, ``B``,
``C``), in the conv's sum, the gated norm, RMSNorm, softmax, the router and
the matmuls' accumulators. ``A_log``, ``D`` and ``dt_bias`` are float32
leaves. The stored state is float32 (as vLLM's ``mamba_ssm_cache_dtype
float32``).

Served layout of the weights: ``mamba``, ``attn`` and ``moe``, each stacked
over its own layers in order, each with its block's norm ``ln``. ``conv_w`` is
``[taps, channels]`` (the published order is the transpose); the routed
experts ``w1 [layers, held, latent, F]`` and ``w2 [layers, held, F, latent]``
are closed over flattened to one leading axis and indexed by the grouped
matmul itself.

Layout for XLA: every program runs ``layer_types`` through
``lfm2.scan_layers``: a layer's kind is fixed when the program is traced,
every loop body has one kind (a scan over the three ``mamba, moe`` periods
the published list starts with, the rest written out), a kind's layer is
traced once, and no loop is handed a stack it does not use.

Serving state (paged layout only): the KV pool holds the attention layers
alone, ``[n_attention, pages, P, H_kv * d]`` (1 KB a token at 2 KV heads of
128 and one layer), and beside it ``cache["state"]`` (``slots`` is
``max_slots + 1``: the last row is where a dispatch's padding lanes write):

- ``ssm``   ``[n_mamba, slots, J, N, LW]`` float32: ``S`` of every slot and
  Mamba layer in the order the kernels read whole tiles in (``ops/pallas/
  ssd.py``: ``N`` on the sublanes, the channels of ``LW / P`` neighbouring
  heads on the lanes; 4 MiB a slot and layer at the published sizes). The
  decode update reads and writes lanes ``0..S-1`` of one layer in place;
- ``conv``  ``[n_mamba, slots, (d_conv - 1) * channels]`` in the model's
  dtype: the last ``d_conv - 1`` columns of ``xBC`` (before the conv),
  oldest first;
- ``snap``  ``{"ssm", "conv"}`` of the same shapes: a copy taken inside a
  prefill at the one page-aligned length the engine names (``snap_at``);
- ``counters`` ``[2, 4 + 1 + COUNTS_HEAD + held]`` uint32, wrapping: row 0
  decode steps, row 1 prefills; first the recurrence's (Mamba layers run,
  rows, real tokens, chunks), then the expert layers' (layers run, pairs
  routed, pairs held, experts read, pairs a held expert).

Every program takes ``lanes = (slots, snap_at)`` beside the page ids, as
``models/jamba.py``'s do. The stacked state is carried through the decode
step's loops and each Mamba layer updates its own row where it lies; it
never enters a conditional, whose branch that hands it through unchanged is
answered with a copy of the whole stack (PERF.md, PR 37).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..observability import scopes
from ..ops.attention import blocked_causal_attention, causal_attention, continue_attention_by_rows
from ..ops.moe import COUNTS_HEAD, routed_experts
from ..ops.norms import rms_norm
from ..ops.paged import TRASH_PAGE, commit_tokens, commit_whole_pages, init_kv_pages
from ..ops.pallas import ssd
from .experts import describe_moe
from .recurrent import (  # noqa: F401  the seam's three among them
    commit_state, conv_at, counters, install_state, saved_state, state_in,
)
from .stack import (
    embed, final_norm, head_logits, kv_pool, layer_row, mm_weight_dtype, page_walk, plain_attention_op, prefix_attention,
    rows_ctx, scan_layers,
)

N_SSM = 4  # mamba_layers, rows, tokens, chunks
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}  # a character of `hybrid_override_pattern` -> the kind of its block
STACK = {"mamba": "mamba", "attention": "attn", "moe": "moe"}  # a kind -> its stack of weights in the tree
SCOPE = {"mamba": "mixer", "attention": "attn", "moe": "ffn"}  # a kind -> the device scope its block is filed under
PUBLISHED = "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"


def pattern(text: str) -> tuple[str, ...]:
    """``hybrid_override_pattern`` as the kinds of its blocks."""
    bad = set(text) - set(KINDS)
    if bad:
        raise ValueError(f"unknown characters {sorted(bad)} in the layer pattern (M|E|*)")
    return tuple(KINDS[ch] for ch in text)


def relu2(v):
    return jnp.square(jnp.maximum(v, 0))


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    dim: int = 4096
    layer_types: tuple[str, ...] = pattern(PUBLISHED)
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    d_state: int = 128
    n_groups: int = 8
    d_conv: int = 4
    n_experts: int = 512  # the router's width
    experts_per_token: int = 22
    # global ids of the experts this chip holds, in the order of its weights' leading axis; None holds all
    experts_held: Optional[tuple[int, ...]] = None
    latent_dim: int = 1024
    expert_ffn_dim: int = 2688
    shared_ffn_dim: int = 5376
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    norm_eps: float = 1e-5
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

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    @property
    def n_attention(self) -> int:
        return self.count("attention")

    @property
    def n_mamba(self) -> int:
        return self.count("mamba")

    @property
    def n_moe(self) -> int:
        return self.count("moe")

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:  # x, B and C go through the conv together
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def held(self) -> tuple[int, ...]:
        return tuple(range(self.n_experts)) if self.experts_held is None else self.experts_held

    @property
    def state_shape(self) -> tuple[int, int, int]:
        """A slot's ``S`` of one layer in the stored order ``[J, N, LW]``."""
        hp = ssd.heads_per_tile(self.mamba_head_dim, self.mamba_heads // self.n_groups)
        return (self.mamba_heads // hp, self.d_state, hp * self.mamba_head_dim)

    @property
    def state_bytes_per_slot(self) -> int:
        conv = (self.d_conv - 1) * self.conv_channels * jnp.dtype(self.dtype).itemsize
        return self.n_mamba * (self.d_inner * self.d_state * 4 + conv)


PRESETS: dict[str, NemotronHConfig] = {
    # nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 whole: 120.67 B parameters, no single chip
    "nemotron-3-super-120b-a12b": NemotronHConfig(),
    # CPU tests: the published list's first 11 blocks, four heads a group, 4 of 16 experts held
    "nemotron-h-tiny": NemotronHConfig(
        vocab_size=256, dim=32, layer_types=pattern(PUBLISHED[:11]), n_heads=4, n_kv_heads=2, head_dim=16,
        mamba_heads=8, mamba_head_dim=8, d_state=16, n_groups=2, n_experts=16, experts_per_token=3,
        experts_held=(0, 1, 2, 3), latent_dim=16, expert_ffn_dim=24, shared_ffn_dim=48, max_seq_len=512,
        dtype=jnp.float32,
    ),
}


def plan(c: NemotronHConfig) -> dict:
    """The layers of each kind, in order; refuses a kind it does not know and
    a kind without layers (its stack would be empty)."""
    bad = set(c.layer_types) - set(STACK)
    if bad:
        raise ValueError(f"unknown layer types {sorted(bad)} (mamba|attention|moe)")
    if set(c.layer_types) != set(STACK):
        raise ValueError("the nemotron_h family mixes the three kinds; a kind without layers has an empty stack")
    return {kind: tuple(i for i, t in enumerate(c.layer_types) if t == kind) for kind in STACK}


def init_params(config: NemotronHConfig, key: jax.Array) -> dict:
    """Random init in the served layout (module text)."""
    c = config
    d, di, cd, hd = c.dim, c.d_inner, c.conv_channels, c.head_dim
    count = [0]

    def w(shape, scale, dtype=c.dtype):
        count[0] += 1
        return (jax.random.normal(jax.random.fold_in(key, count[0]), shape) * scale).astype(dtype)

    M, A, E, H, held = c.n_mamba, c.n_attention, c.n_moe, c.mamba_heads, len(c.held)
    return {
        "embed": w((c.vocab_size, d), d ** -0.5),
        "norm": jnp.ones((d,), c.dtype),
        "lm_head": w((d, c.vocab_size), d ** -0.5),
        "mamba": {
            "ln": jnp.ones((M, d), c.dtype), "in_proj": w((M, d, di + cd + H), d ** -0.5),
            "conv_w": w((M, c.d_conv, cd), c.d_conv ** -0.5), "conv_b": jnp.zeros((M, cd), c.dtype),
            "dt_bias": jnp.full((M, H), -3.0, jnp.float32),
            "A_log": jnp.broadcast_to(jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)), (M, H)),
            "D": jnp.ones((M, H), jnp.float32), "gate_norm": jnp.ones((M, di), c.dtype),
            "out_proj": w((M, di, d), di ** -0.5),
        },
        "attn": {
            "ln": jnp.ones((A, d), c.dtype), "wq": w((A, d, c.n_heads * hd), d ** -0.5),
            "wk": w((A, d, c.n_kv_heads * hd), d ** -0.5), "wv": w((A, d, c.n_kv_heads * hd), d ** -0.5),
            "wo": w((A, c.n_heads * hd, d), d ** -0.5),
        },
        "moe": {
            "ln": jnp.ones((E, d), c.dtype), "router": w((E, d, c.n_experts), d ** -0.5),
            "router_bias": jnp.zeros((E, c.n_experts), jnp.float32),
            "down": w((E, d, c.latent_dim), d ** -0.5), "up": w((E, c.latent_dim, d), c.latent_dim ** -0.5),
            "w1": w((E, held, c.latent_dim, c.expert_ffn_dim), c.latent_dim ** -0.5),
            "w2": w((E, held, c.expert_ffn_dim, c.latent_dim), c.expert_ffn_dim ** -0.5),
            "sw1": w((E, d, c.shared_ffn_dim), d ** -0.5), "sw2": w((E, c.shared_ffn_dim, d), c.shared_ffn_dim ** -0.5),
        },
    }


def _mamba_pre(h, w, c: NemotronHConfig, conv_in, valid):
    """h [B, T, d] normed input; conv_in [B, taps-1, channels] (``xBC`` before
    the row's first token). -> (x f32 [B, T, H, P], B f32 [B, T, G, N], C, z
    f32 [B, T, d_inner], dt f32 [B, T, H] (0 where not ``valid``), ``xBC``
    with its past [B, taps-1+T, channels] in the model's dtype)."""
    f32 = jnp.float32
    di, cd, gn = c.d_inner, c.conv_channels, c.n_groups * c.d_state
    B, T, _ = h.shape
    with jax.named_scope("mamba_in_proj"):
        zxbcdt = mm_weight_dtype(h, w["in_proj"], f32)
        # xBC in the model's dtype, the one the state keeps its columns in, so that a decode step that
        # reads three back convolves what the prefill convolved
        z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:di + cd].astype(h.dtype), zxbcdt[..., di + cd:]
    with jax.named_scope("mamba_conv"):
        ext = jnp.concatenate([conv_in.astype(h.dtype), xbc], axis=1)
        taps = w["conv_w"].astype(f32)  # [taps, channels]
        act = jax.nn.silu(sum(ext[:, j:j + T].astype(f32) * taps[j] for j in range(c.d_conv)) + w["conv_b"].astype(f32))
        x = act[..., :di].reshape(B, T, c.mamba_heads, c.mamba_head_dim)
        b = act[..., di:di + gn].reshape(B, T, c.n_groups, c.d_state)
        c_ = act[..., di + gn:].reshape(B, T, c.n_groups, c.d_state)
        dt = jnp.where(valid[..., None], jax.nn.softplus(dt + w["dt_bias"].astype(f32)), 0.0)
    return x, b, c_, z, dt, ext


def _mamba_post(y, x, z, w, c: NemotronHConfig, dtype):
    """y, x [B, T, H, P] float32; z [B, T, d_inner] -> the mixer's output."""
    with jax.named_scope("ssd_gate_norm"):
        y = ssd.gate_norm(y, x, z, w["D"], w["gate_norm"], c.n_groups, c.norm_eps)
    return _mamba_out(y.astype(dtype), w)


def _mamba_out(y, w):
    with jax.named_scope("mamba_out_proj"):
        return mm_weight_dtype(y, w["out_proj"])


def _latent_moe(h, w, stacks, layer_index, c: NemotronHConfig, valid, chosen=None):
    """The LatentMoE mixer of expert layer ``layer_index`` (traced) over ``h``
    [B, T, d] normed: ``w`` holds its router and projections, ``stacks`` every
    expert layer's held experts flattened to one leading axis, which the
    grouped matmul indexes from ``layer_index * held``. ``chosen`` [B, T, k]
    is a routing given and not made. -> (output [B, T, d], counters)."""
    B, T, D = h.shape
    k = c.experts_per_token
    x = h.reshape(B * T, D)
    with jax.named_scope("latent_down"):
        u = mm_weight_dtype(x, w["down"])
    y, counts = routed_experts(
        x, w["router"], stacks[0], None, stacks[1], k, held=c.held, score="sigmoid", bias=w["router_bias"],
        renormalize=c.norm_topk_prob, scale=c.routed_scaling_factor, valid=valid.reshape(B * T), act=relu2,
        expert_base=layer_index * len(c.held), chosen=None if chosen is None else chosen.reshape(B * T, k), u=u)
    with jax.named_scope("latent_up"):
        y = mm_weight_dtype(y, w["up"])
    with jax.named_scope("moe_shared"):
        y = y + mm_weight_dtype(relu2(mm_weight_dtype(x, w["sw1"])), w["sw2"])
    return y.reshape(B, T, D), jnp.concatenate([jnp.ones((1,), jnp.uint32), counts])


def _stacks(params):
    moe = params["moe"]
    return tuple(moe[name].reshape((-1,) + moe[name].shape[2:]) for name in ("w1", "w2"))


def _moe_counts(c: NemotronHConfig):
    return jnp.zeros((1 + COUNTS_HEAD + len(c.held),), jnp.uint32)


def _run_rows(params, c: NemotronHConfig, x, ctx, ssm_in, conv_in, make_attn, route=None):
    """The whole stack over rows of tokens (prefill, continuation, tests).
    ``ssm_in`` [n_mamba, B, J, N, LW] and ``conv_in`` [n_mamba, B, (taps-1) *
    channels] are each Mamba layer's state before the rows; ``make_attn(a)``
    gives attention layer ``a``'s (traced index) attention function;
    ``route`` [n_moe, B, T, k] is every expert layer's choice, given (an
    output check's). -> (x, ends {"ssm", "conv"} [n_mamba, B, ...], snaps the
    same, new k [n_attention, B, T, H_kv, d], new v, expert counters)."""
    plan(c)
    B, T, _ = x.shape
    dt = x.dtype
    n, cd = c.d_conv - 1, c.conv_channels
    n_chunks = -(-ctx["lengths"] // ssd.CHUNK)
    stacks = _stacks(params)

    def layer(kind, carry, index, at):
        x, counts = carry
        with scopes.layer(SCOPE[kind]):
            w = layer_row(params[STACK[kind]], at)
            h = rms_norm(x, w["ln"], c.norm_eps)
            if kind == "attention":
                op, k, v = plain_attention_op(h, w, c, make_attn(at))
                out = (k.astype(dt), v.astype(dt))
            elif kind == "mamba":
                xs, b, c_, z, delta, ext = _mamba_pre(h, w, c, conv_in[at].reshape(B, n, cd), ctx["valid"])
                with jax.named_scope("ssm_scan"):
                    a = -jnp.exp(w["A_log"].astype(jnp.float32))
                    y, h_end, h_snap = ssd.scan(delta, xs, b, c_, a, ssm_in[at], ctx["snap_rel"], n_chunks)
                op = _mamba_post(y, xs, z, w, c, dt)
                with jax.named_scope("mamba_conv"):
                    out = (h_end, h_snap, conv_at(ext, ctx["lengths"], n), conv_at(ext, ctx["snap_rel"], n))
            else:
                op, m = _latent_moe(h, w, stacks, at, c, ctx["valid"], None if route is None else route[at])
                counts, out = counts + m, ()
            return (x + op, counts), out

    (x, counts), outs = scan_layers(c.layer_types, (x, _moe_counts(c)), layer)
    h_end, h_snap, c_end, c_snap = outs["mamba"]
    return x, {"ssm": h_end, "conv": c_end}, {"ssm": h_snap, "conv": c_snap}, *outs["attention"], counts


def _zero_state(c: NemotronHConfig, B: int):
    return (jnp.zeros((c.n_mamba, B) + c.state_shape, jnp.float32),
            jnp.zeros((c.n_mamba, B, (c.d_conv - 1) * c.conv_channels), c.dtype))


def forward(params: dict, tokens: jax.Array, config: NemotronHConfig) -> jax.Array:
    """Full-sequence causal forward -> logits [B, T, V] float32 (tests)."""
    c = config
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    ctx = {"positions": positions, "valid": jnp.ones((B, T), bool),
           "lengths": jnp.full((B,), T, jnp.int32), "snap_rel": jnp.full((B,), -1, jnp.int32)}
    x, *_ = _run_rows(params, c, embed(params, tokens, c), ctx, *_zero_state(c, B),
                      lambda a: lambda q, k, v: causal_attention(q, k, v, positions))
    return head_logits(final_norm(x, params, c), params, c)


# ---------------------------------------------------------------------------
# Serving: pages for the attention layers, state beside them
# ---------------------------------------------------------------------------


def init_paged_cache(config: NemotronHConfig, num_pages: int, page_size: int, quantize_kv: bool = False,
                     max_slots: int = 1) -> dict:
    c = config
    cache = init_kv_pages(c.n_attention, num_pages, page_size, c.n_kv_heads, c.head_dim, c.dtype,
                          quantize=quantize_kv)
    slots = max_slots + 1  # the last row takes the padding lanes' writes
    pair = lambda: {  # noqa: E731
        "ssm": jnp.zeros((c.n_mamba, slots) + c.state_shape, jnp.float32),
        "conv": jnp.zeros((c.n_mamba, slots, (c.d_conv - 1) * c.conv_channels), c.dtype),
    }
    width = N_SSM + 1 + COUNTS_HEAD + len(c.held)
    cache["state"] = {**pair(), "snap": pair(), "counters": jnp.zeros((2, width), jnp.uint32)}
    return cache


def _counts(c: NemotronHConfig, rows, tokens, chunks, moe):
    ssm_part = jnp.stack([jnp.uint32(c.n_mamba), *(c.n_mamba * jnp.sum(v).astype(jnp.uint32)
                                                   for v in (rows, tokens, chunks))])
    return jnp.concatenate([ssm_part, moe])


def _prefill_counts(c, lengths, moe):
    with scopes.layer("commit"):
        return _counts(c, lengths > 0, lengths, -(-lengths // ssd.CHUNK), moe)


def prefill_paged_batch(params, cache, tokens, lengths, page_ids, lanes, config: NemotronHConfig, route=None):
    """B whole prompts in one dispatch: K/V into each row's pages, the
    Mamba layers' state at the prompt's end into its slot. -> (cache,
    logits [B, V])."""
    c = config
    slots, snap_at = lanes
    B, T = tokens.shape
    ctx, snap_ok = rows_ctx(lengths, jnp.zeros((B,), jnp.int32), snap_at, T)
    positions = ctx["positions"]
    x, ends, snaps, new_k, new_v, moe = _run_rows(
        params, c, embed(params, tokens, c), ctx, *_zero_state(c, B),
        lambda a: lambda q, k, v: blocked_causal_attention(q, k, v, positions), route)
    pages = commit_whole_pages(kv_pool(cache), {"k": new_k, "v": new_v}, page_ids)
    cache = commit_state(cache, pages, slots, ends, snaps, snap_ok, _prefill_counts(c, lengths, moe))
    x = final_norm(x, params, c)
    return cache, head_logits(x, params, c, last=lengths)


def _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, lanes, c):
    """Rows that start at ``starts`` (page-aligned), attending over their
    gathered prefix pages plus themselves, Mamba layers carried on from the
    slots' state. -> (x normed, new k, new v uncommitted, ends, snaps,
    snap_ok, expert counters)."""
    slots, snap_at = lanes
    B, T = tokens.shape
    ctx, snap_ok = rows_ctx(lengths, starts, snap_at, T)
    positions = ctx["positions"]
    # dense over the keys, a block of query rows at a time: the scores of 2,048 rows of 32 heads against 6,144 keys
    # are 1.6 GB at once, beside 11.5 GB resident
    make_attn = prefix_attention(kv_pool(cache), block_tables, starts, positions, c.n_kv_heads, continue_attention_by_rows)
    x, ends, snaps, new_k, new_v, moe = _run_rows(
        params, c, embed(params, tokens, c), ctx, *state_in(cache, slots, starts), make_attn)
    return final_norm(x, params, c), new_k, new_v, ends, snaps, snap_ok, moe


def prefill_paged_continue(params, cache, tokens, lengths, starts, page_ids, block_tables, lanes,
                           config: NemotronHConfig):
    """Continuation (a prefix hit's suffix, a later chunk of a long
    prompt): -> (cache, last-token logits [B, V])."""
    x, new_k, new_v, ends, snaps, snap_ok, moe = _paged_continue_forward(
        params, cache, tokens, lengths, starts, block_tables, lanes, config)
    pages = commit_whole_pages(kv_pool(cache), {"k": new_k, "v": new_v}, page_ids)
    cache = commit_state(cache, pages, lanes[0], ends, snaps, snap_ok, _prefill_counts(config, lengths, moe))
    return cache, head_logits(x, params, config, last=lengths)


def prefill_paged_continue_kv(params, cache, tokens, lengths, starts, page_ids, block_tables, lanes,
                              config: NemotronHConfig):
    """The continuation's writes without the head (a mid chunk)."""
    _x, new_k, new_v, ends, snaps, snap_ok, moe = _paged_continue_forward(
        params, cache, tokens, lengths, starts, block_tables, lanes, config)
    pages = commit_whole_pages(kv_pool(cache), {"k": new_k, "v": new_v}, page_ids)
    return commit_state(cache, pages, lanes[0], ends, snaps, snap_ok, _prefill_counts(config, lengths, moe))


def decode_step_paged(params, cache, tokens, seq_lens, block_tables, active, config: NemotronHConfig,
                      use_pallas: bool = False, mesh=None, route=None):
    """One token for lanes 0..S-1 (lane b is slot b): the attention layer
    walks the pages; Mamba layers shift their slot's conv columns and take
    one step of the recurrence on ``state["ssm"][layer, :S]`` in place (one
    kernel from the conv's rows to the gated, normed row the output
    projection reads: ``ssd.update``), the whole stack carried through the
    layer loops and never copied; expert
    layers route the live lanes. An inactive lane's state and pages are left
    as they were (its ``dt`` is 0) and it routes nowhere. ``route`` [n_moe, S,
    1, k]: the experts' choice given (an output check's)."""
    c = config
    S = tokens.shape[0]
    pool = kv_pool(cache)
    P = pool["k"].shape[2]
    make_attn = page_walk(pool, block_tables, seq_lens, use_pallas)
    dt = c.dtype
    n, cd = c.d_conv - 1, c.conv_channels
    stacks = _stacks(params)

    def layer(kind, carry, index, at):
        x, h_all, conv_all, counts = carry
        with scopes.layer(SCOPE[kind]):
            w = layer_row(params[STACK[kind]], at)
            h = rms_norm(x, w["ln"], c.norm_eps)
            out = ()
            if kind == "attention":
                op, k, v = plain_attention_op(h, w, c, make_attn(at), walk="page_walk")
                out = (k[:, 0].astype(dt), v[:, 0].astype(dt))
            elif kind == "mamba":
                with jax.named_scope("mamba_conv"):
                    old = jax.lax.dynamic_slice(conv_all, (at, 0, 0), (1, S, n * cd))[0]
                xs, b, c_, z, delta, ext = _mamba_pre(h, w, c, old.reshape(S, n, cd), active[:, None])
                with jax.named_scope("mamba_conv"):
                    new = jnp.where(active[:, None], ext[:, 1:].reshape(S, n * cd), old)
                    conv_all = jax.lax.dynamic_update_slice(conv_all, new[None], (at, 0, 0))
                with jax.named_scope("ssm_update"):
                    a = -jnp.exp(w["A_log"].astype(jnp.float32))
                    # the row comes back gated and normed: the skip, the gate and the grouped norm are the kernel's
                    y, h_all = ssd.update(h_all, at, delta[:, 0], xs[:, 0], b[:, 0], c_[:, 0], a, z=z[:, 0], d=w["D"],
                                          norm=w["gate_norm"], eps=c.norm_eps, dtype=dt)
                op = _mamba_out(y[:, None], w)
            else:
                op, m = _latent_moe(h, w, stacks, at, c, active[:, None], None if route is None else route[at])
                counts = counts + m
            return (x + op, h_all, conv_all, counts), out

    st = cache["state"]
    plan(c)  # refuses a kind it does not know or a kind without layers
    (x, h_all, conv_all, moe), outs = scan_layers(
        c.layer_types, (embed(params, tokens[:, None], c), st["ssm"], st["conv"], _moe_counts(c)), layer)
    with scopes.layer("commit"):
        target = jnp.where(active, block_tables[jnp.arange(S), seq_lens // P], TRASH_PAGE)
        pages = commit_tokens(pool, dict(zip(("k", "v"), outs["attention"])), target, seq_lens % P)
        counts = _counts(c, active, active, active, moe)
        state = {"ssm": h_all, "conv": conv_all, "snap": st["snap"], "counters": st["counters"].at[0].add(counts)}
    x = final_norm(x[:, 0], params, c)
    return {**pages, "state": state}, head_logits(x, params, c)


def describe_counters(config: NemotronHConfig, total) -> dict:
    """``Engine.stats()["ssm"]`` and ``["moe"]`` from the counters summed by
    the engine (``total`` [2, 4 + 1 + COUNTS_HEAD + held], None before the
    first dispatch), decode steps and prefills apart. ``ssm``: Mamba layers
    run, rows (lanes updated, or rows scanned), real tokens and chunks of
    the scan, each summed over the Mamba layers, and the bytes of state a
    slot holds. ``moe``: expert layers run, (token, choice) pairs routed,
    pairs that landed on held experts, held experts read, the pairs each
    held expert took (``experts.describe_moe``)."""
    held = len(config.held)
    if total is None:
        total = [[0] * (N_SSM + 1 + COUNTS_HEAD + held)] * 2

    def ssm_row(r):
        return {"mamba_layers": int(r[0]), "rows": int(r[1]), "tokens": int(r[2]), "chunks": int(r[3])}

    return {
        "ssm": {"state_bytes_per_slot": config.state_bytes_per_slot, "decode": ssm_row(total[0]),
                "prefill": ssm_row(total[1])},
        **describe_moe(config, [r[N_SSM:] for r in total]),
    }
