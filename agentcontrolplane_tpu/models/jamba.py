"""Jamba model family (``model_type: jamba``, dense: ``num_experts`` 1):
Mamba-1 layers with a few attention layers among them.

Every layer is ``h = x + Mixer(RMSNorm(x))``, ``y = h + SwiGLU(RMSNorm(h))``
with ``Mixer`` one of

- ``mamba``: ``[u, z] = x W_in``; ``u'_t = silu(b + sum_j w[j] u_{t-3+j})``
  (depthwise, causal, ``d_conv`` taps); ``[dt, B, C] = u' W_x``, each through
  its own RMSNorm with a weight; ``delta = softplus(dt W_dt + b_dt)``;
  ``A = -exp(A_log)``; the recurrence ``h_t = exp(delta_t A) h_{t-1} +
  delta_t B_t u'_t``, ``y_t = sum_n h_t C_t + D u'_t`` (``ops/pallas/
  ssm_scan.py``); ``Mixer = (y * silu(z)) W_out``;
- ``attention``: grouped-query attention without bias and without any
  position encoding (the Mamba layers carry position).

Precision: the residual stream and every matmul's inputs in the model's
dtype; float32 inside the recurrence (``delta``, ``exp(delta A)``, ``h``,
the sum over n), in RMSNorm, softmax and the matmuls' accumulators.
``A_log``, ``D`` and ``dt_bias`` are float32 leaves, as published Mamba
keeps them.

Served layout of the weights: ``mamba``, ``attn`` (each stacked over its own
layers in order) and ``ff`` (norm and SwiGLU over all layers). ``conv_w`` is
``[taps, d_inner]`` and ``A_log`` ``[d_state, d_inner]``, the channels on the
lanes (the published order is the transpose of both).

Layout for XLA, as ``models/lfm2.py``'s and for its reasons: the decode step
runs ``layer_types`` through ``scan_layers`` (the published pattern: a scan
over two periods of ``mamba x 7, attention, mamba x 6``, each run of Mamba
layers an inner scan), so a layer's kind is fixed when the program is traced,
every loop body has one kind and no loop is handed a stack it does not use;
rows of tokens (prefill, continuation, ``forward``) run ONE ``lax.scan`` over
all layers whose body switches on the layer's kind, each kind reading its own
row of its own stack (a second Mamba body is a second trace of the
``ssm_scan`` kernel in each of 15 prefill programs: PERF.md, PR 41).

Serving state (paged layout only): the KV pool holds the attention layers
alone, ``[n_attention, pages, P, H_kv * d]``, and beside it
``cache["state"]``, a tree (``slots`` is ``max_slots + 1``: the last row is
where a dispatch's padding lanes write):

- ``ssm``   ``[n_mamba, slots, d_state, d_inner]`` float32: ``h`` of every
  slot and Mamba layer. States on the sublanes and channels on the lanes: a
  slot's row of a layer is whole float32 tiles, contiguous, and the decode
  update reads and writes lanes ``0..S-1`` of one layer in place;
- ``conv``  ``[n_mamba, slots, (d_conv - 1) * d_inner]`` in the model's
  dtype: the last ``d_conv - 1`` columns of ``u`` (before the conv), oldest
  first, flattened into the row so that three columns are not padded to a
  sublane tile of sixteen;
- ``snap``  ``{"ssm", "conv"}`` of the same shapes: a copy taken inside a
  prefill at the one page-aligned length the engine names (``snap_at``);
- ``counters`` ``[2, 4]`` uint32, wrapping: row 0 decode steps, row 1
  prefills; Mamba layers run, rows (lanes updated or rows scanned), real
  tokens, chunks of the scan.

Every program takes ``lanes = (slots, snap_at)`` beside the page ids, as
``models/lfm2.py``'s do. A row that starts at 0 starts from a zero state; one
that starts later reads its slot's, which the engine has set (the previous
chunk left it, or ``install_state`` copied it in).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import scopes
from ..ops.attention import blocked_causal_attention, causal_attention
from ..ops.norms import rms_norm
from ..ops.paged import TRASH_PAGE, commit_tokens, commit_whole_pages, init_kv_pages
from ..ops.pallas import ssm_scan as ssm
from .recurrent import (  # noqa: F401  the seam's three among them
    commit_state, conv_at, counters, install_state, saved_state, state_in,
)
from .stack import (
    embed, final_norm, head_logits, kv_pool, layer_row, mm_weight_dtype, page_walk, plain_attention_op, prefix_attention,
    rows_ctx, scan_layers,
)

N_COUNTERS = 4  # mamba_layers, rows, tokens, chunks
STACK = {"mamba": "mamba", "attention": "attn"}  # a kind of layer -> its stack of weights in the tree


def _pattern(n_layers: int, period: int, offset: int) -> tuple[str, ...]:
    """The ``jamba`` convention: layer i is attention where ``i % period ==
    offset``, Mamba otherwise."""
    return tuple("attention" if i % period == offset else "mamba" for i in range(n_layers))


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    dim: int = 2560
    n_heads: int = 20
    n_kv_heads: int = 1
    head_dim: int = 128
    layer_types: tuple[str, ...] = _pattern(28, 14, 7)
    ffn_dim: int = 8192
    d_inner: int = 5120  # mamba_expand * dim
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    conv_bias: bool = True
    norm_eps: float = 1e-6
    max_seq_len: int = 262144
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
    def n_mamba(self) -> int:
        return sum(t == "mamba" for t in self.layer_types)

    @property
    def state_bytes_per_slot(self) -> int:
        conv = (self.d_conv - 1) * self.d_inner * jnp.dtype(self.dtype).itemsize
        return self.n_mamba * (self.d_state * self.d_inner * 4 + conv)


PRESETS: dict[str, JambaConfig] = {
    # ai21labs/AI21-Jamba2-3B whole: 3.03B parameters, 6.06 GB of bfloat16
    "jamba2-3b": JambaConfig(),
    # CPU tests: four layers, one of them attention
    "jamba-tiny": JambaConfig(
        vocab_size=256, dim=32, n_heads=4, n_kv_heads=1, head_dim=16, layer_types=_pattern(4, 4, 1),
        ffn_dim=64, d_inner=64, d_state=16, dt_rank=8, max_seq_len=512, dtype=jnp.float32,
    ),
}


def plan(c: JambaConfig) -> dict:
    """Which layers are attention, and each layer's row in its kind's stack."""
    bad = set(c.layer_types) - {"mamba", "attention"}
    if bad:
        raise ValueError(f"unknown layer types {sorted(bad)} (mamba|attention)")
    if len(set(c.layer_types)) < 2:
        raise ValueError("the jamba family mixes both kinds; a stack of one kind has an empty stack for the other")
    is_attn = np.array([k == "attention" for k in c.layer_types], dtype=bool)
    return {
        "is_attn": is_attn,
        "attn_row": np.where(is_attn, np.cumsum(is_attn) - 1, 0).astype(np.int32),
        "mamba_row": np.where(~is_attn, np.cumsum(~is_attn) - 1, 0).astype(np.int32),
    }


def init_params(config: JambaConfig, key: jax.Array) -> dict:
    """Random init in the served layout (module text)."""
    c = config
    d, di, n, r, hd = c.dim, c.d_inner, c.d_state, c.dt_rank, c.head_dim
    count = [0]

    def w(shape, scale, dtype=c.dtype):
        count[0] += 1
        return (jax.random.normal(jax.random.fold_in(key, count[0]), shape) * scale).astype(dtype)

    L, M, A = c.n_layers, c.n_mamba, c.n_attention
    params = {
        "embed": w((c.vocab_size, d), d ** -0.5),
        "norm": jnp.ones((d,), c.dtype),
        "mamba": {
            "ln1": jnp.ones((M, d), c.dtype), "in_proj": w((M, d, 2 * di), d ** -0.5),
            "conv_w": w((M, c.d_conv, di), c.d_conv ** -0.5), "conv_b": jnp.zeros((M, di), c.dtype),
            "x_proj": w((M, di, r + 2 * n), di ** -0.5),
            "dt_norm": jnp.ones((M, r), c.dtype), "b_norm": jnp.ones((M, n), c.dtype),
            "c_norm": jnp.ones((M, n), c.dtype),
            "dt_proj": w((M, r, di), r ** -0.5), "dt_bias": jnp.full((M, di), -4.0, jnp.float32),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[None, :, None], (M, n, di)),
            "D": jnp.ones((M, di), jnp.float32), "out_proj": w((M, di, d), di ** -0.5),
        },
        "attn": {
            "ln1": jnp.ones((A, d), c.dtype), "wq": w((A, d, c.n_heads * hd), d ** -0.5),
            "wk": w((A, d, c.n_kv_heads * hd), d ** -0.5), "wv": w((A, d, c.n_kv_heads * hd), d ** -0.5),
            "wo": w((A, c.n_heads * hd, d), d ** -0.5),
        },
        "ff": {"ln2": jnp.ones((L, d), c.dtype), "w1": w((L, d, c.ffn_dim), d ** -0.5),
               "w3": w((L, d, c.ffn_dim), d ** -0.5), "w2": w((L, c.ffn_dim, d), c.ffn_dim ** -0.5)},
    }
    if not c.tie_embeddings:
        params["lm_head"] = w((d, c.vocab_size), d ** -0.5)
    return params


def _mamba_pre(h, layer, c: JambaConfig, conv_in, valid):
    """h [B, T, d] normed input; conv_in [B, taps-1, d_inner] (u before the
    row's first token). -> (u' f32 [B, T, d_inner], z f32, delta f32 (0 where
    not ``valid``), B f32 [B, T, N], C f32, u with its past [B, taps-1+T,
    d_inner] in the model's dtype)."""
    f32 = jnp.float32
    di, n, r = c.d_inner, c.d_state, c.dt_rank
    T = h.shape[1]
    with jax.named_scope("mamba_in_proj"):
        xz = mm_weight_dtype(h, layer["in_proj"], f32)
        # u in the model's dtype, the one the state keeps its columns in, so
        # that a decode step that reads three back convolves what the
        # prefill convolved
        u, z = xz[..., :di].astype(h.dtype), xz[..., di:]
    with jax.named_scope("mamba_conv"):
        u_ext = jnp.concatenate([conv_in.astype(h.dtype), u], axis=1)
        taps = layer["conv_w"].astype(f32)  # [taps, d_inner]
        conv = sum(u_ext[:, j:j + T].astype(f32) * taps[j] for j in range(c.d_conv))
        if c.conv_bias:
            conv = conv + layer["conv_b"].astype(f32)
        u_act = jax.nn.silu(conv)
    with jax.named_scope("mamba_x_proj"):
        dbc = mm_weight_dtype(u_act.astype(h.dtype), layer["x_proj"], f32)
        dt = rms_norm(dbc[..., :r], layer["dt_norm"], c.norm_eps)
        b = rms_norm(dbc[..., r:r + n], layer["b_norm"], c.norm_eps)
        c_ = rms_norm(dbc[..., r + n:], layer["c_norm"], c.norm_eps)
        delta = jax.nn.softplus(mm_weight_dtype(dt.astype(h.dtype), layer["dt_proj"], f32) + layer["dt_bias"].astype(f32))
        delta = jnp.where(valid[..., None], delta, 0.0)
    return u_act, z, delta, b, c_, u_ext


def _mamba_post(y, u_act, z, layer, dtype):
    with jax.named_scope("mamba_out_proj"):
        y = (y + layer["D"].astype(jnp.float32) * u_act) * jax.nn.silu(z)
        return mm_weight_dtype(y.astype(dtype), layer["out_proj"])


def _swiglu(x, ff, c: JambaConfig):
    """``x`` with the dense feed-forward's residual added."""
    with scopes.layer("ffn"), jax.named_scope("ffn_dense"):
        h = rms_norm(x, ff["ln2"], c.norm_eps)
        return x + mm_weight_dtype(jax.nn.silu(mm_weight_dtype(h, ff["w1"])) * mm_weight_dtype(h, ff["w3"]), ff["w2"])


def _scanned(params, c: JambaConfig):
    pl_ = plan(c)
    return (params["ff"], jnp.asarray(pl_["is_attn"]), jnp.asarray(pl_["attn_row"]), jnp.asarray(pl_["mamba_row"]))


def _run_rows(params, c: JambaConfig, x, ctx, ssm_in, conv_in, make_attn):
    """The whole stack over rows of tokens (prefill, continuation, tests).
    ``ssm_in`` [n_mamba, B, N, d_inner] and ``conv_in`` [n_mamba, B,
    (taps-1) * d_inner] are each Mamba layer's state before the rows;
    ``make_attn(a)`` gives attention layer ``a``'s (traced index) attention
    function. -> (x, ends {"ssm", "conv"} [n_mamba, B, ...], snaps the same,
    new k [n_attention, B, T, H_kv, d], new v)."""
    pl_ = plan(c)
    B, T, _ = x.shape
    dt, f32 = x.dtype, jnp.float32
    n = c.d_conv - 1
    kv_shape = (B, T, c.n_kv_heads, c.head_dim)
    h_shape, cv_shape = (B, c.d_state, c.d_inner), (B, n * c.d_inner)
    n_chunks = -(-ctx["lengths"] // ssm.CHUNK)

    def attention(x, a_row, m_row):
        with scopes.layer("attn"):
            layer = layer_row(params["attn"], a_row)
            op, k, v = plain_attention_op(rms_norm(x, layer["ln1"], c.norm_eps), layer, c, make_attn(a_row))
            zh, zc = jnp.zeros(h_shape, f32), jnp.zeros(cv_shape, dt)
            return op, zh, zh, zc, zc, k.astype(dt), v.astype(dt)

    def mamba(x, a_row, m_row):
        with scopes.layer("mixer"):
            layer = layer_row(params["mamba"], m_row)
            u_act, z, delta, b, c_, u_ext = _mamba_pre(
                rms_norm(x, layer["ln1"], c.norm_eps), layer, c, conv_in[m_row].reshape(B, n, c.d_inner),
                ctx["valid"])
            with jax.named_scope("ssm_scan"):
                a = -jnp.exp(layer["A_log"].astype(f32))
                y, h_end, h_snap = ssm.scan(delta, u_act, b, c_, a, ssm_in[m_row], ctx["snap_rel"], n_chunks)
            op = _mamba_post(y, u_act, z, layer, dt)
            zero = jnp.zeros(kv_shape, dt)
            with jax.named_scope("mamba_conv"):
                ends = conv_at(u_ext, ctx["lengths"], n), conv_at(u_ext, ctx["snap_rel"], n)
            return op, h_end, h_snap, *ends, zero, zero

    def body(x, scanned):
        ff, is_attn, a_row, m_row = scanned
        out = jax.lax.cond(is_attn, attention, mamba, x, a_row, m_row)
        with scopes.layer("ffn"):  # the kind is the chip's to know here: its residual is filed with the FF
            x = x + out[0]
        return _swiglu(x, ff, c), out[1:]

    x, (h_end, h_snap, c_end, c_snap, ks, vs) = jax.lax.scan(body, x, _scanned(params, c))
    attn_at, mamba_at = pl_["is_attn"].nonzero()[0], (~pl_["is_attn"]).nonzero()[0]
    with scopes.layer("commit"):  # each kind's rows out of what the one body stacked for both
        ends = {"ssm": h_end[mamba_at], "conv": c_end[mamba_at]}
        snaps = {"ssm": h_snap[mamba_at], "conv": c_snap[mamba_at]}
        return x, ends, snaps, ks[attn_at], vs[attn_at]


def _zero_state(c: JambaConfig, B: int):
    return (jnp.zeros((c.n_mamba, B, c.d_state, c.d_inner), jnp.float32),
            jnp.zeros((c.n_mamba, B, (c.d_conv - 1) * c.d_inner), c.dtype))


def forward(params: dict, tokens: jax.Array, config: JambaConfig) -> jax.Array:
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


def init_paged_cache(config: JambaConfig, num_pages: int, page_size: int, quantize_kv: bool = False,
                     max_slots: int = 1) -> dict:
    c = config
    cache = init_kv_pages(c.n_attention, num_pages, page_size, c.n_kv_heads, c.head_dim, c.dtype,
                          quantize=quantize_kv)
    slots = max_slots + 1  # the last row takes the padding lanes' writes
    pair = lambda: {  # noqa: E731
        "ssm": jnp.zeros((c.n_mamba, slots, c.d_state, c.d_inner), jnp.float32),
        "conv": jnp.zeros((c.n_mamba, slots, (c.d_conv - 1) * c.d_inner), c.dtype),
    }
    cache["state"] = {**pair(), "snap": pair(), "counters": jnp.zeros((2, N_COUNTERS), jnp.uint32)}
    return cache


def _counts(c: JambaConfig, rows, tokens, chunks):
    return jnp.stack([jnp.uint32(c.n_mamba), *(c.n_mamba * jnp.sum(x).astype(jnp.uint32)
                                                for x in (rows, tokens, chunks))])


def _prefill_counts(c, lengths):
    with scopes.layer("commit"):
        return _counts(c, lengths > 0, lengths, -(-lengths // ssm.CHUNK))


def prefill_paged_batch(params, cache, tokens, lengths, page_ids, lanes, config: JambaConfig):
    """B whole prompts in one dispatch: K/V into each row's pages, the
    Mamba layers' state at the prompt's end into its slot. -> (cache,
    logits [B, V])."""
    c = config
    slots, snap_at = lanes
    B, T = tokens.shape
    ctx, snap_ok = rows_ctx(lengths, jnp.zeros((B,), jnp.int32), snap_at, T)
    positions = ctx["positions"]
    x, ends, snaps, new_k, new_v = _run_rows(
        params, c, embed(params, tokens, c), ctx, *_zero_state(c, B),
        lambda a: lambda q, k, v: blocked_causal_attention(q, k, v, positions))
    pages = commit_whole_pages(kv_pool(cache), {"k": new_k, "v": new_v}, page_ids)
    cache = commit_state(cache, pages, slots, ends, snaps, snap_ok, _prefill_counts(c, lengths))
    x = final_norm(x, params, c)
    return cache, head_logits(x, params, c, last=lengths)


def _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, lanes, c):
    """Rows that start at ``starts`` (page-aligned), attending over their
    gathered prefix pages plus themselves, Mamba layers carried on from the
    slots' state. -> (x normed, new k, new v uncommitted, ends, snaps,
    snap_ok)."""
    slots, snap_at = lanes
    B, T = tokens.shape
    ctx, snap_ok = rows_ctx(lengths, starts, snap_at, T)
    positions = ctx["positions"]
    make_attn = prefix_attention(kv_pool(cache), block_tables, starts, positions, c.n_kv_heads)
    x, ends, snaps, new_k, new_v = _run_rows(
        params, c, embed(params, tokens, c), ctx, *state_in(cache, slots, starts), make_attn)
    return final_norm(x, params, c), new_k, new_v, ends, snaps, snap_ok


def prefill_paged_continue(params, cache, tokens, lengths, starts, page_ids, block_tables, lanes,
                           config: JambaConfig):
    """Continuation (a prefix hit's suffix, a later chunk of a long
    prompt): -> (cache, last-token logits [B, V])."""
    x, new_k, new_v, ends, snaps, snap_ok = _paged_continue_forward(
        params, cache, tokens, lengths, starts, block_tables, lanes, config)
    pages = commit_whole_pages(kv_pool(cache), {"k": new_k, "v": new_v}, page_ids)
    cache = commit_state(cache, pages, lanes[0], ends, snaps, snap_ok, _prefill_counts(config, lengths))
    return cache, head_logits(x, params, config, last=lengths)


def prefill_paged_continue_kv(params, cache, tokens, lengths, starts, page_ids, block_tables, lanes,
                              config: JambaConfig):
    """The continuation's writes without the head (a mid chunk)."""
    _x, new_k, new_v, ends, snaps, snap_ok = _paged_continue_forward(
        params, cache, tokens, lengths, starts, block_tables, lanes, config)
    pages = commit_whole_pages(kv_pool(cache), {"k": new_k, "v": new_v}, page_ids)
    return commit_state(cache, pages, lanes[0], ends, snaps, snap_ok, _prefill_counts(config, lengths))


def decode_step_paged(params, cache, tokens, seq_lens, block_tables, active, config: JambaConfig,
                      use_pallas: bool = False, mesh=None):
    """One token for lanes 0..S-1 (lane b is slot b): attention layers walk
    the pages; Mamba layers shift their slot's conv columns and take one
    step of the recurrence on ``state["ssm"][layer, :S]`` in place, the
    whole stack carried through the layer scan and never copied. An
    inactive lane's state and pages are left as they were (its ``delta`` is
    0)."""
    c = config
    S = tokens.shape[0]
    pool = kv_pool(cache)
    P = pool["k"].shape[2]
    make_attn = page_walk(pool, block_tables, seq_lens, use_pallas)
    dt, f32 = c.dtype, jnp.float32
    n, di = c.d_conv - 1, c.d_inner

    # The stacked state is carried through the loops and each Mamba layer
    # updates its own row where it lies; it never enters a conditional,
    # whose branch that hands it through unchanged is answered with a copy
    # of the whole stack (1.1 GB a layer: PERF.md, PR 37).
    def layer(kind, carry, index, at):
        x, h_all, conv_all = carry
        with scopes.layer("attn" if kind == "attention" else "mixer"):
            weights = layer_row(params[STACK[kind]], at)
            h = rms_norm(x, weights["ln1"], c.norm_eps)
            if kind == "attention":
                op, k, v = plain_attention_op(h, weights, c, make_attn(at), walk="page_walk")
                out = (k[:, 0].astype(dt), v[:, 0].astype(dt))
            else:
                with jax.named_scope("mamba_conv"):
                    old = jax.lax.dynamic_slice(conv_all, (at, 0, 0), (1, S, n * di))[0]
                u_act, z, delta, b, c_, u_ext = _mamba_pre(h, weights, c, old.reshape(S, n, di), active[:, None])
                with jax.named_scope("mamba_conv"):
                    new = jnp.where(active[:, None], u_ext[:, 1:].reshape(S, n * di), old)
                    conv_all = jax.lax.dynamic_update_slice(conv_all, new[None], (at, 0, 0))
                with jax.named_scope("ssm_update"):
                    a = -jnp.exp(weights["A_log"].astype(f32))
                    y, h_all = ssm.update(h_all, at, delta[:, 0], u_act[:, 0], b[:, 0], c_[:, 0], a)
                op = _mamba_post(y[:, None], u_act, z, weights, dt)
                out = ()
            x = x + op
        with scopes.layer("ffn"):
            ff = layer_row(params["ff"], index)
        return (_swiglu(x, ff, c), h_all, conv_all), out

    st = cache["state"]
    plan(c)  # refuses a pattern of one kind or of a kind it does not know
    (x, h_all, conv_all), outs = scan_layers(
        c.layer_types, (embed(params, tokens[:, None], c), st["ssm"], st["conv"]), layer)
    with scopes.layer("commit"):
        target = jnp.where(active, block_tables[jnp.arange(S), seq_lens // P], TRASH_PAGE)
        pages = commit_tokens(pool, dict(zip(("k", "v"), outs["attention"])), target, seq_lens % P)
        counts = _counts(c, active, active, active)
        state = {"ssm": h_all, "conv": conv_all, "snap": st["snap"], "counters": st["counters"].at[0].add(counts)}
    x = final_norm(x[:, 0], params, c)
    return {**pages, "state": state}, head_logits(x, params, c)


def describe_counters(config: JambaConfig, total) -> dict:
    """``Engine.stats()["ssm"]`` from the counters summed by the engine
    (``total`` [2, 4], None before the first dispatch): decode steps and
    prefills apart, Mamba layers run, rows (lanes updated, or rows scanned),
    real tokens (padding apart) and chunks of the scan, each summed over
    the Mamba layers; and the bytes of state a slot holds."""
    if total is None:
        total = [[0] * N_COUNTERS] * 2

    def row(r):
        return {"mamba_layers": int(r[0]), "rows": int(r[1]), "tokens": int(r[2]), "chunks": int(r[3])}

    return {"ssm": {"state_bytes_per_slot": config.state_bytes_per_slot, "decode": row(total[0]),
                    "prefill": row(total[1])}}
