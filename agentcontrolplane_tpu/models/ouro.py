"""Ouro model family (``model_type: ouro`` as ``ByteDance/Ouro-2.6B``
publishes it): ONE stack of layers run ``loops`` times over the same
weights, a norm and an exit gate between the loops.

``x = E[token]`` (no scale). For loop ``t`` and layer ``l``, the same weights
in every loop::

    x = x + RMSNorm(Attn_l(RMSNorm(x; ln1_l)); ln1_post_l)
    x = x + RMSNorm(MLP_l(RMSNorm(x; ln2_l)); ln2_post_l)

and after the last layer of each loop ``h_t = RMSNorm(x; norm)``, ``g_t =
w_g . h_t + b_g`` (one output) and **``x = h_t``** goes into loop ``t + 1``:
the last norm is applied between the loops, not once before the head.
``Attn_l`` is plain multi-head attention (no bias, rotary over the whole
head in the half-split form, causal softmax over ``sqrt(head_dim)``) over
**this loop's own** keys and values; ``MLP_l`` a SwiGLU. The layer is
``llama.attn_mlp`` with ``post_norms=True`` as it stands: this module
brings the loops around it and nothing of a layer.

**What is cached**: K and V of loop ``t``, layer ``l`` in cache layer ``t *
n_layers + l``: the pool is ``{"k", "v": [loops * n_layers, pages, P, H_kv *
d]}`` (``ops/paged.py`` ``init_kv_pages``), the layout the page walk reads,
``loops`` times as deep as the weights (192 cache layers over 48 layers at
the published sizes: 1.5 MiB a token). Loop ``t`` never reads another
loop's rows. Every token runs every loop and writes every cache layer
whatever the exit choice is, as the published forward pass does.

**The exit** (made at the head, over the last row's ``loops`` states):
``lambda_t = sigmoid(g_t)``; ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``
for ``t < loops - 1`` and the rest of the mass for the last; ``c_t = sum_{j
<= t} p_j``; ``e = min{t : c_t >= exit_threshold}``, the last loop if none;
``logits = W_head h_e``. At the published threshold 1 that is the last loop
unless a gate saturates.

Layout for XLA: a scan over the loops whose body is a scan over
``(params["layers"], arange(n_layers))``; the pool stays out of both scans'
carries and inputs (each walk is handed the whole pool flattened over its
cache layers and block tables offset by ``t * n_layers + l``) and never
passes through a conditional (PERF.md, PR 37); one scatter after the loops
commits every cache layer's new rows. Those rows are what a prefill costs:
``tokens x loops x n_layers x 2 x H_kv x d`` values stacked before the
commit (a 256-token bucket at the published sizes is 0.4 GB a sequence), so
``refusals`` holds ``prefill_batch_max x`` the widest bucket to
``PREFILL_ROWS_SHARE`` of the pool's rows.

One departure from the dense family's layout, changing no result: ``wq``
and ``wk`` are kept **outputs first** (``[n_layers, H d, D]``, as the source
stores every matrix) and handed to ``attn_mlp`` transposed, which the
compiler folds into the product. Kept inputs first, the chip's compiler
copied both stacks transposed before the first step of every decode block
and at the head of every prefill: 0.8 GB of temporaries in each program at
the published sizes, beside a pool that leaves 2.6 GB of the chip (the
compile rehearsal, ``tests/engine/test_chip_compile.py``; PERF.md PR 46; the
same finding as ``models/kanana.py``'s ``wq_nope``). ``wv``, ``wo`` and the
SwiGLU's matrices it takes inputs first.

The family keeps no state a slot (its programs take the page ids alone, as
the dense family's) and counts on the device: ``cache["state"]["counts"]``
``[2, COUNTS_HEAD + loops]`` uint32, row 0 decode steps and row 1 prefills:
tokens, passes (tokens x loops run), cache rows written, then how many rows'
logits were read from each loop's state.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..observability import scopes
from ..ops.attention import blocked_causal_attention, causal_attention, continue_attention
from ..ops.norms import rms_norm
from ..ops.paged import (
    TRASH_PAGE, commit_tokens, commit_whole_pages, flat_pages, gather_pages, init_kv_pages, layer_tables,
    paged_decode_attention_reference_cache_plus_new, pool_leaves,
)
# the one family this file imports (`tests/engine/test_model_seam.py`): `OuroConfig(LlamaConfig)` says so in its type,
# and the layer IS the dense family's (`llama.attn_mlp` with `post_norms=True`), so an edit there is an edit here
from . import llama
from .llama import LlamaConfig, attn_mlp, embed, final_norm_w, head_logits

OUT_FIRST = ("wq", "wk")  # projections kept outputs first, as the source stores every matrix (module text)
COUNTS_HEAD = 3  # tokens, passes, cache rows; then a count a loop of the exits taken there
PREFILL_ROWS_SHARE = 0.125  # of the pool's rows: the most a prefill dispatch may stack before its commit


@dataclass(frozen=True)
class OuroConfig(LlamaConfig):
    post_norms: bool = True  # a norm on both sublayers' outputs, before the residual
    loops: int = 4  # total_ut_steps: passes of the stack over the same weights
    exit_threshold: float = 1.0  # early_exit_threshold: the cumulated exit mass a loop must reach

    @property
    def cache_layers(self) -> int:
        """The pool's depth: a cache layer a loop and layer."""
        return self.loops * self.n_layers


PRESETS: dict[str, OuroConfig] = {
    # ByteDance/Ouro-2.6B: 2.67 B parameters, 5.34 GB of bfloat16, 1.5 MiB of cache a token
    "ouro-2.6b": OuroConfig(
        vocab_size=49152, dim=2048, n_layers=48, n_heads=16, n_kv_heads=16, ffn_dim=5632, norm_eps=1e-6,
        rope_theta=1e6, max_seq_len=65536, loops=4, exit_threshold=1.0,
    ),
    # CPU tests: 2 loops of 3 layers
    "ouro-tiny": OuroConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=4, ffn_dim=128, norm_eps=1e-6,
        rope_theta=10000.0, max_seq_len=512, loops=2, dtype=jnp.float32,
    ),
}


def init_params(config: OuroConfig, key: jax.Array) -> dict:
    """The dense family's leaves (``ln1_post`` / ``ln2_post`` among them),
    ``wq`` and ``wk`` outputs first, and the exit gate: ``gate_w`` [D] and
    ``gate_b`` [1], float32."""
    params = llama.init_params(config, key)
    for name in OUT_FIRST:
        params["layers"][name] = jnp.swapaxes(params["layers"][name], 1, 2)
    k = jax.random.fold_in(key, 0x6F75726F)
    params["gate_w"] = jax.random.normal(k, (config.dim,), jnp.float32) * config.dim ** -0.5
    params["gate_b"] = jnp.zeros((1,), jnp.float32)
    return params


def exit_choice(gates: jax.Array, threshold: float) -> jax.Array:
    """``gates`` [loops, ...] float32 -> the loop each row's logits are read
    from, int32 [...] (module text)."""
    lam = jax.nn.sigmoid(gates.astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)  # prod_{j<t} (1 - lambda_j)
    p = jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)
    reached = jnp.cumsum(p, axis=0) >= threshold
    last = gates.shape[0] - 1
    return jnp.where(jnp.any(reached, axis=0), jnp.argmax(reached, axis=0), last).astype(jnp.int32)


def _run_loops(params, c: OuroConfig, x, positions, make_attn, pick, scope: str = "prefill_attention"):
    """The stack ``c.loops`` times. ``make_attn(index)`` gives the attention
    of cache layer ``index`` (traced: ``t * n_layers + l``) as ``attn_mlp``
    takes it, a function that leaves its new rows on ``.new_kv``, ``scope``
    the name that operator's ops go by; ``pick(h [B, T, D]) -> [B, D]`` takes
    the row whose logits are read.
    -> (every loop's state of that row [loops, B, D], its gates [loops, B]
    float32, new K and V [loops * n_layers, B, T, H_kv, d] each)."""
    L = c.n_layers
    norm_w = final_norm_w(params, c)
    # the query and key projections are indexed where they are used and not
    # by the scan: the read of a layer's row (a sixth of a decode step's
    # weight bytes) so carries the scope of the product it is for, where the
    # scan's own slicing carries none
    indexed = {name: params["layers"][name] for name in OUT_FIRST}
    scanned_layers = {name: a for name, a in params["layers"].items() if name not in OUT_FIRST}

    def loop(x, t):
        def layer(x, scanned):
            weights, l = scanned
            with scopes.layer("attn"), jax.named_scope("attn_qkv"):
                weights = {**weights, **{name: a[l].T for name, a in indexed.items()}}
            attn = make_attn(t * L + l)
            x, _, _ = attn_mlp(x, weights, c, positions, attn, walk=scope)
            return x, attn.new_kv

        x, new = jax.lax.scan(layer, x, (scanned_layers, jnp.arange(L, dtype=jnp.int32)))
        with scopes.layer("head"):
            with jax.named_scope("loop_norm"):
                x = rms_norm(x, norm_w, c.norm_eps)  # what the next loop takes in
            with jax.named_scope("exit_gate"):
                h = pick(x)
                gate = h.astype(jnp.float32) @ params["gate_w"].astype(jnp.float32) + params["gate_b"][0]
        return x, (h, gate, *new)

    _, (h, gates, new_k, new_v) = jax.lax.scan(loop, x, jnp.arange(c.loops, dtype=jnp.int32))
    merge = lambda a: a.reshape((c.loops * L,) + a.shape[2:])  # noqa: E731
    return h, gates, merge(new_k), merge(new_v)


def _exit_logits(params, c: OuroConfig, h, gates):
    """-> (logits [B, V] float32 from each row's chosen loop, the choice [B])."""
    with scopes.layer("head"):
        with jax.named_scope("exit_gate"):
            e = exit_choice(gates, c.exit_threshold)
        with jax.named_scope("exit_select"):
            chosen = jnp.take_along_axis(h, e[None, :, None], axis=0)[0]
        with jax.named_scope("head_product"):
            return head_logits(chosen, params, c), e


def forward(params: dict, tokens: jax.Array, config: OuroConfig) -> jax.Array:
    """Full-sequence causal forward -> logits [B, T, V] float32 (tests): the
    exit chosen a position."""
    c = config
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def make_attn(index):
        def attn(q, k, v):
            attn.new_kv = (k, v)
            return causal_attention(q, k, v, positions)

        return attn

    h, gates, _, _ = _run_loops(params, c, embed(params, tokens, c), positions, make_attn,
                                lambda x: x.reshape(B * T, c.dim))
    return _exit_logits(params, c, h, gates)[0].reshape(B, T, -1)


# ---------------------------------------------------------------------------
# Serving: a pool `loops` times as deep as the weights
# ---------------------------------------------------------------------------


def init_paged_cache(config: OuroConfig, num_pages: int, page_size: int, quantize_kv: bool = False,
                     max_slots: int = 1) -> dict:
    c = config
    if quantize_kv:
        raise ValueError("the ouro family keeps its pages in the model's dtype (int8 pages for a pool deeper than "
                         "the weights: ROADMAP Queue B)")
    cache = init_kv_pages(c.cache_layers, num_pages, page_size, c.n_kv_heads, c.head_dim, c.dtype)
    cache["state"] = {"counts": jnp.zeros((2, COUNTS_HEAD + c.loops), jnp.uint32)}
    return cache


def _committed(cache, pool, c: OuroConfig, row: int, tokens, exits=None, heads=None):
    """The cache with its pages replaced and the dispatch counted: ``tokens``
    rows run (each ``loops`` passes, ``cache_layers`` rows written), and
    where logits were read (``exits`` [B] the loops chosen, ``heads`` [B]
    bool the rows that count) the exits taken."""
    tokens = jnp.asarray(tokens).astype(jnp.uint32)
    taken = jnp.zeros((c.loops,), jnp.uint32)
    if exits is not None:
        taken = jnp.sum((exits[:, None] == jnp.arange(c.loops)[None, :]) & heads[:, None], axis=0, dtype=jnp.uint32)
    added = jnp.concatenate([jnp.stack([tokens, tokens * c.loops, tokens * c.cache_layers]), taken])
    return {**pool, "state": {"counts": cache["state"]["counts"].at[row].add(added)}}


def _commit_pages(cache, c: OuroConfig, new_k, new_v, page_ids, lengths, exits=None):
    """A prefill's, a continuation's or a mid chunk's one write of whole
    pages, every cache layer's, and its count."""
    with scopes.layer("commit"):
        pool = commit_whole_pages(pool_leaves(cache), {"k": new_k, "v": new_v}, page_ids)
        return _committed(cache, pool, c, 1, jnp.sum(lengths), exits, lengths > 0)


def _rows(lengths, starts, T):
    ar = jnp.arange(T)
    return jnp.where(ar[None, :] < lengths[:, None], starts[:, None] + ar[None, :], -1)


def _last_row(lengths):
    return lambda x: x[jnp.arange(x.shape[0]), jnp.maximum(lengths, 1) - 1]


def prefill_paged_batch(params, cache, tokens, lengths, page_ids, config: OuroConfig):
    """B whole prompts in one dispatch, every loop's K and V into its own
    cache layers of each row's pages. -> (cache, logits [B, V])."""
    c = config
    B, T = tokens.shape
    positions = _rows(lengths, jnp.zeros((B,), jnp.int32), T)

    def make_attn(index):
        def attn(q, k, v):
            attn.new_kv = (k, v)
            return blocked_causal_attention(q, k, v, positions)

        return attn

    h, gates, new_k, new_v = _run_loops(params, c, embed(params, tokens, c), positions, make_attn,
                                        _last_row(lengths))
    logits, e = _exit_logits(params, c, h, gates)
    return _commit_pages(cache, c, new_k, new_v, page_ids, lengths, e), logits


def _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, c: OuroConfig):
    """Rows that start at ``starts`` (page-aligned) attend, in each loop,
    over that loop's rows of their gathered prefix pages plus themselves
    (the key order and masks are ``llama._paged_continue_forward``'s).
    Nothing is written here. -> (h, gates, new K, new V)."""
    B, T = tokens.shape
    positions = _rows(lengths, starts, T)
    pool = pool_leaves(cache)
    NP, P = pool["k"].shape[1:3]
    M = block_tables.shape[1]
    r_idx = jnp.arange(P * M)
    row_pos = (r_idx % M) * P + r_idx // M  # offset-major: the within-page axis outermost
    cache_pos = jnp.where(row_pos[None, :] < starts[:, None], row_pos[None, :], -1)
    key_pos = jnp.concatenate([cache_pos, positions], axis=1)

    def make_attn(index):
        def attn(q, k, v):
            tables = layer_tables(block_tables, index, NP)
            rows = lambda name, new: jnp.concatenate([  # noqa: E731
                jnp.swapaxes(gather_pages(pool, name, tables, new.dtype, c.n_kv_heads), 1, 2).reshape(
                    B, P * M, c.n_kv_heads, c.head_dim), new], axis=1)
            attn.new_kv = (k, v)
            return continue_attention(q, rows("k", k), rows("v", v), positions, key_pos)

        return attn

    return _run_loops(params, c, embed(params, tokens, c), positions, make_attn, _last_row(lengths))


def prefill_paged_continue(params, cache, tokens, lengths, starts, page_ids, block_tables, config: OuroConfig):
    """Continuation (a prefix hit's suffix, a later chunk of a long prompt, a
    resumed request's tail): -> (cache, last-token logits [B, V])."""
    c = config
    h, gates, new_k, new_v = _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, c)
    logits, e = _exit_logits(params, c, h, gates)
    return _commit_pages(cache, c, new_k, new_v, page_ids, lengths, e), logits


def prefill_paged_continue_kv(params, cache, tokens, lengths, starts, page_ids, block_tables, config: OuroConfig):
    """The continuation's writes without the head (a mid chunk)."""
    c = config
    _h, _gates, new_k, new_v = _paged_continue_forward(params, cache, tokens, lengths, starts, block_tables, c)
    return _commit_pages(cache, c, new_k, new_v, page_ids, lengths)


def decode_step_paged(params, cache, tokens, seq_lens, block_tables, active, config: OuroConfig,
                      use_pallas: bool = False, mesh=None, interpret: bool = False):
    """One token for lanes 0..S-1 (lane b is slot b): ``loops x n_layers``
    walks, each over its own cache layer of the whole pool."""
    c = config
    S = tokens.shape[0]
    pool = pool_leaves(cache)
    NP, P = pool["k"].shape[1:3]
    k_flat, v_flat = flat_pages(pool["k"]), flat_pages(pool["v"])

    def make_attn(index):
        def attn(q, k, v):
            args = (q[:, 0], k_flat, v_flat, layer_tables(block_tables, index, NP), seq_lens, k[:, 0], v[:, 0])
            if use_pallas or interpret:
                from ..ops.pallas.paged_attention import paged_decode_attention_cache_plus_new

                out = paged_decode_attention_cache_plus_new(*args, interpret=interpret)
            else:
                out = paged_decode_attention_reference_cache_plus_new(*args)
            attn.new_kv = (k[:, 0], v[:, 0])
            return out[:, None]

        return attn

    h, gates, new_k, new_v = _run_loops(params, c, embed(params, tokens[:, None], c), seq_lens[:, None], make_attn,
                                        lambda x: x[:, 0], scope="page_walk")
    logits, e = _exit_logits(params, c, h, gates)
    with scopes.layer("commit"):
        target = jnp.where(active, block_tables[jnp.arange(S), seq_lens // P], TRASH_PAGE)
        pool = commit_tokens(pool, {"k": new_k, "v": new_v}, target, seq_lens % P)
        return _committed(cache, pool, c, 0, jnp.sum(active), e, active), logits


def counters(cache: dict) -> jax.Array:
    """The loops' counters as the programs keep them."""
    return cache["state"]["counts"]


def describe_counters(config: OuroConfig, total) -> dict:
    """``Engine.stats()["loops"]`` from the counters summed by the engine
    (``total`` [2, COUNTS_HEAD + loops], None before the first dispatch),
    decode steps and prefills apart: ``tokens`` run, ``passes`` of the stack
    (``loops`` a token: every token runs every loop), ``cache_rows`` written
    (``loops x n_layers`` a token), ``exit_at`` the rows whose logits were
    read from each loop's state."""
    c = config
    if total is None:
        total = [[0] * (COUNTS_HEAD + c.loops)] * 2

    def row(r):
        return {"tokens": int(r[0]), "passes": int(r[1]), "cache_rows": int(r[2]),
                "exit_at": [int(n) for n in r[COUNTS_HEAD:]]}

    return {"loops": {"loops": c.loops, "layers": c.n_layers, "cache_layers": c.cache_layers,
                      "exit_threshold": c.exit_threshold, "decode": row(total[0]), "prefill": row(total[1])}}


def refusals(asked: dict) -> list[tuple[bool, str]]:
    """What the engine was asked for that this family does not serve, in
    words (``models.programs``)."""
    return [
        (asked["kv_layout"] != "paged", "kv_layout='slot': its pool is deeper than its weights and only the paged programs index it; serve it with kv_layout='paged'"),
        (asked["spec_len"] > 0, "spec_len > 0: it has no verify program over a pool a loop deep"),
        (asked["tp"] > 1 or asked["sp"] > 1, "tensor or context parallelism: its gate and its pool have no sharding here; serve it on a tp=1 mesh"),
        (asked["quantize_weights"], "weight-only int8: its matrices are served in the dtype they were made in"),
        (asked["quantize_kv"], "quantize_kv: its pages are kept in the model's dtype"),
        (asked["coordination"], "multi-host lockstep serving"),
        (asked["prefill_rows"] > PREFILL_ROWS_SHARE * asked["pool_rows"],
         f"prefill_batch_max x its widest prefill bucket = {asked['prefill_rows']} rows: a prefill stacks every "
         f"cache layer's new rows before its one commit, and more than {PREFILL_ROWS_SHARE:g} of the pool's "
         f"{asked['pool_rows']} rows is a temporary the size of the cache; lower prefill_batch_max or the buckets"),
    ]
