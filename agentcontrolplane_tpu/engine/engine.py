"""The TPU serving engine: continuous batching over a slot KV cache.

This is the in-tree replacement for the reference's delegation to LLM SaaS
(north star: "concurrent Task/ToolCall CRs are continuously batched into a
single decode stream with tensor-parallel allreduce over ICI").

Architecture:

- One **engine thread** owns the device state (params stay resident; the KV
  cache is threaded through jitted steps with donation, so XLA updates it in
  place). Requests arrive on a thread-safe queue from the asyncio control
  plane and resolve ``concurrent.futures.Future``s.
- **Admission**: a waiting request takes a free slot; its prompt is padded to
  a power-of-two bucket and run through the jitted prefill (one compiled
  program per bucket), which also samples the first token on-device.
- **Decode**: one jitted step advances ALL active slots one token and samples
  on-device — only [S] token ids cross to the host per step. Sequences join
  at prefill and leave at EOS/stop/max-tokens; the batch never drains to
  admit new work (no head-of-line blocking — SURVEY.md §7.4 hard-part #1).
- **Sharding**: params/cache carry NamedShardings over a ``('tp',)`` mesh;
  jit propagates them, XLA inserts the ICI allreduces.

The scheduler's lease interaction: the control plane's per-task lease
serializes per Task, but requests from many Tasks batch here freely — the
lease layer never serializes the engine.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import hashlib
import heapq
import logging
import os
import queue
import threading
import time
import uuid
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models import LlamaConfig, PRESETS, preset, programs  # noqa: F401 (re-exported names)
from ..observability import scopes
from ..observability.metrics import REGISTRY
from ..ops.paged import TRASH_PAGE, page_bytes, pool_leaves, ring_size, set_pages
from ..ops.sampling import NEG_INF, masked_logits, masks_wanted, sample, speculative_sample
from ..parallel.mesh import (
    kv_cache_shardings,
    serving_mesh,
)
from .lanes import DECODE, PREFILL, VERIFY, dispatch_key
from .tokenizer import ByteTokenizer, Tokenizer

log = logging.getLogger("acp_tpu.engine")

# consecutive crashes, with no request finished in between, after which the
# engine stops being restartable (ensure_running returns False)
_CRASH_LOOP_LIMIT = 2

# Fixed values: no flag, CRD field, benchmark cell or deployment sets one. An
# engine copies those a test varies into attributes of the same name.

# HBM bound of the prefix cache, in total cached KV tokens: per cached token
# one K+V row per layer (L * H_kv * d * 2 * dtype bytes); the token bound
# keeps worst-case cache HBM explicit instead of silently scaling with
# bucket sizes
PREFIX_CACHE_MAX_TOKENS = 4096
# paged: how many decode blocks of pages to reserve per slot ahead of need,
# so the block table isn't dirtied (re-uploaded) every dispatch
PAGE_LOOKAHEAD_BLOCKS = 8
# bound on distinct fused program shapes: a NEW (chunk bucket x batch x
# decode width x phase-set) combination past this many falls back to the
# split dispatches for that cycle (which reuse already-compiled programs)
# instead of compiling yet another megastep variant — fusion must not turn
# the jit cache into a combinatorial zoo. 0 = never fuse.
MEGASTEP_MAX_PROGRAMS = 32
# dispatch-cycle stall watchdog: a busy cycle (fault throttles included)
# whose wall time exceeds BOTH STALL_MULT x the fastest cycle seen (the
# cadence floor) and STALL_MIN_S records a `stall` flight event +
# acp_engine_stalls_total — the cheap gray-failure signal the fleet health
# state machine (fleet/health.py) consumes. Observation-only: a stall never
# changes what is sampled.
STALL_MULT = 8.0
STALL_MIN_S = 0.25


def _in_phase(name: str, opener: str = "phase"):
    """Run an engine-thread method inside the profiler's ``name`` phase
    (observability/profiler.py): for methods that are one phase whole."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(self, *args, **kwargs):
            with getattr(self.profiler, opener)(name):
                return fn(self, *args, **kwargs)

        return inner

    return wrap


def _in_setup(name: str):
    """The same for a method of the engine's start, inside the ``name``
    set-up phase (``DispatchProfiler.setup``: the caller's thread, not the
    engine's)."""
    return _in_phase(name, opener="setup")


def _init_phase(init):
    """``Engine.__init__`` whole inside the ``init`` set-up phase. The two
    recorders are made first, for that and because the constructor's first
    upload counts on them (``_put``):

    - the flight recorder (observability/flight.py): ring-buffer record of
      every scheduler decision, always on (ACP_FLIGHT=0 disables for bench
      A/B). Public attribute: the REST/CLI introspection surface reads it
      via its own cross-thread-safe methods.
    - the compute efficiency observatory (observability/profiler.py): per-
      dispatch program telemetry, cold-compile tracking, goodput/waste
      ledger, set-up phases. Public attribute likewise. ACP_PROF=0 reduces
      every hook to one bool branch (bench A/B), and the hooks never touch
      dispatch inputs/outputs — profiler on/off is byte-identical."""

    @functools.wraps(init)
    def inner(self, *args, **kwargs):
        from ..observability.flight import FlightRecorder
        from ..observability.profiler import DispatchProfiler

        self.flight = FlightRecorder()
        self.profiler = DispatchProfiler(flight=self.flight)
        with self.profiler.setup("init"):
            init(self, *args, **kwargs)

    return inner


class EngineOverloadedError(RuntimeError):
    """The admission queue is at its configured cap: the request was shed,
    not queued. Callers should retry after ``retry_after_s`` (the REST
    layer maps this to 503 + Retry-After)."""

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = retry_after_s


class DeadlineExceededError(RuntimeError):
    """The request's ``timeout_s`` deadline expired while it was still
    queued — it was failed fast without spending any prefill compute."""


@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    max_tokens: int = 512  # budget for SAMPLED tokens (forced prefix is free)
    # grammar-constrained decoding: force a structurally valid JSON object
    # (engine/constrain.py); generation ends when the object closes
    json_only: bool = False
    # teacher-forced generation prefix (token ids): prefilled with the
    # prompt, returned as part of the output, and — with json_only — the
    # constraint automaton is seeded past it. This is how tool_choice
    # "required" forces the '{"name": "X", "arguments": {' envelope so the
    # completion is guaranteed to be a parseable call to X.
    forced_prefix: tuple = ()


@dataclass
class GenerationResult:
    text: str
    tokens: list[int]
    finish_reason: str  # "stop" | "length" | "cancelled"
    prompt_tokens: int
    ttft_ms: float  # time to first token
    latency_ms: float
    # times this request was preempted (KV pool pressure) and resumed;
    # preemption is invisible in the output — this is the only trace
    preempt_count: int = 0
    # prefill/decode disaggregation (fleet/): when the request was
    # submitted with export_kv=True, the prompt's written KV rides out as
    # a HostKVEntry (rows [0, cut), page-aligned in paged mode, int8 +
    # scale twins when the cache is quantized) for a decode replica to
    # restore through inject_host_kv. None when export was skipped
    # (pool off, truncated prompt, too few rows).
    kv_handoff: Optional[object] = None


@dataclass
class _Request:
    rid: str
    prompt: list[int]
    sampling: SamplingParams
    future: Future
    # called from the ENGINE thread with each block's newly sampled token
    # ids (must not block; bridge to asyncio with call_soon_threadsafe)
    on_tokens: Optional[callable] = None
    # overlapped tool execution: called from the ENGINE thread as
    # ``(index, MessageToolCall)`` the moment a streamed tool call's braces
    # close — while the model is still decoding the rest of the turn. Must
    # not block (bridge to asyncio with call_soon_threadsafe). Set by
    # submit(on_tool_call=...), which also builds ``tool_parser``.
    on_tool_call: Optional[callable] = None
    tool_parser: Optional[object] = None  # toolparse.ToolStreamParser
    # detokenization holdback for the stream parser: token ids whose text
    # is still an incomplete UTF-8 sequence at a commit boundary
    detok_pending: list[int] = field(default_factory=list)
    # (monotonic emit time, MessageToolCall) per early-emitted call; the
    # same list object is exposed as ``future.early_tool_calls``
    early_calls: list = field(default_factory=list)
    # park-on-finish: when generation completes normally, keep the slot
    # PARKED (prompt KV resident, surplus pages released) so the next turn
    # of the same conversation — sent while this turn's tool calls execute
    # — resumes with a suffix-only prefill (see Engine._park)
    park: bool = False
    # tail-truncated prompts keep their suffix, not their prefix — they can
    # neither hit nor usefully seed the prefix cache
    truncated: bool = False
    enqueued: float = field(default_factory=time.monotonic)
    # preempt-and-resume state: tokens this request already SAMPLED (beyond
    # any forced prefix) before a preemption freed its slot. On re-admission
    # the prefill row is prompt + forced_prefix + resume_tokens, so decode
    # continues exactly where it left off — callers never see truncation.
    resume_tokens: list[int] = field(default_factory=list)
    preempt_count: int = 0
    # absolute monotonic deadline (submit's timeout_s): a request still
    # QUEUED past it is failed fast instead of wasting prefill compute
    deadline: Optional[float] = None
    # wall-clock of the FIRST first-token (survives preemption: TTFT and
    # the ttft metric are observed once per request, not once per resume)
    first_token_at: float = 0.0
    # OTLP trace linkage (SpanContext-like or {"trace_id","span_id"} dict):
    # at finish, the flight recorder exports this request's phase windows
    # as child spans under it — engine internals join the Task's trace
    trace: Optional[object] = None
    # prewarm requests skip per-request flight events and phase histograms
    # (hundreds of synthetic requests would drown the real timelines)
    prewarm: bool = False
    # fleet disaggregation: extract the prompt KV at finish and attach it
    # to the GenerationResult (see _export_kv_handoff). Mutually exclusive
    # with park — the handoff entry, not the parked slot, is the reuse unit.
    export_kv: bool = False
    # completed (True) when the request takes a slot (prefill starts).
    # Clients key their generation timeout off this, so queue wait under
    # saturation doesn't eat the per-request budget (mirrored onto
    # future.admitted by submit). A concurrent Future rather than an Event:
    # asyncio callers bridge it with wrap_future (callback-based) instead
    # of parking a default-executor thread per queued request — 64 queued
    # requests would otherwise exhaust the shared executor.
    admitted: Future = field(default_factory=Future)
    # families with per-slot state beside the pages (models.programs(...)
    # .has_state): the one page-aligned length of this admission at which
    # the prefill saves the state (a prefix entry, a park or a host swap at
    # exactly that length can resume); 0 = none was saved
    state_cut: int = 0

    def emit(self, tokens: list[int]) -> None:
        if self.on_tokens is not None and tokens:
            try:
                self.on_tokens(tokens)
            except Exception:  # a broken consumer must not kill the engine
                self.on_tokens = None


@dataclass
class _Slot:
    request: _Request
    generated: list[int] = field(default_factory=list)
    prompt_len: int = 0
    prefix_len: int = 0  # leading forced tokens in ``generated``
    first_token_at: float = 0.0
    admit_seq: int = 0  # admission order (victim policy tie-break)
    # speculative decoding: per-slot adaptive draft-length controller
    # (engine/spec.py). Host-only — preemption saves nothing, re-admission
    # rebuilds it fresh. None when the engine runs with spec_len == 0.
    spec: Optional[object] = None
    # prompt+generated as one int32 array for the drafter, appended
    # incrementally (``generated`` only grows within a slot's lifetime;
    # re-admission builds a fresh slot). Reboxing the whole context every
    # verify dispatch would be O(ctx) host work in the decode hot loop.
    ctx_buf: Optional[np.ndarray] = None
    ctx_len: int = 0
    # parked: generation finished (future resolved) but the slot lingers
    # holding its PROMPT KV so the conversation's next turn — typically
    # arriving as soon as this turn's overlapped tool calls complete —
    # prefills only the suffix. Parked slots never decode, yield their
    # pages voluntarily under pool pressure, and expire after park_max_s.
    parked: bool = False
    parked_at: float = 0.0
    park_cut: int = 0  # KV rows valid for adoption (page-aligned in paged)
    # chunked prefill: the slot is admitted (slot id + KV pages reserved)
    # but its prompt KV is only partially written — the unified scheduler
    # advances it one chunk per dispatch cycle, interleaved with decode.
    # A prefilling slot never decodes; it is a first-class preemption
    # citizen (preempting it loses no sampled tokens — the request requeues
    # and re-enters the chunk loop from its prefix-cache start on
    # re-admission) and its deadline expiring mid-prefill releases the
    # partial KV. ``prefill_pos`` = KV rows written so far; ``prefill_row``
    # caches _full_row(request) so the hot loop doesn't rebuild it.
    prefilling: bool = False
    prefill_pos: int = 0
    prefill_row: Optional[list] = None
    # admission-time chunk-rate plan (engine/planner.py): chunks of
    # progress this slot should make per scheduler cycle so its deadline
    # is met by arithmetic, not EDF luck. Projected at admission and
    # reprojected on preempt→resume and park→adopt re-admissions; 1 for
    # deadline-free requests (exactly the PR 7 one-chunk cadence).
    chunk_quota: int = 1
    # host-tier swap-in: the HostKVEntry whose rows are being restored into
    # this slot's KV through the token-budget loop (one restore chunk per
    # scheduler cycle, budget-costed like a prefill chunk). Cleared when
    # prefill_pos reaches the entry's cut; the model prefill then resumes
    # from there. swap_stall_s accumulates the engine-thread seconds spent
    # blocked inside host->device restore copies (the host_stall phase).
    swap_entry: Optional[object] = None
    swap_stall_s: float = 0.0
    # async host-KV prefetch (host_prefetch, paged layout): the NEXT restore
    # chunk's rows, already launched host->device with non-blocking device
    # puts — {"start", "n", "groups": [(ids_dev, blocks_dev), ...]} in the
    # same pow2 page groups the blocking _swap_in_rows would scatter. The
    # commit half consumes it next cycle (scatter inside the dispatch
    # window, megastep-absorbed when fused) so the copy overlaps model
    # compute instead of stalling the engine thread. Cleared on commit,
    # fallback, abort, and swap teardown; a stale or mismatched stage is
    # discarded and the blocking path runs — byte-identical either way.
    swap_staged: Optional[dict] = None
    # cross-request shared-prefix dedup: (leader slot, leader rid, cut) —
    # this slot's rows [0, cut) are the leader's refcount-shared pages. A
    # follower admitted while its leader was still mid-prefill WAITS (no
    # chunks dispatched) until the leader has written the shared rows;
    # a leader dying mid-prefill rewinds its followers to the rows it
    # actually wrote (see _unshare_followers). None once the wait clears.
    share_of: Optional[tuple] = None


def _next_bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _a_leaf(tree: dict):
    """One page-shaped leaf of a cache or of rows taken from it, whatever
    the family names its leaves: what a profiler record blocks on."""
    return next(iter(pool_leaves(tree).values()))


def _pow2_sizes(n: int) -> list[int]:
    """Greedy power-of-two decomposition (7 -> [4, 2, 1]) — the swap
    extract/restore dispatch sizes, so each is a bounded jit cache entry
    and no dispatch ever pads past real data (a padded write could clobber
    neighboring live KV rows)."""
    out: list[int] = []
    b = 1
    while b * 2 <= n:
        b *= 2
    while n:
        while b > n:
            b //= 2
        out.append(b)
        n -= b
    return out


def _pow2_chunks(items: list, max_chunk: int) -> list[list]:
    """Split into power-of-two-sized chunks (7 -> [4, 2, 1]) so each batch
    size is its own (bounded) jit cache entry."""
    out: list[list] = []
    i = 0
    while i < len(items):
        b = 1
        while b * 2 <= min(len(items) - i, max_chunk):
            b *= 2
        out.append(items[i : i + b])
        i += b
    return out


# -- what every sampling program nests around its model step ------------------

def constrain_logits(logits, table, con_state, constrained, min_close, budget):
    """Mask logits to grammar-legal tokens for constrained slots.
    ``budget`` [S] = sampled tokens remaining INCLUDING this one:
    tokens are additionally restricted to those whose next state can
    still close the JSON within budget-1, so constrained generations
    ALWAYS complete inside max_tokens (no truncated objects)."""
    nxt = table[jnp.clip(con_state, 0, table.shape[0] - 1)]  # [S, W]
    # the table is as wide as the TOKENIZER's vocab; logits are as
    # wide as the model's. Tokens beyond the table are forbidden:
    # pad the gathered rows, never the [states, V] table (at a
    # 152k vocab the padded table alone is ~5 GB of HBM)
    nxt = jnp.pad(
        nxt, ((0, 0), (0, logits.shape[-1] - nxt.shape[-1])),
        constant_values=-1,
    )  # [S, V]
    allowed = nxt >= 0
    closable = (
        min_close[jnp.clip(nxt, 0, min_close.shape[0] - 1)]
        <= budget[:, None] - 1
    )
    budget_allowed = allowed & closable
    # if the budget is already unsatisfiable, keep plain grammar
    # legality rather than masking everything (never sample garbage)
    feasible = budget_allowed.any(axis=-1, keepdims=True)
    allowed = jnp.where(feasible, budget_allowed, allowed)
    return jnp.where(constrained[:, None] & ~allowed, jnp.float32(NEG_INF), logits)


def advance_constraint(table, con_state, constrained, toks):
    width = table.shape[1]
    nxt = table[
        jnp.clip(con_state, 0, table.shape[0] - 1),
        jnp.minimum(toks, width - 1),
    ]
    nxt = jnp.where(toks < width, nxt, -1)  # beyond the table: illegal
    return jnp.where(constrained, nxt, con_state)


def sample_lanes(logits, key, ln, table, min_close):
    """Constrained sampling for a [B] batch of first tokens, from a
    prefill dispatch's unpacked lanes and the key of its counter."""
    with scopes.layer("sample"):
        logits = constrain_logits(
            logits, table, ln["con_states"], ln["constrained"], min_close, ln["budgets"]
        )
        toks = sample(
            logits, dispatch_key(key, ln["n"]), ln["temps"], ln["top_ks"], ln["top_ps"]
        )
        return toks, advance_constraint(table, ln["con_states"], ln["constrained"], toks)


def make_decode_block(step_fn, stop_toks: tuple, max_ctx: int, block_size: int):
    """The K-step decode block around a layout's ``step_fn``. ``stop_toks``
    and ``max_ctx`` are trace-time constants: finish detection runs ON
    DEVICE so decode blocks can chain device-resident state (see
    _decode_once) — a slot that samples a stop token, exhausts its budget,
    or hits the context edge deactivates itself mid-block and stops
    advancing/writing, keeping the device state consistent with the host's
    bookkeeping without a per-block re-upload."""

    def decode_block(params, cache, lanes, key, table, min_close, *extra):
        ln = DECODE.unpack(lanes)
        temps, top_ks, top_ps = ln["temps"], ln["top_ks"], ln["top_ps"]
        constrained = ln["constrained"]
        # the rows a block samples by do not change inside it, and a lane
        # only ever goes dead: asked once, of the lanes live at its start
        with scopes.layer("sample"):
            wanted = masks_wanted(top_ks, top_ps, ln["active"])

        # everything a step does around the model is the sampler's (acp.sample)
        def step(carry, _):
            cache, tokens, seq_lens, con_states, budgets, active, rng = carry
            with scopes.layer("sample"):
                rng, sub = jax.random.split(rng)
            cache, logits = step_fn(params, cache, tokens, seq_lens, active, *extra)
            with scopes.layer("sample"):
                logits = constrain_logits(
                    logits, table, con_states, constrained, min_close, budgets
                )
                next_toks = sample(logits, sub, temps, top_ks, top_ps, wanted)
                next_toks = jnp.where(active, next_toks, tokens)
                con_states = advance_constraint(table, con_states, constrained, next_toks)
                seq_lens = seq_lens + active.astype(jnp.int32)
                budgets = budgets - active.astype(jnp.int32)
                is_stop = jnp.zeros_like(active)
                for st in stop_toks:
                    is_stop = is_stop | (next_toks == st)
                active = active & ~is_stop & (budgets > 0) & (seq_lens + 1 < max_ctx)
            return (cache, next_toks, seq_lens, con_states, budgets, active, rng), next_toks

        with scopes.layer("sample"):
            first_key = dispatch_key(key, ln["n"], ln["chain"])
        (cache, tokens, seq_lens, con_states, budgets, active, _), toks = jax.lax.scan(
            step,
            (cache, ln["tokens"], ln["seq_lens"], ln["con_states"], ln["budgets"],
             ln["active"], first_key),
            None, length=block_size,
        )
        # the carry is the lanes themselves, donated and handed back:
        # a block nothing dirtied feeds them in again as they are, and
        # draws from the next key of this dispatch's chain
        lanes = DECODE.update(
            lanes, tokens=tokens, seq_lens=seq_lens, con_states=con_states,
            budgets=budgets, active=active, chain=ln["chain"] + 1,
        )
        return cache, toks, con_states, lanes

    # raw (unjitted): the split path jits it standalone; the fused
    # megastep composes the same body so both paths trace the same
    # graph per phase
    return decode_block


class _DraftSampler:
    """What a drafting family's step asks of the engine (``models.programs``
    ``draft_step``): the draft drawn from the drafted logits, and the
    accept, both under the lanes' own constraint masks, sampling parameters,
    stops, budgets and the context's edge, as the decode block's one-token
    step applies them. ``after`` holds the lanes as the step leaves them."""

    def __init__(self, key, tokens, seq_lens, con_states, budgets, active, ln, wanted,
                 table, min_close, stop_toks, max_ctx):
        self.key_draft, self.key_accept = jax.random.split(key)
        self.lanes = (tokens, seq_lens, con_states, budgets, active)
        self.ln, self.wanted = ln, wanted
        self.table, self.min_close, self.stop_toks, self.max_ctx = table, min_close, stop_toks, max_ctx
        self.after = None

    def _masked(self, logits, con_states, budgets):
        ln = self.ln
        logits = constrain_logits(logits, self.table, con_states, ln["constrained"], self.min_close, budgets)
        return masked_logits(logits, ln["top_ks"], ln["top_ps"], self.wanted)

    def _stops(self, toks):
        hit = jnp.zeros(toks.shape, bool)
        for st in self.stop_toks:
            hit = hit | (toks == st)
        return hit

    def propose(self, q_logits):
        _tokens, _seq_lens, con_states, budgets, _active = self.lanes
        with scopes.layer("sample"), jax.named_scope("spec_accept"):
            q_logits = self._masked(q_logits, con_states, budgets)
            temps = self.ln["temps"]
            drawn = jax.random.categorical(self.key_draft, q_logits / jnp.maximum(temps, 1e-6)[:, None], axis=-1)
            draft = jnp.where(temps <= 0.0, jnp.argmax(q_logits, axis=-1), drawn).astype(jnp.int32)
            return draft, q_logits

    def accept(self, logits, draft, q_logits):
        tokens, seq_lens, con_states, budgets, active = self.lanes
        constrained = self.ln["constrained"]
        with scopes.layer("sample"), jax.named_scope("spec_accept"):
            # the second row is judged in the state the draft would leave
            drafted_state = advance_constraint(self.table, con_states, constrained, draft)
            p_logits = jnp.stack([self._masked(logits[:, 0], con_states, budgets),
                                  self._masked(logits[:, 1], drafted_state, budgets - 1)], axis=1)
            kept, first, second = speculative_sample(p_logits, q_logits, draft, self.key_accept, self.ln["temps"])
            # the second token lands only where the lane would have lived on after the first
            both = active & kept & ~self._stops(first) & (budgets > 1) & (seq_lens + 2 < self.max_ctx)
            emitted = jnp.where(active, 1 + both.astype(jnp.int32), 0)
            out = jnp.stack([jnp.where(active, first, -1), jnp.where(both, second, -1)], axis=1)
            last = jnp.where(both, second, first)
            con_states = advance_constraint(self.table, con_states, constrained & active, first)
            con_states = advance_constraint(self.table, con_states, constrained & both, second)
            seq_lens, budgets = seq_lens + emitted, budgets - emitted
            live = active & ~self._stops(last) & (budgets > 0) & (seq_lens + 1 < self.max_ctx)
            self.after = (jnp.where(active, last, tokens), seq_lens, con_states, budgets, live)
            return out, emitted, kept


def make_draft_block(step_fn, stop_toks: tuple, max_ctx: int, block_size: int):
    """The K-step decode block of a family that drafts by itself
    (``models.programs`` ``draft_step``): :func:`make_decode_block`'s carry,
    lanes and hand-back, around a verify-and-draft step that commits up to
    ``draft_rows`` tokens a lane. Lengths, budgets, constraint states, stops and
    the context's edge advance by the emitted count on the device; the
    block's tokens are ``[block, lanes, draft_rows]`` with -1 where a step
    emitted none, so one fetch hands the host each lane's tokens and their
    counts. What the drafter carries from step to step (and from block to
    block) is in the family's cache, which is donated and handed back with
    the lanes."""

    def decode_block(params, cache, lanes, key, table, min_close, *extra):
        ln = DECODE.unpack(lanes)
        with scopes.layer("sample"):
            wanted = masks_wanted(ln["top_ks"], ln["top_ps"], ln["active"])

        def step(carry, _):
            cache, tokens, seq_lens, con_states, budgets, active, rng = carry
            with scopes.layer("sample"):
                rng, sub = jax.random.split(rng)
            sampler = _DraftSampler(sub, tokens, seq_lens, con_states, budgets, active, ln, wanted,
                                    table, min_close, stop_toks, max_ctx)
            cache, out, _emitted, *_ = step_fn(params, cache, tokens, seq_lens, active, sampler, *extra)
            return (cache, *sampler.after, rng), out

        with scopes.layer("sample"):
            first_key = dispatch_key(key, ln["n"], ln["chain"])
        (cache, tokens, seq_lens, con_states, budgets, active, _), toks = jax.lax.scan(
            step,
            (cache, ln["tokens"], ln["seq_lens"], ln["con_states"], ln["budgets"],
             ln["active"], first_key),
            None, length=block_size,
        )
        lanes = DECODE.update(
            lanes, tokens=tokens, seq_lens=seq_lens, con_states=con_states,
            budgets=budgets, active=active, chain=ln["chain"] + 1,
        )
        return cache, toks, con_states, lanes

    return decode_block


class Engine:
    @_init_phase
    def __init__(
        self,
        config: LlamaConfig | str = "bench-1b",
        params: Optional[dict] = None,
        tokenizer: Optional[Tokenizer] = None,
        mesh=None,
        max_slots: int = 64,
        max_ctx: int = 2048,
        prefill_buckets: Sequence[int] = (64, 128, 256, 512, 1024, 2048),
        prefill_batch_max: int = 8,  # burst admissions batch up to this many prompts
        width_buckets: Sequence[int] = (1, 2, 4, 8, 16, 32),  # low-occupancy decode widths
        prefix_cache_entries: int = 4,  # 0 disables (slot: KV copies; paged: shared pages)
        decode_block_size: int = 8,
        kv_layout: str = "slot",  # "slot" | "paged"
        page_size: int = 16,
        kv_pages: int = 0,  # paged: total pages (0 = slot-equivalent capacity)
        # admission-queue cap: a submission arriving with max_queue requests
        # already waiting (submit queue + admission deque) is SHED
        # (EngineOverloadedError -> REST 503 + Retry-After) instead of
        # queueing unboundedly. 0 = unbounded (tests, embedded use).
        max_queue: int = 0,
        # chunked prefill + unified token-budget scheduler: > 0 splits every
        # prefill into chunks of at most this many tokens that CO-SCHEDULE
        # with decode blocks and speculative verify dispatches — one long
        # prompt no longer head-of-line-blocks every decoding slot for its
        # whole prefill. Greedy outputs are byte-identical chunked on vs off
        # (chunks only re-shape WHEN prompt KV is written, never what is
        # sampled). 0 = off (the default): the whole prefill runs at
        # admission, exactly the pre-chunking engine. Paged layout rounds
        # the chunk up to a page multiple (non-final chunks must commit
        # whole pages); values above the largest prefill bucket clamp to it.
        prefill_chunk: int = 0,
        # per-dispatch-cycle token budget the scheduler spends across
        # {pending prefill chunks, decode block, draft verify}. 0 = auto:
        # active_decoding_slots * decode_block_size + prefill_chunk *
        # prefilling_slots (every mid-prefill slot advances one chunk per
        # cycle while decode runs every cycle). The budget is a throttle on
        # prefill aggressiveness, not a hard gate: decode always dispatches,
        # and at least one chunk advances per cycle so neither side can
        # starve the other. Only meaningful with prefill_chunk > 0.
        token_budget: int = 0,
        # model-free speculative decoding (prompt lookup): per slot, an
        # n-gram drafter proposes up to spec_len tokens from earlier
        # occurrences in prompt + generated-so-far, and ONE batched verify
        # dispatch scores every position — accepted prefix + one corrected
        # token land per dispatch instead of one token per model step.
        # Greedy outputs are byte-identical to spec_len=0 (the accept op
        # emits the VERIFIED argmax at every position; drafts only decide
        # how many positions commit). 0 disables (the default).
        spec_len: int = 0,
        spec_ngram: int = 3,  # longest n-gram the drafter matches on
        # fused megastep dispatch: a busy chunked cycle's work — pending
        # mid-prefill chunks, final-chunk continuation prefills, and the
        # decode block (or the speculative verify pass) — compiles into ONE
        # program, so the steady-state cycle issues a single device
        # dispatch instead of 1 + #chunk-batches + #final-batches. Greedy
        # outputs are byte-identical megastep on or off (the phases are the
        # same model programs, cache-threaded in the same order; only the
        # dispatch boundary moves). False = the PR 7 split dispatches, kept
        # for A/B. Inert while nothing is mid-prefill (the plain decode /
        # verify iteration is already one dispatch).
        megastep: bool = True,
        # admission-time chunk-rate planner (engine/planner.py): deadline
        # requests get a per-cycle chunk quota (tokens remaining / cycles
        # until deadline) instead of the flat one-chunk-per-cycle cadence.
        # Reprojected on preempt-resume and park-adopt. Inert without
        # deadlines and under multi-host coordination (leader-local wall
        # clock, same rule as EDF ordering).
        rate_planner: bool = True,
        # scheduler autopilot (engine/planner.py): every
        # planner.AUTOPILOT_INTERVAL busy cycles, steer prefill_chunk /
        # token_budget / spec_len one bounded step from the flight
        # recorder's phase attribution + budget utilization + spec
        # acceptance. Off by default; constructor-disabled under
        # coordination (host-local wall-clock inputs would fork lockstep).
        autopilot: bool = False,
        # parked-slot lifetime: a slot parked at generation end (see
        # _Request.park) that no follow-up turn adopts within this window
        # is released. 0 disables parking entirely. Parking is also
        # disabled under multi-host coordination — the expiry decision is
        # wall-clock and would fork lockstep (same rule as deadlines).
        park_max_s: float = 30.0,
        # host-RAM KV offload tier (ops/paged.py HostKVPool): > 0 bounds a
        # host pool that preemption, park expiry, and mid-prefill deadline
        # drops swap their written KV rows into INSTEAD of discarding them
        # — re-admission swaps the rows back (a device->host->device copy)
        # rather than re-running the whole prefill. Entries are matched by
        # rid (preempt -> resume) or by token-prefix (a later request
        # re-sending the same conversation/persona). Greedy outputs are
        # byte-identical swap on or off (restored KV is a bit-exact copy of
        # what recompute would produce). 0 = off: exactly today's
        # discard-and-recompute behavior. CLI: --tpu-host-kv-bytes.
        host_kv_bytes: int = 0,
        # async host-KV prefetch (paged layout): after each restore chunk
        # commits, the NEXT chunk's rows are staged host->device with
        # non-blocking device puts so the copy overlaps model compute; the
        # scatter into pages happens inside the next cycle's dispatch
        # window (megastep-absorbed when fused). The first restore chunk
        # stays on the blocking path (it anchors the host_swap_slow/error
        # fault ordering), and any stage that is stale, mismatched, or
        # aborted by engine.prefetch_error degrades to the blocking copy —
        # byte-identical on or off; only swap_stall_s / the host_stall
        # flight phase shrink. Inert in the slot layout and when
        # host_kv_bytes=0.
        host_prefetch: bool = True,
        # cross-request shared-prefix page dedup (paged layout only): at
        # admission, a request whose page-aligned prompt prefix matches a
        # live slot's row (or an earlier member of the same admission
        # group) refcount-SHARES those prompt pages instead of allocating
        # a private copy — N concurrent tasks on one agent persona hold 1
        # copy of its pages, not N. Writes past the shared prefix go to
        # fresh pages, so decode never mutates a shared page; greedy
        # outputs are byte-identical dedup on or off. Inert in the slot
        # layout (per-slot context rows cannot be shared).
        prefix_dedup: bool = True,
        # armed runtime invariant checker (engine/invariants.py): audit the
        # engine's host-side bookkeeping — page-accounting conservation,
        # mirror counters vs recomputed truth, slot state legality — after
        # every dispatch cycle, crashing the engine on the first violation
        # instead of serving corrupt state. None reads $ACP_INVARIANTS; off
        # by default and one plain-bool branch per loop iteration when
        # disarmed (the fault seam's near-free posture).
        check_invariants: Optional[bool] = None,
        quantize: Optional[str] = None,  # "int8" = weight-only int8 serving
        # alias for quantize="int8" matching the CRD/CLI knob names
        # (--tpu-quantize-weights / LLM.spec.tpu.quantizeWeights)
        quantize_weights: bool = False,
        # int8 KV cache with per-row-per-head scales (both layouts): write
        # paths quantize on commit, attention dequantizes after the gather,
        # so a fixed HBM page/slot budget holds ~2x the tokens and the
        # host-RAM tier + shared-prefix dedup carry the quantized bytes
        # (both multipliers compound). UNLIKE every other serving knob this
        # legitimately relaxes greedy byte-identity — outputs are gated by
        # the pinned accuracy fixture (engine/accuracy.py; top-1 greedy
        # agreement + logit-MAE bounds vs the bf16 path) instead. Off (the
        # default) stays bit-for-bit identical to the pre-quantization
        # engine. CLI: --tpu-quantize-kv; CRD: LLM.spec.tpu.quantizeKv.
        quantize_kv: bool = False,
        seed: int = 0,
        # Multi-host lockstep serving (engine/coordination.py): rank 0
        # passes a CoordinationLeader (it drains the submit queue and
        # broadcasts per-iteration admission frames); other ranks pass a
        # CoordinationFollower (they replay the frame stream — their
        # submit() is disabled). None = single-host (the default).
        coordination: Optional[object] = None,
    ):
        from ..xla_cache import enable_persistent_compilation_cache

        enable_persistent_compilation_cache()
        # Every op the engine's programs lower keeps its name stack, not
        # ten Python frames: the frames ride into the HLO's metadata, and
        # the profiler pays for them on every op event when a trace is
        # stopped — a 6 s trace of the 7B's decode took 45.6 s to stop with
        # them, 30.8 s without (PERF.md, PR 28), and that time grows with
        # every step the engine gets faster. Kernel and program names (what
        # trace readers match) are unchanged.
        jax.config.update("jax_traceback_in_locations_limit", 0)
        self._gc_frozen = False  # prewarm froze the heap; stop() gives it back
        self._coordination = coordination
        self._coord_follower = coordination is not None and hasattr(coordination, "recv")
        self.decode_block_size = max(1, decode_block_size)
        if kv_layout not in ("slot", "paged"):
            raise ValueError(f"kv_layout must be 'slot' or 'paged', got {kv_layout!r}")
        self.kv_layout = kv_layout
        self.page_size = page_size
        self.page_lookahead_blocks = PAGE_LOOKAHEAD_BLOCKS
        if isinstance(config, str):
            config = preset(config)
        self.config = config
        # the one seam to the model family's programs (models/__init__.py)
        self._model = programs(config)
        self._has_state = self._model.has_state
        # a second cache a slot, the window layers' ring (models/mellum.py):
        # fixed to the slot, written by the programs alone, copied nowhere
        self._window_cache = self._model.window_cache
        # device counters a family keeps in its cache (None: it keeps none)
        self._counters = self._model.counters
        # tokens a lane may commit a decode step: 1, or what a family that
        # drafts by itself verifies a step (models.programs `draft_step`)
        self._step_rows = self._model.draft_rows if self._model.draft_step is not None else 1
        self.tokenizer = tokenizer or ByteTokenizer()
        self.max_slots = max_slots
        self.max_ctx = min(max_ctx, config.max_seq_len)
        if self.max_ctx < max_ctx:
            log.warning(
                "max_ctx %d clamped to the model's max_seq_len %d — prompts "
                "beyond it are tail-truncated (and skip the prefix cache)",
                max_ctx, config.max_seq_len,
            )
        self.prefill_buckets = [b for b in prefill_buckets if b <= self.max_ctx] or [
            self.max_ctx
        ]
        self.mesh = mesh if mesh is not None else serving_mesh()
        from jax.sharding import NamedSharding, PartitionSpec as _P

        # all per-dispatch host->device uploads go through _put as
        # mesh-replicated GLOBAL arrays: identical on a single host, and
        # required for coordinated multi-host serving, where every process
        # contributes the same replicated value (a plain jnp.asarray would
        # make a process-local array that cannot mix with the mesh-global
        # cache/params in one dispatch)
        self._replicated = NamedSharding(self.mesh, _P())
        # upload guard for _put (see its docstring): identity copy that
        # breaks CPU zero-copy aliasing between numpy and XLA buffers.
        # CPU-only — TPU/GPU device_put never aliases the host buffer, and
        # the copy would transiently double device memory for the largest
        # array. Assigned before ANY _put call — __init__ uploads state.
        self._jit_upload_copy = (
            jax.jit(jnp.copy) if jax.default_backend() == "cpu" else None
        )
        tp = dict(self.mesh.shape).get("tp", 1)
        sp = dict(self.mesh.shape).get("sp", 1)
        # what the family does not serve it says itself (models.programs
        # `refusals`): refused here, in words, and never served wrongly
        asked = {
            "kv_layout": kv_layout, "spec_len": spec_len, "tp": tp, "sp": sp,
            "quantize_weights": bool(quantize) or quantize_weights, "quantize_kv": bool(quantize_kv),
            "coordination": coordination is not None, "host_kv_bytes": host_kv_bytes,
            # the most rows one prefill dispatch stacks before its commit,
            # beside the rows the paged pool holds (a family whose cache is
            # deeper than its weights bounds the one by the other)
            "prefill_rows": max(1, prefill_batch_max) * max(self.prefill_buckets),
            "pool_rows": (kv_pages * page_size) or (max_slots * self.max_ctx + page_size),
        }
        for hit, why in self._model.refusals(asked):
            if hit:
                raise ValueError(
                    f"the {self._model.family} family does not serve with {why}"
                )
        if tp > 1 and self.config.n_kv_heads % tp:
            raise ValueError(
                f"n_kv_heads={self.config.n_kv_heads} cannot shard over tp={tp} "
                "(MQA/GQA KV heads must divide tp — serve gemma-2b-style MQA "
                "models with tp=1)"
            )
        if sp > 1:
            # context parallelism: the slot cache's ctx dim shards over sp
            # (kv_cache_specs); the paged pools shard their WITHIN-PAGE dim
            # over sp (every rank holds a 1/sp slice of every page, so page
            # gathers stay rank-local and prefix-page sharing is preserved
            # — the attention reductions keep (page, offset) unmerged and
            # compile to per-shard partials + tiny all-reduces, pinned by
            # tests/parallel/test_context_parallel_serving.py)
            if kv_layout == "paged" and self.page_size % sp:
                raise ValueError(
                    f"page_size={self.page_size} must be divisible by the "
                    f"mesh's sp={sp} for context-parallel paged serving"
                )
            if self.max_ctx % sp:
                raise ValueError(
                    f"max_ctx={self.max_ctx} must be divisible by the mesh's "
                    f"sp={sp} for context-parallel serving"
                )
        if self.config.attn_logit_softcap and kv_layout == "paged":
            raise ValueError(
                "gemma-2-style models (attention soft-cap) serve "
                "with kv_layout='slot' — the paged attention kernel has no "
                "soft-cap path"
            )
        if self.config.sliding_window and self.max_ctx > self.config.sliding_window:
            raise ValueError(
                f"max_ctx={self.max_ctx} exceeds this model's sliding window "
                f"({self.config.sliding_window}): the llama family keeps one "
                "cache for every layer and no window cache, so gemma-2's "
                "alternating local layers are exact only within one window — "
                "lower --tpu-ctx to the window size (a family with a window "
                "cache beside its pages, models/mellum.py, serves past it)"
            )
        self.prefill_batch_max = max(1, prefill_batch_max)
        # decode dispatch widths: smallest bucket covering the active slots
        # (each width is its own jit cache entry; keep the set small so cold
        # compiles stay bounded). max_slots is always a member.
        self.width_buckets = sorted(
            {w for w in width_buckets if 0 < w < max_slots} | {max_slots}
        )

        t0 = time.monotonic()
        if quantize not in (None, "int8"):
            raise ValueError(f"unsupported quantization {quantize!r}")
        if quantize_weights:
            quantize = "int8"
        self.quantize_kv = bool(quantize_kv)
        if params is None or quantize == "int8":
            # the engine makes or quantizes weights itself: a phase of its own
            with self.profiler.setup("init.params"):
                if params is None and quantize == "int8" and tp == 1:
                    # host-side quantized random init: the device-init path below
                    # peaks at the FULL bf16 model + one tensor (16GB for 8B — by
                    # itself a whole v5e chip); this one only ever places int8+scales
                    from .weights import random_quantized_init

                    params = random_quantized_init(config, seed=seed)
                elif params is None:
                    _init = self._model.init_params
                    abstract = jax.eval_shape(lambda k: _init(config, k), jax.random.key(0))
                    shardings = (
                        # a family that gives no layout of its own over a mesh is
                        # held whole (tp=1 only: it refuses the rest above)
                        jax.tree_util.tree_map(lambda _: self._replicated, abstract)
                        if self._model.shardings is None
                        else self._model.shardings.params(self.mesh, config, abstract)
                    )
                    params = jax.jit(
                        lambda k: _init(config, k), out_shardings=shardings
                    )(jax.random.key(seed))
                if quantize == "int8":
                    # Quantize per-matrix, dropping each bf16 original as its int8
                    # replacement lands (in-place layer-dict mutation) so peak device
                    # memory is the bf16 params + ONE extra tensor. For big
                    # checkpoints prefer load-time quantization (weights.py
                    # quantize="int8"), which never materializes bf16 at all; already
                    # -quantized leaves are skipped here.
                    from ..ops.quant import QUANTIZABLE, QuantizedTensor, quantize as _q

                    layers = params["layers"]
                    for key in QUANTIZABLE:
                        if not isinstance(layers[key], QuantizedTensor):
                            layers[key] = jax.jit(_q)(layers[key])
        self.quantize = quantize
        self.params = params
        # per-device bytes held by weights (QuantizedTensor leaves flatten
        # to their int8 values + f32 scales, so this is the SERVED
        # footprint — the observable ~2x of quantize_weights). A sharded
        # leaf's .nbytes is the GLOBAL logical size, so sum per-shard bytes
        # per device and take the max — the per-chip HBM cost (tp-sharded
        # leaves count 1/tp per chip, replicated leaves their full size).
        # Immutable after init.
        per_device: dict = {}
        for leaf in jax.tree_util.tree_leaves(params):
            shards = getattr(leaf, "addressable_shards", None)
            if not shards:
                per_device[None] = per_device.get(None, 0) + int(
                    getattr(leaf, "nbytes", 0)
                )
            else:
                for s in shards:
                    per_device[s.device] = (
                        per_device.get(s.device, 0) + int(s.data.nbytes)
                    )
        self.weight_bytes = int(max(per_device.values(), default=0))
        REGISTRY.gauge_set(
            "acp_engine_weight_bytes", float(self.weight_bytes),
            help="per-device bytes held by model weights as served, max "
            "across local devices (int8 values + scales under "
            "quantize_weights, bf16 otherwise)",
        )
        if self.kv_layout == "paged":
            if self.max_ctx % self.page_size:
                raise ValueError(
                    f"page_size {self.page_size} must divide max_ctx {self.max_ctx}"
                )
            bad = [b for b in self.prefill_buckets if b % self.page_size]
            if bad:
                raise ValueError(
                    f"prefill buckets {bad} are not multiples of page_size {self.page_size}"
                )
            self.max_pages_per_seq = self.max_ctx // self.page_size
            self.num_pages = kv_pages or (max_slots * self.max_pages_per_seq + 1)
        self._init_kv_state()
        if self.kv_layout == "paged":
            # Compiled pallas path on real TPU (tp>1 goes through the
            # shard_map wrapper over head-sharded pages — GSPMD treats
            # pallas_call as opaque); CPU uses the exact XLA reference
            # (interpret-mode kernel equivalence is in tests). The kernel
            # takes head widths 64, 128 and 256 (paged_attention.py
            # heads_per_window): a KV head's [P, d] column window of the
            # page buffer whole where head_dim is a multiple of the 128-lane
            # width (128 for llama/qwen/mistral, 256 for gemma), two KV
            # heads to a lane window at 64 (lfm2: bf16 or f32 pages, an even
            # number of KV heads a chip); each compiled for a described v5e
            # in tests/engine/test_chip_compile.py. What is still refused
            # (other widths such as the tiny CPU-test configs', an odd head
            # count or int8 pages at 64) falls back to the exact XLA gather
            # reference — on a TPU that is a finding, counted below, and
            # chip_smoke.py fails on it.
            # sp>1 composes: each context-parallel rank runs the kernel
            # over its page slices (pos_base masking) and the unnormalized
            # (acc, m, l) states merge across ranks with one pmax + two
            # [S, H]-sized psums (paged_attention.py *_sp_sharded).
            # quantize_kv rides the kernel too: the int8 page walk DMAs the
            # f32 scale rows with each fetch and applies them in VMEM
            # (paged_attention.py), so the pool stays int8 in HBM and decode
            # keeps the no-gather path.
            # The walk is the family's (models.programs `walk`): the page
            # walk over K and V pages at a head geometry, or the latent
            # walk over a row a token. Its width is static, chosen by the
            # kernel from what it sees on one device (rows of a page a rank
            # holds, page dtype, KV heads a chip): report the G this
            # engine's walks compile with, so a geometry that fell to one
            # page a turn is seen in stats() and the log, not guessed, and
            # beside it what the walk keeps started ahead of the turn it
            # folds, from the same rule that sizes its scratch. None: no
            # kernel takes this geometry (0 in stats(): the reference).
            axes = dict(self.mesh.shape)
            leaf = self.cache[self._model.page_leaf]  # the walk's own: [L, num_pages, page_size, ...]
            rows = leaf.shape[2] // axes.get("sp", 1)  # of a page, a rank
            walk = self._model.walk(config, rows, leaf.dtype, tp, self.quantize_kv)
            self._use_pallas = jax.default_backend() == "tpu" and walk is not None
            if jax.default_backend() == "tpu" and not self._use_pallas:
                reason = "head_dim"
                log.warning(
                    "paged kv_layout on TPU without the Pallas kernel: %s; "
                    "decode uses the XLA gather reference (materializes the "
                    "gathered context every step)",
                    f"head_dim {config.head_dim} with {config.n_kv_heads // tp} "
                    f"KV heads a chip{' and int8 pages' if self.quantize_kv else ''} "
                    "is none of the walk's geometries (128 or 256 wide, or 64 "
                    "wide with bf16/f32 pages and an even number of KV heads; "
                    "a latent row: whole pages of rows whose value is whole lane tiles)",
                )
                # a silent perf cliff deserves a first-class signal: count
                # it and drop a flight breadcrumb so dashboards and dumps
                # show WHY decode is on the slow path (docs/observability.md).
                REGISTRY.counter_add(
                    "acp_engine_kernel_fallbacks_total",
                    1.0,
                    labels={"kernel": "paged_decode", "reason": reason},
                    help="accelerator kernel paths that fell back to the XLA "
                    "reference at engine init (kernel= which kernel, reason= "
                    "why); 0 on a healthy TPU deployment — the quantized "
                    "paged-decode path dispatches the int8 Pallas walk",
                )
                self.flight.record("kernel_fallback", kernel="paged_decode", reason=reason)
            self.pages_per_turn = self.turns_in_flight = self.bytes_in_flight = 0
            if self._use_pallas:
                self.pages_per_turn, self.turns_in_flight, self.bytes_in_flight = walk
                log.info(
                    "paged decode: Pallas page walk, pages_per_turn=%d "
                    "(%d tokens a turn), turns_in_flight=%d, bytes_in_flight=%d",
                    self.pages_per_turn, self.pages_per_turn * rows,
                    self.turns_in_flight, self.bytes_in_flight,
                )
        log.info("engine init: params+cache in %.1fs", time.monotonic() - t0)

        # computed ON device (jit + out_shardings) rather than device_put so
        # the replicated key is valid under multihost meshes too. It is never
        # split on the host: every program mixes its dispatch's counter (a
        # row of the packed lanes, engine/lanes.py) into it, so a dispatch
        # draws from a key no other dispatch has and nothing but the model
        # programs runs on the device. Followers replay the leader's
        # dispatches and so count alike.
        self._base_key = jax.jit(
            lambda: jax.random.key(seed), out_shardings=self._replicated
        )()
        self._dispatch_n = 0
        # the slot a fused phase's padding lanes name: out of range for a
        # family with per-slot state, so their state writes are dropped
        self._pad_slot = self.max_slots if self._has_state else 0
        self._queue: "queue.Queue[Optional[_Request]]" = queue.Queue()
        # admission order is strict FIFO: requests the pool can't fit yet
        # stay at the head of this deque (no starvation of large requests)
        import collections

        self._waiting: "collections.deque[_Request]" = collections.deque()
        self._outstanding: set = set()  # undone futures; failed on crash
        self._slots: dict[int, _Slot] = {}
        self._free = list(range(max_slots))
        # host mirrors of per-slot device state
        self._seq_lens = np.zeros(max_slots, dtype=np.int32)
        self._last_tokens = np.zeros(max_slots, dtype=np.int32)
        self._temps = np.zeros(max_slots, dtype=np.float32)
        self._top_ks = np.zeros(max_slots, dtype=np.int32)
        self._top_ps = np.ones(max_slots, dtype=np.float32)
        # grammar constraint: per-slot automaton state (lazy-built table)
        self._con_states = np.zeros(max_slots, dtype=np.int32)
        self._constrained = np.zeros(max_slots, dtype=bool)
        # table width = TOKENIZER vocab; the model's vocab (logits width)
        # may be larger — those extra logits are simply forbidden under
        # constraint (constrain_logits pads the gathered rows)
        # prefix KV cache (slot layout): LRU of prompt-prefix -> device KV
        # [L, cut, H_kv, d] (the paged layout shares pages instead). Agent workloads re-send growing conversations
        # with identical system prompts; a hit copies the cached KV into the
        # slot and prefills only the suffix — per-turn prefill becomes
        # O(new tokens) instead of O(whole conversation).
        import collections as _collections

        # A family with a window cache keeps no prefix entry, shares no
        # prompt page between live slots and parks no slot: each would hand
        # a request full-layer pages whose window cache it has not (a copy
        # is 45 MB a slot at Mellum2's widths, and a ring cut back to a
        # page-aligned length has lost the rows before it). These three are
        # leave to reuse where reuse is possible, as they are inert in the
        # slot layout or under coordination; here it never is, so they are
        # off, and stats() shows them off.
        if self._window_cache and (prefix_cache_entries > 0 or prefix_dedup or park_max_s > 0):
            log.info(
                "the %s family keeps a window cache a slot: prefix entries, "
                "prefix dedup and parked slots are off for it",
                self._model.family,
            )
            prefix_cache_entries, prefix_dedup, park_max_s = 0, False, 0.0
        self._prefix_enabled = prefix_cache_entries > 0  # acp: mirror (immutable)
        self._prefix_cache_entries = prefix_cache_entries  # acp: mirror (immutable)
        self._prefix_cache: "_collections.OrderedDict[tuple, dict]" = (
            _collections.OrderedDict()
        )
        # engine thread mutates; stats() reads from REST threads
        self._prefix_lock = threading.Lock()
        self._jit_copy_prefix: dict[int, Any] = {}
        self._jit_extract_prefix: dict[int, Any] = {}
        self._prefix_hits = 0
        self._prefix_misses = 0
        # continuation batch sizes actually dispatched (prewarm coverage
        # is verified against this, not assumed from submit timing)
        self._cont_batch_sizes: set[int] = set()
        self._spill_batch_sizes: set[int] = set()
        self._chunk_batch_sizes: set[int] = set()  # KV-only chunk dispatches
        # plain prefill (bucket, B) pairs dispatched — each is its own
        # compiled program; prewarm's mid-batch phase verifies against this
        self._full_batch_shapes: set[tuple[int, int]] = set()
        self._token_table = None
        self._min_close = None
        self._table_lock = threading.Lock()
        self._dummy_table = self._put(np.full((1, self.config.vocab_size), -1, dtype=np.int32))
        self._dummy_min_close = self._put(np.zeros((1,), dtype=np.int32))
        # remaining sampled-token budget per slot (budget-aware constraint)
        self._budgets = np.zeros(max_slots, dtype=np.int32)
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        self._crashed = False
        # crashes since a request last finished (engine thread only): the
        # crash-loop bound in _run's handler reads it
        self._crash_streak = 0
        self._restart_lock = threading.Lock()
        # rids whose callers abandoned the request (client timeout/disconnect);
        # slots are released at the next engine-loop iteration so orphaned
        # generations don't pin capacity to max_tokens
        self._cancelled: set[str] = set()
        # the cancel set the ENGINE LOOP consumes. Single-host it is the
        # same object as _cancelled; under coordination it holds only
        # rids that have been replicated through the frame stream, so every
        # rank applies cancels at the same iteration (lockstep).
        self._applied_cancels: set[str] = (
            self._cancelled if coordination is None else set()
        )
        self._admission_held = 0  # hold depth; see hold_admission()
        self._admission_lock = threading.Lock()  # guards the depth counter
        # device-resident decode state (see _decode_once): None until the
        # first block; _state_dirty forces a re-upload of the host mirrors
        # whenever slot assignment changed (admission/finish/cancel/restart)
        self._dev: Optional[dict] = None
        self._state_dirty = True
        self._tables_dirty = True
        self.decode_steps = 0
        self.tokens_generated = 0
        self.table_uploads = 0  # paged: block-table host->device re-uploads
        # dispatches that sample (decode blocks, verify passes, prefill
        # groups), and those of them in which a live lane asked for top-k /
        # top-p, so that the program ran that threshold search (ops/sampling.py)
        self.sampling_dispatches = 0
        self.sampling_topk_dispatches = 0
        self.sampling_topp_dispatches = 0
        self.max_queue = max(0, max_queue)
        self.preemptions = 0  # pool-pressure preempt-and-resume events
        # chunked prefill + unified token-budget scheduler (see _dispatch_once
        # / _prefill_chunks). Both knobs are plain mutable attributes read
        # per admission/cycle so benches and tests can A/B them on one
        # engine (the chunk loop reuses the continuation programs the
        # legacy spill path already compiles).
        self.prefill_chunk = max(0, int(prefill_chunk))
        self.token_budget = max(0, int(token_budget))
        self._prefilling_count = 0  # acp: mirror — int mirror for cross-thread stats()
        self.prefill_chunks = 0  # chunk dispatches (per-slot chunks)
        self.hol_wait_s = 0.0  # decode-stall seconds attributable to prefill
        # enqueue -> first admission, summed over requests (prewarm and
        # resumes excluded), and how many: the scheduler's queue wait
        self.queue_wait_s = 0.0
        self.queue_admits = 0
        # (budget, tokens spent) last cycle — replaced atomically as a whole
        # tuple, never mutated in place, so scrape reads are torn-free
        self._budget_last = (0, 0)  # acp: mirror
        self._budget_spent_total = 0  # acp: mirror
        self._budget_total = 0  # acp: mirror
        # speculative decoding state/counters (see _decode_spec)
        self.spec_len = max(0, int(spec_len))
        self.spec_ngram = max(1, int(spec_ngram))
        self.spec_proposed = 0  # draft tokens sent to verification
        self.spec_accepted = 0  # draft tokens the model agreed with
        self.spec_dispatches = 0  # verify dispatches issued
        # fused megastep dispatch (see _megastep_dispatch). _fuse_pending
        # carries one cycle's planned-but-undispatched chunk work from
        # _prefill_chunks to the decode/verify dispatch site; it never
        # survives a cycle (every _decode_once entry consumes it).
        self.megastep = bool(megastep)
        self.megastep_max_programs = MEGASTEP_MAX_PROGRAMS
        self._fuse_pending: Optional[dict] = None
        self._megastep_shapes: set[tuple] = set()  # fused shapes dispatched
        self.megastep_dispatches = 0  # fused program dispatches issued
        self.megastep_fallbacks = 0  # cycles split-dispatched (shape bound)
        # admission-time chunk-rate planner + autopilot (engine/planner.py)
        from .planner import Autopilot, AutopilotLimits, CycleClock

        self.rate_planner = bool(rate_planner)
        self._cycle_clock = CycleClock()
        self.quota_projections = 0  # rate plans issued (admit + reproject)
        self.quota_reprojections = 0  # reprojections (resume/adopt)
        self.autopilot_enabled = bool(autopilot) and coordination is None
        self._autopilot = (  # acp: mirror (immutable; stats reads plain ints off it)
            Autopilot(
                AutopilotLimits(
                    chunk_min=self.page_size if kv_layout == "paged" else 8,
                    chunk_max=self.prefill_buckets[-1],
                    budget_max=4 * self.max_slots * self.decode_block_size
                    + 4 * self.prefill_buckets[-1],
                    spec_len_max=16,
                ),
            )
            if self.autopilot_enabled
            else None
        )
        # gray-failure instrumentation: the dispatch watchdog (see _stall_check)
        self.stall_mult = STALL_MULT
        self.stall_min_s = STALL_MIN_S
        self.stalls = 0  # dispatch cycles the watchdog judged stalled
        self.sheds = 0  # admission sheds (bounded queue / fault site)
        self._cycle_s = 0.0  # acp: mirror — cycle EWMA snapshot for stats()
        # fastest busy cycle seen: the stall baseline. The EWMA seeds on
        # the first (compile-heavy) cycles and decays with alpha=0.1, so
        # judging against it leaves the watchdog deaf for dozens of
        # cycles after start; the min converges to honest cadence after a
        # single fast cycle and a slow cycle can never inflate it.
        self._cycle_floor = 0.0
        # overlapped tool execution (see _stream / _park). _parked_count is
        # a plain int mirror of "slots in _slots with parked=True" so
        # cross-thread readers (stats()) never iterate the engine-mutated
        # dict — same racy-but-safe ints-only contract as the other stats.
        self._parked_count = 0  # acp: mirror
        self.park_max_s = 0.0 if coordination is not None else max(0.0, park_max_s)
        # KV memory tiers (see _swap_out/_swap_in_rows and _collect_group's
        # dedup-leader scan). The host pool and allocator are engine-thread
        # -owned; stats() reads the mirror ints below instead.
        from ..ops.paged import HostKVPool

        self.host_kv_bytes = max(0, int(host_kv_bytes))
        self._host_pool = (
            HostKVPool(self.host_kv_bytes) if self.host_kv_bytes else None
        )
        # mutable for bench A/B (the swap-in stall scoreboard flips it
        # between runs); read per restore chunk, so a flip applies to the
        # next chunk boundary, never mid-copy
        self.host_prefetch = bool(host_prefetch)
        self.prefix_dedup = bool(prefix_dedup)
        # fleet tier (fleet/router.py): replica identity assigned at pool
        # registration — read by the fleet.replica_crash fault match in
        # _run — and the cross-thread handoff inject queue: any thread
        # enqueues HostKVEntry objects via inject_host_kv; the engine
        # thread lands them in the host pool at the top of _fill_slots,
        # BEFORE admission matching, so inject-then-submit ordering
        # guarantees the entry is visible to the submitted request.
        self.fleet_replica_id: Optional[str] = None
        self._kv_inject: "queue.Queue" = queue.Queue()
        self.kv_injects = 0  # handoff entries landed in the host pool
        self.kv_swap_outs = 0  # KV rows offloaded to the host tier (events)
        # per-slot state beside the pages (state families): copies taken of
        # a slot's saved state (prefix entries, host swaps, handoffs),
        # states installed into a slot before a continuation, and prefix,
        # dedup or host hits refused for want of a state saved at their cut
        self.state_saves = 0
        self.state_restores = 0
        self.state_refused = 0
        self._jit_install_state = None
        self._jit_saved_state = None
        # the family's device counters (self._counters): the newest copy a
        # program gave back beside the cache (_with_counters), what stats()
        # last read of it, and the sum of their differences (np.uint64)
        self._counters_snap = None
        self._counters_seen = None
        self._counters_total = None
        self._counters_lock = threading.Lock()
        self.kv_swap_ins = 0  # host-tier restores (swap-in completions)
        self.prefix_shares = 0  # admissions that refcount-shared prompt pages
        self._host_kv_used = 0  # acp: mirror — host pool bytes in use
        self._host_kv_entries = 0  # acp: mirror — host pool entry count
        self._prefix_shared_pages = 0  # acp: mirror — pages with refcount > 1
        # jitted swap helpers, keyed by power-of-two size so compile counts
        # stay logarithmic (extract/restore decompose into pow2 chunks)
        self._jit_swap_gather: dict[int, Any] = {}  # paged: page gather
        self._jit_swap_scatter: dict[int, Any] = {}  # paged: page scatter
        self._jit_swap_extract: dict[int, Any] = {}  # slot: row slice out
        self._jit_swap_restore: dict[int, Any] = {}  # slot: row slice in
        self.tool_calls_early = 0  # calls emitted before generation ended
        self.tool_overlap_saved_s = 0.0  # sum of (finish - emit) per early call
        self.parks = 0  # slots parked at generation end
        self.park_adoptions = 0  # parked slots adopted by a follow-up turn
        self.park_releases = 0  # parked slots released (pressure/expiry/stop)
        self._admit_seq = 0  # monotonically increasing admission stamp
        # fault-injection seam (faults.FAULTS): near-free when disabled —
        # every hook is guarded by the plain-bool ``enabled`` attribute
        from ..faults import FAULTS as _faults

        self._faults = _faults
        self.check_invariants = (
            bool(check_invariants)
            if check_invariants is not None
            else os.environ.get("ACP_INVARIANTS", "") not in ("", "0")
        )

        self._build_jitted()

    def _put(self, x) -> jax.Array:  # acp: megastep-seam — upload guard, not a model program
        """One host array to the device, replicated: THE upload of the
        engine thread, counted in ``stats()["perf"]["uploads"]``. A dispatch
        makes few of them: its per-lane scalars go as one packed buffer
        (engine/lanes.py), beside the token rows, the page ids and, when
        they changed, the block tables."""
        self.profiler.count_upload()
        if jax.process_count() > 1:
            # multihost: device_put cannot target non-addressable devices;
            # every process supplies its local shards of the same replicated
            # value (the coordination layer guarantees the values match)
            arr = np.asarray(x)
            out = jax.make_array_from_callback(
                arr.shape, self._replicated, lambda idx: arr[idx]
            )
        else:
            out = jax.device_put(x, self._replicated)
        # CPU backend: device_put may ZERO-COPY alias the host numpy buffer.
        # Feeding that alias into the donation-heavy dispatch pipeline lets
        # XLA reuse memory the Python heap also owns — observed as
        # nondeterministic greedy outputs / host-mirror corruption under
        # timing jitter. A jitted identity copy forces an XLA-owned buffer
        # (one compile per shape/dtype; shapes are bucketed and bounded).
        if self._jit_upload_copy is not None:
            return self._jit_upload_copy(out)
        return out

    def _count_sampling(self, wanted: tuple) -> None:
        """One sampling dispatch whose lanes' ``masks_wanted`` is ``wanted``:
        the question the program asks of the same numbers on the chip."""
        self.sampling_dispatches += 1
        self.sampling_topk_dispatches += bool(wanted[0])
        self.sampling_topp_dispatches += bool(wanted[1])

    def _next_key_n(self) -> int:
        """The counter of the next dispatch that draws: it rides the
        dispatch's lanes and the program mixes it into the base key
        (lanes.dispatch_key)."""
        n = self._dispatch_n
        self._dispatch_n = (n + 1) % (1 << 31)
        return n

    # -- jitted programs -------------------------------------------------

    @_in_setup("init.programs")
    def _build_jitted(self):
        """Two jitted programs per layout: prefill+first-sample, and the
        K-step decode block (one dispatch advances all slots K tokens,
        amortizing host round trips; inactive slots neither advance
        nor write; the host truncates each slot's [K] tokens at its first
        stop token). The block builder is shared across layouts — only the
        per-step cache update differs."""
        config = self.config
        stop_toks = tuple(sorted({int(t) for t in self.tokenizer.stop_tokens}))

        def make_verify(verify_fn):
            """Speculative verify + on-device accept in one dispatch: the
            multi-token continuation machinery scores every draft position,
            then ``speculative_accept`` walks them with the SAME constraint
            masking / stop / budget semantics as the decode block — greedy
            emission at every position is the verified argmax, so spec-on
            greedy output is byte-identical to spec-off. One fetch returns
            (tokens, emitted counts, constraint states)."""
            from ..ops.sampling import speculative_accept

            def verify_block(params, cache, inputs, lanes, key, table, min_close, *extra):
                ln = VERIFY.unpack(lanes)
                constrained = ln["constrained"]
                cache, logits = verify_fn(
                    params, cache, inputs, ln["n_input"], ln["starts"], *extra
                )
                with scopes.layer("sample"):
                    out_toks, n_emit, new_states = speculative_accept(
                        logits, inputs, ln["n_input"], ln["active"],
                        dispatch_key(key, ln["n"]), ln["temps"], ln["top_ks"],
                        ln["top_ps"], stop_toks, ln["budgets"], ln["force_reject"][0],
                        constrain_fn=lambda l, s, b: constrain_logits(
                            l, table, s, constrained, min_close, b
                        ),
                        advance_fn=lambda s, t, take: jnp.where(
                            take, advance_constraint(table, s, constrained, t), s
                        ),
                        con_states=ln["con_states"],
                    )
                return cache, out_toks, n_emit, new_states

            return verify_block  # raw; jitted standalone AND fused below

        def make_megastep(mid_fn, final_fn, decode_block, verify_block,
                          plain_fn=None):
            """The fused per-cycle program (see _megastep_dispatch): one
            compiled dispatch runs [staged swap-in scatters] -> [mid-chunk
            KV writes] -> [plain full-prompt prefill + first-token sample]
            -> [final-chunk continuation prefill + first-token sample] ->
            [decode block | speculative verify], with the cache threaded
            phase to phase so the write/read ordering is exactly the split
            path's dispatch order. Each phase is the SAME raw body the
            split programs jit standalone (the swaps phase is literally
            _swap_in_rows' scatter expression; plains run the plain causal
            program's raw body, byte-for-byte the chunked-off dispatch),
            so per-phase math is identical and greedy outputs stay byte-
            identical. Absent phases pass None (an empty pytree: presence
            is part of the trace, so every phase combination is its own
            compiled shape — bounded by megastep_max_programs). swaps is a
            tuple of (page_ids, blocks) pow2 scatter groups; the restored
            slots' pages are disjoint from every other phase's (page
            ownership is per-slot), so phase order among the prefill
            phases cannot change bytes. Every phase takes its per-lane
            scalars as one packed buffer (engine/lanes.py) and derives its
            key from ``key``, the engine's base key, and the counter in its
            own lanes. Donation: the cache and the decode lanes, matching
            the split decode block's in-place reuse; dec_aux (the grammar
            table, min_close, the block tables) is host-retained across
            blocks and must NOT donate. plain_fn is None in the
            slot layout — plains/swaps only absorb under paged KV (their
            padding lanes need TRASH_PAGE routing to stay harmless)."""

            def megastep(params, cache, key, swaps, mids, plains, finals,
                         dec_lanes, dec_aux, ver):
                p_out = f_out = d_out = v_out = None
                if swaps is not None:
                    for s_ids, s_blocks in swaps:
                        cache = {**cache, **{
                            name: set_pages(cache[name], s_ids, s_blocks[name])
                            for name in s_blocks
                        }}
                if mids is not None:
                    cache = mid_fn(params, cache, *mids)
                if plains is not None:
                    lanes, tables = plains
                    cache, *p_out = plain_fn(params, cache, *lanes, key, *tables)
                if finals is not None:
                    lanes, tables = finals
                    cache, *f_out = final_fn(params, cache, *lanes, key, *tables)
                if dec_lanes is not None:
                    table, min_close, extra = dec_aux
                    cache, *d_out = decode_block(
                        params, cache, dec_lanes, key, table, min_close, *extra
                    )
                if ver is not None:
                    inputs, lanes, *rest = ver
                    cache, *v_out = verify_block(params, cache, inputs, lanes, key, *rest)
                return cache, p_out, f_out, d_out, v_out

            return self._with_counters(megastep)(
                lambda f: jax.jit(f, donate_argnums=(1, 7))
            )

        has_state = self._has_state

        if self.kv_layout == "paged":
            decode_step_paged = self._model.decode_step_paged
            prefill_paged_batch = self._model.prefill_paged_batch
            prefill_paged_continue = self._model.prefill_paged_continue
            prefill_paged_continue_kv = self._model.prefill_paged_continue_kv

            use_pallas = self._use_pallas

            def page_arg(page_ids, ln):
                # a family with per-slot state reads which slot's state each
                # row carries and where its snapshot is due beside the ids
                return (page_ids, (ln["slots"], ln["snap_at"])) if has_state else page_ids

            def prefill_and_sample(params, pages, tokens, lanes, page_ids, key, table, min_close):
                ln = PREFILL.unpack(lanes)
                pages, logits = prefill_paged_batch(
                    params, pages, tokens, ln["lengths"], page_arg(page_ids, ln), config
                )
                toks, states = sample_lanes(logits, key, ln, table, min_close)
                return pages, toks, states

            self._jit_prefill_paged = self._with_counters(prefill_and_sample)(
                lambda f: jax.jit(f, donate_argnums=(1,))
            )

            def paged_continue_and_sample(params, pages, tokens, lanes, page_ids, block_tables, key, table, min_close):
                ln = PREFILL.unpack(lanes)
                pages, logits = prefill_paged_continue(
                    params, pages, tokens, ln["lengths"], ln["starts"],
                    page_arg(page_ids, ln), block_tables, config,
                )
                toks, states = sample_lanes(logits, key, ln, table, min_close)
                return pages, toks, states

            self._jit_prefill_paged_continue = self._with_counters(
                paged_continue_and_sample
            )(lambda f: jax.jit(f, donate_argnums=(1,)))

            def paged_continue_kv(params, pages, tokens, lanes, page_ids, block_tables):
                ln = PREFILL.unpack(lanes)
                return prefill_paged_continue_kv(
                    params, pages, tokens, ln["lengths"], ln["starts"],
                    page_arg(page_ids, ln), block_tables, config,
                )

            mesh = self.mesh
            draft_step = self._model.draft_step
            if draft_step is not None:
                # the family's own verify-and-draft step in the block's place
                # for the one-token step: same lanes, same carry, same name
                decode_block = make_draft_block(
                    lambda params, pages, tokens, seq_lens, active, sampler, block_tables: draft_step(
                        params, pages, tokens, seq_lens, block_tables, active, sampler, config,
                        use_pallas=use_pallas, mesh=mesh,
                    ),
                    stop_toks, self.max_ctx, self.decode_block_size,
                )
            else:
                decode_block = make_decode_block(
                    lambda params, pages, tokens, seq_lens, active, block_tables: decode_step_paged(
                        params, pages, tokens, seq_lens, block_tables, active, config,
                        use_pallas=use_pallas, mesh=mesh,
                    ),
                    stop_toks, self.max_ctx, self.decode_block_size,
                )
            self._jit_decode_paged = self._with_counters(decode_block)(
                lambda f: jax.jit(f, donate_argnums=(1, 2))
            )
            # spec_len > 0 is refused for a family without a verify program
            verify_paged_continue = getattr(self._model, "verify_paged_continue", None)
            verify_block = make_verify(
                lambda params, pages, inputs, n_input, starts, block_tables: verify_paged_continue(
                    params, pages, inputs, n_input, starts, block_tables, config
                )
            )
            self._jit_verify = jax.jit(verify_block, donate_argnums=(1,))
            self._jit_megastep = make_megastep(
                paged_continue_kv, paged_continue_and_sample, decode_block,
                verify_block, plain_fn=prefill_and_sample,
            )
        else:
            prefill_batch = self._model.prefill_batch
            decode_step = self._model.decode_step
            prefill_continue = self._model.prefill_continue
            prefill_continue_kv = self._model.prefill_continue_kv

            def prefill_and_sample(params, cache, tokens, lanes, key, table, min_close):
                ln = PREFILL.unpack(lanes)
                cache, logits = prefill_batch(
                    params, cache, tokens, ln["lengths"], ln["slots"], config
                )
                toks, states = sample_lanes(logits, key, ln, table, min_close)
                return cache, toks, states

            self._jit_prefill = jax.jit(prefill_and_sample, donate_argnums=(1,))

            def continue_and_sample(params, cache, tokens, lanes, key, table, min_close):
                ln = PREFILL.unpack(lanes)
                cache, logits = prefill_continue(
                    params, cache, tokens, ln["lengths"], ln["starts"], ln["slots"], config
                )
                toks, states = sample_lanes(logits, key, ln, table, min_close)
                return cache, toks, states

            self._jit_prefill_continue = jax.jit(continue_and_sample, donate_argnums=(1,))

            def continue_kv(params, cache, tokens, lanes):
                ln = PREFILL.unpack(lanes)
                return prefill_continue_kv(
                    params, cache, tokens, ln["lengths"], ln["starts"], ln["slots"], config
                )

            decode_block = make_decode_block(
                lambda params, cache, tokens, seq_lens, active: decode_step(
                    params, cache, tokens, seq_lens, config, active=active
                ),
                stop_toks, self.max_ctx, self.decode_block_size,
            )
            self._jit_decode = jax.jit(decode_block, donate_argnums=(1, 2))
            verify_continue = self._model.verify_continue
            verify_block = make_verify(
                lambda params, cache, inputs, n_input, starts: verify_continue(
                    params, cache, inputs, n_input, starts, config
                )
            )
            self._jit_verify = jax.jit(verify_block, donate_argnums=(1,))
            self._jit_megastep = make_megastep(
                continue_kv, continue_and_sample, decode_block, verify_block
            )

    # -- public API ------------------------------------------------------

    @_in_setup("init.pool")
    def _init_kv_state(self) -> None:
        """(Re)build the device KV cache and host allocator state — shared
        by __init__ and crash recovery (ensure_running) so the restart path
        can never diverge from fresh construction. With the profiler on it
        waits for the pool, so that the ``init.pool`` phase is the pool's
        whole cost (its init program, then the device filling it) and not
        its enqueue."""
        self._dev = None
        self._state_dirty = True
        self._tables_dirty = True
        if self.kv_layout == "slot":
            self.cache = jax.jit(  # acp: donated
                lambda: self._model.init_kv_cache(
                    self.config, self.max_slots, self.max_ctx,
                    quantize_kv=self.quantize_kv,
                ),
                out_shardings=kv_cache_shardings(self.mesh, self.quantize_kv),
            )()
        else:
            from ..ops.paged import PageAllocator

            init_cache = lambda: self._model.init_paged_cache(  # noqa: E731
                self.config, self.num_pages, self.page_size,
                quantize_kv=self.quantize_kv, max_slots=self.max_slots,
            )
            if self._model.shardings is None:
                # the family's own pool layout and whatever tree it keeps
                # beside the pages under "state", whole on every device
                # (tp=1, refused otherwise at construction)
                page_shardings = jax.tree_util.tree_map(
                    lambda _: self._replicated, jax.eval_shape(init_cache)
                )
            else:
                page_shardings = self._model.shardings.paged_pool(self.mesh, self.quantize_kv)
            # a family's device counters start from 0 again (and wrap at
            # 2**32): stats() sums their differences into _counters_total
            self._counters_seen = None
            self._counters_snap = None
            self.cache = jax.jit(  # acp: donated
                init_cache, out_shardings=page_shardings
            )()
            # what one page costs over every cache layer and leaf (scale
            # twins among them), read off the pool and not off the config
            self.page_bytes = page_bytes(self.cache, self.num_pages)  # acp: mirror (immutable)
            self.page_leaves = sorted(  # acp: mirror (immutable) — the leaves those bytes are summed over
                name for name, a in pool_leaves(self.cache).items() if a.shape[1] == self.num_pages)
            self._allocator = PageAllocator(
                self.num_pages, track_scales=self.quantize_kv
            )
            self._slot_pages: dict[int, list[int]] = {}
            # a family with a window cache: the slots whose ring is held,
            # and by which request (taken at admission with the slot's first
            # pages, given back where its pages are)
            self._window_rings: dict[int, str] = {}
            self._rings_held = 0  # acp: mirror — len(_window_rings), for stats()
            self._block_tables = np.full(
                (self.max_slots, self.max_pages_per_seq), TRASH_PAGE, dtype=np.int32
            )
        # the cache's depth, off the leaf (a family's pool may be deeper than its weights)
        leaf = self.cache[self._model.page_leaf] if self.kv_layout == "paged" else _a_leaf(self.cache)
        self.cache_layers = int(leaf.shape[0])  # acp: mirror (immutable)
        if self.profiler.enabled:
            jax.block_until_ready(self.cache)

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stopping = False
        self._thread = threading.Thread(target=self._run, name="tpu-engine", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        # the restart lock serializes against an in-flight crash recovery;
        # clearing _crashed makes a deliberate stop final (no resurrection
        # by a late ensure_running)
        with self._restart_lock:
            self._crashed = False
            if self._gc_frozen:
                # what prewarm froze goes back to the collector with the engine
                self._gc_frozen = False
                gc.unfreeze()
            if self._thread is None:
                return
            self._stopping = True
            self._queue.put(None)
            if self._coord_follower:
                # the loop may be parked in recv(); closing the channel
                # unblocks it, and _admit treats it as a clean stop
                self._coordination.close()
            self._thread.join(timeout=30)
            self._thread = None

    def ensure_running(self) -> bool:
        """Crash recovery (the phase-machine-and-requeue posture of the
        control plane, applied to the data plane): if the engine loop died
        on an exception — NOT a user stop() — rebuild the device-side
        serving state (KV cache, page tables, slot bookkeeping; params are
        untouched) and restart the loop. Callers' failed requests were
        already resolved with errors; the control plane's 5s requeue then
        retries them against the recovered engine. Returns True when the
        engine is running."""
        with self._restart_lock:
            if self._crashed:
                # the crashed thread may still be draining; the restart must
                # own the loop exclusively, so a wedged drain defers recovery
                # to the caller's next retry rather than racing it
                if self._thread is not None:
                    self._thread.join(timeout=30)
                    if self._thread.is_alive():
                        log.error("crashed engine thread still draining; deferring restart")
                        return False
            elif self._thread is not None and self._thread.is_alive():
                return True
            else:
                return False  # deliberately stopped; stay stopped
            log.warning("engine crashed; rebuilding serving state and restarting")
            self._init_kv_state()
            self._slots = {}
            self._parked_count = 0
            self._prefilling_count = 0
            self._publish_park_gauge()
            self._free = list(range(self.max_slots))
            self._waiting.clear()
            self._cancelled.clear()
            self._applied_cancels.clear()
            self._seq_lens[:] = 0
            self._last_tokens[:] = 0
            self._con_states[:] = 0
            self._constrained[:] = False
            self._budgets[:] = 0
            with self._prefix_lock:
                self._prefix_cache.clear()  # entries reference the old arrays only; safe either way
            # host-tier entries SURVIVE a crash rebuild: they are token-
            # derived KV copies, valid against the fresh cache — a
            # control-plane retry of a failed request prefix-matches them
            self._publish_memory_state()
            self._crashed = False
            self._stopping = False
            self._thread = threading.Thread(target=self._run, name="tpu-engine", daemon=True)
            self._thread.start()
            REGISTRY.counter_add("acp_engine_restarts_total", 1.0)
            self.flight.record("restart")
            return True

    def submit(
        self,
        prompt: str | list[int],
        sampling: Optional[SamplingParams] = None,
        on_tokens=None,
        timeout_s: Optional[float] = None,
        on_tool_call=None,
        park: bool = False,
        trace=None,
        _prewarm: bool = False,
        export_kv: bool = False,
    ) -> Future:
        """Thread-safe; returns a Future[GenerationResult]. ``on_tokens``
        (optional) streams newly sampled token ids per decode block from the
        engine thread — keep it non-blocking. ``timeout_s`` propagates the
        caller's deadline into the admission queue: a request still queued
        when it expires fails fast (DeadlineExceededError) without wasting
        prefill. ``_prewarm`` requests bypass the prefix cache entirely (no
        entries, no counters) and are exempt from the queue cap.

        Overlapped tool execution: ``on_tool_call`` is invoked from the
        engine thread as ``(index, MessageToolCall)`` the moment a streamed
        tool call's closing brace is decoded — while the model is still
        generating — so callers can start executing it immediately. The
        emitted calls (with timestamps) are also exposed on the returned
        future as ``early_tool_calls``. ``park=True`` keeps the slot parked
        after a normal finish so the conversation's next turn prefills only
        its suffix (see docs/serving-engine.md "Overlapped tool
        execution"). Neither knob changes WHAT is generated — greedy output
        is byte-identical with them on or off.

        ``export_kv=True`` (fleet prefill/decode disaggregation) extracts
        the prompt's written KV at finish and attaches it to the result as
        ``GenerationResult.kv_handoff`` — a ``HostKVEntry`` a decode
        replica restores via :meth:`inject_host_kv`. Export supersedes
        parking (the entry, not the slot, is the reuse unit)."""
        tokens = self.tokenizer.encode(prompt) if isinstance(prompt, str) else list(prompt)
        if export_kv and self._window_cache:
            raise ValueError(
                f"the {self._model.family} family does not serve with export_kv: "
                "a handoff entry carries a slot's pages and not its window cache"
            )
        s = sampling or SamplingParams()
        prefix_len = len(s.forced_prefix)
        # keep the prompt's TAIL and reserve room to actually generate —
        # otherwise a context-filling prompt leaves a 1-token budget and
        # every response (and any forced tool call) truncates immediately
        reserve = min(s.max_tokens, max(1, self.max_ctx // 2))
        budget = max(1, self.max_ctx - prefix_len - reserve)
        truncated = len(tokens) > budget or _prewarm
        if len(tokens) > budget:
            tokens = tokens[-budget:]
        req = _Request(
            rid=uuid.uuid4().hex[:8],
            prompt=tokens,
            sampling=sampling or SamplingParams(),
            future=Future(),
            on_tokens=on_tokens,
            truncated=truncated,
            deadline=(time.monotonic() + timeout_s) if timeout_s else None,
            on_tool_call=on_tool_call,
            # truncated prompts keep their suffix, not their prefix: the
            # next turn's prompt can never extend them, so parking would
            # pin pages that no adoption can ever use
            park=bool(park) and self.park_max_s > 0 and not truncated
            and not export_kv,
            trace=trace,
            prewarm=bool(_prewarm),
            export_kv=bool(export_kv) and not _prewarm,
        )
        if on_tool_call is not None:
            from .toolparse import ToolStreamParser

            req.tool_parser = ToolStreamParser()
        req.future.early_tool_calls = req.early_calls  # type: ignore[attr-defined]
        # rid rides the future from birth — cancel() keys on it, and a shed
        # request's flight timeline is only findable through it
        req.future.rid = req.rid  # type: ignore[attr-defined]
        if self._coord_follower:
            # any locally-originated request (prewarm included) would break
            # lockstep — followers only replay the leader's frame stream
            req.future.set_exception(RuntimeError(
                "coordinated follower engines do not accept submissions "
                "(submit through rank 0's engine)"
            ))
            return req.future
        if self._thread is None or self._stopping:
            req.future.set_exception(RuntimeError("engine is not running"))
            return req.future
        if not _prewarm:
            # persona fingerprint: the same first-64-token hash the fleet
            # router keys affinity on, so single-engine trace export
            # (observability/trace_export.py) captures the prefix-sharing
            # mix without retaining any prompt content
            persona = hashlib.sha1(
                repr(tokens[:64]).encode()
            ).hexdigest()[:16] if self.flight.enabled else ""
            self.flight.record(
                "submit", rid=req.rid, prompt_tokens=len(tokens),
                timeout_s=timeout_s, park=req.park, key=persona,
            )
        # bounded admission: shed instead of queueing unboundedly. Depth is
        # a racy-but-safe over/under-count by at most the in-flight burst;
        # the cap is an overload valve, not an exact semaphore.
        if not _prewarm:
            forced_full = self._faults.enabled and self._faults.pop(
                "engine.queue_full"
            ) is not None
            depth = self._queue.qsize() + len(self._waiting)
            if forced_full or (self.max_queue and depth >= self.max_queue):
                self.sheds += 1
                REGISTRY.counter_add("acp_engine_shed_requests_total", 1.0)
                self.flight.record("shed", rid=req.rid, depth=depth)
                req.future.set_exception(EngineOverloadedError(
                    f"admission queue full ({depth} waiting, cap "
                    f"{self.max_queue}); retry later",
                    # rough drain estimate: a slot-time per queued request,
                    # floored at 1s — advisory, clients may back off harder
                    retry_after_s=max(1.0, min(30.0, depth * 0.25)),
                ))
                self.flight.discard(req.rid)  # timeline ends at the shed
                return req.future
        self._outstanding.add(req.future)
        req.future.add_done_callback(self._outstanding.discard)
        req.future.admitted = req.admitted  # type: ignore[attr-defined]
        self._queue.put(req)
        return req.future

    @_in_setup("prewarm")
    def prewarm(self, constrained: bool = False) -> None:
        """Compile the jit entries real traffic will hit — a full-width
        burst of short generations with largest-bucket prompts covers the
        batched-prefill chunk sizes, the max-width decode block, and the
        narrow widths the tail decays through. With ``constrained``, a
        second burst compiles the grammar-masked variants (and builds the
        token table). Without this, the FIRST Task after startup pays
        20-40s of TPU compiles — fatal to the 500ms time-to-first-ToolCall
        target. Blocking; run from a background thread if startup latency
        matters more than first-request latency.

        Chunked-prefill engines run the legacy phases with chunking
        TEMPORARILY OFF (the phases' shape verification assumes the
        at-admission dispatch pattern; the continuation programs they
        compile are shared with the chunk loop), then one chunked phase
        warms the chunk-specific shapes."""
        ch, self.prefill_chunk = self.prefill_chunk, 0
        try:
            self._prewarm_phases(constrained)
        finally:
            self.prefill_chunk = ch
        if ch:
            self._prewarm_chunked(constrained)
            if self.megastep:
                self._prewarm_megastep(constrained)
        # from here on, a first-dispatch-of-shape is a compile REAL traffic
        # pays for: the profiler turns it into a cold_compile flight event
        # + acp_engine_cold_compiles_total (serving-time latency bug)
        self.profiler.mark_prewarmed()
        # Everything alive now (the traced programs' closures, jax's caches,
        # the prewarm's own requests) lives as long as the engine: out of the
        # collector's reach until stop(), so that a full collection while
        # serving walks what requests made since and not the whole heap. The
        # engine loop is serial, and a full collection over this process's
        # heap is a block-long pause the device sits through (one or two a
        # minute; PERF.md, PR 31). Process-wide: docs/serving-engine.md, "The
        # host's collector".
        with self.profiler.setup("prewarm.freeze"):
            gc.collect()
            gc.freeze()
        self._gc_frozen = True
        log.info("engine prewarm complete (constrained=%s)", constrained)

    def _prewarm_gap(self, phase: str, **detail) -> None:
        """A planned prewarm (bucket, batch) program shape never formed —
        its compile WILL happen at serving time. Promoted from a bare log
        line to data: a flight event plus a prewarm-coverage counter, so
        the gap is alertable instead of buried in startup logs."""
        log.warning(
            "prewarm: %s batch never formed (%s)",
            phase, " ".join(f"{k}={v}" for k, v in detail.items()),
        )
        self.flight.record("prewarm_gap", phase=phase, **detail)
        REGISTRY.counter_add(
            "acp_engine_prewarm_gaps_total", 1.0, labels={"phase": phase},
            help="prewarm coverage gaps: a planned (bucket, batch) program "
            "shape never formed during prewarm, so its compile will happen "
            "at serving time (pair with acp_engine_cold_compiles_total)",
        )

    @_in_setup("prewarm.chunked")
    def _prewarm_chunked(self, constrained: bool) -> None:
        """Warm the SPLIT chunk loop's shapes: multi-chunk prompts at
        every power-of-two batch size compile the KV-only chunk dispatch
        at the chunk bucket plus the final-chunk continuation buckets.
        Runs with the megastep temporarily OFF: these split programs are
        the fused path's shape-bound fallback, so they must stay warm even
        on a megastep engine (the fused shapes get their own phase,
        _prewarm_megastep)."""
        K = self.decode_block_size
        CHK = self._chunk_tokens()
        long_len = min(self.max_ctx - K - 2, CHK * 2 + max(3, CHK // 2))
        if long_len <= CHK:
            return  # every admissible prompt fits one chunk: legacy shapes cover it
        one = SamplingParams(temperature=0.0, max_tokens=1, json_only=constrained)
        ms, self.megastep = self.megastep, False
        try:
            b = 1
            while b <= min(self.prefill_batch_max, self.max_slots):
                for _attempt in range(5):
                    with self.hold_admission():
                        futs = [
                            self.submit([1] * (long_len - i), one, _prewarm=True)
                            for i in range(b)
                        ]
                    for f in futs:
                        f.result(timeout=1800)
                    if b in self._chunk_batch_sizes:
                        break
                else:
                    self._prewarm_gap("chunked", B=b)
                b *= 2
        finally:
            self.megastep = ms

    @_in_setup("prewarm.megastep")
    def _prewarm_megastep(self, constrained: bool) -> None:
        """Warm the fused megastep's core (bucket, batch, width) shapes:
        one long-running decoder keeps a decode phase live while b long
        prompts chunk through it, forming megastep[m{bucket}x{b}+d{W}x{K}]
        (and the final-chunk / chunks-only variants along the way) for
        every power-of-two b. Coverage is verified against the DISPATCHED
        shape set, with the standard prewarm_gap flight event + counter on
        a miss. Deliberately bounded: higher-occupancy decode widths and
        spec-verify fusions compile on demand and surface through the
        cold-compile observatory rather than paying a full width x batch x
        phase-set matrix at startup."""
        K = self.decode_block_size
        CHK = self._chunk_tokens()
        long_len = min(self.max_ctx - K - 2, CHK * 2 + max(3, CHK // 2))
        if long_len <= CHK:
            return  # nothing ever mid-prefills more than one chunk
        mid_bucket = _next_bucket(min(CHK, long_len), self.prefill_buckets)
        one = SamplingParams(temperature=0.0, max_tokens=1, json_only=constrained)

        def mid_formed(b: int) -> bool:
            want = f"m{mid_bucket}x{b}"
            return any(
                any(part.startswith(want) for part in sh[1])
                for sh in self._megastep_shapes
            )

        b = 1
        while b <= min(self.prefill_batch_max, max(1, self.max_slots - 1)):
            for _attempt in range(5):
                # a decoder long enough to outlive the chunk cycles keeps
                # the fused decode phase in every megastep of this burst
                decode_for = (
                    2 * K * (2 + b * -(-long_len // CHK))
                )
                anchor = self.submit(
                    [1] * max(1, self.prefill_buckets[0] - 1),
                    SamplingParams(temperature=0.0, max_tokens=decode_for),
                    _prewarm=True,
                )
                anchor.admitted.result(timeout=1800)
                steps0 = self.decode_steps
                for _ in range(30000):  # bounded poll, no wall-clock compare
                    if self.decode_steps != steps0:
                        break
                    time.sleep(0.002)
                with self.hold_admission():
                    futs = [
                        self.submit([1] * (long_len - i), one, _prewarm=True)
                        for i in range(b)
                    ]
                for f in futs:
                    f.result(timeout=1800)
                self.cancel(anchor)
                with contextlib.suppress(Exception):
                    anchor.result(timeout=1800)
                # verified AFTER the attempt (like _prewarm_chunked): a
                # shape forming on the final try must not record a gap
                if mid_formed(b):
                    break
            else:
                self._prewarm_gap("megastep", bucket=mid_bucket, B=b)
            b *= 2

    @_in_setup("prewarm.phases")
    def _prewarm_phases(self, constrained: bool = False) -> None:
        # coverage (documented, not aspirational): per mode —
        #   (a) a full-width staggered burst at the largest bucket that
        #       leaves decode room: batched prefill at the max chunk size,
        #       then decode at max width and at EVERY narrower width bucket
        #       as the staggered max_tokens drain the low slots last;
        #   (b) a full-width burst at the LARGEST bucket, 1 token each
        #       (the long-prompt burst prefill shape);
        #   (c) one B=1 prefill per bucket, SEQUENTIAL — each awaited
        #       before the next so admission can't batch them together
        #       (the shape a lone Task hits).
        # Mid-size prefill batches (B=2/4) stay cold — rare and cheap
        # relative to covering the full bucket x batch matrix.
        K = self.decode_block_size
        widths = self.width_buckets
        max_blocks = 1 + len(widths)
        decay_bucket = self.prefill_buckets[0]
        for b in self.prefill_buckets:
            if b + max_blocks * K < self.max_ctx:
                decay_bucket = b
        if constrained:
            # build the token table BEFORE any compiles: once it exists every
            # program (constrained or not) is traced against the real table
            # shape, so the unconstrained phases below warm the same entries
            # mixed traffic will hit — not a dummy-table variant that real
            # serving immediately abandons after the first constrained request
            self._get_token_table()
        # ONE pass: with the table pre-built, constrained and unconstrained
        # requests hit the same compiled programs (json_only is runtime data,
        # not a trace shape), so a second mode pass would warm nothing new
        for json_only in [constrained]:
            # phase a: staggered decay burst (barrier: the next phase must
            # find every slot free, or its batch can't form at full width)
            with self.hold_admission():
                futs = []
                for i in range(self.max_slots):
                    # slot i outlives slot j>i: the active set decays through
                    # every width bucket
                    blocks = 1 + sum(1 for w in widths if i < w)
                    sp = SamplingParams(
                        temperature=0.0, max_tokens=blocks * K + 1, json_only=json_only
                    )
                    futs.append(
                        self.submit([1] * max(1, decay_bucket - 1), sp, _prewarm=True)
                    )
            for f in futs:
                f.result(timeout=1800)
            # phase b: full-width burst at the largest bucket
            if self.prefill_buckets[-1] != decay_bucket:
                one = SamplingParams(temperature=0.0, max_tokens=1, json_only=json_only)
                with self.hold_admission():
                    futs = [
                        self.submit([1] * (self.prefill_buckets[-1] - 1), one, _prewarm=True)
                        for _ in range(self.max_slots)
                    ]
                for f in futs:
                    f.result(timeout=1800)
            # phase c: lone-request shapes, sequential so admission can't
            # batch them together
            for b in self.prefill_buckets:
                sp = SamplingParams(temperature=0.0, max_tokens=1, json_only=json_only)
                self.submit([1] * max(1, b - 1), sp, _prewarm=True).result(timeout=1800)
            # phase c2: remaining (bucket, batch) plain-prefill programs —
            # staggered arrivals (the operator's reconcile cadence) land
            # mid-size chunks (B=2/4) that the full-width bursts above never
            # form; each (bucket, B) is its own compiled program. Verified
            # against the dispatch record like phases d/e.
            one = SamplingParams(temperature=0.0, max_tokens=1, json_only=json_only)
            Bsz = 2
            while Bsz <= min(self.prefill_batch_max, self.max_slots):
                for idx, b in enumerate(self.prefill_buckets):
                    prev = self.prefill_buckets[idx - 1] if idx else 0
                    if (b, Bsz) in self._full_batch_shapes:
                        continue  # covered by an earlier phase/run
                    if b - Bsz <= prev:
                        continue  # bucket too narrow for Bsz distinct lengths
                    for _attempt in range(5):
                        with self.hold_admission():
                            futs = [
                                self.submit([1] * (b - 1 - i), one, _prewarm=True)
                                for i in range(Bsz)
                            ]
                        for f in futs:
                            f.result(timeout=1800)
                        if (b, Bsz) in self._full_batch_shapes:
                            break
                    else:
                        self._prewarm_gap("plain", bucket=b, B=Bsz)
                Bsz *= 2
            # phase d: the prefix-cache CONTINUATION program: a seed request,
            # then hitting bursts at every power-of-two batch size up to
            # min(prefill_batch_max, max_slots) (distinct tails so a burst
            # forms one conts chunk). These must go through the real cache
            # path, so they are NOT _prewarm requests; their dummy entries
            # (token-1/2 keys) and their exact hit/miss deltas are removed
            # right after.
            if self._prefix_enabled:
                # phase-d requests ride the REAL submit path (non-
                # _prewarm, to exercise the cache) — lift the admission
                # cap so a small max_queue can't shed prewarm's own burst.
                # Dedup is paused too: its leader scan would intercept the
                # same-prefix burst before the cache could, and the
                # continuation batch shapes this phase exists to compile
                # would never form.
                cap, self.max_queue = self.max_queue, 0
                dd, self.prefix_dedup = self.prefix_dedup, False
                try:
                    seed_len = self.prefill_buckets[0] + 1
                    one = SamplingParams(temperature=0.0, max_tokens=1, json_only=json_only)
                    self.submit([1] * seed_len, one).result(timeout=1800)
                    d_hits = 0
                    b = 1
                    while b <= min(self.prefill_batch_max, self.max_slots):
                        # burst formation depends on queue-drain timing: verify
                        # the batch size actually DISPATCHED and retry, rather
                        # than assuming the b submits landed in one group
                        for _attempt in range(5):
                            with self.hold_admission():
                                futs = [
                                    self.submit([1] * seed_len + [2] * (8 + i), one)
                                    for i in range(b)
                                ]
                            for f in futs:
                                f.result(timeout=1800)
                            d_hits += b
                            if b in self._cont_batch_sizes:
                                break
                        else:
                            self._prewarm_gap("continuation", B=b)
                        b *= 2
                    with self._prefix_lock:
                        for key in [
                            k for k in self._prefix_cache if set(k) <= {1, 2}
                        ]:
                            old = self._prefix_cache.pop(key)
                            if "pages" in old:
                                self._allocator.free(old["pages"])
                        self._prefix_hits = max(0, self._prefix_hits - d_hits)
                        self._prefix_misses = max(0, self._prefix_misses - 1)
                finally:
                    self.max_queue = cap
                    self.prefix_dedup = dd
            # phase e: chunked-prefill SPILL shapes (configs whose largest
            # bucket is below max_ctx): long prompts at every power-of-two
            # batch size, with the same verified-dispatch retry as phase d
            CH = self.prefill_buckets[-1]
            if CH < self.max_ctx:
                long_len = min(self.max_ctx - K - 2, CH * 2)
                one = SamplingParams(temperature=0.0, max_tokens=1, json_only=json_only)
                b = 1
                while b <= min(self.prefill_batch_max, self.max_slots):
                    for _attempt in range(5):
                        with self.hold_admission():
                            futs = [
                                self.submit([1] * (long_len + i), one, _prewarm=True)
                                for i in range(b)
                            ]
                        for f in futs:
                            f.result(timeout=1800)
                        if b in self._spill_batch_sizes:
                            break
                    else:
                        self._prewarm_gap("spill", B=b)
                    b *= 2

    def cancel(self, future: Future) -> None:
        """Abort the request behind a Future returned by :meth:`submit`.
        Thread-safe and best-effort: a waiting request is failed immediately
        on the engine thread; an active slot is freed (KV pages released) at
        the next decode iteration with finish_reason "cancelled"."""
        rid = getattr(future, "rid", None)
        # accept already-CANCELLED futures: asyncio.wait_for(wrap_future(f))
        # cancels the underlying concurrent Future before the caller's
        # except-block runs, but the slot is still decoding
        if rid is not None and (not future.done() or future.cancelled()):
            self._cancelled.add(rid)

    def generate(self, prompt: str | list[int], sampling: Optional[SamplingParams] = None) -> GenerationResult:
        """Synchronous helper (tests/benchmarks). Requires a started engine."""
        return self.submit(prompt, sampling).result(timeout=600)

    def stats(self) -> dict:  # acp: cross-thread
        """Point-in-time status snapshot (served at /v1/engine). Reads of
        engine-thread state are racy-but-safe: ints/lens only (enforced by
        the acplint thread-ownership pass against the mirror registry)."""
        out = {
            "model": {
                "dim": self.config.dim,
                "layers": self.config.n_layers,
                # the cache's depth, off the leaf: a family may keep more
                # cache layers than it has layers of weights (models/ouro.py)
                "cache_layers": self.cache_layers,
                "vocab": self.config.vocab_size,
                "quantize": self.quantize,
                "quantize_kv": self.quantize_kv,
                "weight_bytes": self.weight_bytes,
            },
            "kv_layout": self.kv_layout,
            "max_slots": self.max_slots,
            "max_ctx": self.max_ctx,
            "active_slots": self._n_active(),
            "parked_slots": self._parked_count,
            "prefilling_slots": self._prefilling_count,
            "waiting": len(self._waiting),
            "max_queue": self.max_queue,
            "preemptions": self.preemptions,
            "preempted_waiting": self._preempted_waiting(),
            "decode_block_size": self.decode_block_size,
            "decode_steps": self.decode_steps,
            "tokens_generated": self.tokens_generated,
            # gray-failure signals (fleet/health.py samples these): cycle
            # cadence EWMA, watchdog stall count, admission sheds
            "cycle_s": round(self._cycle_s, 6),
            "stalls": self.stalls,
            "sheds": self.sheds,
            # decode efficiency: tokens committed per model step. Without
            # speculation this is <= 1 (finished lanes pad blocks); with it,
            # each verify dispatch counts ONE step however many tokens land,
            # so > 1 means speculation is paying.
            "tokens_per_decode_step": (
                round(self.tokens_generated / self.decode_steps, 4)
                if self.decode_steps else 0.0
            ),
            "tool_overlap": {
                "early_calls": self.tool_calls_early,
                "overlap_saved_s": round(self.tool_overlap_saved_s, 4),
                "parks": self.parks,
                "park_adoptions": self.park_adoptions,
                "park_releases": self.park_releases,
                "park_max_s": self.park_max_s,
            },
            # unified token-budget scheduler (chunked prefill); utilization
            # is tokens dispatched / per-cycle budget — persistently low
            # means the budget is oversized for the traffic, ~1.0 with
            # waiting chunks means prefill is throttled by it
            "scheduler": {
                "chunked_prefill": self.prefill_chunk > 0,
                "prefill_chunk": self.prefill_chunk,
                "token_budget": self.token_budget,  # 0 = auto-sized
                "prefill_chunks_total": self.prefill_chunks,
                "hol_wait_seconds": round(self.hol_wait_s, 4),
                "queue_wait": {
                    "s": round(self.queue_wait_s, 6), "n": self.queue_admits,
                },
                "budget_utilization_last": (
                    round(min(1.0, self._budget_last[1] / self._budget_last[0]), 4)
                    if self._budget_last[0] else 0.0
                ),
                "budget_utilization_avg": (
                    round(min(1.0, self._budget_spent_total / self._budget_total), 4)
                    if self._budget_total else 0.0
                ),
                # fused megastep dispatch: one compiled program per busy
                # cycle instead of 1 + #chunk-batches + #final-batches
                "megastep": {
                    "enabled": self.megastep,
                    "dispatches": self.megastep_dispatches,
                    "shapes": len(self._megastep_shapes),
                    "max_programs": self.megastep_max_programs,
                    "fallbacks": self.megastep_fallbacks,
                },
                # admission-time chunk-rate planner + autopilot
                "planner": {
                    "enabled": self.rate_planner,
                    "quota_projections": self.quota_projections,
                    "quota_reprojections": self.quota_reprojections,
                    "autopilot": self.autopilot_enabled,
                    "autopilot_adjustments": (
                        self._autopilot.adjustments
                        if self._autopilot is not None else 0
                    ),
                },
            },
            "spec": {
                "enabled": self.spec_len > 0,
                "spec_len": self.spec_len,
                "ngram": self.spec_ngram,
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "acceptance_rate": (
                    round(self.spec_accepted / self.spec_proposed, 4)
                    if self.spec_proposed else 0.0
                ),
                "verify_dispatches": self.spec_dispatches,
            },
            # KV memory tiers: host-RAM offload pool occupancy + cross-
            # request shared-prefix dedup payoff (mirror ints, engine-side
            # refreshed by _publish_memory_state after every cycle)
            "memory": {
                "host_kv": {
                    "enabled": self.host_kv_bytes > 0,
                    "max_bytes": self.host_kv_bytes,
                    "used_bytes": self._host_kv_used,
                    "entries": self._host_kv_entries,
                    "swap_outs": self.kv_swap_outs,
                    "swap_ins": self.kv_swap_ins,
                    "injects": self.kv_injects,
                },
                "prefix_dedup": {
                    "enabled": self.prefix_dedup and self.kv_layout == "paged",
                    "shares": self.prefix_shares,
                    "shared_pages": self._prefix_shared_pages,
                },
                # int8 KV cache (quantize_kv): at a fixed HBM budget the
                # pool holds ~2x the tokens; compounds with the host tier
                # and dedup above (both carry the quantized bytes)
                "quantized_kv": {
                    "enabled": self.quantize_kv,
                    "pages": (
                        self._allocator.allocated_count  # acp-lint: disable=thread-ownership
                        if self.quantize_kv and self.kv_layout == "paged"
                        else 0
                    ),
                },
            },
            "mesh": {
                name: int(size)
                for name, size in zip(self.mesh.axis_names, self.mesh.devices.shape)
            },
            # flight recorder occupancy (the recorder's own methods take
            # its lock; self.flight is a public attribute, never mutated)
            "flight": self.flight.stats(),
            # compute efficiency observatory: per-program dispatch stats,
            # cold-compile tracking, goodput/waste ledger (the profiler's
            # stats() is its declared cross-thread read surface)
            "perf": self.profiler.stats(),
            # how often the sampler's threshold searches engage
            "sampling": {
                "dispatches": self.sampling_dispatches,
                "topk_dispatches": self.sampling_topk_dispatches,
                "topp_dispatches": self.sampling_topp_dispatches,
            },
        }
        if self.kv_layout == "paged":
            out["kv_pages"] = {
                "total": self.num_pages - 1,
                # free_count is len() of the allocator's free list — the
                # same atomic-len contract as len(self._waiting) below, just
                # behind a property the AST pass can't see through
                "free": self._allocator.free_count,  # acp-lint: disable=thread-ownership
                "page_size": self.page_size,
                # pages one turn of the compiled walk folds (0: no kernel)
                "pages_per_turn": self.pages_per_turn,
                # turns and bytes of K and V its fetches run ahead of the fold
                "turns_in_flight": self.turns_in_flight,
                "bytes_in_flight": self.bytes_in_flight,
                "table_uploads": self.table_uploads,
                # what one page costs over every cache layer and leaf
                "page_bytes": self.page_bytes,
                # the leaves a page is made of, by name (scale twins among them)
                "leaves": list(self.page_leaves),
            }
            model = programs(self.config)  # not the engine thread's fields: any thread asks
            if model.has_state:
                # per-slot state beside the pages: copies taken of a saved
                # state, states installed before a continuation, and hits
                # refused because no state was saved at their cut
                out["kv_pages"].update(
                    state_saves=self.state_saves,
                    state_restores=self.state_restores,
                    state_refused=self.state_refused,
                )
            if model.counters is not None:
                # the family's own blocks of stats, by key
                out.update(model.describe_counters(
                    self.config, self._read_counters()
                ))
            if model.window_cache:
                # the window layers' ring: pages fixed to a slot, the rows
                # a window layer holds of it at any context, and the slots
                # that own theirs now (every admitted slot does, until its
                # release: engine/invariants.py)
                ring = ring_size(self.config.window, self.page_size)
                out["window"].update(
                    pages_per_slot=ring,
                    rows_per_slot=ring * self.page_size,
                    slots_holding=self._rings_held,
                )
        if self._prefix_enabled:
            with self._prefix_lock:
                out["prefix_cache"] = {
                    "entries": len(self._prefix_cache),
                    "capacity": self._prefix_cache_entries,
                    "hits": self._prefix_hits,
                    "misses": self._prefix_misses,
                    "cached_tokens": self._cached_tokens_locked(),
                }
        return out

    def _with_counters(self, program):
        """``program(params, cache, ...) -> (cache, ...)`` jitted by the
        caller's ``jit``: for a family that keeps counters in its cache
        (``models.programs(...).counters``) the traced program gives a second
        copy of them back beside the cache, a buffer of its own that no later
        dispatch donates, and the engine thread keeps the newest for
        ``stats()`` to read from any thread. No program of its own, nothing
        fetched until ``stats()`` asks. Other families' programs are jitted
        as they are."""
        counters = self._counters
        if counters is None:
            return lambda jit: jit(program)

        # the program keeps its name (decode_block, prefill_and_sample, ...):
        # the device's module is called after it and trace readers match on it
        @functools.wraps(program)
        def counted(*args):
            cache, *rest = program(*args)
            with scopes.layer("commit"):
                return ((cache, counters(cache)), *rest)

        def wrap(jit):
            jitted = jit(counted)

            @functools.wraps(jitted)
            def call(*args):
                (cache, snap), *rest = jitted(*args)
                self._counters_snap = snap  # acp: engine thread; one reference store
                return (cache, *rest)

            return call

        return wrap

    def _read_counters(self):  # acp: cross-thread
        """The family's counters summed since construction (np.uint64, or
        None before the first dispatch): the device's wrap at 2**32, so the
        differences between readings are what is summed."""
        with self._counters_lock:
            snap = self._counters_snap
            if snap is not None:
                cur = np.asarray(snap).astype(np.uint64)
                seen = self._counters_seen if self._counters_seen is not None else np.zeros_like(cur)
                delta = (cur - seen) & np.uint64(0xFFFFFFFF)
                self._counters_total = (
                    delta if self._counters_total is None else self._counters_total + delta
                )
                self._counters_seen = cur
            return self._counters_total

    def _preempted_waiting(self) -> int:  # acp: cross-thread
        """Requeued-after-preemption count; tolerant of cross-thread reads
        (the engine thread mutates the deque while stats() iterates).
        Preempted requests are only ever requeued at the FRONT and fresh
        arrivals only append at the back, so they form a contiguous prefix
        — the scan stops at the first non-preempted request instead of
        walking a potentially deep backlog every decode block."""
        n = 0
        try:
            # deque iteration raises (caught below) instead of tearing —
            # the one sanctioned non-len cross-thread read in the engine
            for r in self._waiting:  # acp-lint: disable=thread-ownership
                if not r.preempt_count:
                    break
                n += 1
        except RuntimeError:  # deque mutated during iteration: racy read
            pass
        return n

    # -- engine loop -----------------------------------------------------

    def _run(self) -> None:  # acp: idle-loop
        try:
            while not self._stopping:
                # a new iteration: the profiler closes the cycle of the one
                # before and opens this one's once it has work (slots to
                # advance or requests to admit on entry, or a request that
                # arrives while _admit is parked)
                busy = self._has_work()
                self.profiler.cycle(
                    busy or (bool(self._waiting) and not self._admission_held)
                )
                admitted = self._admit(block=not busy)
                if self._stopping:
                    break
                # stall-watchdog window: everything between here and the
                # post-dispatch check counts as ONE cycle's wall time —
                # including fault-injected throttles (engine.slow_cycle),
                # which is exactly the wedge the watchdog exists to see
                t_cycle = time.monotonic()
                # after _admit, not before: the loop parks in _admit while
                # idle, so a crash armed then would otherwise fire only
                # AFTER the next request completed a full loop iteration —
                # here it fires with that request admitted but unresolved,
                # which is the recovery path worth testing
                if self._faults.enabled and self._faults.pop("engine.crash") is not None:
                    raise RuntimeError("fault injection: engine crash")
                if (
                    self._faults.enabled
                    and self.fleet_replica_id is not None
                    and self._faults.pop(
                        "fleet.replica_crash", steps=self.decode_steps,
                        match={"replica": self.fleet_replica_id},
                    ) is not None
                ):
                    # pool failover drill: only the NAMED replica dies (the
                    # match filter keeps sibling engines in the same process
                    # alive); after_steps gates it mid-decode
                    raise RuntimeError("fault injection: fleet replica crash")
                if self._faults.enabled and (admitted or self._has_work()):
                    # throttle drill: stretch scheduler cycles so wall-clock
                    # races (deadlines, mid-flight cancels) land while
                    # requests are genuinely queued/decoding — a tiny model
                    # on fast hardware otherwise outruns any realistic
                    # timer. Timing-only: sampled tokens are untouched.
                    # BUSY cycles only: _admit's idle park wakes on a short
                    # timeout, and letting those empty iterations pop would
                    # silently drain the times= budget before work arrives.
                    # match on the fleet identity (when registered) so a
                    # spec armed with replica="rN" throttles exactly the
                    # named replica — the gray-failure drill — while an
                    # unscoped spec keeps firing on any engine
                    slow = self._faults.pop(
                        "engine.slow_cycle",
                        match={"replica": self.fleet_replica_id},
                    )
                    if slow is not None:
                        time.sleep(float(slow.get("delay_s", 0.01)))
                with self.profiler.phase("admit"):
                    self._sweep_parked()
                if not self._has_work():
                    if not admitted:
                        # park sweeps / admission pressure can free shared
                        # pages or swap KV without a dispatch following —
                        # keep the memory mirrors fresh on the idle path too
                        self._publish_memory_state()
                        continue
                # the cycle's scheduling (cancels, expiries, chunk and
                # budget planning) is `admit` self time; the dispatches
                # inside open their own launch / fetch / commit
                with self.profiler.phase("admit"):
                    self._dispatch_once()
                with self.profiler.phase("publish"):
                    self._stall_check(time.monotonic() - t_cycle)
                    # memory-tier mirrors/gauges refresh BEFORE the armed audit
                    # below, so mirror-vs-truth checks see post-cycle state
                    self._publish_memory_state()
                    # goodput/waste ledger counters + ratio gauge (delta-based;
                    # the scrape path refreshes them too via stats())
                    self.profiler.publish()
                    if self._autopilot is not None:
                        self._autopilot_tick()
                    if self.check_invariants:
                        if self._faults.enabled and self._faults.pop(
                            "engine.invariant_break"
                        ) is not None:
                            # deterministic mirror corruption: prove the armed
                            # checker trips end to end (see faults.py)
                            self._parked_count += 1
                        from .invariants import check_engine_invariants

                        check_engine_invariants(self)
            if self._coordination is not None and not self._coord_follower:
                # a stop() that lands mid-iteration ends the loop at its
                # `while` before _admit drains the sentinel and publishes
                # the stop frame: the followers must get one all the same
                # (a second stop frame finds them gone, which is harmless)
                self._coordination.publish([], [], stop=True)
        except Exception as e:  # an engine crash must not hang callers
            log.exception("engine loop crashed")
            # flight-record the crash and snapshot the black box BEFORE any
            # state is torn down — the dump must show the engine as the
            # crash found it (last-N events + stats + allocator audit);
            # ACP_FLIGHT_DUMP_DIR unset (the default) skips the file
            self.flight.record("crash", error=repr(e))
            self.flight.dump_crash(self, e)
            self._slots.clear()
            self._parked_count = 0
            self._prefilling_count = 0
            self._publish_park_gauge()
            self._stopping = True
            # restartable (see ensure_running) — unless the crash repeats
            # before any request finished. That is a deterministic failure
            # (a program the chip's compiler refuses, a program that does
            # not fit): a restart rebuilds exactly the state that just
            # failed, so the engine stays down and callers get "stopped"
            # instead of a crash -> restart loop, one lap per request.
            self._crash_streak += 1
            self._crashed = self._crash_streak < _CRASH_LOOP_LIMIT
            if not self._crashed:
                log.error(
                    "engine crashed %d times without finishing a request; "
                    "staying down: %r", self._crash_streak, e,
                )
            REGISTRY.counter_add("acp_engine_crashes_total", 1.0)
            for fut in list(self._outstanding):
                if not fut.done():
                    fut.set_exception(RuntimeError(f"engine crashed: {e}"))
        self.profiler.end_cycle()
        # drain: fail any queued/waiting requests
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is not None:
                self._waiting.append(req)
        while self._waiting:
            fut = self._waiting.popleft().future
            if not fut.done():  # crash handler may have failed it already
                fut.set_exception(RuntimeError("engine stopped"))
        for slot in list(self._slots):
            self._finish(slot, "stop")
        # drop whatever live timelines the drain didn't retire (the global
        # window keeps the raw events — including for the crash dump above)
        self.flight.discard_live()

    @contextlib.contextmanager
    def hold_admission(self):
        """Deterministic batch formation: while held, submitted requests
        accumulate in the waiting deque (the engine keeps decoding active
        slots) and on release ONE admission group forms with the whole
        batch. Prewarm uses this so its (bucket, B) / continuation /
        spill batch shapes form on the first attempt instead of racing the
        engine loop's drain timing — a missed shape there is a 20-40s cold
        compile in the middle of real serving."""
        with self._admission_lock:
            self._admission_held += 1
        try:
            yield
        finally:
            with self._admission_lock:
                self._admission_held -= 1

    def _admit(self, block: bool) -> bool:
        """Move queued requests into free slots (prefill), strictly FIFO.
        Returns True if anything was admitted.

        Multi-host lockstep: the request stream is the ONLY nondeterministic
        input to admission, so the leader broadcasts each iteration's drained
        requests + cancel snapshot as a frame and followers replay it — every
        process then runs the identical pure admission logic and joins the
        identical global dispatches (see engine/coordination.py).

        An idle engine parks here: the first arrival is waited for in the
        profiler's ``park`` phase, outside any cycle, and opens the cycle;
        the rest is ``admit``. (A follower waits for the leader's frame
        inside ``admit``.)"""
        head: list = []  # what arrived while parked, sentinel included
        if (
            block and not self._waiting and not self._has_work()
            and not self._coord_follower
        ):
            with self.profiler.phase("park"):
                with contextlib.suppress(queue.Empty):
                    head.append(self._queue.get(timeout=0.05))
            if head:
                self.profiler.begin_cycle()
        with self.profiler.phase("admit"):
            return self._admit_arrivals(head)

    def _admit_arrivals(self, head: list) -> bool:
        """The admission proper (``_admit`` less the idle wait): drain the
        queue behind ``head``, apply cancels and expiries, fill slots."""
        if self._coord_follower:
            try:
                frame = self._coordination.recv()
            except (ConnectionError, OSError) as e:
                if self._stopping:  # local stop() closed the channel
                    return False
                raise RuntimeError(f"serving coordination channel lost: {e}") from e
            if frame["stop"]:
                self._stopping = True
                return False
            from .coordination import deserialize_request

            for doc in frame["reqs"]:
                self._waiting.append(deserialize_request(doc))
            self._applied_cancels.update(frame["cancels"])
            held = bool(frame.get("hold"))
        else:
            # drain the cross-thread queue into the ordered waiting deque
            drained: list[_Request] = []
            saw_stop = False
            while True:
                try:
                    req = head.pop() if head else self._queue.get_nowait()
                except queue.Empty:
                    break
                if req is None:
                    saw_stop = True
                    break
                drained.append(req)
            # the hold state is read ONCE and drives both the frame and the
            # local decision — a live re-read below could release between
            # publish and fill, desynchronizing ranks
            held = bool(self._admission_held)
            if self._coordination is not None:
                # leader: only cancels whose requests are already part of
                # the replicated stream may be published — a cancel racing
                # its own still-in-transit request would be pruned by
                # followers before the request arrives, then admitted there
                # but cancelled here. Unpublishable cancels wait in
                # _cancelled for a later frame; truly stale rids (request
                # already finished) are pruned against the in-transit queue.
                # Snapshot FIRST: cancel() adds rids from other threads with
                # no lock, so every prune below must remove only rids this
                # snapshot examined against liveness views taken AFTER it.
                # The previous live-set intersection dropped a cancel that
                # landed after the snapshots for a request submitted after
                # the transit peek — that request then decoded to max_tokens
                # uncancellable.
                # Expire BEFORE the snapshot: an expired-while-queued rid
                # then rides THIS frame's cancel list and is dropped from
                # every rank's waiting deque before _fill_slots — otherwise
                # the dead request would be prefilled once while its cancel
                # waited for the next frame.
                self._expire_deadlines()
                snapshot = set(self._cancelled)
                published_live = {r.rid for r in self._waiting}
                published_live.update(
                    sl.request.rid for sl in self._slots.values()
                )
                published_live.update(r.rid for r in drained)
                pending = snapshot & published_live
                with self._queue.mutex:
                    transit = {
                        r.rid for r in self._queue.queue if r is not None
                    }
                # pending publishes now; snapshot rids live nowhere are
                # truly stale; anything cancel() added since the snapshot
                # stays for the next iteration's examination
                self._cancelled -= pending
                self._cancelled -= snapshot - (transit | published_live)
                # publish BEFORE applying, so a crash between the two can
                # only lose work symmetrically (followers time out)
                self._coordination.publish(
                    drained, sorted(pending), stop=saw_stop, hold=held
                )
                self._applied_cancels.update(pending)
            if saw_stop:
                self._stopping = True
                # hand the drained-but-never-admitted requests to the
                # shutdown drain so their futures fail instead of hanging
                self._waiting.extend(drained)
                return False
            self._waiting.extend(drained)

        if self._applied_cancels and self._waiting:
            kept = type(self._waiting)()
            while self._waiting:
                r = self._waiting.popleft()
                if r.rid in self._applied_cancels:
                    self._applied_cancels.discard(r.rid)
                    r.future.cancel()
                    if not r.prewarm:
                        self.flight.record("cancel", rid=r.rid, where="queued")
                        self.flight.discard(r.rid)
                else:
                    kept.append(r)
            self._waiting = kept
        if self._applied_cancels:
            # purge rids that raced _finish (request already completed): a
            # stale rid could collide with a future request's rid. A rid is
            # live if its request is waiting or active — plus, single-host
            # only, still in transit in the cross-thread queue (peeked under
            # the queue mutex; without this a submit-then-cancel racing the
            # drain loses the cancel). Under coordination in-transit rids
            # are never in _applied_cancels, so the liveness rule is
            # identical on every rank.
            # snapshot-then-subtract, NOT a live intersection: single-host
            # _applied_cancels IS _cancelled, which cancel() mutates from
            # other threads — an intersection drops a cancel added after the
            # liveness views for a request still in transit
            snapshot = set(self._applied_cancels)
            live = {r.rid for r in self._waiting}
            live.update(sl.request.rid for sl in self._slots.values())
            if self._coordination is None:
                with self._queue.mutex:
                    live.update(r.rid for r in self._queue.queue if r is not None)
            self._applied_cancels -= snapshot - live

        self._expire_deadlines()
        if held:
            if not self._has_work():
                # idle hold: don't busy-spin against the submitting thread
                time.sleep(0.002)
            return False
        return self._fill_slots()

    def _expire_deadlines(self) -> None:  # acp: leader-local
        """Fail queued requests whose deadline passed — fast, before any
        prefill is spent on them. Single-host: fail in place. Coordinated
        leader: route through the replicated cancel stream (wall-clock
        decisions must not fork lockstep); followers never expire locally."""
        if self._coord_follower:
            return
        expired = [
            r for r in self._waiting
            if r.deadline is not None
            and time.monotonic() > r.deadline
            and not r.future.done()
        ]
        if not expired:
            return
        if self._coordination is not None:
            for r in expired:
                # the future lives only on the leader (followers reject
                # local submissions): resolving it here is host-local and
                # cannot fork lockstep, while the rid rides the replicated
                # cancel stream so every rank drops the request from its
                # waiting deque in the same frame. The stream's later
                # future.cancel() is a no-op on the already-failed future —
                # without this the client would see a spurious
                # CancelledError instead of the deadline 504.
                r.future.set_exception(DeadlineExceededError(
                    self._expiry_message(r)
                ))
                REGISTRY.counter_add("acp_engine_deadline_expired_total", 1.0)
                self._record_expire(r, "queued")
                self._cancelled.add(r.rid)  # rides the next published frame
            return
        gone = {id(r) for r in expired}
        kept = type(self._waiting)(r for r in self._waiting if id(r) not in gone)
        self._waiting = kept
        for r in expired:
            r.future.set_exception(DeadlineExceededError(self._expiry_message(r)))
            REGISTRY.counter_add("acp_engine_deadline_expired_total", 1.0)
            self._record_expire(r, "queued")

    def _record_expire(self, req: _Request, where: str) -> None:
        """Flight-record a deadline expiry and retire the timeline (the
        request is terminal; its phases end at the expiry)."""
        if req.prewarm:
            return
        self.flight.record("expire", rid=req.rid, where=where)
        self.flight.discard(req.rid)

    @staticmethod
    def _expiry_message(req: _Request) -> str:
        """Distinguish never-admitted expiry from expiry while requeued
        after a preemption — the latter DID spend compute and stream
        tokens, and conflating them misleads capacity debugging."""
        return (
            "deadline expired while queued (never admitted)"
            if req.first_token_at == 0.0
            else "deadline expired while requeued after preemption"
        )

    def _fill_slots(self) -> bool:
        """Admit from the waiting deque into free slots (the prefill side
        of _admit, split out so the coordinated multi-host loop can replay
        broadcast admissions without touching the local submit queue)."""
        self._drain_kv_inject()
        admitted = False
        while self._waiting and (self._free or self._has_parked()):
            group = self._collect_group()
            if not group:
                break  # head request can't fit (KV pages); FIFO, wait
            admitted = True
            for item in group:
                # starts the client's generation clock; a caller that gave
                # up (timeout/cancel) may have cancelled the future already
                with contextlib.suppress(InvalidStateError):
                    item[0].admitted.set_result(True)
            # per item: resolve the prefix-cache start (match + page
            # assembly already happened in _collect_group), then spill any
            # overlong remainder through intermediate continuation chunks
            # (chunked prefill — both layouts)
            enriched: list[list] = []  # [item, start, swap_entry, share_of]
            now = time.monotonic()  # one reading a group: queue wait ends here
            for item in group:
                req, slot, _pages, match = item
                start = 0
                swap = None
                share = None
                if match is not None and match[1].get("in_slot"):
                    # adopted parked slot: the prompt KV is already resident
                    # in THIS slot — no copy, just a suffix start offset
                    start = match[1]["cut"]
                elif match is not None and match[1].get("swap") is not None:
                    # host-tier restore: rows swap back in chunk by chunk
                    # through the budget loop (start stays 0 — prefill_pos
                    # advances as restored rows land)
                    swap = match[1]["swap"]
                elif match is not None and match[1].get("share_of") is not None:
                    # dedup follower: rows [0, cut) are the leader's
                    # refcount-shared pages — nothing to copy, but the
                    # model prefill may have to WAIT for the leader to
                    # write them (mid-prefill leader), so the follower is
                    # admitted through the prefilling path in every mode
                    start = match[1]["cut"]
                    share = (*match[1]["share_of"], start)
                elif match is not None:
                    if self.kv_layout == "slot":
                        self._copy_prefix_into_slot(slot, match[1])
                    # paged: the shared prefix pages are already in the
                    # block table; nothing to copy
                    start = match[1]["cut"]
                    self._prefix_hits += 1
                    REGISTRY.counter_add("acp_engine_prefix_cache_hit_requests", 1.0)
                elif self._prefix_enabled and not req.truncated:
                    self._prefix_misses += 1
                    REGISTRY.counter_add("acp_engine_prefix_cache_miss_requests", 1.0)
                if self._has_state:
                    # the state the suffix resumes from, into the slot's
                    # live state before any program of this admission runs
                    if swap is not None:
                        self._install_state(slot, swap.state)
                    elif match is not None and match[1].get("in_slot"):
                        self._install_state(slot, slot)  # its own snapshot
                    elif start > 0:
                        self._install_state(slot, match[1]["state"])
                    req.state_cut = self._state_cut_for(
                        req, swap.cut if swap is not None else start
                    )
                if not req.prewarm:
                    # admit = the reservation decision: slot id (+ pages in
                    # paged mode) taken, prefix-cache start resolved. In
                    # chunked mode no model compute has run yet (reserve)
                    self.flight.record(
                        "admit", rid=req.rid, slot=slot,
                        start=start, pages=len(_pages) if _pages else 0,
                        resumed=req.preempt_count > 0,
                        adopted=bool(match is not None and match[1].get("in_slot")),
                        chunked=bool(self.prefill_chunk),
                        swapped=swap is not None, shared=share is not None,
                        cycle=self.profiler.cycle_n,
                    )
                    if not req.preempt_count:
                        # a request's FIRST admission ends its queue wait
                        # (a resume's wait is preempt_stall, not queueing)
                        self.queue_wait_s += now - req.enqueued
                        self.queue_admits += 1
                enriched.append([item, start, swap, share])
            if self.kv_layout == "paged":
                # block tables must exist before spill chunks reference them
                for item in group:
                    _req, slot, pages, _m = item
                    assert pages is not None
                    self._slot_pages[slot] = pages
                    if self._window_cache:
                        # the slot's ring goes to this request with the slot
                        self._window_rings[slot] = _req.rid
                        self._rings_held = len(self._window_rings)
                    self._block_tables[slot, :] = TRASH_PAGE
                    self._block_tables[slot, : len(pages)] = pages
            if self.prefill_chunk:
                # chunked mode: admission only RESERVES (slot id + pages +
                # prefix-cache start); all prefill compute happens one chunk
                # per dispatch cycle in _prefill_chunks, interleaved with
                # decode — a long prompt never stalls decoding slots for its
                # whole prefill
                for item, start, swap, share in enriched:
                    req, slot, _pages, _m = item
                    # re-admission edges REPROJECT the chunk-rate plan:
                    # preempt->resume and park->adopt both land here
                    reason = (
                        "resume" if req.preempt_count
                        else "adopt" if _m is not None and _m[1].get("in_slot")
                        else "admit"
                    )
                    self._begin_chunked_prefill(
                        req, slot, start, swap=swap, share_of=share,
                        reason=reason,
                    )
                continue
            # host restores and dedup followers go through the prefilling
            # path even with chunking off: a restore is budget-metered and
            # a follower may wait on its leader — both drain through the
            # chunk loop (keyed on _prefilling_count, not the knob)
            deferred = [e for e in enriched if e[2] is not None or e[3] is not None]
            direct = [e for e in enriched if e[2] is None and e[3] is None]
            for item, start, swap, share in deferred:
                req, slot, _pages, _m = item
                self._begin_chunked_prefill(req, slot, start, swap=swap, share_of=share)
            with self._hol_clock():
                self._spill_long_chunks(direct)
                plain = [e for e in direct if e[1] == 0]  # cheaper causal program
                conts = [e for e in direct if e[1] > 0]  # suffix continuation
                for chunk in _pow2_chunks(plain, self.prefill_batch_max):
                    self._prefill_group([e[0] for e in chunk])
                for chunk in _pow2_chunks(conts, self.prefill_batch_max):
                    self._prefill_group(
                        [e[0] for e in chunk],
                        starts_np=np.asarray([e[1] for e in chunk], dtype=np.int32),
                    )
        return admitted

    def _spill_long_chunks(self, enriched: list[list]) -> None:  # acp: megastep-seam
        # acp: dispatch-lanes toks,starts,slots,page_ids
        """Chunked prefill, batched across the admission group: round-robin
        one largest-bucket chunk per long request per dispatch (KV writes
        only; the sampled token is discarded) until every remainder fits one
        bucket. Mutates each item's start offset in place."""
        CH = self.prefill_buckets[-1]
        while True:
            need = [
                e for e in enriched
                if len(self._full_row(e[0][0])) - e[1] > CH
            ]
            if not need:
                return
            for batch in _pow2_chunks(need, self.prefill_batch_max):
                with self.profiler.phase("launch"):
                    B = len(batch)
                    self._spill_batch_sizes.add(B)
                    toks = np.zeros((B, CH), dtype=np.int32)
                    starts = np.zeros(B, dtype=np.int32)
                    slots = np.zeros(B, dtype=np.int32)
                    for i, e in enumerate(batch):
                        (req, slot, _, _m), start = e[0], e[1]
                        toks[i] = self._full_row(req)[start : start + CH]
                        starts[i] = start
                        slots[i] = slot
                    prof_t0 = self.profiler.start()
                    page_ids = None
                    if self.kv_layout == "paged":
                        P = self.page_size
                        page_ids = np.zeros((B, CH // P), dtype=np.int32)
                        for i, e in enumerate(batch):
                            slot, start = e[0][1], e[1]
                            page_ids[i] = self._slot_pages[slot][start // P : (start + CH) // P]
                    _tok = self._continue_kv_only(
                        toks, np.full(B, CH, dtype=np.int32), starts, slots,
                        [e[0][0] for e in batch], page_ids,
                    )
                    if self.profiler.enabled:
                        # spill rounds run full CH-token rows: no bucket padding
                        self.profiler.record(
                            f"spill[{self.kv_layout},{CH}x{B}]", prof_t0,
                            out=_tok, real_tokens=B * CH, real_slots=B,
                        )
                        pre = sum(CH for e in batch if e[0][0].prewarm)
                        self.profiler.account(goodput=B * CH - pre, prewarm=pre)
                for e in batch:
                    e[1] += CH

    # -- chunked prefill + unified token-budget scheduler -----------------

    @contextlib.contextmanager
    def _hol_clock(self):
        """Attribute prefill wall time to head-of-line decode stall: while
        any slot is actively DECODING, every second spent inside a prefill
        dispatch is a second those slots' tokens arrive late. Wraps the
        legacy at-admission prefill (the monolithic stall chunking removes)
        and the chunked path's per-cycle chunk dispatches (the residual
        stall that remains), so the same metric compares both modes."""
        stalled = self._n_active() > 0
        t0 = time.monotonic()
        try:
            yield
        finally:
            if stalled:
                dt = time.monotonic() - t0
                self.hol_wait_s += dt
                REGISTRY.counter_add(
                    "acp_engine_hol_wait_seconds", dt,
                    help="seconds decoding slots were stalled behind "
                    "prefill dispatches (head-of-line blocking)",
                )

    def _chunk_tokens(self) -> int:
        """Effective chunk size: clamped to the largest prefill bucket
        (each chunk is one continuation dispatch at a compiled bucket) and,
        in paged mode, rounded UP to a page multiple — non-final chunks
        commit whole pages, so every chunk boundary must be page-aligned.
        prefill_chunk == 0 here means the knob was toggled off while slots
        were still mid-prefill (_dispatch_once drains them through the
        chunk loop regardless): drain at the largest bucket — collapsing
        to 1-token chunks would break paged page alignment and crawl."""
        ch = min(
            self.prefill_chunk or self.prefill_buckets[-1],
            self.prefill_buckets[-1],
        )
        if self.kv_layout == "paged":
            ch = -(-ch // self.page_size) * self.page_size
        return max(1, ch)

    def _begin_chunked_prefill(
        self,
        req: _Request,
        slot: int,
        start: int,
        swap: Optional[object] = None,
        share_of: Optional[tuple] = None,
        reason: str = "admit",
    ) -> None:
        """Admit a request as a PREFILLING slot: the slot id and (paged) KV
        pages are reserved and the prefix-cache start resolved, but no model
        compute has run — the unified scheduler advances it chunk by chunk.
        ``start`` rows of KV are already valid (prefix-cache copy, shared
        pages, or an adopted parked slot's resident prompt). ``swap`` is a
        host-tier entry whose rows restore through the budget loop before
        any model chunk; ``share_of`` marks a dedup follower that may wait
        on its leader's prefill (see _prefill_chunks)."""
        self._admit_seq += 1
        sl = _Slot(
            request=req,
            prompt_len=len(req.prompt),
            prefix_len=len(req.sampling.forced_prefix),
            admit_seq=self._admit_seq,
            prefilling=True,
            prefill_pos=start,
        )
        sl.prefill_row = self._full_row(req)
        sl.swap_entry = swap
        sl.share_of = share_of
        self._project_quota(slot, sl, reason)
        self._slots[slot] = sl
        self._prefilling_count += 1
        self._seq_lens[slot] = start
        self._last_tokens[slot] = 0
        self._state_dirty = True  # the lane must upload as inactive

    def _project_quota(self, slot: int, sl: _Slot, reason: str) -> None:  # acp: leader-local
        """Admission-time chunk-rate plan (engine/planner.py): convert the
        request's deadline into a per-cycle chunk quota so the prefill
        finishes by arithmetic, not EDF luck. Projected at admission and
        REPROJECTED at the re-admission edge of every displacement event —
        preempt→resume and park→adopt both re-enter here, so a displaced
        request's plan always reflects its remaining tokens and remaining
        time. Leader-local: deadlines are host wall clock, so followers
        (and every rank under coordination — the EDF fallback rule) keep
        quota 1."""
        if self._coord_follower:
            return
        sl.chunk_quota = 1
        if (
            not self.rate_planner
            or self._coordination is not None
            or sl.request.deadline is None
        ):
            return
        from .planner import project_quota

        tokens_left = max(0, len(sl.prefill_row or []) - sl.prefill_pos)
        seconds_left = sl.request.deadline - time.monotonic()
        sl.chunk_quota = project_quota(
            tokens_left,
            self._chunk_tokens(),
            seconds_left,
            self._cycle_clock.cycle_s or 0.05,
        )
        self.quota_projections += 1
        if reason != "admit":
            self.quota_reprojections += 1
            REGISTRY.counter_add(
                "acp_engine_quota_reprojections_total", 1.0,
                help="chunk-rate plans recomputed at a re-admission edge "
                "(preempt-resume / park-adopt) — each is a displaced "
                "request whose remaining-time arithmetic changed",
            )
        if not sl.request.prewarm:
            self.flight.record(
                "quota", rid=sl.request.rid, slot=slot,
                quota=sl.chunk_quota, tokens_left=tokens_left,
                seconds_left=round(max(0.0, seconds_left), 4),
                reason=reason,
            )

    def _autopilot_tick(self) -> None:
        """Scheduler autopilot (engine/planner.py): on interval
        boundaries, let the observed phase attribution steer the
        scheduling knobs one bounded step. The flight recorder graduates
        from diagnostic to controller; every adjustment is itself a
        flight event, so the control loop stays inspectable."""
        ap = self._autopilot
        if ap is None or not ap.due():
            return
        from ..observability.flight import phase_summaries

        phases = {k: v.get("p99", 0.0) for k, v in phase_summaries().items()}
        util = (
            self._budget_spent_total / self._budget_total
            if self._budget_total else 0.0
        )
        acc = (
            self.spec_accepted / self.spec_proposed
            if self.spec_proposed else None
        )
        knobs = {
            "prefill_chunk": self.prefill_chunk,
            "token_budget": self.token_budget,
            "spec_len": self.spec_len,
        }
        changes = ap.step(phases, util, acc, knobs)
        if not changes:
            return
        for knob, value in changes.items():
            setattr(self, knob, value)
        self.flight.record("autopilot", **{f"set_{k}": v for k, v in changes.items()})
        REGISTRY.counter_add(
            "acp_engine_autopilot_adjustments_total", 1.0,
            help="scheduler-knob adjustments applied by the autopilot "
            "(prefill_chunk / token_budget / spec_len steered from phase "
            "attribution, budget utilization and spec acceptance)",
        )
        log.info("autopilot adjusted knobs: %s", changes)

    def _stall_check(self, dt: float) -> None:
        """Dispatch watchdog: ``dt`` is the full busy-cycle wall time
        (fault throttles included); a cycle over ``stall_mult`` x the
        replica's normal cadence *and* over ``stall_min_s`` is a stall.
        The cadence baseline is the MIN busy-cycle time seen
        (``_cycle_floor``) — one-sided, so a slow cycle can never mask
        later stalls the way a compile-polluted EWMA would. Also
        publishes the EWMA mirror the cross-thread stats surface (and
        the fleet health sampler behind it) reads."""
        self._cycle_s = self._cycle_clock.cycle_s
        if dt > 0 and (self._cycle_floor == 0.0 or dt < self._cycle_floor):
            self._cycle_floor = dt
        base = self._cycle_floor
        if base <= 0.0 or dt < self.stall_min_s or dt < self.stall_mult * base:
            return
        self.stalls += 1
        self.flight.record("stall", cycle_s=round(dt, 4), floor_s=round(base, 5))
        REGISTRY.counter_add(
            "acp_engine_stalls_total", 1.0,
            help="dispatch cycles the engine-side watchdog judged stalled "
            "(wall time over stall_mult x the cycle-cadence EWMA and over "
            "stall_min_s) — the gray-failure signal the fleet health "
            "state machine consumes",
        )

    def _has_work(self) -> bool:
        """Anything the dispatch loop must advance: decoding or mid-prefill
        slots (parked slots are speculative capacity, not work)."""
        return len(self._slots) - self._parked_count > 0

    def _dispatch_once(self) -> None:
        """One unified scheduler cycle. Chunked-off (or nothing mid-
        prefill): exactly the legacy decode iteration. Chunked-on: spend the
        per-cycle token budget across pending prefill chunks (deadline-
        weighted order) and the decode/verify dispatch. Policy guarantees,
        pinned by tests: decode dispatches EVERY cycle active slots exist
        (never starved by pending chunks), and at least one chunk advances
        per cycle (a tight budget throttles prefill, never deadlocks it)."""
        # the planner's cycle clock runs from the phase boundary that
        # opened this call (the loop's `admit`) to the last one inside it
        # (the final commit's close): the profiler's stamps, not new reads
        t0 = self.profiler.stamp()
        if not self._prefilling_count:
            # chunked off, or nothing mid-prefill: the legacy decode
            # iteration. Keyed on _prefilling_count, not the knob: slots
            # admitted as prefilling must drain through the chunk loop even
            # if prefill_chunk was toggled off mid-flight (benches/tests
            # A/B the knob on a live engine).
            self._decode_once()
            self._cycle_clock.observe(self.profiler.stamp() - t0)
            return
        self._apply_cancels()
        self._expire_prefilling()
        n_active = self._n_active()
        decode_reserve = n_active * self.decode_block_size
        budget = self.token_budget or (
            decode_reserve + self._chunk_tokens() * max(1, self._prefilling_count)
        )
        spent = self._prefill_chunks(max(0, budget - decode_reserve))
        if self._n_active() or self._fuse_pending is not None:
            # a fused cycle enters the decode site even with nothing
            # decoding: the pending chunk lanes flush as a chunks-only
            # megastep there
            steps0 = self.decode_steps
            self._decode_once()
            if self.decode_steps > steps0:
                # block path advances K steps, a verify dispatch 1 — count
                # the dispatch's compute rows (estimate; utilization is an
                # observability aid, not an accounting invariant)
                spent += n_active * min(
                    self.decode_steps - steps0, self.decode_block_size
                )
        self._cycle_clock.observe(self.profiler.stamp() - t0)
        self._budget_last = (budget, spent)
        self._budget_spent_total += spent
        self._budget_total += budget
        REGISTRY.gauge_set(
            "acp_engine_token_budget_utilization",
            min(1.0, spent / budget) if budget else 0.0,
            help="tokens dispatched last scheduler cycle / per-cycle token "
            "budget (chunked prefill mode)",
        )

    def _apply_cancels(self) -> None:
        """Free slots whose requests were cancelled (shared by the decode
        path and the chunked scheduler — a cancelled mid-prefill slot must
        release its partial KV before more chunks are spent on it)."""
        if not self._applied_cancels:
            return
        for slot, sl in list(self._slots.items()):
            if sl.request.rid in self._applied_cancels:
                self._finish(slot, "cancelled")

    def _expire_prefilling(self) -> None:  # acp: leader-local
        """Deadline expiry for mid-prefill slots: release the partial KV
        and fail the request — spending more chunks on a dead deadline is
        pure waste. Same coordination discipline as _expire_deadlines:
        single-host releases in place; the leader resolves the future
        host-locally and routes the release through the replicated cancel
        stream; followers never expire on wall clock."""
        if self._coord_follower:
            return
        now = time.monotonic()
        expired = [
            (s, sl) for s, sl in self._slots.items()
            if sl.prefilling
            and sl.request.deadline is not None
            and now > sl.request.deadline
            and not sl.request.future.done()
        ]
        for slot, sl in expired:
            req = sl.request
            req.future.set_exception(DeadlineExceededError(
                "deadline expired mid-prefill (partial prompt KV released)"
            ))
            REGISTRY.counter_add("acp_engine_deadline_expired_total", 1.0)
            self._record_expire(req, "mid_prefill")
            if self._coordination is not None:
                self._cancelled.add(req.rid)  # rides the next published frame
            else:
                # offload the partial prompt KV before it is dropped — a
                # control-plane retry of the same task prefix-matches it
                if not self._swap_out(slot, sl, reason="expire") and not req.prewarm:
                    # dropped outright: the chunks already spent are waste
                    self.profiler.reclassify("preempt_discard", sl.prefill_pos)
                self._drop_prefilling_slot(slot)

    def _drop_prefilling_slot(self, slot: int) -> _Slot:
        """Release a mid-prefill slot's bookkeeping (partial KV pages, host
        mirrors, slot id). The caller owns resolving/requeueing the
        request."""
        sl = self._slots.pop(slot)
        self._prefilling_count -= 1
        self._unshare_followers(slot, sl)
        self._state_dirty = True
        self._seq_lens[slot] = 0
        self._last_tokens[slot] = 0
        self._con_states[slot] = 0
        self._constrained[slot] = False
        heapq.heappush(self._free, slot)
        if self.kv_layout == "paged":
            self._release_slot_pages(slot)
            self._block_tables[slot, :] = TRASH_PAGE
            self._tables_dirty = True
        return sl

    def _use_megastep(self) -> bool:
        """Fused dispatch applies: the knob is on and the cycle has chunk
        work to fuse with the decode/verify dispatch. The non-chunked
        engine never fuses — its cycle is already one dispatch."""
        return self.megastep

    def _slot_chunk_tokens(self, sl: _Slot, CHK: int) -> int:
        """Per-cycle chunk size for one mid-prefill slot. The rate
        planner's quota (chunks/cycle, engine/planner.py) collapses into
        ONE larger continuation lane of quota*CHK tokens rather than
        quota separate lanes — consecutive chunks of a slot cannot be
        lanes of the same fused dispatch (the later lane would gather KV
        rows the earlier lane writes in the same program), and one bigger
        bucket is cheaper than quota dispatches in the split path too.
        Capped at the largest compiled prefill bucket; CHK and the
        buckets are page multiples, so paged alignment is preserved."""
        q = sl.chunk_quota if self.rate_planner else 1
        return min(max(1, q) * CHK, self.prefill_buckets[-1])

    def _chunk_items(self, batch: list) -> list:
        """(slot, sl, start, n) chunk tuples -> _prefill_group items."""
        paged = self.kv_layout == "paged"
        return [
            (sl.request, slot,
             self._slot_pages.get(slot) if paged else None, None)
            for slot, sl, _st, _n in batch
        ]

    def _run_restores(
        self, restores: list, defer: bool = False
    ) -> tuple[set, int, list]:
        """Dispatch or stage-commit this round's host-tier swap-in rows.
        The blocking path issues the host->device copies immediately; a
        chunk whose rows were prefetched last cycle (_stage_swap_in)
        instead commits the already-staged device arrays — with
        ``defer=True`` (a fused cycle) the staged scatter rides the
        megastep as its swaps phase, so the deferred entries
        ``(slot, sl, st, n, groups)`` come back for _megastep_dispatch /
        _dispatch_pending_split to land. Returns ``(aborted_slots,
        refunded_tokens, deferred)``: a restore the
        ``engine.host_swap_error`` fault cancelled dispatched nothing, so
        its budget refunds and it stays out of the round's flight/counter
        record; a stage the ``engine.prefetch_error`` fault aborts (or a
        stale/mismatched stage) degrades to the blocking copy, byte-
        identically — the scatter writes the same rows either way."""
        aborted: set[int] = set()
        refund = 0
        deferred: list = []
        if not restores:
            return aborted, refund, deferred
        with self._hol_clock():
            for slot, sl, st, n in restores:
                if self._faults.enabled and st == 0:
                    spec = self._faults.pop("engine.host_swap_slow")
                    if spec is not None:
                        slow = float(spec.get("seconds", 0.05))
                        time.sleep(slow)
                        sl.swap_stall_s += slow  # attributed as host_stall
                    if self._faults.pop("engine.host_swap_error") is not None:
                        # restore "failed" before any rows landed: fall
                        # back to recomputing the whole prefill (the entry
                        # was consumed; byte-identity is unaffected)
                        self.flight.record(
                            "swap_in", rid=sl.request.rid, slot=slot,
                            error=True,
                        )
                        # the preserved rows now get recomputed by model
                        # chunks after all — host-swap-error recompute waste
                        self.profiler.reclassify(
                            "swap_recompute", self._swap_in_cut(sl)
                        )
                        sl.swap_entry = None
                        sl.swap_staged = None
                        aborted.add(slot)
                        refund += n
                        continue
                staged, sl.swap_staged = sl.swap_staged, None
                use_staged = (
                    staged is not None
                    and staged["start"] == st
                    and staged["n"] == n
                )
                if use_staged and self._faults.enabled:
                    if self._faults.pop("engine.prefetch_error") is not None:
                        # aborted async stage: drop the staged copies and
                        # run the blocking swap-in — same bytes land, only
                        # the overlap (and its stall saving) is lost
                        self.flight.record(
                            "prefetch_abort", rid=sl.request.rid, slot=slot,
                            start=st,
                        )
                        use_staged = False
                if use_staged and defer:
                    deferred.append((slot, sl, st, n, staged["groups"]))
                    continue
                if use_staged:
                    sl.swap_stall_s += self._commit_staged_swap(
                        staged["groups"]
                    )
                else:
                    sl.swap_stall_s += self._swap_in_rows(
                        slot, sl.swap_entry, st, n
                    )
                self._advance_restore(slot, sl, st, n)
        return aborted, refund, deferred

    def _advance_restore(self, slot: int, sl: _Slot, st: int, n: int) -> None:
        """Post-commit bookkeeping for one restore chunk (shared by the
        blocking path, the staged split commit, and the megastep's swaps-
        phase commit): advance the host mirrors, finish the swap-in at the
        cut, and otherwise stage the NEXT chunk's rows so the copy
        overlaps the rest of this cycle's compute."""
        sl.prefill_pos = st + n
        self._seq_lens[slot] = sl.prefill_pos
        if sl.prefill_pos >= self._swap_in_cut(sl):
            self._finish_swap_in(slot, sl)
        elif self.host_prefetch and self.kv_layout == "paged":
            self._stage_swap_in(slot, sl)

    @_in_phase("launch")
    def _commit_staged_swap(self, groups: list) -> float:  # acp: megastep-seam # acp: kv-seam # acp: swap-stage
        """Commit half of the prefetch split (split-dispatch form): scatter
        the staged device arrays into the pages with the SAME jitted
        scatter the blocking path uses — ids and blocks hold identical
        values, so the cache bytes are identical; the host->device copy
        already overlapped last cycle's compute, so the only blocking cost
        left is the dispatch itself."""
        t0 = time.monotonic()
        P = self.page_size
        for ids, blocks in groups:
            m = int(ids.shape[0])
            fn = self._jit_swap_scatter.get(m)
            if fn is None:
                fn = jax.jit(
                    lambda c, ids, blocks: {**c, **{
                        name: set_pages(c[name], ids, blocks[name])
                        for name in blocks
                    }},
                    donate_argnums=(0,),
                )
                self._jit_swap_scatter[m] = fn
            prof_t0 = self.profiler.start()
            self.cache = fn(self.cache, ids, blocks)
            self.profiler.record(
                f"swap_scatter[{m}]", prof_t0, out=_a_leaf(self.cache),
                real_tokens=m * P,
            )
        REGISTRY.counter_add(
            "acp_engine_kv_prefetch_commits_total", 1.0,
            help="host-KV restore chunks whose rows were prefetched (staged "
            "host->device a cycle early) and landed by scatter commit — the "
            "async-prefetch overlap win; chunks NOT counted here paid the "
            "blocking copy as host_stall",
        )
        return time.monotonic() - t0

    def _stage_swap_in(self, slot: int, sl: _Slot) -> None:  # acp: swap-stage
        """Stage half of the prefetch split: slice the NEXT restore
        chunk's host rows and launch them host->device with non-blocking
        device puts, in the same pow2 page groups the blocking
        _swap_in_rows would scatter. Nothing is committed — the pages are
        untouched until the commit half lands the scatter inside the next
        cycle's dispatch window, so an invalidated slot (preempt/cancel)
        simply drops the staged arrays. Paged layout only: the slot
        layout's dynamic_update_slice restore stays blocking."""
        entry = sl.swap_entry
        start = sl.prefill_pos
        n = min(
            self._slot_chunk_tokens(sl, self._chunk_tokens()),
            self._swap_in_cut(sl) - start,
        )
        if n <= 0:
            sl.swap_staged = None
            return
        rows = entry.rows
        P = self.page_size
        pages = self._slot_pages[slot][start // P : (start + n) // P]
        groups: list = []
        i = 0
        for m in _pow2_sizes(len(pages)):
            ids = np.asarray(pages[i : i + m], dtype=np.int32)
            lo = start + i * P
            blocks = {
                name: a[:, lo : lo + m * P].reshape(
                    a.shape[0], m, P, *a.shape[2:]
                )
                for name, a in rows.items()
            }
            groups.append((
                self._put(ids),
                {name: self._put(b) for name, b in blocks.items()},
            ))
            i += m
        sl.swap_staged = {"start": start, "n": n, "groups": groups}

    def _record_chunk_round(
        self, landed: list, spent: int, budget: int, restore_slots: set
    ) -> None:
        """One round's chunk bookkeeping, shared by the split path and the
        megastep commit: per-chunk flight events (only chunks that really
        dispatched), the round's budget-spend event, and the counters."""
        self.prefill_chunks += len(landed)
        if self.flight.enabled:
            # the EDF/quota pick + budget spend this cycle: one event per
            # chunk that actually dispatched plus the round's accounting
            for slot, sl, st, n in landed:
                if not sl.request.prewarm:
                    self.flight.record(
                        "prefill_chunk", rid=sl.request.rid, slot=slot,
                        start=st, n=n,
                        final=st + n >= len(sl.prefill_row or ()),
                        swap=slot in restore_slots,
                    )
            self.flight.record(
                "prefill_round", scheduled=len(landed), spent=spent,
                budget=budget,
            )
        REGISTRY.counter_add(
            "acp_engine_prefill_chunks_total", float(len(landed)),
            help="prefill chunk dispatches (per-slot chunks) under the "
            "unified token-budget scheduler",
        )

    def _prefill_chunks(self, chunk_budget: int) -> int:
        """One scheduler round of chunked prefill: give each mid-prefill
        slot its planned per-cycle chunk (the rate planner's quota; one
        base chunk without a deadline), in deadline-weighted order
        (earliest deadline first, then admission order; under multi-host
        coordination deadlines are leader-local wall clock, so ordering
        falls back to admission order — the same lockstep rule as deadline
        expiry), until the chunk budget is spent. The first chunk always
        dispatches even over budget (minimum-progress guarantee).
        Non-final chunks write KV only; a final chunk samples the slot's
        first token and flips it to decoding via the shared _prefill_group
        path. With the megastep enabled, mid chunks and continuation
        finals are PLANNED here but dispatch fused with this cycle's
        decode/verify program (_fuse_pending -> _megastep_dispatch);
        plain finals (start 0) keep the plain causal program — byte-for-
        byte the chunked-off dispatch — and still join this cycle's
        decode lanes. Returns tokens spent."""
        pre = [(s, sl) for s, sl in self._slots.items() if sl.prefilling]
        if not pre:
            return 0
        if self._faults.enabled:
            # deterministic mid-prefill preemption: lands on the PARTIALLY
            # prefilled slot with the most progress (steps = total chunks
            # dispatched, so after_steps=N lets N chunks land first)
            spec = self._faults.pop(
                "engine.preempt_mid_prefill", steps=self.prefill_chunks
            )
            if spec is not None:
                victim = max(pre, key=lambda t: (t[1].prefill_pos, t[0]))[0]
                self._preempt(victim, reason="fault")
                pre = [(s, sl) for s, sl in self._slots.items() if sl.prefilling]
                if not pre:
                    return 0
        if self._coordination is None:
            pre.sort(key=lambda t: (
                t[1].request.deadline
                if t[1].request.deadline is not None else float("inf"),
                t[1].admit_seq,
            ))
        else:
            pre.sort(key=lambda t: t[1].admit_seq)
        # dedup followers whose leader hasn't written the shared rows yet
        # WAIT (no chunk, no budget) — dispatching their suffix would read
        # garbage below the cut. A leader that finished its prefill (or
        # whose death already rewound this follower) clears the latch.
        ready: list[tuple[int, _Slot]] = []
        for slot, sl in pre:
            if sl.share_of is not None:
                lead = self._slots.get(sl.share_of[0])
                if (
                    lead is not None
                    and lead.prefilling
                    and lead.request.rid == sl.share_of[1]
                    and lead.prefill_pos < sl.share_of[2]
                ):
                    continue
                sl.share_of = None  # shared rows written; follower proceeds
            ready.append((slot, sl))
        pre = ready
        if not pre:
            return 0
        CHK = self._chunk_tokens()
        sched: list[tuple[int, _Slot, int, int]] = []  # (slot, sl, start, n)
        spent = 0
        for slot, sl in pre:
            cap = self._slot_chunk_tokens(sl, CHK)
            if sl.swap_entry is not None:
                # a swapped chunk costs budget like a prefill chunk (EDF-
                # ordered with them): the restore copy competes for the
                # same cycle the model chunks would
                n = min(cap, self._swap_in_cut(sl) - sl.prefill_pos)
            else:
                n = min(cap, len(sl.prefill_row) - sl.prefill_pos)
            if sched and spent + n > chunk_budget:
                break  # budget spent; later (EDF-ordered) slots wait a cycle
            sched.append((slot, sl, sl.prefill_pos, n))
            spent += n
        restores = [c for c in sched if c[1].swap_entry is not None]
        restore_slots = {c[0] for c in restores}
        model = [c for c in sched if c[1].swap_entry is None]
        mids = [c for c in model if c[2] + c[3] < len(c[1].prefill_row)]
        finals = [c for c in model if c[2] + c[3] >= len(c[1].prefill_row)]
        # finals whose whole row fits one chunk (start 0) take the plain
        # causal program — byte-for-byte the chunked-off dispatch; only
        # true continuations need the offset program
        plain = [c for c in finals if c[2] == 0]
        conts = [c for c in finals if c[2] > 0]
        paged = self.kv_layout == "paged"
        staged_ready = any(
            c[1].swap_staged is not None
            and c[1].swap_staged["start"] == c[2]
            and c[1].swap_staged["n"] == c[3]
            for c in restores
        )
        fused = self._use_megastep() and (
            mids or conts or (paged and plain) or staged_ready
        )
        aborted_slots, refund, deferred = self._run_restores(
            restores, defer=bool(fused and paged)
        )
        spent -= refund
        if fused:
            # fused cycle: mid chunks, continuation finals — and on the
            # paged layout plain (start-0) finals plus prefetch-staged
            # restore scatters — defer into the single fused program the
            # decode/verify site dispatches (_megastep_dispatch). Their
            # commit bookkeeping (prefill_pos, flight, counters) rides the
            # megastep commit so nothing is recorded that didn't dispatch.
            # Slot-layout plain finals still dispatch immediately (and join
            # this very cycle's decode lanes, as in the split path); an
            # absorbed plain samples its first token INSIDE the megastep,
            # so it joins the NEXT cycle's lanes — a scheduling shift only,
            # greedy bytes are unchanged.
            plains_pend: list = plain if paged else []
            if not paged:
                with self._hol_clock():
                    for batch in _pow2_chunks(plain, self.prefill_batch_max):
                        self._prefill_group(self._chunk_items(batch))
            deferred_keys = {(c[0], c[2]) for c in deferred}
            landed_now = [
                c for c in sched
                if c[0] not in aborted_slots
                and (
                    (c in plain and not paged)
                    or (
                        c[0] in restore_slots
                        and (c[0], c[2]) not in deferred_keys
                    )
                )
            ]
            self._fuse_pending = {
                "mids": mids, "finals": conts, "plains": plains_pend,
                "swaps": deferred, "landed": landed_now,
                "spent": spent, "budget": chunk_budget,
                "restores": restore_slots,
            }
            return spent
        with self._hol_clock():
            for batch in _pow2_chunks(mids, self.prefill_batch_max):
                self._chunk_dispatch(batch)
            for batch in _pow2_chunks(plain, self.prefill_batch_max):
                self._prefill_group(self._chunk_items(batch))
            for batch in _pow2_chunks(conts, self.prefill_batch_max):
                self._prefill_group(
                    self._chunk_items(batch),
                    starts_np=np.asarray([st for _, _, st, _ in batch], dtype=np.int32),
                )
        for slot, sl, st, n in mids:
            sl.prefill_pos = st + n
            self._seq_lens[slot] = sl.prefill_pos
        landed = [c for c in sched if c[0] not in aborted_slots]
        self._record_chunk_round(landed, spent, chunk_budget, restore_slots)
        return spent

    @_in_phase("launch")
    def _chunk_dispatch(  # acp: megastep-seam — split chunk program (fused fallback)
        # acp: dispatch-lanes toks,lengths,starts,slots,page_ids
        self, batch: list[tuple[int, "_Slot", int, int]]
    ) -> None:
        """One batched KV-only chunk dispatch (the per-cycle analogue of
        _spill_long_chunks' rounds): each row runs tokens [start, start+n)
        of its slot's prefill row through the continuation program, writing
        KV without sampling. Rows may have different lengths (final-size
        remainders never land here, but budget clipping is caller policy)."""
        B = len(batch)
        self._chunk_batch_sizes.add(B)
        bucket = _next_bucket(max(n for _, _, _, n in batch), self.prefill_buckets)
        toks = np.zeros((B, bucket), dtype=np.int32)
        lengths = np.zeros(B, dtype=np.int32)
        starts = np.zeros(B, dtype=np.int32)
        slots = np.zeros(B, dtype=np.int32)
        for i, (slot, sl, st, n) in enumerate(batch):
            toks[i, :n] = sl.prefill_row[st : st + n]
            lengths[i] = n
            starts[i] = st
            slots[i] = slot
        prof_t0 = self.profiler.start()
        page_ids = None
        if self.kv_layout == "paged":
            P = self.page_size
            page_ids = np.full((B, bucket // P), TRASH_PAGE, dtype=np.int32)
            for i, (slot, _sl, st, n) in enumerate(batch):
                # chunk boundaries are page-aligned (see _chunk_tokens), so
                # the commit's whole-page writes touch exactly this chunk's
                # fresh pages — never a page holding earlier KV
                sub = self._slot_pages[slot][st // P : -(-(st + n) // P)]
                page_ids[i, : len(sub)] = sub
        _tok = self._continue_kv_only(
            toks, lengths, starts, slots, [sl.request for _, sl, _, _ in batch], page_ids
        )
        if self.profiler.enabled:
            real = int(lengths.sum())
            self.profiler.record(
                f"chunk[{self.kv_layout},{bucket}x{B}]", prof_t0, out=_tok,
                real_tokens=real, padded_tokens=B * bucket - real,
                real_slots=B,
            )
            pre = sum(n for _, sl, _, n in batch if sl.request.prewarm)
            self.profiler.account(
                goodput=real - pre, prewarm=pre, pad_bucket=B * bucket - real
            )

    # -- per-slot state beside the pages (models.programs().has_state) ----

    def _state_cut_for(self, req: _Request, start: int) -> int:
        """The one length of this admission at which the state is saved:
        the prompt's last page boundary (``_save_prefix``'s cut, which a
        park keeps to as well), 0 where the admission starts past it and so
        never computes it."""
        cap = min(len(req.prompt), len(self._full_row(req)) - 1)
        cut = (cap // self.page_size) * self.page_size
        return cut if start <= cut else 0

    @_in_phase("launch")
    def _install_state(self, slot: int, state) -> None:  # acp: megastep-seam — once an admission that resumes
        """The slot's live state = ``state``: what a continuation that
        starts past 0 resumes from. ``state`` is the family's tree for one
        slot as ``_saved_state`` gave it (device arrays, or a host entry's
        numpy leaves: whatever arrays the family keeps, the engine reads
        none of them), or an int: the slot whose snapshot to copy."""
        if self._jit_install_state is None:
            install, saved = self._model.install_state, self._model.saved_state
            self._jit_install_state = (
                jax.jit(lambda c, s, st: install(c, s, st), donate_argnums=(0,)),
                jax.jit(lambda c, s, src: install(c, s, saved(c, src)), donate_argnums=(0,)),
            )
        by_tree, by_slot = self._jit_install_state
        if isinstance(state, int):
            self.cache = by_slot(self.cache, jnp.int32(slot), jnp.int32(state))
        else:
            state = jax.tree_util.tree_map(  # a host entry's leaves go up; a device entry's stay
                lambda a: a if isinstance(a, jax.Array) else self._put(np.asarray(a)), state
            )
            self.cache = by_tree(self.cache, jnp.int32(slot), state)
        self.state_restores += 1

    def _saved_state(self, slot: int, host: bool = False):  # acp: megastep-seam — once a prefix entry, swap-out or handoff
        """A copy of the slot's snapshot (its state at state_cut) as the
        family's tree: on the device, or with ``host`` as numpy leaves for a
        host entry (``HostKVEntry.state``)."""
        if self._jit_saved_state is None:
            self._jit_saved_state = jax.jit(self._model.saved_state)
        self.state_saves += 1
        saved = self._jit_saved_state(self.cache, jnp.int32(slot))
        return jax.tree_util.tree_map(np.asarray, saved) if host else saved

    # -- prefix KV cache (slot layout) -----------------------------------

    @staticmethod
    def _full_row(req: _Request) -> list[int]:
        """The tokens a request prefills: prompt + teacher-forced prefix,
        plus — after a preemption — everything it had already sampled, so
        the resumed decode continues exactly where it left off."""
        return (
            list(req.prompt)
            + list(req.sampling.forced_prefix)
            + list(req.resume_tokens)
        )

    def _match_prefix(self, req: _Request) -> Optional[tuple]:
        """Longest cached entry whose key is a strict prefix of the row
        (strict: at least one suffix token must remain to produce logits)."""
        if req.truncated:
            return None
        full = self._full_row(req)
        with self._prefix_lock:
            best_key, best = None, None
            for key, entry in self._prefix_cache.items():
                cut = entry["cut"]
                if cut < len(full) and (best is None or cut > best["cut"]):
                    if tuple(full[:cut]) == key:
                        best_key, best = key, entry
            if best_key is None:
                return None
            self._prefix_cache.move_to_end(best_key)
            return (best_key, best)

    @_in_phase("launch")
    def _copy_prefix_into_slot(self, slot: int, entry: dict) -> None:  # acp: megastep-seam # acp: kv-seam
        cut = entry["cut"]
        fn = self._jit_copy_prefix.get(cut)
        if fn is None:

            def copy(cache, slot_, rows):
                # dict-generic over the cache's keys so a quantized cache's
                # scale rows ("ks"/"vs", one rank lower) copy with the values
                return {
                    name: jax.lax.dynamic_update_slice(
                        arr, rows[name][:, None],
                        (0, slot_) + (0,) * (arr.ndim - 2),
                    )
                    for name, arr in cache.items()
                }

            fn = jax.jit(copy, donate_argnums=(0,))
            self._jit_copy_prefix[cut] = fn
        prof_t0 = self.profiler.start()
        self.cache = fn(
            self.cache, jnp.int32(slot),
            {name: entry[name] for name in self.cache},
        )
        self.profiler.record(
            f"prefix_copy[{cut}]", prof_t0, out=_a_leaf(self.cache),
            real_tokens=cut, real_slots=1,
        )

    def _save_prefix(self, full: list[int], prompt_len: int, slot: int, state_cut: int = 0) -> None:  # acp: megastep-seam # acp: kv-seam
        """After a prefill: snapshot the slot's leading KV as a reusable
        prefix entry (LRU-capped). Slot layout: a device COPY at the largest
        bucket/chunk boundary. Paged layout: zero-copy — take a reference on
        the slot's leading (full, immutable) pages. The cut never reaches
        past the PROMPT into the teacher-forced generation prefix — the
        next turn's rendered prompt contains the serialized assistant
        message, not the raw forced tokens, so a key crossing that boundary
        could never match again."""
        if not self._prefix_enabled:
            return
        cap = min(prompt_len, len(full) - 1)
        if self.kv_layout == "paged":
            cut = (cap // self.page_size) * self.page_size  # full pages only
        else:
            cut = 0
            for b in self.prefill_buckets:
                if b <= cap:
                    cut = b
            # chunked-prefill configs (largest bucket << max_ctx): snapshot
            # at the largest chunk-multiple instead, or long conversations
            # would be reusable only up to one bucket
            CH = self.prefill_buckets[-1]
            cut = max(cut, (cap // CH) * CH)
        if cut < min(self.prefill_buckets[0], 4 * self.page_size):
            return  # too short to be worth caching
        if self._has_state and state_cut != cut:
            return  # no state was saved at this length: nothing could resume from it
        key = tuple(full[:cut])
        with self._prefix_lock:
            if key in self._prefix_cache:
                self._prefix_cache.move_to_end(key)
                return
        if self.kv_layout == "paged":
            pages = self._slot_pages[slot][: cut // self.page_size]
            self._allocator.share(pages)
            entry = {"cut": cut, "pages": list(pages)}
            if self._has_state:
                entry["state"] = self._saved_state(slot)
        else:
            fn = self._jit_extract_prefix.get(cut)
            if fn is None:
                L = self.config.n_layers

                def extract(cache, slot_):
                    # dict-generic: values [L, cut, H, d] and (quantized)
                    # scale rows [L, cut, H] slice with the same indices
                    return {
                        name: jax.lax.dynamic_slice(
                            arr,
                            (0, slot_) + (0,) * (arr.ndim - 2),
                            (L, 1, cut) + arr.shape[3:],
                        )[:, 0]
                        for name, arr in cache.items()
                    }

                fn = jax.jit(extract)  # read-only: cache NOT donated
                self._jit_extract_prefix[cut] = fn
            with self.profiler.phase("launch"):
                prof_t0 = self.profiler.start()
                rows = fn(self.cache, jnp.int32(slot))
                self.profiler.record(
                    f"prefix_extract[{cut}]", prof_t0, out=_a_leaf(rows),
                    real_tokens=cut, real_slots=1,
                )
            entry = {"cut": cut, **rows}
        with self._prefix_lock:
            self._prefix_cache[key] = entry
            while len(self._prefix_cache) > self._prefix_cache_entries or (
                len(self._prefix_cache) > 1
                and self._cached_tokens_locked() > PREFIX_CACHE_MAX_TOKENS
            ):
                _, old = self._prefix_cache.popitem(last=False)  # evict LRU
                if "pages" in old:
                    self._allocator.free(old["pages"])  # drop the cache ref

    def _cached_tokens_locked(self) -> int:
        """Distinct tokens pinned by the cache (hold _prefix_lock). Paged
        entries from one growing conversation SHARE pages — counting each
        entry's cut would double-count them and evict prematurely."""
        toks = 0
        pages: set[int] = set()
        for e in self._prefix_cache.values():
            if "pages" in e:
                pages.update(e["pages"])
            else:
                toks += e["cut"]
        return toks + len(pages) * self.page_size

    def _evict_one_prefix_entry(self) -> bool:
        """Evict the LRU prefix entry (allocation pressure). True if one
        was evicted."""
        with self._prefix_lock:
            if not self._prefix_cache:
                return False
            _, old = self._prefix_cache.popitem(last=False)
        if "pages" in old:
            self._allocator.free(old["pages"])
        return True

    def _collect_group(self) -> list[tuple[_Request, int, Optional[list[int]], Optional[tuple]]]:
        """Pop up to prefill_batch_max admissible head requests, reserving a
        slot (and KV pages, in paged mode) for each, and resolving each
        request's prefix-cache match. Paged hits assemble their block list
        as SHARED prefix pages (refcounted, never re-written) + freshly
        allocated suffix pages. Strict FIFO: stop at the first request that
        can't get pages."""
        group: list[tuple[_Request, int, Optional[list[int]], Optional[tuple]]] = []
        while (
            self._waiting
            and len(group) < self.prefill_batch_max
            and (self._free or self._has_parked())
        ):
            req = self._waiting[0]
            s = req.sampling
            # queued-deadline expiry happens in _expire_deadlines, which
            # _admit runs (and the leader publishes) before every
            # _fill_slots — by here the head of the deque is live
            if s.json_only and s.forced_prefix:
                # seed the automaton past the forced prefix; an illegal
                # prefix can never complete, so fail it up front
                if self._seed_con_state(s.forced_prefix) < 0:
                    self._waiting.popleft()
                    if not req.prewarm:
                        self.flight.record(
                            "cancel", rid=req.rid, where="illegal_prefix"
                        )
                        self.flight.discard(req.rid)
                    req.future.set_exception(
                        RuntimeError("forced_prefix is not a legal JSON prefix")
                    )
                    continue
            match: Optional[tuple] = None
            if self._prefix_enabled and not req.truncated:
                match = self._match_prefix(req)
            full = self._full_row(req)
            # host-tier candidate: an exact-rid entry (preempt -> resume)
            # or the longest token-prefix entry (park expiry / deadline
            # drop whose conversation came back). Peek only — reservation
            # may still fail, so consumption waits for the commit below.
            host_e = None
            host_cut = 0
            if self._host_pool is not None and not req.truncated:
                host_e = self._host_pool.get(req.rid)
                if host_e is not None and not (
                    0 < host_e.cut < len(full)
                    and tuple(full[: host_e.cut]) == host_e.tokens
                ):
                    host_e = None
                if host_e is None:
                    host_e = self._host_pool.match_prefix(full)
                if host_e is not None:
                    host_cut = min(host_e.cut, len(full) - 1)
                    if self.kv_layout == "paged":
                        host_cut = (host_cut // self.page_size) * self.page_size
                    if host_cut < self._swap_min_rows():
                        host_e, host_cut = None, 0
                    elif self._has_state and (
                        host_e.state is None or host_cut != host_e.cut
                    ):
                        # the rows could be restored, but no state was saved
                        # at the length they would resume from: a miss
                        self.state_refused += 1
                        host_e, host_cut = None, 0
            # dedup candidate: share a live slot's (or an earlier group
            # member's) prompt pages instead of materializing a copy
            dedup = self._match_dedup_leader(full, group) if not req.truncated else None
            # parked-slot adoption: a slot parked by this conversation's
            # previous turn holds its prompt KV in place — resume there
            # (suffix-only prefill, no copy). Candidate selection is by
            # covered rows, ties broken by mechanism cost: in-place
            # adoption beats a zero-copy cache share beats a dedup share
            # (which may wait on its leader) beats a host restore (which
            # pays a host->device copy).
            adopt = self._match_parked(req)
            best_cut, _prio, kind = max(
                (self._slots[adopt].park_cut if adopt is not None else 0, 3, "adopt"),
                (match[1]["cut"] if match is not None else 0, 2, "cache"),
                (dedup[2] if dedup is not None else 0, 1, "dedup"),
                (host_cut, 0, "host"),
            )
            if best_cut <= 0:
                kind = None
            if kind == "adopt":
                item = self._adopt_parked(req, adopt)
                if item is None:
                    break  # pages short even after yielding; head waits (FIFO)
                if item:
                    group.append(item[0])
                continue  # oversize-prompt rejection popped the head
            # no adoption possible: parked capacity yields a free slot —
            # preferring NOT to release the dedup leader itself (its pages
            # are the share). If the leader is the only parked capacity,
            # release it anyway; the dedup branch below demotes a vanished
            # leader to a plain undeduped admission.
            if not self._free and not self._release_lru_parked(
                exclude=dedup[0] if dedup is not None else None
            ):
                if not self._release_lru_parked():
                    break
            pages: Optional[list[int]] = None
            shared: list[int] = []
            if self.kv_layout == "paged":
                total_pages = -(-len(full) // self.page_size)
                if self._reject_oversize_head(req, total_pages):
                    continue
                if kind == "cache":
                    shared = list(match[1]["pages"])
                elif kind == "dedup":
                    leader_pages = self._slot_pages.get(dedup[0])
                    if leader_pages is None:  # leader reserved in THIS group
                        leader_pages = next(
                            (it[2] for it in group if it[1] == dedup[0]), None
                        )
                    if leader_pages is None:
                        # the leader vanished between selection and
                        # reservation (released for its slot id above):
                        # admit undeduped rather than crash or mis-share
                        kind = None
                    else:
                        shared = list(
                            leader_pages[: best_cut // self.page_size]
                        )
                # take the share FIRST: if allocation pressure evicts the
                # matched entry below, our reference keeps its pages alive
                self._allocator.share(shared)
                fresh: Optional[list[int]] = None
                while fresh is None:
                    try:
                        fresh = self._allocator.alloc(total_pages - len(shared))
                    except MemoryError:
                        # parked slots yield first (speculative capacity for
                        # ONE possible future turn), then cache entries —
                        # under pressure both must give way or an idle
                        # engine could livelock with the head request
                        # waiting on pages nothing will free
                        if self._release_lru_parked():
                            continue
                        if not self._evict_one_prefix_entry():
                            break
                if fresh is None:
                    self._allocator.free(shared)  # undo; head waits (FIFO)
                    break
                pages = shared + fresh
            if kind == "dedup":
                match = (None, {"cut": best_cut, "share_of": (dedup[0], dedup[1])})
                self.prefix_shares += 1
                if not req.prewarm:
                    self.flight.record(
                        "prefix_share", rid=req.rid, cut=best_cut,
                        leader=dedup[1], pages=len(shared),
                    )
            elif kind == "host":
                # reservation held: consume the entry (its bytes return to
                # the host budget; the restore is scheduled chunk by chunk)
                self._host_pool.pop(host_e.rid)
                match = (None, {"cut": best_cut, "swap": host_e})
            elif kind is None:
                match = None
            self._waiting.popleft()
            # lowest-index slot first: keeps active slots compacted at low
            # indices so decode width bucketing stays narrow
            group.append((req, heapq.heappop(self._free), pages, match))
        return group

    def _seed_con_state(self, prefix: Sequence[int]) -> int:
        """Walk the token table over a forced prefix; -1 = illegal."""
        self._get_token_table()  # ensure built
        state = self._table_start
        for tok in prefix:
            if state < 0 or tok >= self._token_table_np.shape[1]:
                return -1
            state = int(self._token_table_np[state, tok])
        return state

    def _get_token_table(self):
        """Lazy-build + cache the grammar token table on device. Called from
        the engine thread AND from caller threads (prewarm, bench setup), so
        the build is lock-serialized and ``_token_table`` is assigned LAST:
        readers that key on ``_token_table is not None`` (e.g. _decode_once's
        use_real) must never observe a half-built state where ``_min_close``
        is still None."""
        if self._token_table is None:
            with self._table_lock:
                if self._token_table is not None:
                    return self._token_table
                from .constrain import build_token_table

                t0 = time.monotonic()
                table = build_token_table(self.tokenizer)
                # tokenizer-wide, not model-vocab-wide: constrain_logits pads
                # the gathered rows to the logits' width on device
                width = min(self.config.vocab_size, table.token_trans.shape[1])
                trans = np.ascontiguousarray(
                    table.token_trans[:, :width], dtype=np.int32
                )
                self._token_table_np = trans  # host-side walks (prefix seeding)
                self._min_close = self._put(table.min_close.astype(np.int32))
                self._table_start = table.start_state
                self._token_table = self._put(trans)  # LAST: publishes the rest
                log.info(
                    "built JSON constraint table: %d states x %d tokens in %.1fs",
                    *table.token_trans.shape, time.monotonic() - t0,
                )
        return self._token_table

    def _prefill_lanes(
        self, chunk: list, starts: np.ndarray, width: Optional[int] = None
    ) -> dict:
        # acp: dispatch-lanes tokens,lengths,lane_starts,slots,snap_at,temps,top_ks,top_ps,con_states0,constrained0,budgets,full_lens
        # acp: budget-seam — the ONE admission-time budget computation (the
        # +1-for-the-first-token form); decode/verify recomputation goes
        # through _slot_budget
        """Build the batched prefill/continuation lanes for B
        already-reserved requests — shared by the split _prefill_group
        dispatch and the megastep's fused plain and final phases, so both
        upload the same numbers (the budget seam must have exactly one
        home). ``width`` pads the batch (a fused phase's power of two):
        padding lanes sample garbage that is never committed, and their
        writes land on the trash page (paged: length 0) or the clamped
        never-readable row (slot layout: start max_ctx); a family with
        per-slot state gets a slot out of range, whose state writes are
        dropped, and no snapshot (-1). ``lanes`` is the one packed buffer
        the program reads them from (engine/lanes.py PREFILL)."""
        B = len(chunk)
        Bp = width or B
        # bucket over what actually runs through the model (full row on a
        # miss; suffix on a hit)
        bucket = max(
            _next_bucket(len(self._full_row(r)) - int(starts[i]), self.prefill_buckets)
            for i, (r, _, _, _) in enumerate(chunk)
        )
        tokens = np.zeros((Bp, bucket), dtype=np.int32)
        lengths = np.zeros(Bp, dtype=np.int32)
        lane_starts = np.full(
            Bp, self.max_ctx if self.kv_layout == "slot" else 0, dtype=np.int32
        )
        slots = np.full(Bp, self._pad_slot, dtype=np.int32)
        snap_at = np.full(Bp, -1, dtype=np.int32)
        temps = np.zeros(Bp, dtype=np.float32)
        top_ks = np.zeros(Bp, dtype=np.int32)
        top_ps = np.ones(Bp, dtype=np.float32)
        con_states0 = np.zeros(Bp, dtype=np.int32)
        constrained0 = np.zeros(Bp, dtype=bool)
        budgets = np.ones(Bp, dtype=np.int32)
        full_lens = np.zeros(B, dtype=np.int32)
        any_json = any(r.sampling.json_only for r, _, _, _ in chunk)
        if any_json:
            table = self._get_token_table()
            min_close = self._min_close
        else:
            table = self._token_table if self._token_table is not None else self._dummy_table
            min_close = (
                self._min_close if self._min_close is not None else self._dummy_min_close
            )
        lane_starts[:B] = starts
        for i, (req, slot, _, _m) in enumerate(chunk):
            s = req.sampling
            row = self._full_row(req)
            plen = len(row)
            full_lens[i] = plen
            suffix = row[int(starts[i]) :]
            tokens[i, : len(suffix)] = suffix
            lengths[i] = len(suffix)
            slots[i] = slot
            snap_at[i] = req.state_cut or -1
            temps[i] = s.temperature
            top_ks[i] = s.top_k
            top_ps[i] = s.top_p
            # ctx-bounded: 1 token now + decode capacity to the ctx edge
            # (the decode block deactivates the slot device-side at max_ctx-1);
            # a resumed request's budget excludes what it already sampled
            budgets[i] = min(
                s.max_tokens - len(req.resume_tokens),
                1 + max(0, self.max_ctx - 1 - plen),
            )
            if s.json_only:
                seed = tuple(s.forced_prefix) + tuple(req.resume_tokens)
                con_states0[i] = self._seed_con_state(seed) if seed else self._table_start
                constrained0[i] = True
        self._count_sampling(masks_wanted(top_ks, top_ps))
        return {
            "bucket": bucket, "tokens": tokens, "lengths": lengths,
            "full_lens": full_lens, "table": table, "min_close": min_close,
            "lanes": PREFILL.pack(
                Bp, n=self._next_key_n(), lengths=lengths, starts=lane_starts,
                slots=slots, snap_at=snap_at, temps=temps, top_ks=top_ks,
                top_ps=top_ps, con_states=con_states0, constrained=constrained0,
                budgets=budgets,
            ),
        }

    def _kv_lanes(self, lengths, starts, slots, reqs: list) -> jax.Array:
        # acp: dispatch-lanes snap_at
        """The uploaded lanes of a dispatch that only writes KV (a spill
        round, a chunk, the megastep's mid phase): its sampled token is
        discarded or never drawn, so the sampling rows hold what samples
        nothing special (greedy, unconstrained, a budget of 1). Rows past
        ``reqs`` are a fused phase's padding: no snapshot."""
        snap_at = np.full(len(lengths), -1, dtype=np.int32)
        snap_at[: len(reqs)] = [r.state_cut or -1 for r in reqs]
        return self._put(PREFILL.pack(
            len(lengths), n=self._next_key_n(), lengths=lengths, starts=starts,
            slots=slots, snap_at=snap_at, temps=0.0, top_ks=0, top_ps=1.0,
            con_states=0, constrained=False, budgets=1,
        ))

    def _continue_kv_only(  # acp: megastep-seam — the split spill and chunk dispatches
        self, toks, lengths, starts, slots, reqs: list, page_ids
    ) -> jax.Array:
        """One continuation dispatch whose sampled token is discarded (a
        spill round, a split chunk): KV writes only, four uploads at most.
        Returns the token array, for the profiler to wait on."""
        args = [self._put(toks), self._kv_lanes(lengths, starts, slots, reqs)]
        if self.kv_layout == "paged":
            args += [self._put(page_ids), self._put(self._block_tables[slots])]
            program = self._jit_prefill_paged_continue
        else:
            program = self._jit_prefill_continue
        self.cache, tok, _state = program(
            self.params, self.cache, *args, self._base_key,
            self._dummy_table, self._dummy_min_close,
        )
        return tok

    def _prefill_group(  # acp: megastep-seam
        self,
        chunk: list[tuple[_Request, int, Optional[list[int]]]],
        starts_np: Optional[np.ndarray] = None,
    ) -> None:
        """One batched prefill dispatch for B already-reserved requests
        (B = power of two <= prefill_batch_max). Burst admissions no longer
        serialize: 64 arrivals are 8 dispatches of 8 prompts, not 64
        batch-1 prefills. With ``starts_np`` (prefix-cache hits and/or
        chunked-prefill remainders; slot KV below each start is already
        populated), only the SUFFIX runs through the model
        (prefill_continue)."""
        with self.profiler.phase("launch"):
            B = len(chunk)
            starts = starts_np if starts_np is not None else np.zeros(B, dtype=np.int32)
            ln = self._prefill_lanes(chunk, starts)
            bucket, full_lens, lengths = ln["bucket"], ln["full_lens"], ln["lengths"]
            table, min_close = ln["table"], ln["min_close"]
            if starts_np is None:
                self._full_batch_shapes.add((bucket, B))
            else:
                self._cont_batch_sizes.add(B)
            # a plain dispatch uploads three arrays (token rows, lanes, page
            # ids), a continuation its block tables besides
            args = [self._put(ln["tokens"]), self._put(ln["lanes"])]
            prof_t0 = self.profiler.start()
            if self.kv_layout == "paged":
                P = self.page_size
                # suffix pages only (the model writes just the suffix; shared
                # prefix pages are referenced via the block table, never written)
                # slot pages / block tables were installed at admission (they
                # must exist before spill chunks reference them)
                page_ids = np.full((B, bucket // P), TRASH_PAGE, dtype=np.int32)
                for i, (_req, _slot, pages, _m) in enumerate(chunk):
                    assert pages is not None
                    fresh = pages[int(starts[i]) // P :]
                    page_ids[i, : len(fresh)] = fresh
                args.append(self._put(page_ids))
                program = self._jit_prefill_paged
                if starts_np is not None:
                    args.append(self._put(
                        self._block_tables[[slot for _, slot, _, _ in chunk]]
                    ))
                    program = self._jit_prefill_paged_continue
            else:
                program = (
                    self._jit_prefill if starts_np is None
                    else self._jit_prefill_continue
                )
            cache, firsts, con_states = program(
                self.params, self.cache, *args, self._base_key, table, min_close
            )
            self.cache = cache
            if self.profiler.enabled:
                # program key mirrors the jit cache keying: kind x bucket x
                # batch x layout, +tbl once the real grammar table shape traces
                kind = "prefill_cont" if starts_np is not None else "prefill"
                tbl = "+tbl" if table is not self._dummy_table else ""
                real = int(lengths.sum())
                self.profiler.record(
                    f"{kind}[{self.kv_layout},{bucket}x{B}{tbl}]", prof_t0,
                    out=firsts, real_tokens=real,
                    padded_tokens=B * bucket - real, real_slots=B,
                )
                pre = sum(
                    int(lengths[i]) for i, (r, _, _, _) in enumerate(chunk)
                    if r.prewarm
                )
                self.profiler.account(
                    goodput=real - pre, prewarm=pre, pad_bucket=B * bucket - real
                )
        with self.profiler.phase("fetch"):
            # one combined round trip (see _decode_once; the fetch floor is
            # per transfer, not per byte)
            firsts, con_states = jax.device_get((firsts, con_states))
        self._finish_prefill_dispatch(chunk, firsts, con_states, full_lens)

    @_in_phase("commit")
    def _finish_prefill_dispatch(  # acp: megastep-seam — _save_prefix extracts KV
        self,
        chunk: list,
        firsts: np.ndarray,
        con_states: np.ndarray,
        full_lens: np.ndarray,
    ) -> None:
        """Host-side commit of one prefill dispatch's results (shared by
        the split _prefill_group and the megastep's fused final phase):
        snapshot prefixes, flip PREFILLING slots to decoding, stream first
        tokens + forced prefixes, and finish slots whose first token was
        terminal. ``self.cache`` must already hold the post-dispatch
        cache (prefix snapshots extract from it)."""
        # snapshot prefixes for future hits (engine thread; the state can't
        # change before decode extends past the cut). Hit slots save too:
        # their rows/tables now hold the FULL prompt KV, so the next turn can
        # reuse this whole context, not just the old prefix.
        if self._prefix_enabled:
            for req, slot, _, _m in chunk:
                if not req.truncated:
                    self._save_prefix(
                        self._full_row(req), len(req.prompt), slot, req.state_cut
                    )
        self._state_dirty = True  # new slots: decode must re-upload state
        now = time.monotonic()
        for i, (req, slot, _, _m) in enumerate(chunk):
            s = req.sampling
            first_tok = int(firsts[i])
            self._con_states[slot] = int(con_states[i])
            self._constrained[slot] = bool(s.json_only)
            is_first = req.first_token_at == 0.0
            if is_first:
                req.first_token_at = now
                REGISTRY.observe(
                    "acp_engine_ttft_seconds", now - req.enqueued,
                    help="time to first token",
                )
            if not req.prewarm:
                # prefill complete: prompt KV resident, first token sampled.
                # For a resumed request this is also the end of its
                # preempt_stall window (phase attribution keys on it).
                self.flight.record(
                    "prefill_done", rid=req.rid, slot=slot,
                    seq=int(full_lens[i]), first=is_first,
                    cycle=self.profiler.cycle_n,
                )
            prior = self._slots.get(slot)
            if prior is not None and prior.prefilling:
                # chunked prefill's FINAL chunk: the slot existed mid-prefill
                # (same request); it flips to decoding here, keeping its
                # admission stamp so victim-policy recency is admission
                # order, not final-chunk order
                self._prefilling_count -= 1
                admit_seq = prior.admit_seq
            else:
                self._admit_seq += 1
                admit_seq = self._admit_seq
            sl = _Slot(
                request=req,
                prompt_len=len(req.prompt),
                prefix_len=len(s.forced_prefix),
                first_token_at=req.first_token_at,
                admit_seq=admit_seq,
            )
            # active slots keep their prefill row too when the dedup
            # leader scan (its only consumer) is live: it compares token
            # prefixes against live slots on every admission, and
            # rebuilding prompt+prefix+resume per scan is O(slots x row)
            # on the engine thread. Gated so inert configs don't pin an
            # O(row) list per slot for nothing; the scan falls back to
            # _full_row for slots admitted while the knob was off.
            if self.prefix_dedup and self.kv_layout == "paged":
                sl.prefill_row = self._full_row(req)
            if self.spec_len:
                from .spec import SpecState

                sl.spec = SpecState(limit=self.spec_len)
            sl.generated.extend(s.forced_prefix)
            sl.generated.extend(req.resume_tokens)
            sl.generated.append(first_tok)
            if first_tok not in self.tokenizer.stop_tokens:
                # resumed requests already emitted prefix + resume tokens
                # before preemption — only the fresh token streams out
                self._stream(
                    req,
                    [first_tok] if req.resume_tokens
                    else list(s.forced_prefix) + [first_tok],
                )
            elif s.forced_prefix and not req.resume_tokens:
                self._stream(req, list(s.forced_prefix))
            self._slots[slot] = sl
            self._seq_lens[slot] = full_lens[i]  # cached prefix + suffix
            self._last_tokens[slot] = first_tok
            self._temps[slot] = s.temperature
            self._top_ks[slot] = s.top_k
            self._top_ps[slot] = s.top_p
            if (
                first_tok in self.tokenizer.stop_tokens
                or len(sl.generated) - sl.prefix_len >= s.max_tokens
            ):
                self._finish(
                    slot, "stop" if first_tok in self.tokenizer.stop_tokens else "length"
                )

    _step_rows = 1  # until the constructor has read the family's seam

    @property
    def _block_rows(self) -> int:
        """Rows a decode block may write a slot: a step's committed tokens
        and, for a family that drafts, the refused row past the last."""
        return self.decode_block_size * self._step_rows + (self._step_rows > 1)

    def _ensure_pages_for_block(self, need_tokens: Optional[dict] = None) -> None:
        """Paged mode: every active slot's table must cover the next K
        tokens before dispatch (or, per slot, ``need_tokens[slot]`` —
        the speculative verify path writes 1 + draft-length KV rows in one
        dispatch). A slot the pool can't cover triggers
        PREEMPT-AND-RESUME (never a silent truncation): prefix-cache
        entries yield first, then a policy victim is preempted — its
        generated-so-far tokens are saved on the request, its pages freed,
        and it is requeued at the FRONT of the admission queue to resume
        later via a prompt+partial prefill."""
        if self._faults.enabled:
            self._faults.apply_page_pressure(self._allocator)
        K = self._block_rows
        # Pass 1 — strict coverage: every slot gets exactly the pages this
        # block needs; lookahead can never starve a slot that strictly fits.
        crossed: list[int] = []
        for slot in list(self._slots):
            if slot not in self._slots:
                continue  # preempted as a victim for an earlier slot
            if self._slots[slot].parked or self._slots[slot].prefilling:
                # parked slots never decode; mid-prefill slots reserved
                # their whole row's pages at admission — neither needs
                # decode-block coverage
                continue
            need = K if need_tokens is None else need_tokens.get(slot, K)
            needed = -(-(int(self._seq_lens[slot]) + need) // self.page_size)
            # ctx edge: the decode block deactivates the slot on device at
            # max_ctx-1, so a fully-populated table is always enough — clamp
            # instead of force-finishing (a force-finish here could truncate
            # a json_only generation whose budget-aware closure planned on
            # the last few tokens before the edge)
            needed = min(needed, self.max_pages_per_seq)
            have = len(self._slot_pages.get(slot, []))
            if needed <= have:
                continue
            new_pages = self._alloc_with_preemption(needed - have, slot, need_tokens)
            if new_pages is None:
                continue  # slot itself was preempted (requeued or finished)
            self._append_pages(slot, new_pages)
            crossed.append(slot)
        # Pass 2 — opportunistic lookahead top-up, only for slots whose
        # table went dirty THIS round (their upload is already being paid):
        # with K == page_size a slot would otherwise cross a page boundary
        # on EVERY block, re-uploading the block table (one serialized
        # host->device RTT in the hot loop) per dispatch. Topping up to
        # `page_lookahead_blocks` blocks of pages makes it one upload per
        # lookahead window; a failed top-up is harmless.
        # speculation writes up to spec_len+1 rows per dispatch; size the
        # lookahead window to whichever dispatch shape is larger
        ahead = max(K, self.spec_len + 1) * self.page_lookahead_blocks
        for slot in crossed:
            if slot not in self._slot_pages:
                continue
            want = min(
                -(-(int(self._seq_lens[slot]) + ahead) // self.page_size),
                self.max_pages_per_seq,
            )
            have = len(self._slot_pages[slot])
            if want <= have:
                continue
            try:
                self._append_pages(slot, self._allocator.alloc(want - have))
            except MemoryError:
                pass  # pool tight: strict coverage already satisfied

    def _alloc_reclaiming_lookahead(
        self, n: int, requester: int, need_tokens: Optional[dict] = None
    ) -> list[int] | None:
        """Alloc ``n`` pages; on exhaustion, claw back other slots' UNUSED
        lookahead pages (beyond their strict next-block need) and retry.
        Without this, pass-2 top-ups from earlier rounds could hoard pages
        and preempt a strictly-fitting slot in a later round — 'lookahead
        never starves a strict fit' must hold across rounds, not just within
        one. The trimmed slots' tables re-upload next boundary crossing;
        that cost only occurs when the pool is already exhausted.

        ``need_tokens`` is THIS dispatch's per-slot row count (speculative
        verify writes 1 + draft rows, which can exceed the decode block):
        the reclaim floor must honor it, or a later slot's allocation in the
        same pass strips pages an earlier slot was just granted for its
        draft tail — the dispatch would then write that KV to the trash
        page while the host advances ``seq_len`` over it, and every later
        attention pass for the slot reads garbage."""
        try:
            return self._allocator.alloc(n)
        except MemoryError:
            pass
        K = self._block_rows
        reclaimed = False
        for slot in self._slots:
            table = self._slot_pages.get(slot)
            if slot == requester or not table:
                continue
            if self._slots[slot].parked:
                continue  # already trimmed to its park cut; nothing spare
            if self._slots[slot].prefilling:
                # a mid-prefill slot's "spare" pages are the reservation its
                # upcoming chunks write into — trimming them would tear the
                # admission-time all-pages-reserved invariant (the chunk
                # loop never allocates). Pressure takes the whole slot via
                # _pick_victim instead.
                continue
            need = K if need_tokens is None else max(K, need_tokens.get(slot, K))
            strict = min(
                -(-(int(self._seq_lens[slot]) + need) // self.page_size),
                self.max_pages_per_seq,
            )
            if len(table) > strict:
                excess = table[strict:]
                del table[strict:]
                self._block_tables[slot, strict : strict + len(excess)] = TRASH_PAGE
                self._allocator.free(excess)
                self._tables_dirty = True
                reclaimed = True
        if not reclaimed:
            return None
        try:
            return self._allocator.alloc(n)
        except MemoryError:
            return None

    def _alloc_with_preemption(
        self, n: int, requester: int, need_tokens: Optional[dict] = None
    ) -> list[int] | None:
        """Alloc ``n`` pages for an active slot, escalating on exhaustion:
        (1) claw back other slots' unused lookahead pages, (2) evict prefix
        -cache entries (cache must never starve live work), (3) preempt
        policy victims until the allocation fits or the requester itself is
        the victim. Returns None iff the requester was preempted."""
        while True:
            pages = self._alloc_reclaiming_lookahead(n, requester, need_tokens)
            if pages is not None:
                return pages
            if self._release_lru_parked():
                continue
            if self._evict_one_prefix_entry():
                continue
            victim = self._pick_victim()
            if victim is None:
                # no active slots left to yield (shouldn't happen — the
                # requester is active); preempt the requester defensively
                victim = requester
            self._preempt(victim)
            if victim == requester:
                return None

    def _pick_victim(self) -> Optional[int]:
        """Preemption victim policy (documented in docs/serving-engine.md):
        fewest sampled tokens first (least work lost / cheapest resume
        prefill), ties broken by MOST recently admitted (LIFO — the oldest
        requests keep their progress, mirroring the front-of-queue resume
        order so the engine converges instead of thrashing). Mid-prefill
        slots have sampled nothing, so they sort first among non-parked
        slots — preempting one loses only chunk compute, never tokens."""
        if not self._slots:
            return None
        # parked slots volunteer first (oldest park): their generation is
        # done and their caller already has its result — evicting one
        # costs at most a future suffix-prefill, never lost work
        parked = [(sl.parked_at, s) for s, sl in self._slots.items() if sl.parked]
        if parked:
            return min(parked)[1]
        return min(
            self._slots,
            key=lambda s: (
                len(self._slots[s].generated) - self._slots[s].prefix_len,
                -self._slots[s].admit_seq,
            ),
        )

    def _preempt(self, slot: int, reason: str = "pool_pressure") -> None:
        """Evacuate an active slot under pool pressure WITHOUT finishing
        it: save its sampled-so-far tokens and scheduling state on the
        request, free its pages, and requeue it at the front of the
        admission queue. On re-admission it prefills prompt+partial and
        decode continues — the caller's result is byte-identical (greedy)
        to an uncontended run, with only ``preempt_count`` as evidence."""
        if self._slots[slot].parked:
            # a parked slot has nothing to save or requeue — its future
            # resolved at park time; the "preemption" is a pure release
            self._release_parked(slot, reason=reason)
            return
        sl = self._slots.pop(slot)
        req = sl.request
        if sl.prefilling:
            # mid-prefill victim: no NEW sampled tokens to save — the
            # partial prompt KV is released with the pages and the request
            # re-enters the chunk loop from its (fresh) prefix-cache start
            # on re-admission. Byte-identical: nothing was sampled in THIS
            # admission. req.resume_tokens is left UNTOUCHED: a resumed
            # request preempted again mid-resume-prefill keeps its earlier
            # progress (its ``generated`` list is empty while prefilling —
            # overwriting from it here silently wiped the resume state and
            # re-streamed the whole generation after the second resume).
            self._prefilling_count -= 1
            self._unshare_followers(slot, sl)
        else:
            req.resume_tokens = list(sl.generated[sl.prefix_len:])
        # host KV tier: offload the written rows before the pages go —
        # re-admission then swaps them back instead of re-running prefill
        rows_written = sl.prefill_pos if sl.prefilling else int(self._seq_lens[slot])
        if not self._swap_out(slot, sl, reason="preempt") and not req.prewarm:
            # no host copy landed: the written KV is discarded and the
            # resume recomputes it — goodput retroactively becomes waste
            self.profiler.reclassify("preempt_discard", rows_written)
        req.preempt_count += 1
        self.preemptions += 1
        self._state_dirty = True
        self._seq_lens[slot] = 0
        self._last_tokens[slot] = 0
        self._con_states[slot] = 0
        self._constrained[slot] = False
        heapq.heappush(self._free, slot)
        if self.kv_layout == "paged":
            self._release_slot_pages(slot)
            self._block_tables[slot, :] = TRASH_PAGE
            self._tables_dirty = True
        REGISTRY.counter_add(
            "acp_engine_preemptions_total", 1.0,
            help="slots preempted (and requeued) under KV pool pressure",
        )
        if not req.prewarm:
            # the victim + why: the decision the post-mortem always wants
            self.flight.record(
                "preempt", rid=req.rid, slot=slot, reason=reason,
                sampled=len(req.resume_tokens), count=req.preempt_count,
                mid_prefill=sl.prefilling,
            )
        # a request too big for the WHOLE pool can never be resumed — the
        # resume prefill itself would not fit. Finish honestly at current
        # length (this is real memory exhaustion, not contention; the old
        # force-finish behavior, now reserved for the impossible case).
        if self.kv_layout == "paged":
            K = self._block_rows
            ever_needed = min(
                -(-(len(self._full_row(req)) + K) // self.page_size),
                self.max_pages_per_seq,
            )
            if ever_needed > self._allocator.num_pages - 1:
                log.warning(
                    "rid %s needs %d pages to resume but the pool has %d; "
                    "finishing at current length", req.rid, ever_needed,
                    self._allocator.num_pages - 1,
                )
                self._resolve_preempted_as_length(req)
                return
        self._waiting.appendleft(req)
        log.info(
            "preempted rid %s (slot %d, %d tokens sampled, preempt #%d); "
            "requeued at front", req.rid, slot, len(req.resume_tokens),
            req.preempt_count,
        )

    def _resolve_preempted_as_length(self, req: _Request) -> None:
        """Terminal path for a preempted request that can never fit the
        pool again: resolve with what it generated (finish_reason length)."""
        gen = list(req.sampling.forced_prefix) + list(req.resume_tokens)
        if gen and gen[-1] in self.tokenizer.stop_tokens:
            gen = gen[:-1]
        now = time.monotonic()
        result = GenerationResult(
            text=self.tokenizer.decode(gen),
            tokens=gen,
            finish_reason="length",
            prompt_tokens=len(req.prompt),
            ttft_ms=(req.first_token_at - req.enqueued) * 1e3,
            latency_ms=(now - req.enqueued) * 1e3,
            preempt_count=req.preempt_count,
        )
        if not req.prewarm:
            self.flight.finish(
                req.rid, "length", trace=req.trace,
                tokens=len(gen), preempts=req.preempt_count,
                cycle=self.profiler.cycle_n,
            )
        if not req.future.done():
            req.future.set_result(result)
        REGISTRY.counter_add("acp_engine_requests_total", 1.0)
        REGISTRY.counter_add("acp_engine_tokens_total", float(len(gen)))

    def _release_slot_pages(self, slot: int) -> None:
        """A slot gives back what it held of both caches: its full-layer
        pages to the allocator, and (a family with a window cache) its ring,
        which is the slot's again for whoever takes the slot next."""
        self._allocator.free(self._slot_pages.pop(slot, []))
        self._window_rings.pop(slot, None)
        self._rings_held = len(self._window_rings)

    def _append_pages(self, slot: int, new_pages: list[int]) -> None:
        table = self._slot_pages[slot]
        have = len(table)
        self._block_tables[slot, have : have + len(new_pages)] = new_pages
        table.extend(new_pages)
        self._tables_dirty = True

    def _ensure_dev_state(self) -> dict:
        """Device-resident decode state: the per-slot lanes (tokens,
        seq_lens, active, sampling parameters, con_states, budgets, and the
        dispatch counter its key derives from: engine/lanes.py DECODE) are
        ONE packed buffer that round-trips through the decode block's carry
        and is fed back DONATED on the next block. Only a "dirty" block —
        admission, finish, cancel (anything that changed host-side slot
        assignment) — re-packs the host mirrors and uploads them, once.
        Steady-state blocks cost one dispatch + one result fetch and upload
        nothing; clean and dirty blocks run the same compiled program.
        Shared by the split decode block and the megastep's fused decode
        phase (both must upload the same lanes). Paged block tables ride
        the same dirty discipline: re-uploaded only when a page was
        appended (or the state itself was re-uploaded), never per block."""
        if self._state_dirty or self._dev is None:
            # width bucketing: dispatch the smallest compiled width covering
            # the active slots (allocation is lowest-slot-first, so occupancy
            # stays compacted) — one live request doesn't pay max_slots of
            # compute. Width is recomputed only on dirty blocks; finishes
            # mark dirty, so the decay through narrower widths is preserved.
            max_active = max(
                s for s, sl in self._slots.items()
                if not sl.parked and not sl.prefilling
            ) + 1
            W = next(w for w in self.width_buckets if w >= max_active)
            active_mask = np.zeros(W, dtype=bool)
            for slot, sl in self._slots.items():
                if not sl.parked and not sl.prefilling and slot < W:
                    active_mask[slot] = True
            # once the token table exists it is passed unconditionally
            # (matching the prefill path): keying jit entries on "any slot
            # constrained" would DOUBLE the decode-width program matrix, and
            # the table is a device-resident array with no per-dispatch
            # transfer cost
            use_real = self._token_table is not None
            for slot, sl in self._slots.items():
                if not sl.parked and not sl.prefilling:
                    self._budgets[slot] = self._slot_budget(slot, sl)
            self._dev = {
                "W": W,
                "lanes": self._put(DECODE.pack(
                    W, n=self._next_key_n(), chain=0,
                    tokens=self._last_tokens[:W], seq_lens=self._seq_lens[:W],
                    active=active_mask, temps=self._temps[:W],
                    top_ks=self._top_ks[:W], top_ps=self._top_ps[:W],
                    con_states=self._con_states[:W],
                    constrained=self._constrained[:W], budgets=self._budgets[:W],
                )),
                "table": self._token_table if use_real else self._dummy_table,
                "min_close": self._min_close if use_real else self._dummy_min_close,
                # every block fed from these lanes is counted by them
                "masks": masks_wanted(self._top_ks[:W], self._top_ps[:W], active_mask),
            }
            self._state_dirty = False
        d = self._dev
        if self.kv_layout == "paged" and (
            self._tables_dirty or "block_tables" not in d
        ):
            d["block_tables"] = self._put(self._block_tables[: d["W"]])
            self._tables_dirty = False
            self.table_uploads += 1
        return d

    def _decode_once(self) -> None:  # acp: megastep-seam
        pending = self._fuse_pending
        self._fuse_pending = None
        self._apply_cancels()
        if not self._n_active():
            self._megastep_flush(pending)
            return
        if self._faults.enabled:
            spec = self._faults.pop("engine.force_preempt", steps=self.decode_steps)
            if spec is not None:
                victim = self._pick_victim()
                if victim is not None:
                    self._preempt(victim, reason="fault")
        if not self._n_active():
            self._megastep_flush(pending)
            return
        # speculative decoding: when enabled and at least one slot has a
        # draft, ONE verify dispatch replaces this iteration's decode block
        # (it commits 1 + accepted tokens per slot). When no slot drafts —
        # adversarial text, decayed adaptive caps — fall through to the
        # plain block path, which is exactly the spec-off engine. A fused
        # cycle's pending chunk lanes ride whichever dispatch wins.
        if self.spec_len and self._decode_spec(pending):
            return
        with self.profiler.phase("launch"):
            K = self.decode_block_size
            if self.kv_layout == "paged":
                self._ensure_pages_for_block()
                if not self._n_active():
                    self._megastep_flush(pending)
                    return
            d = self._ensure_dev_state()
            W = d["W"]
            n_act = self._n_active()
            KB = self.decode_block_size
            if pending is not None:
                out = self._megastep_dispatch(pending, d=d, n_act=n_act)
                if out is not None:
                    return
                # fused shape over the program bound: dispatch the pending
                # chunk lanes through the split programs, then the plain block
                self._dispatch_pending_split(pending)
                if not self._n_active():
                    self._publish_decode_gauges()
                    return
                d = self._ensure_dev_state()  # finals may have joined
                W = d["W"]
                n_act = self._n_active()
            common = (d["lanes"], self._base_key, d["table"], d["min_close"])
            prof_t0 = self.profiler.start()
            if self.kv_layout == "paged":
                cache, tok_block, con_states, carry = self._jit_decode_paged(
                    self.params, self.cache, *common, d["block_tables"]
                )
            else:
                cache, tok_block, con_states, carry = self._jit_decode(
                    self.params, self.cache, *common
                )
            prog_key = (
                f"decode[{self.kv_layout},{W}x{KB}"
                f"{'+tbl' if d['table'] is not self._dummy_table else ''}]"
            )
            if self.profiler.enabled:
                # real/padded here are the DISPATCH-time view (lanes active as
                # uploaded); mid-block deactivations land precisely in the
                # account() call after the commit loop below
                self.profiler.record(
                    prog_key, prof_t0,
                    out=tok_block, real_tokens=n_act * KB,
                    padded_tokens=(W - n_act) * KB,
                    real_slots=n_act, padded_slots=W - n_act, blocks=1,
                )
        with self.profiler.phase("fetch"):
            # ONE host round trip for both results — through a high-RTT link
            # sequential np.asarray fetches double the per-block latency floor.
            # con_states must stay mirrored so the next dirty upload (admission
            # into some other slot) doesn't clobber live automaton states.
            con_states, tok_block = jax.device_get((con_states, tok_block))
        self.cache = cache
        self._commit_decode_block(tok_block, con_states, carry, d, prog_key)

    @_in_phase("commit")
    def _commit_decode_block(
        self,
        tok_block: np.ndarray,
        con_states: np.ndarray,
        carry: jax.Array,
        d: dict,
        prog_key: str,
    ) -> None:
        """Host-side commit of one decode-block dispatch (split or fused):
        re-seat the device-resident carry (the lanes the program handed
        back), mirror constraint states, commit
        each lane's tokens, and attribute the block's compute."""
        W = d["W"]
        self._count_sampling(d["masks"])
        d["lanes"] = carry
        self._con_states[:W] = con_states
        # tok_block: [K, W]
        K = tok_block.shape[0]
        self.decode_steps += K
        # one event per decode dispatch (batch-level, not per slot/token):
        # a timeline reader sees the cadence, not a flood
        self.flight.record(
            "decode_block", width=W, steps=K, active=self._n_active(),
            program=prog_key, cycle=self.profiler.cycle_n,
        )
        emitted = pre_emitted = 0
        for slot, sl in list(self._slots.items()):
            if sl.parked or sl.prefilling:
                continue  # parked/mid-prefill lanes were not in this dispatch
            if slot >= W:
                continue  # joined after the lanes were built (fused finals)
            n0 = len(sl.generated)
            # [K] tokens a lane, or [K, rows] of a family that drafts: -1
            # where a step emitted none (after a lane's finish too, where
            # the one-token block repeats its last token)
            self._consume_tokens(slot, sl, (int(t) for t in tok_block[:, slot].reshape(-1) if t >= 0))
            # sl stays valid after a _finish pops the slot — the delta is
            # this dispatch's committed tokens (stop tokens included: the
            # termination signal is useful compute)
            if sl.request.prewarm:
                pre_emitted += len(sl.generated) - n0
            else:
                emitted += len(sl.generated) - n0
        if self.profiler.enabled:
            # every one of the W*K computed positions lands in exactly one
            # cause: committed tokens are goodput (or prewarm), the rest —
            # inactive lanes and post-finish steps — is width padding
            self.profiler.account(
                goodput=emitted, prewarm=pre_emitted,
                pad_width=W * K * self._step_rows - emitted - pre_emitted,
            )
        self._publish_decode_gauges()

    # -- fused megastep dispatch ------------------------------------------

    def _validate_pending(self, pending: dict) -> None:
        """Planning ran before this cycle's decode-site faults and page-
        pressure preemptions (the split path dispatches chunks first, so
        its preempts discard ALREADY-landed chunks; fusing inverts that
        order). Drop planned lanes whose slot was preempted, cancelled or
        expired since planning — dispatching them would write KV into
        freed (possibly reallocated) pages. Dropped lanes stay counted as
        budget spent (split parity: their dispatch would have landed
        before the preempt discarded it) but never reach the flight/
        counter record, which covers only real dispatches."""

        def live(c):
            slot, sl, st, _n = c
            return (
                self._slots.get(slot) is sl
                and sl.prefilling
                and sl.prefill_pos == st
                and sl.swap_entry is None
            )

        pending["mids"] = [c for c in pending["mids"] if live(c)]
        pending["finals"] = [c for c in pending["finals"] if live(c)]
        pending["plains"] = [c for c in pending["plains"] if live(c)]

        def live_swap(c):
            # a deferred staged restore stays valid only while the slot is
            # STILL mid-restore at the staged start (a preempt/cancel since
            # planning freed the pages the staged scatter would write)
            slot, sl, st, _n, _groups = c
            return (
                self._slots.get(slot) is sl
                and sl.prefilling
                and sl.prefill_pos == st
                and sl.swap_entry is not None
            )

        pending["swaps"] = [c for c in pending["swaps"] if live_swap(c)]

    def _dispatch_pending_split(self, pending: dict) -> None:
        """Fallback for a fused cycle that cannot (or should not) compile
        a new megastep shape: dispatch the planned lanes through the
        already-compiled split programs — staged restore scatters first
        (their rows are this cycle's oldest KV), then mid chunks, plain
        finals, and continuation finals — then record the round."""
        self._validate_pending(pending)
        mids, conts = pending["mids"], pending["finals"]
        plains, swaps = pending["plains"], pending["swaps"]
        with self._hol_clock():
            for slot, sl, st, n, groups in swaps:
                sl.swap_stall_s += self._commit_staged_swap(groups)
                self._advance_restore(slot, sl, st, n)
            for batch in _pow2_chunks(mids, self.prefill_batch_max):
                self._chunk_dispatch(batch)
            for batch in _pow2_chunks(plains, self.prefill_batch_max):
                self._prefill_group(self._chunk_items(batch))
            for batch in _pow2_chunks(conts, self.prefill_batch_max):
                self._prefill_group(
                    self._chunk_items(batch),
                    starts_np=np.asarray(
                        [st for _, _, st, _ in batch], dtype=np.int32
                    ),
                )
        for slot, sl, st, n in mids:
            sl.prefill_pos = st + n
            self._seq_lens[slot] = sl.prefill_pos
        self._record_chunk_round(
            pending["landed"] + [c[:4] for c in swaps] + mids + plains
            + conts, pending["spent"], pending["budget"],
            pending["restores"],
        )

    def _megastep_flush(self, pending: Optional[dict]) -> None:
        """Dispatch a fused cycle's pending chunk lanes when the cycle
        ended up with no decode/verify phase to fuse with (no active
        slots, or pressure preempted them all): a chunks-only megastep."""
        if pending is None:
            return
        if self._megastep_dispatch(pending) is None:
            self._dispatch_pending_split(pending)

    def _fuse_mid_lanes(self, batch: list) -> tuple:
        # acp: dispatch-lanes toks,lengths,starts,slots,page_ids,tables
        """Lane arrays for the megastep's mid-chunk phase: one batch,
        padded to a power of two (the split path's pow2 DECOMPOSITION has
        no padding rows; fusion trades those rows — accounted as pad_fuse
        waste — for dispatching once). Padding lanes write harmlessly:
        the slot layout clamps starts=max_ctx writes to the never-readable
        max_ctx-1 row (the spec-verify lane-default trick), paged routes
        every page write to TRASH_PAGE."""
        B = len(batch)
        Bp = 1 << (B - 1).bit_length()
        bucket = _next_bucket(max(n for _, _, _, n in batch), self.prefill_buckets)
        toks = np.zeros((Bp, bucket), dtype=np.int32)
        lengths = np.zeros(Bp, dtype=np.int32)
        starts = np.full(
            Bp, self.max_ctx if self.kv_layout == "slot" else 0, dtype=np.int32
        )
        slots = np.full(Bp, self._pad_slot, dtype=np.int32)
        for i, (slot, sl, st, n) in enumerate(batch):
            toks[i, :n] = sl.prefill_row[st : st + n]
            lengths[i] = n
            starts[i] = st
            slots[i] = slot
        lanes = (
            self._put(toks),
            self._kv_lanes(lengths, starts, slots, [sl.request for _, sl, _, _ in batch]),
        )
        if self.kv_layout == "paged":
            P = self.page_size
            page_ids = np.full((Bp, bucket // P), TRASH_PAGE, dtype=np.int32)
            for i, (slot, _sl, st, n) in enumerate(batch):
                # chunk boundaries are page-aligned (see _chunk_tokens), so
                # the commit's whole-page writes touch exactly this chunk's
                # fresh pages — never a page holding earlier KV
                sub = self._slot_pages[slot][st // P : -(-(st + n) // P)]
                page_ids[i, : len(sub)] = sub
            tables = np.full(
                (Bp, self.max_pages_per_seq), TRASH_PAGE, dtype=np.int32
            )
            tables[:B] = self._block_tables[[slot for slot, _, _, _ in batch]]
            lanes += (self._put(page_ids), self._put(tables))
        return lanes, bucket, Bp

    def _fuse_final_lanes(self, batch: list) -> tuple:
        # acp: dispatch-lanes page_ids,tables
        """Lane arrays for the megastep's final-chunk phase: the shared
        _prefill_lanes builder (the budget seam must have one home) at a
        power-of-two width. Padding lanes sample garbage that is never
        committed; their writes land on the trash page / clamped
        never-readable row exactly like _fuse_mid_lanes padding."""
        chunk = self._chunk_items(batch)
        starts = np.asarray([st for _, _, st, _ in batch], dtype=np.int32)
        B = len(batch)
        Bp = 1 << (B - 1).bit_length()
        ln = self._prefill_lanes(chunk, starts, width=Bp)
        bucket = ln["bucket"]
        lanes = (self._put(ln["tokens"]), self._put(ln["lanes"]))
        if self.kv_layout == "paged":
            P = self.page_size
            page_ids = np.full((Bp, bucket // P), TRASH_PAGE, dtype=np.int32)
            for i, (slot, _sl, st, _n) in enumerate(batch):
                fresh = self._slot_pages[slot][st // P :]
                page_ids[i, : len(fresh)] = fresh
            tables = np.full(
                (Bp, self.max_pages_per_seq), TRASH_PAGE, dtype=np.int32
            )
            tables[:B] = self._block_tables[[slot for slot, _, _, _ in batch]]
            lanes += (self._put(page_ids), self._put(tables))
        return (lanes, (ln["table"], ln["min_close"])), bucket, Bp, chunk, ln

    def _fuse_plain_lanes(self, batch: list) -> tuple:
        # acp: dispatch-lanes page_ids
        """Lane arrays for the megastep's plain-prefill phase (paged
        layout only): start-0 finals whose whole row fits one chunk run
        the plain causal program's raw body — byte-for-byte the
        chunked-off dispatch — padded to a power-of-two batch. Padding
        lanes sample garbage that is never committed and route every page
        write to TRASH_PAGE, exactly like _fuse_mid_lanes padding."""
        chunk = self._chunk_items(batch)
        B = len(batch)
        Bp = 1 << (B - 1).bit_length()
        ln = self._prefill_lanes(chunk, np.zeros(B, dtype=np.int32), width=Bp)
        bucket = ln["bucket"]
        P = self.page_size
        page_ids = np.full((Bp, bucket // P), TRASH_PAGE, dtype=np.int32)
        for i, (_req, _slot, pages, _m) in enumerate(chunk):
            assert pages is not None
            page_ids[i, : len(pages)] = pages
        lanes = (self._put(ln["tokens"]), self._put(ln["lanes"]), self._put(page_ids))
        return (lanes, (ln["table"], ln["min_close"])), bucket, Bp, chunk, ln

    def _megastep_dispatch(  # acp: megastep-seam
        self,
        pending: dict,
        d: Optional[dict] = None,
        n_act: int = 0,
        ver: Optional[tuple] = None,
        ver_meta: Optional[dict] = None,
    ) -> Optional[bool]:
        """THE fused dispatch: one compiled program runs this cycle's
        pending staged swap-in scatters + mid chunks + plain finals +
        continuation finals + (decode block | spec verify). Returns True
        when it dispatched and committed; None when the caller must fall
        back to the split programs (a NEW fused shape past
        megastep_max_programs — fusion must not turn the jit cache into a
        combinatorial zoo, so rare shapes reuse the split programs that
        are already compiled)."""
        self._validate_pending(pending)
        mids, finals = pending["mids"], pending["finals"]
        plains, swaps = pending["plains"], pending["swaps"]
        if not mids and not finals and not plains:
            if not swaps and d is None and ver is None:
                # everything the cycle planned was invalidated pre-dispatch
                self._record_chunk_round(
                    pending["landed"], pending["spent"], pending["budget"],
                    pending["restores"],
                )
                return True
            if d is None and ver is None:
                # scatter-only cycle: nothing to fuse WITH — the split
                # commit is already a single dispatch, so a new fused
                # shape would buy nothing
                return None
            if not swaps:
                return None  # nothing to fuse; run the plain decode/verify
        # the shape key is host arithmetic — compute it and apply the
        # program bound BEFORE building/uploading any lane arrays, so a
        # fallback cycle never pays device transfers it throws away
        KB = self.decode_block_size
        mid_bucket = mid_Bp = fin_bucket = fin_Bp = pl_bucket = pl_Bp = 0
        if mids:
            mid_bucket = _next_bucket(
                max(n for _, _, _, n in mids), self.prefill_buckets
            )
            mid_Bp = 1 << (len(mids) - 1).bit_length()
        if plains:
            pl_bucket = max(
                _next_bucket(len(sl.prefill_row), self.prefill_buckets)
                for _slot, sl, _st, _n in plains
            )
            pl_Bp = 1 << (len(plains) - 1).bit_length()
        if finals:
            fin_bucket = max(
                _next_bucket(len(sl.prefill_row) - st, self.prefill_buckets)
                for _slot, sl, st, _n in finals
            )
            fin_Bp = 1 << (len(finals) - 1).bit_length()
        tbl = "+tbl" if self._token_table is not None else ""
        parts = []
        if swaps:
            # the scatter group sizes ARE the trace shape (one cache
            # scatter per pow2 group, in order)
            parts.append("s" + "-".join(
                str(int(ids.shape[0]))
                for _slot, _sl, _st, _n, groups in swaps
                for ids, _blocks in groups
            ))
        if mids:
            parts.append(f"m{mid_bucket}x{mid_Bp}")
        if plains:
            parts.append(f"p{pl_bucket}x{pl_Bp}")
        if finals:
            parts.append(f"f{fin_bucket}x{fin_Bp}")
        W = T = 0
        if d is not None:
            W = d["W"]
            parts.append(f"d{W}x{KB}")
        elif ver is not None:
            W, T = ver_meta["W"], ver_meta["T"]
            parts.append(f"v{W}x{T}")
        shape = (self.kv_layout, tuple(parts), tbl)
        if (
            shape not in self._megastep_shapes
            and len(self._megastep_shapes) >= self.megastep_max_programs
        ):
            self.megastep_fallbacks += 1
            REGISTRY.counter_add(
                "acp_engine_megastep_fallbacks_total", 1.0,
                help="fused cycles split-dispatched because a new megastep "
                "shape would exceed megastep_max_programs (the bound on "
                "distinct fused jit entries)",
            )
            return None
        with self.profiler.phase("launch"):
            mid_lanes = fin_lanes = pl_lanes = swap_arg = None
            fin_chunk = fin_ln = pl_chunk = pl_ln = None
            if swaps:
                swap_arg = tuple(
                    (ids, blocks)
                    for _slot, _sl, _st, _n, groups in swaps
                    for ids, blocks in groups
                )
            if mids:
                mid_lanes, mid_bucket, mid_Bp = self._fuse_mid_lanes(mids)
            if plains:
                pl_lanes, pl_bucket, pl_Bp, pl_chunk, pl_ln = (
                    self._fuse_plain_lanes(plains)
                )
            if finals:
                fin_lanes, fin_bucket, fin_Bp, fin_chunk, fin_ln = (
                    self._fuse_final_lanes(finals)
                )
            dec_lanes = dec_aux = None
            if d is not None:
                dec_lanes = d["lanes"]
                extra = (d["block_tables"],) if self.kv_layout == "paged" else ()
                dec_aux = (d["table"], d["min_close"], extra)
            key = f"megastep[{self.kv_layout},{'+'.join(parts)}{tbl}]"
            new_shape = shape not in self._megastep_shapes
            self._megastep_shapes.add(shape)
            prof_t0 = self.profiler.start()
            cache, p_out, f_out, d_out, v_out = self._jit_megastep(
                self.params, self.cache, self._base_key, swap_arg, mid_lanes,
                pl_lanes, fin_lanes, dec_lanes, dec_aux, ver,
            )
            self.megastep_dispatches += 1
            if new_shape:
                self.flight.record("megastep_shape", program=key)
            mid_real = sum(n for _, _, _, n in mids)
            pl_real = int(pl_ln["lengths"].sum()) if plains else 0
            fin_real = int(fin_ln["lengths"].sum()) if finals else 0
            swap_real = sum(
                int(ids.shape[0]) * self.page_size for ids, _ in (swap_arg or ())
            )
            if self.profiler.enabled:
                # swap rows count as real tokens only (the split scatter
                # records real_tokens with no goodput accounting; fused keeps
                # that) — restored rows are moved KV, not computed tokens
                real = mid_real + pl_real + fin_real + swap_real
                padded = 0
                if mids:
                    padded += mid_Bp * mid_bucket - mid_real
                if plains:
                    padded += pl_Bp * pl_bucket - pl_real
                if finals:
                    padded += fin_Bp * fin_bucket - fin_real
                real_slots = len(mids) + len(plains) + len(finals)
                padded_slots = (
                    (mid_Bp - len(mids)) + (pl_Bp - len(plains))
                    + (fin_Bp - len(finals))
                )
                if d is not None:
                    real += n_act * KB
                    padded += (W - n_act) * KB
                    real_slots += n_act
                    padded_slots += W - n_act
                elif ver is not None:
                    real += ver_meta["real_in"]
                    padded += W * T - ver_meta["real_in"]
                    real_slots += ver_meta["n_part"]
                    padded_slots += W - ver_meta["n_part"]
                out_probe = (
                    d_out[0] if d_out is not None
                    else v_out[0] if v_out is not None
                    else f_out[0] if f_out is not None
                    else p_out[0] if p_out is not None
                    else _a_leaf(cache)  # chunks-only: block on the committed KV
                )
                self.profiler.record(
                    key, prof_t0, out=out_probe, real_tokens=real,
                    padded_tokens=padded, real_slots=real_slots,
                    padded_slots=padded_slots,
                    blocks=1 if d is not None else 0,
                )
                # the fused phases classify exactly as their split programs
                # would, plus pad_fuse for the pow2-padding rows fusion adds
                # (the split pow2 DECOMPOSITION has none)
                if mids:
                    pre = sum(n for _, sl, _, n in mids if sl.request.prewarm)
                    self.profiler.account(
                        goodput=mid_real - pre, prewarm=pre,
                        pad_bucket=len(mids) * mid_bucket - mid_real,
                        pad_fuse=(mid_Bp - len(mids)) * mid_bucket,
                    )
                if plains:
                    pre = sum(
                        int(pl_ln["lengths"][i])
                        for i, (r, _, _, _) in enumerate(pl_chunk)
                        if r.prewarm
                    )
                    self.profiler.account(
                        goodput=pl_real - pre, prewarm=pre,
                        pad_bucket=len(plains) * pl_bucket - pl_real,
                        pad_fuse=(pl_Bp - len(plains)) * pl_bucket,
                    )
                if finals:
                    pre = sum(
                        int(fin_ln["lengths"][i])
                        for i, (r, _, _, _) in enumerate(fin_chunk)
                        if r.prewarm
                    )
                    self.profiler.account(
                        goodput=fin_real - pre, prewarm=pre,
                        pad_bucket=len(finals) * fin_bucket - fin_real,
                        pad_fuse=(fin_Bp - len(finals)) * fin_bucket,
                    )
        with self.profiler.phase("fetch"):
            # ONE host round trip for every phase's results (None phases fetch
            # nothing — device_get maps over the pytree)
            tok_block, con_states, carry = d_out if d_out is not None else (None,) * 3
            f_np, p_np, dec_fetch, ver_np = jax.device_get((
                f_out, p_out, (con_states, tok_block), v_out,
            ))
        with self.profiler.phase("commit"):
            self.cache = cache
            # commit order matters: swap bookkeeping and mid chunks advance
            # first (bookkeeping only — their cache writes already landed in
            # the program), then the decode/verify commit — its lanes predate
            # this cycle's finals, so it must run BEFORE finals/plains flip
            # their slots to ACTIVE (a freed-and-reused slot id would
            # otherwise read garbage lanes) — and the finals/plains commit
            # last. A fused restore adds NO stall seconds: the host->device
            # copy overlapped last cycle and the scatter rode this dispatch.
            for slot, sl, st, n, _groups in swaps:
                REGISTRY.counter_add(
                    "acp_engine_kv_prefetch_commits_total", 1.0,
                    help="host-KV restore chunks whose rows were prefetched "
                    "(staged host->device a cycle early) and landed by scatter "
                    "commit — the async-prefetch overlap win; chunks NOT "
                    "counted here paid the blocking copy as host_stall",
                )
                self._advance_restore(slot, sl, st, n)
            for slot, sl, st, n in mids:
                sl.prefill_pos = st + n
                self._seq_lens[slot] = sl.prefill_pos
            if d_out is not None:
                con_states, tok_block = dec_fetch
                self._commit_decode_block(tok_block, con_states, carry, d, key)
            if v_out is not None:
                out_toks, n_emit, new_states = ver_np
                self._commit_spec_verify(
                    out_toks, n_emit, new_states, ver_meta, key
                )
            if finals:
                firsts, fstates = f_np
                B = len(finals)
                self._finish_prefill_dispatch(
                    fin_chunk, firsts[:B], fstates[:B], fin_ln["full_lens"]
                )
            if plains:
                p_firsts, p_states = p_np
                B = len(plains)
                self._finish_prefill_dispatch(
                    pl_chunk, p_firsts[:B], p_states[:B], pl_ln["full_lens"]
                )
            self._record_chunk_round(
                pending["landed"] + [c[:4] for c in swaps] + mids + plains
                + finals, pending["spent"], pending["budget"],
                pending["restores"],
            )
        return True

    def _consume_tokens(self, slot: int, sl: _Slot, toks) -> None:
        """Host-side commit of one dispatch's newly sampled tokens for one
        slot (shared by the decode block and the speculative verify path):
        advance the host mirrors, stream to the caller, and finish at the
        first stop token / exhausted budget / context edge — the same spots
        the device deactivated the lane, so host and device bookkeeping
        never diverge."""
        s = sl.request.sampling
        done = None
        block_new: list[int] = []
        for tok in toks:
            self._seq_lens[slot] += 1
            self._last_tokens[slot] = tok
            sl.generated.append(tok)
            self.tokens_generated += 1
            if tok in self.tokenizer.stop_tokens:
                done = "stop"
                break
            block_new.append(tok)
            if (
                len(sl.generated) - sl.prefix_len >= s.max_tokens
                or self._seq_lens[slot] + 1 >= self.max_ctx
            ):
                done = "length"
                break
        self._stream(sl.request, block_new)
        if done is not None:
            self._finish(slot, done)

    def _stream(self, req: _Request, tokens: list[int]) -> None:
        """Engine-thread commit of newly sampled tokens to the caller:
        forwards the raw ids (on_tokens) and — when overlapped tool
        execution is on — detokenizes the delta and feeds the request's
        incremental tool parser, firing ``on_tool_call`` for every call
        whose braces closed in this commit. Shared by every path that
        emits tokens (prefill first-token + forced prefix, the plain
        decode block, and speculative multi-token commits), so early
        dispatch sees the same token stream in every engine mode."""
        req.emit(tokens)
        if req.tool_parser is None or not tokens:
            return
        req.detok_pending.extend(tokens)
        text = self.tokenizer.decode(req.detok_pending)
        if text.endswith("�"):
            return  # partial multi-byte char at a commit boundary; hold
        req.detok_pending.clear()
        self._feed_tool_parser(req, text)

    def _stream_flush(self, req: _Request) -> None:
        """Final flush at generation end: feed any held-back text (an
        incomplete UTF-8 tail never completed) so the parser has consumed
        exactly the generated text before the batch reconcile."""
        if req.tool_parser is None or not req.detok_pending:
            return
        text = self.tokenizer.decode(req.detok_pending)
        req.detok_pending.clear()
        self._feed_tool_parser(req, text)

    def _feed_tool_parser(self, req: _Request, text: str) -> None:
        try:
            calls = req.tool_parser.feed(text)
        except Exception:  # a broken parser must not kill the engine
            log.exception("tool stream parser failed; disabling for rid %s", req.rid)
            req.tool_parser = None
            return
        if not calls:
            return
        now = time.monotonic()
        for tc in calls:
            idx = len(req.early_calls)
            req.early_calls.append((now, tc))
            self.tool_calls_early += 1
            if not req.prewarm:
                # the emit edge of this call's tool_overlap_hidden window
                self.flight.record(
                    "tool_call", rid=req.rid, index=idx,
                    name=tc.function.name,
                )
            REGISTRY.counter_add(
                "acp_engine_tool_calls_early_total", 1.0,
                help="tool calls emitted from the decode stream before "
                "generation finished",
            )
            if req.on_tool_call is not None:
                try:
                    req.on_tool_call(idx, tc)
                except Exception:  # a broken consumer must not kill the engine
                    log.exception("on_tool_call failed; disabling for rid %s", req.rid)
                    req.on_tool_call = None

    @_in_phase("publish")
    def _publish_decode_gauges(self) -> None:
        REGISTRY.gauge_set(
            "acp_engine_active_slots", self._n_active(),
            help="occupied decode slots (parked slots excluded — see "
            "acp_engine_parked_slots)",
        )
        REGISTRY.gauge_set(
            "acp_engine_waiting_requests", len(self._waiting),
            help="admission queue depth",
        )
        REGISTRY.gauge_set(
            "acp_engine_preempted_waiting",
            self._preempted_waiting(),
            help="preempted requests requeued and awaiting resume",
        )
        REGISTRY.gauge_set(
            "acp_engine_prefilling_slots",
            float(self._prefilling_count),
            help="slots admitted but still mid-prefill under the chunked "
            "token-budget scheduler",
        )

    def _slot_budget(self, slot: int, sl: _Slot) -> int:  # acp: budget-seam
        """Sampled tokens this slot may still emit — min of its remaining
        ``max_tokens`` and the context edge (the device deactivates a slot
        after the token that lands it at max_ctx-1). The decode block and
        the speculative verify dispatch MUST share this computation: the
        device-side budget decrement and host max_tokens accounting stay
        consistent only if both paths upload the same number."""
        token_left = sl.request.sampling.max_tokens - (
            len(sl.generated) - sl.prefix_len
        )
        ctx_left = self.max_ctx - 1 - int(self._seq_lens[slot])
        return max(0, min(token_left, ctx_left))

    def _slot_ctx(self, sl: _Slot) -> np.ndarray:
        """Prompt+generated as one int32 view for the drafter, synced by
        appending only the tokens emitted since the last dispatch."""
        n_prompt = len(sl.request.prompt)
        total = n_prompt + len(sl.generated)
        if sl.ctx_buf is None:
            sl.ctx_buf = np.empty(max(total, self.max_ctx), dtype=np.int32)
            sl.ctx_buf[:n_prompt] = sl.request.prompt
            sl.ctx_len = n_prompt
        elif total > sl.ctx_buf.shape[0]:
            sl.ctx_buf = np.concatenate(
                [sl.ctx_buf, np.empty(total, dtype=np.int32)]
            )
        if sl.ctx_len < total:
            sl.ctx_buf[sl.ctx_len : total] = sl.generated[sl.ctx_len - n_prompt :]
            sl.ctx_len = total
        return sl.ctx_buf[:total]

    def _decode_spec(self, pending: Optional[dict] = None) -> bool:  # acp: megastep-seam
        # acp: dispatch-lanes inputs,n_input,starts,active,budgets,proposed
        """One speculative decode iteration: draft host-side (n-gram prompt
        lookup over prompt + generated-so-far), verify every position in a
        single batched dispatch, commit the accepted prefix + one corrected
        token per slot. Returns False (nothing dispatched) when no active
        slot produced a draft — the caller then runs the plain decode block,
        which is byte-for-byte today's non-speculative path.

        Composition notes:
        - KV: the verify program writes every draft position optimistically;
          rollback of a rejected tail is implicit — the host advances
          ``seq_lens`` only over emitted tokens and attention never reads
          beyond ``seq_len`` (paged: the extra rows sit in pages the slot
          already owns, exactly like decode-block lookahead pages).
        - Device-resident decode state: the spec path syncs with the host
          every dispatch by construction (the drafter needs the sampled
          tokens), so it packs and uploads its lanes each time (VERIFY,
          engine/lanes.py) and marks ``_state_dirty`` — a later fallback
          block re-uploads the carried state like any other dirty block.
        - Preemption/prefix cache: drafts are host-only; page pressure in
          ``_ensure_pages_for_block`` preempts exactly as in the block path
          (preempted slots are dropped from this dispatch).
        """
        with self.profiler.phase("launch"):
            from .spec import ngram_propose

            T = self.spec_len + 1  # one trace shape per width bucket
            drafts: dict[int, list[int]] = {}
            budgets_eff: dict[int, int] = {}
            any_draft = False
            for slot, sl in self._slots.items():
                if sl.parked or sl.prefilling:
                    continue
                budget = self._slot_budget(slot, sl)
                budgets_eff[slot] = budget
                # the dispatch emits up to draft+1 tokens and writes draft+1 KV
                # rows: cap the draft so both stay within budget (and therefore
                # within the context edge — budget <= ctx_left)
                cap = min(sl.spec.cap(), budget - 1) if sl.spec else 0
                d: list[int] = []
                if cap > 0:
                    d = ngram_propose(self._slot_ctx(sl), self.spec_ngram, cap)
                drafts[slot] = d
                any_draft = any_draft or bool(d)
            if not any_draft:
                return False
            if self.kv_layout == "paged":
                # page coverage for the widest row each slot verifies; a slot
                # preempted under pressure here simply leaves the dispatch
                self._ensure_pages_for_block(
                    {slot: 1 + len(d) for slot, d in drafts.items()}
                )
                if not self._n_active():
                    self._megastep_flush(pending)
                    return True
                drafts = {s: d for s, d in drafts.items() if s in self._slots}
                if not any(drafts.values()):
                    return False  # the drafted slots were preempted; block-decode
            force_reject = bool(
                self._faults.enabled
                and self._faults.pop("engine.spec_mismatch") is not None
            )
            W = next(
                w for w in self.width_buckets
                if w >= max(
                    s for s, sl in self._slots.items()
                    if not sl.parked and not sl.prefilling
                ) + 1
            )
            inputs = np.zeros((W, T), dtype=np.int32)
            # lanes NOT in this dispatch (free, parked, mid-prefill) must write
            # their optimistic K/V somewhere HARMLESS: n_input=0 sends every
            # paged write to the trash page (token_write_targets masks by
            # length), and starts=max_ctx clamps the slot layout's scatter to
            # row max_ctx-1, which attention can never read (a lane deactivates
            # at max_ctx-1). The old defaults (n_input=1, starts=0) scattered
            # one garbage row into position 0 of the lane's LIVE KV — harmless
            # for free lanes (the next prefill overwrites from 0) but corrupting
            # for parked prompt KV awaiting adoption and for mid-prefill slots.
            n_input = np.zeros(W, dtype=np.int32)
            starts = np.full(W, self.max_ctx, dtype=np.int32)
            active = np.zeros(W, dtype=bool)
            budgets = np.zeros(W, dtype=np.int32)
            proposed = np.zeros(W, dtype=np.int32)
            for slot, sl in self._slots.items():
                if sl.parked or sl.prefilling:
                    continue
                d = drafts.get(slot, [])
                inputs[slot, 0] = self._last_tokens[slot]
                if d:
                    inputs[slot, 1 : 1 + len(d)] = d
                n_input[slot] = 1 + len(d)
                starts[slot] = self._seq_lens[slot]
                active[slot] = True
                budgets[slot] = budgets_eff[slot]
                proposed[slot] = len(d)
            use_real = self._token_table is not None
            self._count_sampling(masks_wanted(self._top_ks[:W], self._top_ps[:W], active))
            # three uploads: the draft rows, the lanes, the block tables
            args = [
                self.params,
                self.cache,
                self._put(inputs),
                self._put(VERIFY.pack(
                    W, n=self._next_key_n(), n_input=n_input, starts=starts,
                    active=active, force_reject=force_reject,
                    temps=self._temps[:W], top_ks=self._top_ks[:W],
                    top_ps=self._top_ps[:W], con_states=self._con_states[:W],
                    constrained=self._constrained[:W], budgets=budgets,
                )),
                self._base_key,
                self._token_table if use_real else self._dummy_table,
                self._min_close if use_real else self._dummy_min_close,
            ]
            if self.kv_layout == "paged":
                args.append(self._put(self._block_tables[:W]))
            ver_meta = {
                "W": W, "T": T, "drafts": drafts, "proposed": proposed,
                "force_reject": force_reject, "real_in": int(n_input.sum()),
                "n_part": int(active.sum()),
            }
            if pending is not None:
                # fused cycle: the verify pass rides the megastep with the
                # pending chunk lanes (one dispatch). Shape-bound fallback
                # split-dispatches the chunks, then verifies standalone below
                # (finals activated by the fallback join the NEXT cycle's
                # lanes — per-request greedy bytes are unaffected).
                if self._megastep_dispatch(
                    pending, ver=(*args[2:4], *args[5:]), ver_meta=ver_meta
                ):
                    return True
                self._dispatch_pending_split(pending)
                # the fallback's chunk dispatches DONATED the cache args[1]
                # captured above and reassigned self.cache — verifying against
                # the stale buffer would crash (deleted buffer) or silently
                # discard this cycle's chunk KV writes
                args[1] = self.cache
            prof_t0 = self.profiler.start()
            cache, out_toks, n_emit, new_states = self._jit_verify(*args)
            self.cache = cache
            spec_prog_key = (
                f"spec_verify[{self.kv_layout},{W}x{T}{'+tbl' if use_real else ''}]"
            )
            if self.profiler.enabled:
                n_part = ver_meta["n_part"]
                real_in = ver_meta["real_in"]
                self.profiler.record(
                    spec_prog_key, prof_t0,
                    out=out_toks, real_tokens=real_in,
                    padded_tokens=W * T - real_in,
                    real_slots=n_part, padded_slots=W - n_part,
                )
        with self.profiler.phase("fetch"):
            # one combined host round trip, same discipline as the block path
            out_toks, n_emit, new_states = jax.device_get((out_toks, n_emit, new_states))
        self._commit_spec_verify(
            out_toks, n_emit, new_states, ver_meta, spec_prog_key
        )
        return True

    @_in_phase("commit")
    def _commit_spec_verify(
        self,
        out_toks: np.ndarray,
        n_emit: np.ndarray,
        new_states: np.ndarray,
        ver_meta: dict,
        prog_key: str,
    ) -> None:
        """Host-side commit of one speculative-verify dispatch (split or
        fused): mirror constraint states, commit accepted prefixes + the
        corrected token per slot, feed the AIMD controllers, and attribute
        the pass's compute."""
        W, T = ver_meta["W"], ver_meta["T"]
        drafts = ver_meta["drafts"]
        proposed = ver_meta["proposed"]
        force_reject = ver_meta["force_reject"]
        self._con_states[:W] = new_states
        self.decode_steps += 1  # one model forward, however many tokens land
        self.spec_dispatches += 1
        self._state_dirty = True  # host mirrors advanced; next block re-uploads
        sp_emitted = sp_pre = sp_rejected = 0
        for slot, sl in list(self._slots.items()):
            if sl.parked or sl.prefilling or slot >= W:
                continue
            n = int(n_emit[slot])
            prop = int(proposed[slot])
            n_gen0 = len(sl.generated)
            if prop:
                # emitted = accepted prefix + one corrected token — except
                # when emission ended ON a matching draft token (stop token
                # or budget exhaustion), where the final token is an
                # accepted draft token too. force_reject means the device
                # treated every position as mismatched; a numerically-equal
                # final token must not count as accepted or the AIMD
                # controller would see partial acceptance under the
                # spec_mismatch fault and never decay.
                d = drafts.get(slot, [])
                acc = max(0, n - 1)
                if (
                    not force_reject
                    and 0 < n <= len(d)
                    and int(out_toks[slot, n - 1]) == d[n - 1]
                ):
                    acc = n
                acc = min(acc, prop)
                self.spec_proposed += prop
                self.spec_accepted += acc
                if sl.spec is not None:
                    sl.spec.observe(prop, acc)
                REGISTRY.counter_add(
                    "acp_engine_spec_proposed_total", float(prop),
                    help="draft tokens proposed to speculative verification",
                )
                REGISTRY.counter_add(
                    "acp_engine_spec_accepted_total", float(acc),
                    help="draft tokens accepted by speculative verification",
                )
            if n > 0:
                self._consume_tokens(slot, sl, (int(t) for t in out_toks[slot, :n]))
            d_tok = len(sl.generated) - n_gen0
            if sl.request.prewarm:
                sp_pre += d_tok
            else:
                sp_emitted += d_tok
            if prop:
                # positions the verify pass computed past the emitted
                # prefix: rejected draft tail (the speculation gamble lost)
                sp_rejected += max(0, 1 + prop - n)
        if self.profiler.enabled:
            self.profiler.account(
                goodput=sp_emitted, prewarm=sp_pre,
                spec_rejected=sp_rejected,
                pad_width=W * T - sp_emitted - sp_pre - sp_rejected,
            )
        if self.flight.enabled:
            # one aggregate event per verify dispatch: the propose/verify/
            # accept decision, with how much the drafts actually paid
            self.flight.record(
                "spec_verify",
                slots=int(sum(1 for d in drafts.values() if d)),
                proposed=int(sum(len(d) for d in drafts.values())),
                emitted=int(sum(int(n_emit[s]) for s in drafts)),
                forced_reject=force_reject,
                program=prog_key, cycle=self.profiler.cycle_n,
            )
        self._publish_decode_gauges()

    def _finish(self, slot: int, reason: str) -> None:
        sl = self._slots.get(slot)
        if sl is None:
            return
        if sl.parked:
            # the future resolved when the slot parked; a finish now is a
            # cancel/stop/drain — release the lingering bookkeeping
            self._release_parked(slot, reason=reason)
            return
        if sl.prefilling:
            # a finish can only reach a mid-prefill slot via cancel, a
            # replicated deadline release, or shutdown drain — nothing was
            # sampled, so there is no result to resolve: release the
            # partial KV and fail like a never-admitted request
            self._drop_prefilling_slot(slot)
            req = sl.request
            self._cancelled.discard(req.rid)
            self._applied_cancels.discard(req.rid)
            if not req.prewarm:
                self.flight.record(
                    "cancel", rid=req.rid, slot=slot, where="mid_prefill",
                    reason=reason,
                )
                self.flight.discard(req.rid)
            if not req.future.done():
                if reason == "cancelled":
                    req.future.cancel()
                else:
                    req.future.set_exception(RuntimeError("engine stopped"))
            return
        req = sl.request
        if reason in ("stop", "length"):
            # a cancelled/drained request must not fire late tool events —
            # its caller is gone and an early CR would be pure orphan
            self._stream_flush(req)
        if req.early_calls:
            # overlap window this turn made available: time between each
            # call becoming dispatchable and the generation completing
            now = time.monotonic()
            saved = sum(now - t for t, _ in req.early_calls)
            self.tool_overlap_saved_s += saved
            REGISTRY.counter_add(
                "acp_engine_tool_overlap_saved_seconds", saved,
                help="per early tool call, seconds between its dispatch "
                "becoming possible and its turn's generation finishing",
            )
        if (
            req.park
            and reason in ("stop", "length")
            and not self._stopping
            and self._park_cut_for(sl) > 0
        ):
            self._park(slot, sl, reason)
            return
        kv_entry = None
        if req.export_kv and reason in ("stop", "length") and not self._stopping:
            # disaggregation: extract the prompt KV BEFORE the slot (and in
            # paged mode its pages) is torn down below
            kv_entry = self._export_kv_handoff(slot, sl)
        self._slots.pop(slot)
        self._state_dirty = True  # device lane must be re-uploaded inactive
        self._cancelled.discard(req.rid)
        self._applied_cancels.discard(req.rid)
        self._seq_lens[slot] = 0
        self._last_tokens[slot] = 0
        self._con_states[slot] = 0
        self._constrained[slot] = False
        heapq.heappush(self._free, slot)
        if self.kv_layout == "paged":
            self._release_slot_pages(slot)
            self._block_tables[slot, :] = TRASH_PAGE
        self._resolve_result(sl, reason, slot=slot, kv_entry=kv_entry)

    def _resolve_result(
        self, sl: _Slot, reason: str, slot: int = -1, kv_entry=None
    ) -> None:
        """Resolve a slot's future with its GenerationResult — shared by the
        normal finish and the park transition (a parked slot's caller gets
        its result immediately; only the KV bookkeeping lingers)."""
        gen = sl.generated
        if gen and gen[-1] in self.tokenizer.stop_tokens:
            gen = gen[:-1]
        now = time.monotonic()
        result = GenerationResult(
            text=self.tokenizer.decode(gen),
            tokens=gen,
            finish_reason=reason,
            prompt_tokens=sl.prompt_len,
            ttft_ms=(sl.first_token_at - sl.request.enqueued) * 1e3,
            latency_ms=(now - sl.request.enqueued) * 1e3,
            preempt_count=sl.request.preempt_count,
            kv_handoff=kv_entry,
        )
        if not sl.request.prewarm:
            # terminal flight event + phase attribution export (histograms
            # and, when the request carried a trace context, OTLP child
            # spans under the Task's trace). BEFORE the future resolves, so
            # a caller that immediately queries /timeline sees a complete
            # record instead of racing the engine thread.
            self.flight.finish(
                sl.request.rid, reason, slot=slot, trace=sl.request.trace,
                tokens=len(gen), preempts=sl.request.preempt_count,
                cycle=self.profiler.cycle_n,
            )
        if not sl.request.future.done():
            sl.request.future.set_result(result)
        self._crash_streak = 0  # progress: the next crash is not a loop
        REGISTRY.counter_add("acp_engine_requests_total", 1.0)
        REGISTRY.counter_add("acp_engine_tokens_total", float(len(gen)))

    # -- parked slots (overlapped tool execution) -------------------------

    def _park_cut_for(self, sl: _Slot) -> int:
        """KV rows a parked slot can lend the conversation's next turn:
        the PROMPT rows only (the next turn re-renders the assistant
        message, so generated-token KV can never match), page-aligned in
        paged mode because continuation prefill resumes at page grain."""
        if self._has_state:
            # only where this admission saved the state can a turn resume
            return sl.request.state_cut
        if self.kv_layout == "paged":
            return (sl.prompt_len // self.page_size) * self.page_size
        return sl.prompt_len

    def _park(self, slot: int, sl: _Slot, reason: str) -> None:
        """Voluntary park at generation end (the preempt machinery's page
        discipline, minus the victim scan and the requeue): the caller's
        future resolves NOW with the finished result; the slot stays
        occupied holding only its prompt KV — surplus pages are freed —
        so the next turn of this conversation prefills just its suffix.
        Under pool pressure parked slots are the first to yield
        (_release_parked), and an unclaimed park expires after
        park_max_s."""
        req = sl.request
        self._state_dirty = True
        self._cancelled.discard(req.rid)
        self._applied_cancels.discard(req.rid)
        cut = self._park_cut_for(sl)
        sl.parked = True
        sl.parked_at = time.monotonic()
        sl.park_cut = cut
        self._parked_count += 1
        # host mirrors: the lane is finished on device (never advances);
        # seq_len records the rows that remain meaningful for adoption
        self._seq_lens[slot] = cut
        self._last_tokens[slot] = 0
        self._con_states[slot] = 0
        self._constrained[slot] = False
        self._budgets[slot] = 0
        if self.kv_layout == "paged":
            keep = cut // self.page_size
            table = self._slot_pages.get(slot, [])
            if len(table) > keep:
                excess = table[keep:]
                del table[keep:]
                self._block_tables[slot, keep : keep + len(excess)] = TRASH_PAGE
                self._allocator.free(excess)
                self._tables_dirty = True
        self.parks += 1
        REGISTRY.counter_add(
            "acp_engine_parks_total", 1.0,
            help="slots parked at generation end awaiting the "
            "conversation's next turn",
        )
        if not req.prewarm:
            self.flight.record("park", rid=req.rid, slot=slot, cut=cut)
        self._publish_park_gauge()
        self._resolve_result(sl, reason, slot=slot)

    def _release_parked(self, slot: int, reason: str = "pressure") -> None:
        """Free a parked slot entirely (pressure, expiry, stop, or a
        forced preemption landing on it). The future resolved at park
        time, so this is pure bookkeeping — the voluntary, no-victim-scan
        analogue of _preempt's page release."""
        sl = self._slots.get(slot)
        if sl is None or not sl.parked:
            return
        if reason in ("pressure", "expired", "pool_pressure", "fault"):
            # the prompt KV is still reusable (same persona/conversation
            # re-arriving later): offload it before the pages go, so the
            # host tier's prefix match can restore instead of re-prefilling
            self._swap_out(slot, sl, reason=f"park_{reason}")
        if not sl.request.prewarm:
            self.flight.record(
                "park_release", rid=sl.request.rid, slot=slot, reason=reason
            )
            # the rid's timeline was retired when the park resolved its
            # future — retire the release event too (extends the finished
            # timeline) instead of leaving an orphan live entry
            self.flight.discard(sl.request.rid)
        self._slots.pop(slot)
        self._parked_count -= 1
        self._state_dirty = True
        self._seq_lens[slot] = 0
        self._last_tokens[slot] = 0
        heapq.heappush(self._free, slot)
        if self.kv_layout == "paged":
            self._release_slot_pages(slot)
            self._block_tables[slot, :] = TRASH_PAGE
            self._tables_dirty = True
        self.park_releases += 1
        self._publish_park_gauge()

    def _release_lru_parked(self, exclude: Optional[int] = None) -> bool:
        """Release the longest-parked slot (if any). True if one yielded."""
        parked = [
            (sl.parked_at, s)
            for s, sl in self._slots.items()
            if sl.parked and s != exclude
        ]
        if not parked:
            return False
        self._release_parked(min(parked)[1])
        return True

    def _sweep_parked(self) -> None:
        """Expire parked slots whose next turn never came (final answers,
        failed tasks). Engine-thread, every loop iteration — cheap."""
        if not self.park_max_s:
            return
        now = time.monotonic()
        expired = [
            s for s, sl in self._slots.items()
            # wall-clock expiry is safe here WITHOUT the leader seam: the
            # constructor forces park_max_s=0 under coordination (parking
            # disabled entirely), so this compare never runs in lockstep
            if sl.parked and now - sl.parked_at > self.park_max_s  # acp-lint: disable=coord-wallclock
        ]
        for slot in expired:
            self._release_parked(slot, reason="expired")

    def _match_parked(self, req: _Request) -> Optional[int]:
        """Parked slot whose prompt KV covers the longest prefix of this
        request's row — the adoption candidate for a conversation's next
        turn. Strict prefix (suffix tokens must remain to prefill)."""
        if req.truncated:
            return None
        full = self._full_row(req)
        best, best_cut = None, 0
        for slot, sl in self._slots.items():
            if not sl.parked:
                continue
            cut = sl.park_cut
            if (
                0 < cut < len(full)
                and cut > best_cut
                and list(sl.request.prompt[:cut]) == full[:cut]
            ):
                best, best_cut = slot, cut
        return best

    def _reject_oversize_head(self, req: _Request, total_pages: int) -> bool:
        """Paged admission guard shared by the free-slot and parked-
        adoption paths: a row bigger than the ENTIRE pool can never fit —
        fail it up front (waiting would spin forever). True if rejected."""
        if total_pages <= self._allocator.num_pages - 1:
            return False
        self._waiting.popleft()
        if not req.prewarm:
            self.flight.record(
                "cancel", rid=req.rid, where="oversize",
                pages_needed=total_pages,
            )
            self.flight.discard(req.rid)
        req.future.set_exception(
            RuntimeError(
                f"prompt needs {total_pages} KV pages but the pool has "
                f"{self._allocator.num_pages - 1}"
            )
        )
        return True

    def _adopt_parked(self, req: _Request, slot: int) -> Optional[list]:
        """Hand a parked slot to the next turn of its conversation (the
        head of the waiting deque). Returns ``[group_item]`` on success,
        ``[]`` when the head was popped and failed (oversize prompt), or
        ``None`` when pages ran short even after yielding — the caller
        breaks and the head waits, with the parked slot intact (FIFO)."""
        cut = self._slots[slot].park_cut
        pages: Optional[list[int]] = None
        if self.kv_layout == "paged":
            total_pages = -(-len(self._full_row(req)) // self.page_size)
            if self._reject_oversize_head(req, total_pages):
                return []
            kept = list(self._slot_pages.get(slot, []))
            fresh: Optional[list[int]] = None
            while fresh is None:
                try:
                    fresh = self._allocator.alloc(total_pages - len(kept))
                except MemoryError:
                    # OTHER parked slots and cache entries yield before the
                    # adoption fails; never release the adoptee itself
                    if self._release_lru_parked(exclude=slot):
                        continue
                    if not self._evict_one_prefix_entry():
                        break
            if fresh is None:
                return None
            pages = kept + fresh
            # keep _slot_pages coherent IMMEDIATELY (the block-table
            # install in _fill_slots re-writes it identically later): a
            # dedup follower in this same admission group may pick the
            # adopter as its leader, and reading the parked slot's stale
            # kept-only list here would truncate its share — rows between
            # the park cut and the share cut would map to never-written
            # follower pages and decode over garbage KV
            self._slot_pages[slot] = list(pages)
        self._slots.pop(slot)  # the new turn takes the slot over in place
        self._parked_count -= 1
        self.park_adoptions += 1
        if not req.prewarm:
            self.flight.record("adopt", rid=req.rid, slot=slot, cut=cut)
        REGISTRY.counter_add(
            "acp_engine_park_adoptions_total", 1.0,
            help="parked slots adopted by their conversation's next turn "
            "(suffix-only prefill)",
        )
        self._publish_park_gauge()
        self._waiting.popleft()
        return [(req, slot, pages, (None, {"cut": cut, "in_slot": True}))]

    def _n_active(self) -> int:  # acp: cross-thread
        """Slots actively DECODING — parked slots linger without work and
        mid-prefill slots haven't sampled yet (see _has_work for the
        loop-level any-work predicate)."""
        return len(self._slots) - self._parked_count - self._prefilling_count

    def _has_parked(self) -> bool:
        return self._parked_count > 0

    def _publish_park_gauge(self) -> None:
        REGISTRY.gauge_set(
            "acp_engine_parked_slots",
            float(self._parked_count),
            help="slots parked at generation end, prompt KV resident, "
            "awaiting the conversation's next turn",
        )

    # -- KV memory tiers: host-RAM offload + shared-prefix dedup ----------

    def inject_host_kv(self, entry) -> bool:
        """Land a :class:`HostKVEntry` in this engine's host-KV tier
        (thread-safe; the fleet router's prefill→decode handoff path).
        The entry is enqueued here and committed to the pool by the engine
        thread at the top of ``_fill_slots`` — BEFORE admission matching —
        so inject-then-submit ordering guarantees a subsequently submitted
        request sees it in ``_collect_group``'s host-tier prefix match.
        Returns False (caller falls back to a full prefill) when the host
        tier is disabled or the engine isn't running."""
        if self._host_pool is None or self._thread is None or self._stopping:
            return False
        self._kv_inject.put(entry)
        return True

    def _drain_kv_inject(self) -> None:
        """Commit injected handoff entries to the host pool (engine
        thread; called from _fill_slots before admission matching)."""
        landed = False
        while True:
            try:
                entry = self._kv_inject.get_nowait()
            except queue.Empty:
                break
            pool = self._host_pool
            if pool is not None and pool.put(entry):
                landed = True
                self.kv_injects += 1
                self.flight.record(
                    "kv_inject", rid=entry.rid, tokens=entry.cut,
                    bytes=entry.nbytes,
                )
            # a refused entry (pool shrunk below its size) just drops:
            # the request it fed recomputes its prefill, byte-identically
        if landed:
            self._publish_memory_state()

    def _export_kv_handoff(self, slot: int, sl: _Slot):  # acp: kv-seam
        """Extract a finishing export_kv request's prompt KV into a
        :class:`HostKVEntry` (the disaggregation handoff unit) — the same
        page-aligned rows-[0, cut) extraction ``_swap_out`` performs, but
        attached to the result instead of this engine's own pool. Returns
        None (caller degrades to no handoff) for truncated prompts, dedup
        followers, or too few written rows."""
        req = sl.request
        if req.truncated or sl.share_of is not None:
            return None
        rows = int(self._seq_lens[slot])
        row = self._full_row(req)
        cut = min(rows, len(row) - 1)  # strict prefix: decode must model >= 1
        if self.kv_layout == "paged":
            cut = (cut // self.page_size) * self.page_size
        if self._has_state:
            cut = min(cut, req.state_cut)  # the one length with a saved state
        if cut < self._swap_min_rows():
            return None
        from ..ops.paged import HostKVEntry

        t0 = time.monotonic()
        if self.kv_layout == "paged":
            out = self._extract_pages(self._slot_pages[slot][: cut // self.page_size])
            out = {name: a[:, :cut] for name, a in out.items()}
        else:
            out = self._extract_rows(slot, cut)
        entry = HostKVEntry(
            rid=f"handoff-{req.rid}", tokens=tuple(row[:cut]),
            rows=out,
            state=self._saved_state(slot, host=True) if self._has_state else None,
        )
        self.flight.record(
            "handoff_export", rid=req.rid, slot=slot, tokens=cut,
            bytes=entry.nbytes, stall_s=round(time.monotonic() - t0, 6),
        )
        return entry

    def _swap_min_rows(self) -> int:
        """Rows below this aren't worth a host round trip. One page (the
        paged grain) — a swap replaces a model forward over the rows, so
        even small KV wins; recompute only beats the copy near zero rows."""
        return self.page_size if self.kv_layout == "paged" else 8

    def _swap_out(self, slot: int, sl: _Slot, reason: str) -> bool:  # acp: kv-seam
        """Offload a slot's written KV rows to the host pool right before
        its HBM pages are released (preemption, park expiry, mid-prefill
        deadline). The entry holds a bit-exact copy of rows [0, cut), so a
        later swap-in reproduces exactly what recompute would — greedy
        byte-identity is preserved by construction. Returns True when an
        entry landed; every failure path (pool off, rows too short, entry
        over budget, injected fault) degrades to today's discard-and-
        recompute behavior."""
        pool = self._host_pool
        if pool is None or self._stopping:
            return False
        req = sl.request
        if req.prewarm or req.truncated or sl.share_of is not None:
            # a waiting dedup follower's shared rows may not be written yet
            return False
        if sl.prefilling:
            rows = sl.prefill_pos
        elif sl.parked:
            rows = sl.park_cut
        else:
            rows = int(self._seq_lens[slot])
        row = self._full_row(req)
        cut = min(rows, len(row) - 1)  # strict prefix: resume must model >= 1 token
        if self.kv_layout == "paged":
            cut = (cut // self.page_size) * self.page_size
        if self._has_state and not (sl.prefilling and sl.swap_entry is not None):
            # rows past the one length whose state was saved could not be
            # resumed from: offload up to it, or nothing if it is not
            # written yet
            if req.state_cut > cut:
                if rows >= self._swap_min_rows():
                    self.state_refused += 1
                return False
            cut = req.state_cut
        if cut < self._swap_min_rows() and not (
            sl.prefilling and sl.swap_entry is not None
        ):
            # too few written rows to be worth a copy — except mid-restore,
            # where the consumed host entry can be re-put without any copy
            return False
        t0 = time.monotonic()
        if self._faults.enabled:
            if self._faults.pop("engine.host_swap_error") is not None:
                # the copy "failed": no entry lands, resume recomputes
                self.flight.record(
                    "swap_out", rid=req.rid, slot=slot, reason=reason,
                    error=True,
                )
                return False
            spec = self._faults.pop("engine.host_swap_slow")
            if spec is not None:
                # inside the timed window: the injected slowness IS the
                # host_stall the flight recorder should attribute
                time.sleep(float(spec.get("seconds", 0.05)))
        from ..ops.paged import HostKVEntry

        if sl.prefilling and sl.swap_entry is not None:
            # mid-restore victim: the WHOLE consumed entry is still in host
            # RAM — re-put it (zero copy, re-keyed to this request's rid so
            # the exact-match resume finds it) instead of re-extracting
            # only the rows that happened to land before the preemption.
            entry = sl.swap_entry
            if entry.rid != req.rid:
                entry = HostKVEntry(
                    rid=req.rid, tokens=entry.tokens, rows=entry.rows,
                    state=entry.state,
                )
            cut = entry.cut
        else:
            if self.kv_layout == "paged":
                rows = self._extract_pages(
                    self._slot_pages[slot][: cut // self.page_size]
                )
                rows = {name: a[:, :cut] for name, a in rows.items()}
            else:
                rows = self._extract_rows(slot, cut)
            entry = HostKVEntry(
                rid=req.rid, tokens=tuple(row[:cut]),
                rows=rows,
                state=self._saved_state(slot, host=True) if self._has_state else None,
            )
        if not pool.put(entry):
            return False  # bigger than the whole budget: recompute instead
        stall = time.monotonic() - t0
        self.kv_swap_outs += 1
        REGISTRY.counter_add(
            "acp_engine_kv_swap_out_total", 1.0,
            help="KV offloads to the host-RAM tier (preemption, park "
            "expiry, and mid-prefill deadline drops that would otherwise "
            "discard written KV)",
        )
        if not req.prewarm:
            self.flight.record(
                "swap_out", rid=req.rid, slot=slot, reason=reason,
                tokens=cut, bytes=entry.nbytes, stall_s=round(stall, 6),
            )
        self._publish_memory_state()
        return True

    @_in_phase("launch")
    def _extract_pages(self, pages: list[int]) -> dict[str, np.ndarray]:  # acp: megastep-seam # acp: kv-seam
        """Gather paged KV pages to host numpy, token-major, every leaf of
        the pool under its own name: ``{"k"/"v": [L, tokens, H_kv * d]}``
        (the pool's rows) plus ``"ks"/"vs": [L, tokens, H_kv]`` scale rows
        when the pool is quantized (the host tier carries the int8 bytes
        verbatim — no requantization round trip), ``{"kv": [L, tokens,
        width]}`` of a latent pool. Dispatches
        decompose into pow2 page counts (bounded jit entries); the
        device->host copies are issued async and joined at the end so the
        DMA overlaps the remaining gathers."""
        P = self.page_size
        cfg = self.config
        chunks: list[dict] = []
        i = 0
        for n in _pow2_sizes(len(pages)):
            fn = self._jit_swap_gather.get(n)
            if fn is None:
                fn = jax.jit(
                    lambda c, ids: {name: a[:, ids] for name, a in pool_leaves(c).items()}
                )
                self._jit_swap_gather[n] = fn
            ids = np.asarray(pages[i : i + n], dtype=np.int32)
            prof_t0 = self.profiler.start()
            out = fn(self.cache, self._put(ids))
            self.profiler.record(
                f"swap_gather[{n}]", prof_t0, out=_a_leaf(out), real_tokens=n * P
            )
            chunks.append(out)
            i += n
        for ch in chunks:
            for a in ch.values():
                if hasattr(a, "copy_to_host_async"):
                    a.copy_to_host_async()
        T = len(pages) * P
        out_np: dict[str, np.ndarray] = {}
        with self.profiler.phase("fetch"):
            for name in chunks[0]:
                parts = [np.asarray(ch[name]) for ch in chunks]
                merged = np.concatenate(parts, axis=1)  # [L, nP_total, P, ...]
                out_np[name] = merged.reshape(
                    (merged.shape[0], T) + merged.shape[3:]
                )
        return out_np

    @_in_phase("launch")
    def _extract_rows(self, slot: int, cut: int) -> dict[str, np.ndarray]:  # acp: megastep-seam # acp: kv-seam
        """Slot layout: slice rows [0, cut) of ``slot`` out of the cache to
        host numpy ``{"k"/"v": [L, cut, H, d]}`` (+ scale rows for a
        quantized cache); pow2 sub-slices, async fetch."""
        L = self.config.n_layers
        chunks: list[dict] = []
        start = 0
        for n in _pow2_sizes(cut):
            fn = self._jit_swap_extract.get(n)
            if fn is None:

                def extract(cache, slot_, start_, n=n):
                    return {
                        name: jax.lax.dynamic_slice(
                            arr,
                            (0, slot_, start_) + (0,) * (arr.ndim - 3),
                            (L, 1, n) + arr.shape[3:],
                        )[:, 0]
                        for name, arr in cache.items()
                    }

                fn = jax.jit(extract)  # read-only: cache NOT donated
                self._jit_swap_extract[n] = fn
            prof_t0 = self.profiler.start()
            out = fn(self.cache, jnp.int32(slot), jnp.int32(start))
            self.profiler.record(
                f"swap_extract[{n}]", prof_t0, out=_a_leaf(out), real_tokens=n
            )
            chunks.append(out)
            start += n
        for ch in chunks:
            for a in ch.values():
                if hasattr(a, "copy_to_host_async"):
                    a.copy_to_host_async()
        with self.profiler.phase("fetch"):
            return {
                name: np.concatenate([np.asarray(ch[name]) for ch in chunks], axis=1)
                for name in self.cache
            }

    @_in_phase("launch")
    def _swap_in_rows(self, slot: int, entry, start: int, n: int) -> float:  # acp: megastep-seam # acp: kv-seam
        """Restore rows [start, start+n) of a host entry into ``slot``'s
        KV (page-aligned in paged mode — callers schedule page-grain
        chunks). Returns the engine-thread seconds spent blocked in the
        host->device copies (the host_stall phase input)."""
        t0 = time.monotonic()
        # the entry's leaves are the cache's own (a quantized cache's entry
        # carries its scale rows: a bf16 entry cannot restore into an int8
        # pool) — _swap_out records whatever leaves the cache has
        rows = entry.rows
        if self.kv_layout == "paged":
            P = self.page_size
            pages = self._slot_pages[slot][start // P : (start + n) // P]
            i = 0
            for m in _pow2_sizes(len(pages)):
                fn = self._jit_swap_scatter.get(m)
                if fn is None:
                    fn = jax.jit(
                        lambda c, ids, blocks: {**c, **{
                            name: set_pages(c[name], ids, blocks[name])
                            for name in blocks
                        }},
                        donate_argnums=(0,),
                    )
                    self._jit_swap_scatter[m] = fn
                ids = np.asarray(pages[i : i + m], dtype=np.int32)
                lo = start + i * P
                blocks = {
                    name: a[:, lo : lo + m * P].reshape(
                        a.shape[0], m, P, *a.shape[2:]
                    )
                    for name, a in rows.items()
                }
                prof_t0 = self.profiler.start()
                self.cache = fn(
                    self.cache, self._put(ids),
                    {name: self._put(b) for name, b in blocks.items()},
                )
                self.profiler.record(
                    f"swap_scatter[{m}]", prof_t0, out=_a_leaf(self.cache),
                    real_tokens=m * P,
                )
                i += m
        else:
            pos = start
            while pos < start + n:
                m = _pow2_sizes(start + n - pos)[0]
                fn = self._jit_swap_restore.get(m)
                if fn is None:

                    def restore(cache, slot_, start_, blocks):
                        return {
                            name: jax.lax.dynamic_update_slice(
                                arr, blocks[name][:, None],
                                (0, slot_, start_) + (0,) * (arr.ndim - 3),
                            )
                            for name, arr in cache.items()
                        }

                    fn = jax.jit(restore, donate_argnums=(0,))
                    self._jit_swap_restore[m] = fn
                prof_t0 = self.profiler.start()
                self.cache = fn(
                    self.cache, jnp.int32(slot), jnp.int32(pos),
                    {
                        name: self._put(a[:, pos : pos + m])
                        for name, a in rows.items()
                    },
                )
                self.profiler.record(
                    f"swap_restore[{m}]", prof_t0, out=_a_leaf(self.cache),
                    real_tokens=m,
                )
                pos += m
        return time.monotonic() - t0

    def _swap_in_cut(self, sl: _Slot) -> int:
        """Rows a mid-restore slot will take from its host entry — the
        entry's cut, never past the strict-prefix edge of this row."""
        cut = min(sl.swap_entry.cut, len(sl.prefill_row) - 1)
        if self.kv_layout == "paged":
            cut = (cut // self.page_size) * self.page_size
        return cut

    def _finish_swap_in(self, slot: int, sl: _Slot) -> None:
        """The restore reached its cut: the slot becomes a plain mid-
        prefill slot (model chunks take over for the remaining suffix)."""
        req = sl.request
        self.kv_swap_ins += 1
        REGISTRY.counter_add(
            "acp_engine_kv_swap_in_total", 1.0,
            help="host-tier KV restores completed (re-admissions that "
            "swapped rows back in instead of re-running prefill)",
        )
        if not req.prewarm:
            self.flight.record(
                "swap_in", rid=req.rid, slot=slot, tokens=sl.prefill_pos,
                stall_s=round(sl.swap_stall_s, 6),
            )
        sl.swap_entry = None
        self._publish_memory_state()

    def _unshare_followers(self, leader_slot: int, leader_sl: _Slot) -> None:
        """A mid-prefill dedup leader is leaving (preempt/expire/cancel):
        rewind every waiting follower to the page-aligned rows the leader
        actually wrote. The shared pages survive (followers hold refs), so
        rows below the rewind stay valid; each follower then recomputes
        the gap itself — multiple followers write bit-identical KV into
        the shared pages, so redundant writes are harmless."""
        if not leader_sl.prefilling:
            return  # leader finished its prefill: every shared row is written
        pos = (leader_sl.prefill_pos // self.page_size) * self.page_size
        rid = leader_sl.request.rid
        for s, sl in self._slots.items():
            if (
                sl.prefilling
                and sl.share_of is not None
                and sl.share_of[0] == leader_slot
                and sl.share_of[1] == rid
            ):
                if sl.prefill_pos > pos:
                    # the follower re-runs rows its dead leader had covered:
                    # the leader's compute of them is now waste
                    self.profiler.reclassify(
                        "dedup_rewind", sl.prefill_pos - pos
                    )
                    sl.prefill_pos = pos
                    self._seq_lens[s] = pos
                sl.share_of = None

    def _match_dedup_leader(
        self, full: list[int], group: Optional[list] = None
    ) -> Optional[tuple]:
        """Longest page-aligned common prefix between ``full`` and a live
        slot's row — or an earlier member of the admission group being
        formed (the burst case: N same-persona tasks arriving at once,
        before any prefill could seed the cache). Returns
        ``(leader_slot, leader_rid, cut)`` or None. Parked leaders share up
        to their park cut (rows resident); active/prefilling leaders up to
        their whole row — a follower behind a still-prefilling leader
        waits for the shared rows to be written (see _prefill_chunks).
        Slots that are themselves waiting dedup followers are skipped
        (their prefill_pos counts rows their OWN leader hasn't written, so
        the follower-wait test would lie); ties keep the first candidate,
        so a burst chains every follower to the one root writer."""
        if self.kv_layout != "paged" or not self.prefix_dedup:
            return None
        if self._has_state:
            # a live leader's pages could be shared, but its state at the
            # common cut was not saved (its one snapshot is at its own
            # prompt's last page boundary, and may not be written yet):
            # counted in _collect_group where a share was otherwise due
            return None
        best: Optional[tuple] = None
        for s, sl in self._slots.items():
            if sl.share_of is not None:
                continue
            # avoid rebuilding rows per scan in the admission path: parked
            # slots compare against the prompt capped at the park cut, and
            # every other slot carries its row as prefill_row (kept after
            # the prefill flip precisely so hot paths don't reconstruct it)
            if sl.parked:
                other, limit = sl.request.prompt, sl.park_cut
            else:
                other = (
                    sl.prefill_row
                    if sl.prefill_row is not None
                    else self._full_row(sl.request)
                )
                limit = len(other)
            cut = self._common_cut(full, other, limit)
            if cut >= self._swap_min_rows() and (best is None or cut > best[2]):
                best = (s, sl.request.rid, cut)
        for g_req, g_slot, _g_pages, g_match in group or ():
            if g_match is not None and (
                g_match[1].get("share_of") is not None
                or g_match[1].get("swap") is not None
            ):
                continue  # follower/mid-restore: not a safe root writer
            cut = self._common_cut(full, self._full_row(g_req))
            if cut >= self._swap_min_rows() and (best is None or cut > best[2]):
                best = (g_slot, g_req.rid, cut)
        return best

    def _common_cut(
        self, full: list[int], other: list[int], limit: Optional[int] = None
    ) -> int:
        """Page-aligned length of the longest shared token prefix, capped
        strictly below ``full``'s end (suffix tokens must remain) and at
        ``limit`` (e.g. a parked leader's resident rows). Compared a page
        at a time (C-speed list-slice equality) — the result is rounded
        down to a page boundary anyway, and a per-token Python loop over
        multi-k prefixes would tax the engine thread exactly during the
        admission bursts dedup exists to speed up."""
        P = self.page_size
        n = min(len(full) - 1, len(other))
        if limit is not None:
            n = min(n, limit)
        pages = n // P
        i = 0
        while i < pages and full[i * P : (i + 1) * P] == other[i * P : (i + 1) * P]:
            i += 1
        return i * P

    def _publish_memory_state(self) -> None:
        """Refresh the cross-thread memory mirrors + gauges from engine-
        thread truth (host pool bytes/entries, refcount-shared pages).
        Cheap; runs after every dispatch cycle and at each swap/share."""
        if self._host_pool is not None:
            self._host_kv_used = self._host_pool.used_bytes
            self._host_kv_entries = len(self._host_pool)
            REGISTRY.gauge_set(
                "acp_engine_host_kv_bytes", float(self._host_kv_used),
                help="bytes of swapped-out KV resident in the host-RAM "
                "offload tier (bounded by --tpu-host-kv-bytes)",
            )
        else:
            self._host_kv_used = 0
            self._host_kv_entries = 0
        # allocated_count is a len() read, same atomic contract as
        # free_count; every allocated page of a quantized pool holds int8
        # KV + its scale rows. Published unconditionally so knobs-off and
        # slot-layout engines export an explicit 0 (dashboards comparing
        # enabled-vs-disabled deploys need a present series, not a gap).
        REGISTRY.gauge_set(
            "acp_engine_quantized_kv_pages",
            float(
                self._allocator.allocated_count
                if self.kv_layout == "paged" and self.quantize_kv
                else 0
            ),
            help="allocated KV pages currently holding int8-"
            "quantized KV (with per-row scale storage); 0 unless "
            "quantize_kv is on",
        )
        if self.kv_layout == "paged":
            self._prefix_shared_pages = self._allocator.shared_count
            REGISTRY.gauge_set(
                "acp_engine_prefix_shared_pages",
                float(self._prefix_shared_pages),
                help="HBM KV pages currently refcount-shared by more than "
                "one owner (cross-request shared-prefix dedup + prefix "
                "cache)",
            )
