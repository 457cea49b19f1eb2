"""N-gram prompt-lookup speculative decoding with batched in-engine
verification.

The load-bearing guarantee: greedy outputs with speculation ON are
byte-identical to the non-speculative engine across the whole stress
matrix — preemption, forced full rejection, prefix-cache hits — because
the accept op emits the VERIFIED argmax at every position; drafts only
decide how many positions commit per dispatch. On repetitive agent-style
traffic (tool echo) each verify dispatch must land well over one token.

Engines are expensive to construct on CPU (each compiles its program set),
so the identity tests share four module-scoped engines (spec on/off x
slot/paged, one geometry); only the stress matrix and the ctx-edge pin
build their own.
"""

import dataclasses
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.spec import (
    REPROBE_DISPATCHES,
    SpecState,
    ngram_propose,
)
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.models.llama import PRESETS, init_params
from agentcontrolplane_tpu.observability.metrics import REGISTRY
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import FAULTS

TOK = ByteTokenizer()
CFG = dataclasses.replace(PRESETS["tiny"], vocab_size=512, max_seq_len=256, n_kv_heads=2)

# repeated tool-call JSON — the self-similar agent traffic shape the
# drafter exploits. The random weights below repeat themselves on it only
# in short runs (enough for some drafts to land); the acceptance-rate
# test serves it with weights that echo by construction (_echo_params)
TOOL_ECHO = '{"tool": "search", "args": {"q": "x"}} {"tool": "search", "args": {"q": "x"}}'


def make_engine(kv_layout="slot", spec_len=8, max_ctx=256, **kw):
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    # ACP_INVARIANTS posture for the whole stress suite: every run
    # double-checks the engine's bookkeeping after each dispatch cycle
    kw.setdefault("check_invariants", True)
    kw.setdefault("prefill_buckets", (64, 256))
    eng = Engine(
        config=CFG,
        tokenizer=TOK,
        mesh=mesh,
        max_slots=4,
        max_ctx=max_ctx,
        decode_block_size=4,
        kv_layout=kv_layout,
        page_size=8,
        spec_len=spec_len,
        **kw,
    )
    eng.start()
    return eng


@pytest.fixture(scope="module")
def engines():
    """Shared spec-on/spec-off engine pairs, both layouts, one geometry."""
    pool = {
        ("slot", 0): make_engine("slot", spec_len=0),
        ("slot", 6): make_engine("slot", spec_len=6),
        ("paged", 0): make_engine("paged", spec_len=0),
        ("paged", 6): make_engine("paged", spec_len=6),
    }
    yield pool
    for eng in pool.values():
        eng.stop()


def counter(name: str) -> float:
    m = REGISTRY._metrics.get(name)
    return 0.0 if m is None else m.values.get((), 0.0)


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    FAULTS.reset()


# -- drafter + controller units ----------------------------------------------


def test_ngram_propose_prefers_longest_then_most_recent():
    ctx = np.array([1, 2, 3, 9, 1, 2, 3, 7, 8, 1, 2, 3], dtype=np.int64)
    # tail 3-gram (1,2,3) occurs at 0 (-> 9) and 4 (-> 7,8); recency wins
    assert ngram_propose(ctx, 3, 4) == [7, 8, 1, 2]
    assert ngram_propose(ctx, 3, 1) == [7]
    # with ngram_max=1, the tail 1-gram (3) most recently continued with 7
    assert ngram_propose(ctx, 1, 2) == [7, 8]


def test_ngram_propose_falls_back_to_shorter_ngrams_and_handles_no_match():
    # tail (5, 6) never occurred before, but 6 did -> 1-gram fallback
    ctx = np.array([6, 4, 5, 6], dtype=np.int64)
    assert ngram_propose(ctx, 3, 3) == [4, 5, 6]
    assert ngram_propose(np.array([1, 2, 3, 4], dtype=np.int64), 3, 4) == []
    assert ngram_propose(np.array([7], dtype=np.int64), 3, 4) == []
    assert ngram_propose(np.array([7, 7, 7], dtype=np.int64), 3, 0) == []


def test_ngram_propose_periodic_overlap():
    # period-1 repetition: the matched window may overlap the tail's own,
    # and an older match with a FULL continuation beats the most recent
    # one clipped at the context edge
    ctx = np.array([9, 9, 9, 9], dtype=np.int64)
    assert ngram_propose(ctx, 3, 2) == [9, 9]
    # period-2 loop: full-length draft continues the cycle
    ctx = np.array([4, 5, 4, 5, 4, 5], dtype=np.int64)
    assert ngram_propose(ctx, 3, 4) == [4, 5, 4, 5]


def test_spec_state_decay_growth_and_reprobe():
    st = SpecState(limit=8)
    assert st.cap() == 8  # optimistic start
    st.observe(8, 0)  # full rejection halves
    assert st.cap() == 4
    st.observe(4, 0)
    st.observe(2, 0)
    st.observe(1, 0)
    assert st.cap() == 0  # decayed all the way to the non-speculative path
    # parked at 0: re-probes with a 1-token draft on the REPROBE-th dispatch
    seq = [st.cap() for _ in range(REPROBE_DISPATCHES - 1)]
    assert all(c == 0 for c in seq[:-1]) and seq[-1] == 1
    st.observe(1, 1)  # full acceptance doubles
    assert st.cap() == 2
    st.observe(2, 1)  # partial acceptance: additive step
    assert st.cap() == 3
    st.observe(3, 0)  # no-draft dispatches teach nothing
    st.observe(0, 0)
    assert st.cur == 1


# -- model layer: the verify pass is the exact model ------------------------


def test_verify_continue_matches_full_forward():
    """verify_continue's all-position logits must agree with the plain
    full-sequence forward at every continuation position — argmax equality
    is what the greedy byte-identity guarantee rides on."""
    import jax.numpy as jnp

    from agentcontrolplane_tpu.models.llama import (
        forward,
        init_kv_cache,
        init_params,
        prefill,
        verify_continue,
    )

    cfg = PRESETS["tiny"]
    params = init_params(cfg, jax.random.key(0))
    cache = init_kv_cache(cfg, 2, 64)
    prompt = jnp.array([5, 7, 11, 13, 17, 19], dtype=jnp.int32)
    cont = jnp.array([23, 29, 31, 37], dtype=jnp.int32)
    cache, _ = prefill(params, cache, prompt, jnp.int32(len(prompt)), jnp.int32(0), cfg)
    tokens = jnp.zeros((2, 6), dtype=jnp.int32).at[0, : len(cont)].set(cont)
    lengths = jnp.array([len(cont), 1], dtype=jnp.int32)
    starts = jnp.array([len(prompt), 0], dtype=jnp.int32)
    _, logits = verify_continue(params, cache, tokens, lengths, starts, cfg)
    full = forward(params, jnp.concatenate([prompt, cont])[None], cfg)[0]
    for i in range(len(cont)):
        ref = full[len(prompt) + i]
        got = logits[0, i]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4, rtol=2e-4)
        assert int(jnp.argmax(got)) == int(jnp.argmax(ref))


# -- engine: greedy byte-identity --------------------------------------------


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_greedy_byte_identity_and_streams(engines, kv_layout):
    sp = SamplingParams(temperature=0.0, max_tokens=20)
    prompts = ["abcabcabcabcabc", TOOL_ECHO[:30], "hello world"]
    off, on = engines[(kv_layout, 0)], engines[(kv_layout, 6)]
    ref = {p: off.generate(p, sp).tokens for p in prompts}
    disp0 = on.spec_dispatches
    for p in prompts:
        stream: list[int] = []
        r = on.submit(p, sp, on_tokens=stream.extend).result(timeout=120)
        assert r.tokens == ref[p], f"spec-on diverged for {p!r} ({kv_layout})"
        assert stream == r.tokens, "streamed tokens must match exactly once"
    assert on.stats()["spec"]["enabled"]
    assert on.spec_dispatches > disp0, "speculation must actually have run"


def test_json_constrained_greedy_identity_with_spec(engines):
    """Grammar-constrained decoding composes: the verify path masks logits
    through the same automaton with the same budget-aware closure."""
    sp = SamplingParams(temperature=0.0, max_tokens=24, json_only=True)
    ref = engines[("slot", 0)].generate("make json", sp)
    r = engines[("slot", 6)].generate("make json", sp)
    assert r.tokens == ref.tokens


def test_max_tokens_budget_exact_with_multi_token_commits(engines):
    """Speculation lands several tokens per dispatch; the device budget
    decrement and the host max_tokens accounting must clip at EXACTLY the
    same token (an odd cap forces a mid-dispatch clip)."""
    sp = SamplingParams(temperature=0.0, max_tokens=17)
    ref = engines[("slot", 0)].generate(TOOL_ECHO, sp)
    r = engines[("slot", 6)].generate(TOOL_ECHO, sp)
    assert r.tokens == ref.tokens
    if r.finish_reason == "length":
        assert len(r.tokens) == sp.max_tokens


def test_spec_composes_with_prefix_cache_hits(engines):
    """Multi-turn agent shape: turn 2 extends turn 1's prompt, hits the
    prefix cache, AND speculates — output must equal the spec-off engine's."""
    sp = SamplingParams(temperature=0.0, max_tokens=12)
    turn1 = "sys: you are a tool agent. " + "abc" * 16
    turn2 = turn1 + " user: again again again"
    outputs = {}
    for spec_len in (0, 6):
        eng = engines[("slot", spec_len)]
        eng.generate(turn1, sp)
        hits0 = eng._prefix_hits
        outputs[spec_len] = eng.generate(turn2, sp).tokens
        assert eng._prefix_hits > hits0, "turn 2 must hit the prefix cache"
    assert outputs[6] == outputs[0]


# -- the acceptance-rate criterion -------------------------------------------


def _echo_params():
    """Weights whose greedy continuation is periodic by construction: with
    the attention output projection zeroed a position's logits depend on
    its own token alone, so greedy decoding is a fixed map over 512 tokens
    and must enter a cycle (here of five tokens, after one). The shared
    engines' random weights do NOT echo: their greedy text on TOOL_ECHO
    repeats only in short runs, and the drafter replayed over that text
    with every draft verified perfectly lands 1.24 tokens a dispatch, so
    on them the bar below would measure the weights' luck, not the engine."""
    params = init_params(CFG, jax.random.key(0))
    params["layers"]["wo"] = jnp.zeros_like(params["layers"]["wo"])
    return params


def test_tool_echo_fixture_accepts_over_1_5_tokens_per_dispatch():
    """On repetitive tool-echo traffic the engine must commit > 1.5 tokens
    per decode dispatch (the CPU-backend acceptance bar), and the decode-
    efficiency stats must say so."""
    eng = make_engine("slot", spec_len=6, params=_echo_params())
    try:
        before = counter("acp_engine_spec_accepted_total")
        tok0, step0, acc0, prop0 = (
            eng.tokens_generated, eng.decode_steps, eng.spec_accepted, eng.spec_proposed,
        )
        r = eng.generate(TOOL_ECHO, SamplingParams(temperature=0.0, max_tokens=120))
        assert len(r.tokens) > 60  # long enough to be a real measurement
        per_step = (eng.tokens_generated - tok0) / (eng.decode_steps - step0)
        assert per_step > 1.5, per_step
        accepted = eng.spec_accepted - acc0
        assert 0 < accepted <= eng.spec_proposed - prop0
        s = eng.stats()
        assert s["tokens_per_decode_step"] > 0
        assert 0.0 < s["spec"]["acceptance_rate"] <= 1.0
        assert counter("acp_engine_spec_accepted_total") == before + accepted
    finally:
        eng.stop()


# -- fault injection: forced worst case --------------------------------------


def test_spec_mismatch_fault_forces_full_rejection_byte_identically(engines):
    sp = SamplingParams(temperature=0.0, max_tokens=24)
    eng = engines[("slot", 6)]
    baseline = eng.generate(TOOL_ECHO, sp)
    acc0, disp0 = eng.spec_accepted, eng.spec_dispatches
    FAULTS.arm("engine.spec_mismatch", times=1000)  # every verify pass
    r = eng.generate(TOOL_ECHO, sp)
    assert r.tokens == baseline.tokens  # worst case still byte-identical
    assert eng.spec_accepted == acc0, "forced mismatch must reject every draft"
    assert eng.spec_dispatches > disp0, "verification must still have run"
    FAULTS.disarm("engine.spec_mismatch")
    # and with the fault gone, acceptance returns
    r2 = eng.generate(TOOL_ECHO, sp)
    assert r2.tokens == baseline.tokens
    assert eng.spec_accepted > acc0


def test_adaptive_decay_under_permanent_mismatch_reaches_block_path(engines):
    """Under permanent forced mismatch the per-slot cap decays to 0 and the
    engine falls back to plain decode blocks (today's path): decode_steps
    grows by K per block again instead of 1 per verify dispatch."""
    eng = engines[("slot", 6)]
    FAULTS.arm("engine.spec_mismatch", times=10_000)
    acc0, disp0 = eng.spec_accepted, eng.spec_dispatches
    r = eng.generate(TOOL_ECHO, SamplingParams(temperature=0.0, max_tokens=80))
    assert len(r.tokens) > 0
    # cap decays 6 -> 3 -> 1 -> 0 after 3 full rejections; the long tail
    # must run as plain blocks, so verify dispatches stay a small fraction
    # of the work (bounded by the decay plus periodic re-probes)
    assert eng.spec_dispatches - disp0 < 20, eng.spec_dispatches - disp0
    assert eng.spec_accepted == acc0


# -- stress matrix: speculation x preemption x mismatch ----------------------


def _stress(n_requests: int, max_tokens: int):
    """Oversubscribed paged pool with speculation ON under forced spec
    mismatch + forced preemption: every greedy output must equal its
    speculation-OFF uncontended run, streamed exactly once."""
    sp = SamplingParams(temperature=0.0, max_tokens=max_tokens)
    prompts = [ch * 20 for ch in "abcdef"[:n_requests]]
    off = make_engine("paged", spec_len=0, max_ctx=64,
                      prefill_buckets=(32, 64), kv_pages=10)
    try:
        solo = {p: off.generate(p, sp).tokens for p in prompts}
    finally:
        off.stop()
    eng = make_engine("paged", spec_len=6, max_ctx=64,
                      prefill_buckets=(32, 64), kv_pages=10)
    try:
        FAULTS.arm("engine.spec_mismatch", times=3)
        FAULTS.arm("engine.force_preempt", after_steps=4)
        streams = {p: [] for p in prompts}
        with eng.hold_admission():
            futs = [eng.submit(p, sp, on_tokens=streams[p].extend) for p in prompts]
        results = dict(zip(prompts, (f.result(timeout=240) for f in futs)))
        for p, r in results.items():
            assert r.tokens == solo[p], f"stress output diverged for {p!r}"
            assert streams[p] == r.tokens, "streamed tokens must arrive exactly once"
            assert r.finish_reason in ("stop", "length")
        assert any(r.preempt_count >= 1 for r in results.values())
        # pages fully recycled once the burst drains
        deadline = time.monotonic() + 5
        while eng._allocator.free_count != eng.num_pages - 1:
            assert time.monotonic() < deadline, "leaked KV pages"
            time.sleep(0.05)
    finally:
        eng.stop()


def test_stress_oversubscribed_spec_preempt_mismatch():
    _stress(n_requests=4, max_tokens=10)


@pytest.mark.slow
def test_stress_oversubscribed_spec_preempt_mismatch_heavy():
    _stress(n_requests=6, max_tokens=16)


def test_reclaim_floor_honors_in_flight_spec_dispatch_need():
    """A speculative verify dispatch writes 1 + draft KV rows — more than
    the decode block. Mid-pass, a later slot's allocation must not claw
    back pages an earlier slot was just granted for its draft tail: the
    dispatch would write that KV to the trash page while the host advances
    seq_len over it, corrupting every later attention pass. Bare-object
    harness; no compiled engine needed."""
    from agentcontrolplane_tpu.engine.engine import Engine, _Slot
    from agentcontrolplane_tpu.ops.paged import TRASH_PAGE, PageAllocator

    eng = Engine.__new__(Engine)
    eng.page_size = 8
    eng.decode_block_size = 4
    eng.max_pages_per_seq = 8
    eng._allocator = PageAllocator(4)  # pages 1..3 usable (0 = trash)
    eng._seq_lens = np.zeros(4, dtype=np.int32)
    eng._block_tables = np.full((4, 8), TRASH_PAGE, dtype=np.int32)
    eng._tables_dirty = False
    eng._slots = {0: _Slot(request=None), 1: _Slot(request=None)}
    # slot 0: seq_len 2, granted 2 pages covering its 1+6-row verify
    # dispatch (ceil((2+7)/8) = 2); slot 1 holds the third page
    eng._seq_lens[0] = 2
    eng._slot_pages = {0: eng._allocator.alloc(2), 1: eng._allocator.alloc(1)}
    eng._block_tables[0, :2] = eng._slot_pages[0]
    eng._block_tables[1, :1] = eng._slot_pages[1]

    # pool exhausted; slot 1 asks for one more page with the dispatch
    # needs threaded: slot 0's floor is ceil((2 + max(4, 7)) / 8) = 2
    # pages — nothing reclaimable, the allocation must fail (escalating
    # to preemption) rather than strip slot 0's granted coverage
    assert eng._alloc_reclaiming_lookahead(1, 1, {0: 7, 1: 4}) is None
    assert len(eng._slot_pages[0]) == 2
    assert eng._block_tables[0, 1] != TRASH_PAGE

    # the plain block path (no dispatch needs) reclaims the page beyond
    # slot 0's strict K-token window (ceil((2 + 4) / 8) = 1 page)
    got = eng._alloc_reclaiming_lookahead(1, 1, None)
    assert got is not None and len(got) == 1
    assert len(eng._slot_pages[0]) == 1
    assert eng._block_tables[0, 1] == TRASH_PAGE


# -- ctx-edge accounting with multi-token commits ----------------------------


@pytest.mark.slow
def test_ctx_edge_off_by_one_pinned_at_max_ctx_minus_1():
    """Regression pin for the max_ctx - 1 edge: a generation that runs to
    the context edge finishes 'length' with prompt + generated == max_ctx
    (the last sampled token lands the sequence at seq_len == max_ctx - 1;
    KV is never written at row max_ctx - 1), identically with speculation
    on and off."""
    sp = SamplingParams(temperature=0.0, max_tokens=500)
    results = {}
    for spec_len in (0, 6):
        eng = make_engine(spec_len=spec_len, max_ctx=64, prefill_buckets=(32, 64))
        try:
            results[spec_len] = eng.generate("abcabcabcabcabc", sp)
        finally:
            eng.stop()
    ref, spec = results[0], results[6]
    assert spec.tokens == ref.tokens
    assert ref.finish_reason == spec.finish_reason
    if ref.finish_reason == "length" and len(ref.tokens) < sp.max_tokens:
        # the edge case this test exists for: generation clipped by ctx
        assert ref.prompt_tokens + len(ref.tokens) == 64


def test_ctx_edge_off_by_one_shared_geometry(engines):
    """Tier-1 ctx-edge pin on the shared engines: a prompt near the 256
    context edge must clip at exactly prompt + generated == max_ctx with
    identical tokens spec-on and spec-off."""
    sp = SamplingParams(temperature=0.0, max_tokens=500)
    prompt = TOOL_ECHO * 3  # ~230 tokens: a dozen tokens of decode room
    ref = engines[("slot", 0)].generate(prompt, sp)
    r = engines[("slot", 6)].generate(prompt, sp)
    assert r.tokens == ref.tokens
    assert r.finish_reason == ref.finish_reason
    if ref.finish_reason == "length" and len(ref.tokens) < sp.max_tokens:
        assert ref.prompt_tokens + len(ref.tokens) == 256
