"""Fused megastep dispatch (ISSUE 13):

- byte-identity: megastep on vs off (the split per-phase dispatches) with
  chunked prefill + speculative decoding + park/adopt all active, both KV
  layouts, under the armed invariant checker — the load-bearing contract;
- ONE dispatch per steady-state busy cycle, asserted via the PR 12
  profiler's program keys: while mid-prefill chunks co-run with decode,
  the only model program dispatching is ``megastep[...]``;
- the shape bound: a new fused shape past ``megastep_max_programs`` falls
  back to the split programs (outputs still byte-identical) and counts
  ``megastep_fallbacks``;
- the goodput ledger's fused-program waste row (``pad_fuse``) stays
  conserved (audited every cycle by the armed checker — these engines all
  run with it on);
- the megastep prewarm phase forms the core fused shapes (and records the
  standard ``prewarm_gap`` event + counter when one cannot form).
"""

import dataclasses
import time

import pytest

import jax

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.models.llama import PRESETS
from agentcontrolplane_tpu.observability.metrics import REGISTRY
from agentcontrolplane_tpu.parallel.mesh import make_mesh

TOK = ByteTokenizer()
CFG = dataclasses.replace(PRESETS["tiny"], vocab_size=512, max_seq_len=256, n_kv_heads=2)
# repetition attractor: the n-gram drafter proposes on it, so spec cells
# really speculate (same trick as test_spec_decode)
ATTRACTOR = "abcabcabc " * 8


def make_engine(kv_layout="slot", **kw):
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    kw.setdefault("check_invariants", True)
    kw.setdefault("prefix_cache_entries", 0)
    eng = Engine(
        config=CFG,
        tokenizer=TOK,
        mesh=mesh,
        max_slots=4,
        max_ctx=128,
        prefill_buckets=(32, 64, 128),
        width_buckets=(1, 2, 4),
        decode_block_size=4,
        kv_layout=kv_layout,
        page_size=8,
        **kw,
    )
    eng.start()
    return eng


def counter(name: str, **labels) -> float:
    m = REGISTRY._metrics.get(name)
    if m is None:
        return 0.0
    return m.values.get(tuple(sorted(labels.items())), 0.0)


def _busy_run(eng):
    """A busy mixed workload: a long-decoding anchor plus long prompts
    chunking through it (plus a short latecomer), so cycles carry
    mid-chunks, continuation finals and decode/verify together."""
    sp_long = SamplingParams(temperature=0.0, max_tokens=30)
    anchor = eng.submit(ATTRACTOR, sp_long)
    assert anchor.admitted.result(timeout=120)
    deadline = time.monotonic() + 120
    while eng.decode_steps == 0 and time.monotonic() < deadline:
        time.sleep(0.002)
    futs = [
        eng.submit("the quick brown fox jumps over " * 4,
                   SamplingParams(temperature=0.0, max_tokens=10)),
        eng.submit("pack my box with five dozen jugs " * 3,
                   SamplingParams(temperature=0.0, max_tokens=10)),
        eng.submit("hello small prompt", SamplingParams(temperature=0.0, max_tokens=8)),
    ]
    return [f.result(timeout=300).tokens for f in [anchor, *futs]]


# -- byte identity ------------------------------------------------------------


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
@pytest.mark.parametrize("spec_len", [0, 4])
def test_megastep_byte_identity_busy_matrix(kv_layout, spec_len):
    """Fused vs split vs unchunked: the same mixed busy workload must emit
    bit-for-bit identical tokens. Chunked prefill + spec active; armed
    invariant checker audits every cycle (incl. ledger conservation with
    the new pad_fuse row)."""
    outs = {}
    for mode, (mega, chunk) in {
        "split": (False, 16),
        "fused": (True, 16),
    }.items():
        eng = make_engine(kv_layout, spec_len=spec_len, megastep=mega,
                          prefill_chunk=chunk)
        try:
            outs[mode] = _busy_run(eng)
            if mode == "fused":
                assert eng.megastep_dispatches > 0, "fused path never ran"
                fused_keys = [
                    k for k in eng.profiler.stats()["programs"]
                    if k.startswith("megastep[")
                ]
                assert fused_keys, "no megastep program keys recorded"
        finally:
            eng.stop()
    # THE load-bearing contract: fused == split, bit for bit. (Chunked vs
    # UNCHUNKED identity is pinned sequentially in test_chunked_prefill;
    # under CONCURRENT load the cycle composition differs between those
    # two modes and the tiny random model's exact argmax ties can flip —
    # the known program-shape nondeterminism class, orthogonal to fusion.
    # Fused vs split runs the identical schedule, so it must be exact.)
    assert outs["fused"] == outs["split"], (kv_layout, spec_len)


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_megastep_byte_identity_with_park_adopt(kv_layout):
    """Two-turn conversation with park-on-finish: turn 2 adopts the parked
    slot (suffix-only continuation) while chunked + fused. Joined output
    must match the unchunked, unfused engine."""
    turn1 = "persona prompt " * 4
    turn2 = turn1 + " and then some follow up words"
    sp = SamplingParams(temperature=0.0, max_tokens=12)

    def run(mega, chunk):
        eng = make_engine(kv_layout, megastep=mega, prefill_chunk=chunk)
        try:
            r1 = eng.submit(turn1, sp, park=True).result(timeout=180)
            r2 = eng.submit(turn2, sp).result(timeout=180)
            return r1.tokens, r2.tokens, eng.park_adoptions
        finally:
            eng.stop()

    t1_ref, t2_ref, _ = run(False, 0)
    t1, t2, adoptions = run(True, 12)
    assert (t1, t2) == (t1_ref, t2_ref)
    assert adoptions >= 1, "turn 2 never adopted the parked slot"


@pytest.mark.parametrize("mega", [False, True])
def test_inactive_lane_decode_write_clamps_to_unread_row(mega):
    """LATENT BUG pinned (found by the fused matrix, but reachable in the
    split path too): the slot layout's decode block used to write one
    garbage K/V row per INACTIVE lane at that lane's uploaded seq_len.
    With a mid-prefill slot BELOW an active slot (here: slot 0 freed by a
    finished request, re-used by a chunking long prompt while slot 1 still
    decodes, so the dispatch width covers lane 0), a not-dirty decode
    block's garbage landed inside prompt rows the chunk loop had already
    written — silently corrupting the prefill. Inactive lanes must clamp
    their write to the never-readable last row (the paged layout always
    masked to TRASH_PAGE). Pinned in both dispatch modes."""
    import numpy as np

    prompt_c = "a curious llama wanders the andes " * 3
    plen = len(TOK.encode(prompt_c))
    sp_c = SamplingParams(temperature=0.0, max_tokens=10)

    def prompt_rows(eng, tokens_out):
        # slot 0's prompt KV rows [1, plen) — row 0 excluded (a free lane's
        # zeroed mirror legally parks pre-fix garbage there), rows beyond
        # the prompt excluded (decode writes them)
        k = np.asarray(eng.cache["k"][:, 0, 1:plen])
        v = np.asarray(eng.cache["v"][:, 0, 1:plen])
        return k, v, tokens_out

    # reference: the SAME chunked engine mode with no neighbour decoding —
    # same continuation programs write the prompt rows, no adjacent lane
    # to spray garbage
    ref_eng = make_engine("slot", megastep=mega, prefill_chunk=16)
    try:
        ref = ref_eng.generate(prompt_c, sp_c).tokens
        deadline = time.monotonic() + 60
        while ref_eng._has_work() and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.05)
        rk, rv, _ = prompt_rows(ref_eng, ref)
    finally:
        ref_eng.stop()
    eng = make_engine("slot", megastep=mega, prefill_chunk=16)
    try:
        # A takes slot 0 and decodes long enough for B to land in slot 1;
        # A then finishes, and C re-uses freed slot 0: mid-prefill BELOW
        # the active lane — the dispatch width now covers C's lane
        # (one admission group: on a loaded machine A could end before B
        # was even submitted, and B took slot 0)
        with eng.hold_admission():
            a = eng.submit("short lived", SamplingParams(temperature=0.0, max_tokens=16))
            b = eng.submit(ATTRACTOR, SamplingParams(temperature=0.0, max_tokens=60))
        assert a.admitted.result(timeout=120) and b.admitted.result(timeout=120)
        a.result(timeout=120)
        deadline = time.monotonic() + 120
        while eng.decode_steps == 0 and time.monotonic() < deadline:
            time.sleep(0.002)
        c = eng.submit(prompt_c, sp_c)
        got = c.result(timeout=300).tokens
        b.result(timeout=300)
        # the hazard topology must have formed (C below B), or the test
        # proves nothing — locate C's slot from its flight admit event
        c_slot = next(
            e["slot"] for e in eng.flight.events(kind="prefill_done")
            if e["detail"].get("seq") == plen
        )
        assert c_slot == 0, f"topology failed to form: C landed in slot {c_slot}"
        # read the cache only once the engine is idle (an in-flight
        # dispatch donates it)
        deadline = time.monotonic() + 60
        while eng._has_work() and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.05)
        gk, gv, _ = prompt_rows(eng, got)
        assert got == ref, (mega, got, ref)
        bad = np.where(~np.isclose(gk, rk).all(axis=(0, 2, 3)))[0]
        assert bad.size == 0, f"prompt KV rows corrupted at {1 + bad} (mega={mega})"
        assert np.allclose(gv, rv)
    finally:
        eng.stop()


# -- one dispatch per steady-state busy cycle ---------------------------------


def test_steady_state_busy_cycle_is_one_dispatch():
    """THE acceptance criterion: while mid-prefill chunks co-run with
    decode, every model program dispatched is the fused megastep — the
    split chunk/decode/verify/continuation programs dispatch ZERO times in
    the window (asserted via profiler program keys)."""
    eng = make_engine("paged", prefill_chunk=8)
    try:
        anchor = eng.submit(ATTRACTOR, SamplingParams(temperature=0.0, max_tokens=40))
        assert anchor.admitted.result(timeout=120)
        deadline = time.monotonic() + 120
        while eng.decode_steps == 0 and time.monotonic() < deadline:
            time.sleep(0.002)

        def split_dispatches():
            progs = eng.profiler.stats()["programs"]
            return {
                k: v["dispatches"] for k, v in progs.items()
                if k.split("[")[0] in
                ("chunk", "decode", "spec_verify", "prefill_cont", "spill")
            }

        def fused_dispatches():
            progs = eng.profiler.stats()["programs"]
            return sum(
                v["dispatches"] for k, v in progs.items()
                if k.startswith("megastep[")
            )

        # settle into the busy window: a long prompt starts chunking while
        # the anchor decodes
        long = eng.submit("w" * 110, SamplingParams(temperature=0.0, max_tokens=6))
        assert long.admitted.result(timeout=120)
        deadline = time.monotonic() + 120
        while not eng._prefilling_count and time.monotonic() < deadline:
            time.sleep(0.001)
        before_split = split_dispatches()
        before_fused = fused_dispatches()
        # the busy window: chunks + decode co-scheduled
        while eng._prefilling_count and time.monotonic() < deadline:
            time.sleep(0.001)
        after_split = split_dispatches()
        after_fused = fused_dispatches()
        assert after_fused > before_fused, "no fused dispatches in the window"
        # the split per-phase programs stayed silent: fused cycles paid
        # exactly one dispatch each. (decode[] may resume AFTER the window
        # — once nothing is mid-prefill the plain block is already one
        # dispatch — so the comparison is within the window only.)
        assert after_split == before_split, (before_split, after_split)
        long.result(timeout=180)
        anchor.result(timeout=180)
    finally:
        eng.stop()


# -- shape bound fallback -----------------------------------------------------


@pytest.mark.parametrize("spec_len", [0, 4])
def test_shape_bound_falls_back_to_split_programs(spec_len):
    """megastep_max_programs=0: every fused shape is over the bound, so
    every fused cycle split-dispatches (fallback counter rises) and the
    output is still byte-identical. spec_len=4 pins the verify-path
    fallback specifically: the standalone verify after the fallback's
    chunk dispatches must re-capture self.cache (the fallback donated the
    one its args snapshot held — a stale-buffer crash pre-fix)."""
    ref = make_engine("slot", megastep=False, prefill_chunk=16,
                      spec_len=spec_len)
    try:
        want = _busy_run(ref)
    finally:
        ref.stop()
    eng = make_engine("slot", megastep=True, prefill_chunk=16,
                      spec_len=spec_len)
    eng.megastep_max_programs = 0
    try:
        fb0 = counter("acp_engine_megastep_fallbacks_total")
        got = _busy_run(eng)
        assert got == want
        assert eng.megastep_dispatches == 0
        assert eng.megastep_fallbacks > 0
        assert counter("acp_engine_megastep_fallbacks_total") > fb0
        assert not any(
            k.startswith("megastep[") for k in eng.profiler.stats()["programs"]
        )
    finally:
        eng.stop()


# -- pad_fuse accounting ------------------------------------------------------


def test_pad_fuse_waste_row_populates_and_conserves():
    """Three concurrent long prompts form a 3-lane mid phase padded to 4:
    the fused-program waste row (pad_fuse) must populate, and the ledger
    must stay conserved (the armed checker also audits this per cycle)."""
    eng = make_engine("paged", prefill_chunk=8)
    try:
        anchor = eng.submit(ATTRACTOR, SamplingParams(temperature=0.0, max_tokens=36))
        assert anchor.admitted.result(timeout=120)
        deadline = time.monotonic() + 120
        while eng.decode_steps == 0 and time.monotonic() < deadline:
            time.sleep(0.002)
        futs = [
            eng.submit(c * 100, SamplingParams(temperature=0.0, max_tokens=4))
            for c in "uvw"
        ]
        for f in [anchor, *futs]:
            f.result(timeout=300)
        led = eng.profiler.ledger()
        assert led["computed"] == led["goodput"] + sum(led["waste"].values())
        assert led["waste"]["pad_fuse"] > 0, led["waste"]
    finally:
        eng.stop()


# -- prewarm coverage ---------------------------------------------------------


def test_prewarm_megastep_forms_fused_shapes():
    eng = make_engine("slot", prefill_chunk=16)
    try:
        gaps0 = counter("acp_engine_prewarm_gaps_total", phase="megastep")
        eng._prewarm_megastep(constrained=False)
        # the core fused shape (chunk bucket, B=1) formed — or the gap was
        # recorded as data; on this tiny config it must form
        assert any(
            any(p.startswith("m32x1") for p in sh[1])
            for sh in eng._megastep_shapes
        ), eng._megastep_shapes
        assert counter("acp_engine_prewarm_gaps_total", phase="megastep") == gaps0
    finally:
        eng.stop()


def test_prewarm_megastep_gap_is_recorded():
    eng = make_engine("slot", prefill_chunk=16)
    try:
        # poison the verification surface so no planned shape can verify:
        # every attempt exhausts and records the standard prewarm gap
        class _Never(set):
            def add(self, item):
                pass

        eng._megastep_shapes = _Never()
        gaps0 = counter("acp_engine_prewarm_gaps_total", phase="megastep")
        eng._prewarm_megastep(constrained=False)
        assert counter("acp_engine_prewarm_gaps_total", phase="megastep") > gaps0
        gaps = eng.flight.events(kind="prewarm_gap")
        assert any(e["detail"].get("phase") == "megastep" for e in gaps)
    finally:
        eng.stop()
