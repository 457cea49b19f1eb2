"""Quantized KV serving (ISSUE 14): the int8 cache across the engine's
whole mechanism matrix, and the byte-identity-relaxed accuracy gate.

What is (and is not) exact under ``quantize_kv``:

- **Run-to-run determinism** — always bit-exact, every configuration.
- **Host-swap round trips** — bit-exact vs the unpreempted same-knob run:
  the int8 bytes + scale rows travel to host RAM and back verbatim (no
  requantization), so preempt-resume through the host tier cannot move a
  token.
- **Megastep fused vs split** — bit-exact: the same phase bodies run in
  the same order on the same quantized bytes; only the dispatch boundary
  moves.
- **vs the bf16 path** — NOT bit-exact (the one legitimate break): gated
  by the pinned accuracy fixture instead (top-1 greedy agreement +
  logit-MAE bounds, thresholds pinned here).
- **Both knobs off** — the cache carries no scale storage at all and the
  plain path stays bit-for-bit (the existing byte-identity matrix is
  untouched; the purity pin below makes the no-scale-storage contract
  explicit).
"""

import dataclasses
import time

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from agentcontrolplane_tpu.engine.accuracy import (
    accuracy_report,
    check_accuracy_gate,
    pinned_fixture,
)
from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.invariants import verify_engine
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.models.llama import PRESETS, init_params
from agentcontrolplane_tpu.ops.quant import SCALE_FLOOR, kv_dequantize, kv_quantize
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import FAULTS

TOK = ByteTokenizer()
TINY = PRESETS["tiny"]
CFG = dataclasses.replace(TINY, vocab_size=512, max_seq_len=256, n_kv_heads=2)

# The pinned gate thresholds (tiny preset, default fixture). Measured at
# pinning time: weights-only 0.984/0.0138, kv-only 0.990/0.0046, both
# 0.990/0.0146 — the margins absorb compiler jitter, not behavior drift.
GATE_MIN_TOP1 = 0.92
GATE_MAX_MAE = 0.05


def make_engine(kv_layout="paged", **kw):
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    kw.setdefault("check_invariants", True)
    kw.setdefault("quantize_kv", True)
    kw.setdefault("max_ctx", 64)
    kw.setdefault("prefill_buckets", (32, 64))
    eng = Engine(
        config=CFG,
        tokenizer=TOK,
        mesh=mesh,
        max_slots=4,
        decode_block_size=4,
        kv_layout=kv_layout,
        page_size=8,
        **kw,
    )
    eng.start()
    return eng


def _settle(eng: Engine) -> None:
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and (eng._has_work() or len(eng._waiting)):
        time.sleep(0.01)
    time.sleep(0.1)


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    FAULTS.reset()


# -- numerics ----------------------------------------------------------------


def test_kv_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 7, 4, 16)), dtype=jnp.float32)
    q, scale = kv_quantize(x)
    assert q.dtype == jnp.int8 and scale.shape == (5, 7, 4)
    err = np.abs(np.asarray(kv_dequantize(q, scale, jnp.float32)) - np.asarray(x))
    # symmetric int8 over head_dim: max error is scale/2 per row
    assert err.max() <= float(np.asarray(scale).max()) * 0.51


def test_kv_quantize_all_zero_rows_take_scale_floor():
    """The guard satellite, KV side: all-zero rows (never-written cache,
    padding lanes) must produce the floor scale — finite, and an exact
    zero round trip — never a 0/0 NaN that poisons later reads."""
    x = jnp.zeros((2, 3, 8), dtype=jnp.float32)
    q, scale = kv_quantize(x)
    assert np.all(np.asarray(scale) == SCALE_FLOOR)
    out = np.asarray(kv_dequantize(q, scale, jnp.float32))
    assert np.all(np.isfinite(out)) and np.all(out == 0.0)


# -- the accuracy gate -------------------------------------------------------


@pytest.mark.parametrize(
    "qw,qkv", [(True, False), (False, True), (True, True)]
)
def test_accuracy_gate_passes_pinned_thresholds(qw, qkv):
    """The byte-identity-relaxed contract: every quantized configuration
    clears the pinned top-1 agreement + logit-MAE gate over the pinned
    fixture, scored through the real serving numerics."""
    params = init_params(TINY, jax.random.key(0))
    rep = accuracy_report(TINY, params, quantize_weights=qw, quantize_kv=qkv)
    assert check_accuracy_gate(rep, GATE_MIN_TOP1, GATE_MAX_MAE) == [], rep
    # and the un-quantized baseline is self-identical (sanity: the fixture
    # harness itself introduces zero noise)
    base = accuracy_report(TINY, params)
    assert base["top1_agreement"] == 1.0 and base["logit_mae"] == 0.0


def test_pinned_fixture_is_pinned():
    """Same (vocab, shape, seed) -> same rows, forever: the gate's fixture
    is a contract, not a re-roll."""
    a = pinned_fixture(TINY.vocab_size)
    b = pinned_fixture(TINY.vocab_size)
    assert a.shape == (4, 48) and np.array_equal(a, b)
    assert a.min() >= 1 and a.max() < TINY.vocab_size


# -- the serving matrix ------------------------------------------------------


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
@pytest.mark.parametrize("spec_len", [0, 4])
@pytest.mark.parametrize("prefill_chunk", [0, 16])
def test_quantized_matrix_serves_deterministically(kv_layout, spec_len, prefill_chunk):
    """Both layouts x spec on/off x chunked on/off, armed checker on:
    quantized serving is run-to-run deterministic and audits clean.
    (Cross-config byte-identity is NOT asserted — chunk boundaries and
    draft windows change which reads see exact vs quantized rows, the
    relaxation the accuracy gate owns.)"""
    eng = make_engine(kv_layout, spec_len=spec_len, prefill_chunk=prefill_chunk)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=10)
        prompt = "abcabcabc " * 4  # attractor so spec cells really draft
        r1 = eng.generate(prompt, sp)
        r2 = eng.generate(prompt, sp)
        assert r1.finish_reason in ("stop", "length")
        assert r1.tokens == r2.tokens
        if spec_len:
            assert eng.spec_dispatches > 0, "spec cell never speculated"
        _settle(eng)
        assert verify_engine(eng) == []
    finally:
        eng.stop()


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_quantized_swap_roundtrip_bit_exact(kv_layout):
    """Preempt -> host swap -> resume under quantize_kv is bit-exact vs
    the unpreempted run: the int8 bytes + scale rows restore verbatim
    (no requantization round trip), spec on, armed checker auditing."""
    eng = make_engine(kv_layout, host_kv_bytes=1 << 22, spec_len=4)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=14)
        base = eng.generate("hello world " * 4, sp).tokens
        FAULTS.arm("engine.force_preempt", after_steps=2)
        r = eng.generate("hello world " * 4, sp)
        assert r.preempt_count >= 1
        assert r.tokens == base, "quantized swap round-trip moved a token"
        assert eng.kv_swap_outs >= 1 and eng.kv_swap_ins >= 1
        _settle(eng)
        assert verify_engine(eng) == []
    finally:
        eng.stop()


def test_quantized_megastep_fused_equals_split():
    """Fused vs split dispatches run the identical schedule on the same
    quantized bytes — bit-for-bit equal, chunked + spec active."""
    outs = {}
    for mega in (False, True):
        eng = make_engine("paged", megastep=mega, prefill_chunk=16, spec_len=4)
        try:
            sp = SamplingParams(temperature=0.0, max_tokens=12)
            with eng.hold_admission():
                futs = [
                    eng.submit("the quick brown fox jumps over " * 3, sp),
                    eng.submit("pack my box with five dozen jugs " * 2, sp),
                    eng.submit("abcabcabc " * 4, sp),
                ]
            outs[mega] = [f.result(timeout=300).tokens for f in futs]
            if mega:
                assert eng.megastep_dispatches > 0
            _settle(eng)
            assert verify_engine(eng) == []
        finally:
            eng.stop()
    assert outs[True] == outs[False]


def test_quantized_park_adopt_roundtrip():
    """Two-turn park/adopt conversation with quantize_kv: the parked
    quantized prompt rows are adopted suffix-only; deterministic across
    repeats and audited clean."""
    turn1 = "persona prompt " * 4
    turn2 = turn1 + " and then a follow up"
    sp = SamplingParams(temperature=0.0, max_tokens=10)

    def run():
        eng = make_engine("paged", max_ctx=128, prefill_buckets=(32, 64, 128))
        try:
            r1 = eng.submit(turn1, sp, park=True).result(timeout=180)
            r2 = eng.submit(turn2, sp).result(timeout=180)
            adoptions = eng.park_adoptions
            _settle(eng)
            assert verify_engine(eng) == []
            return r1.tokens, r2.tokens, adoptions
        finally:
            eng.stop()

    t1a, t2a, adopt_a = run()
    t1b, t2b, _ = run()
    assert adopt_a >= 1, "turn 2 never adopted the parked slot"
    assert (t1a, t2a) == (t1b, t2b)


def test_quantized_dedup_burst_shares_and_matches_solo():
    """A same-persona burst over quantized pages refcount-shares the int8
    prompt pages; outputs equal the solo runs exactly (same quantized
    bytes, shared or private)."""
    eng = make_engine("paged", prefix_cache_entries=0)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        persona = "agent persona prompt! " * 2
        solo = {i: eng.generate(persona + str(i), sp).tokens for i in range(4)}
        shared_peak = [0]

        def on_tokens(_t):
            shared_peak[0] = max(
                shared_peak[0],
                eng.stats()["memory"]["prefix_dedup"]["shared_pages"],
            )

        with eng.hold_admission():
            futs = [
                eng.submit(persona + str(i), sp, on_tokens=on_tokens)
                for i in range(4)
            ]
        res = {i: f.result(timeout=180).tokens for i, f in enumerate(futs)}
        assert res == solo
        assert shared_peak[0] > 0, "burst never shared a quantized page"
        _settle(eng)
        assert verify_engine(eng) == []
    finally:
        eng.stop()


# -- off-knob purity ---------------------------------------------------------


def test_off_knobs_carry_no_scale_storage():
    """Both knobs off: the cache is exactly the plain {k, v} bf16/f32
    layout (no scale twins, no int8) — the structural half of 'the
    existing byte-identity matrix passes untouched'."""
    for layout in ("slot", "paged"):
        eng = make_engine(layout, quantize_kv=False)
        try:
            assert sorted(eng.cache) == ["k", "v"]
            assert eng.cache["k"].dtype == CFG.dtype
            assert not eng.quantize_kv and eng.quantize is None
            sp = SamplingParams(temperature=0.0, max_tokens=6)
            r1 = eng.generate("plain path purity", sp)
            r2 = eng.generate("plain path purity", sp)
            assert r1.tokens == r2.tokens
            _settle(eng)
            assert verify_engine(eng) == []
        finally:
            eng.stop()


def test_quantized_cache_layout_pinned():
    """The quantized cache's dtypes/shapes are the documented contract:
    int8 values + f32 scale twins, one scale a row and KV head: the slot
    cache keeps the heads apart ([L, S, C, H_kv, d]), the paged pool holds
    a row's heads side by side ([L, pages, P, H_kv * d], as the page walk
    reads it)."""
    import math

    for layout in ("slot", "paged"):
        eng = make_engine(layout)
        try:
            assert sorted(eng.cache) == ["k", "ks", "v", "vs"]
            for name in ("k", "v"):
                assert eng.cache[name].dtype == jnp.int8
                assert eng.cache[name + "s"].dtype == jnp.float32
                val, c = eng.cache[name], eng.config
                assert tuple(eng.cache[name + "s"].shape) == tuple(val.shape[:3]) + (c.n_kv_heads,)
                assert math.prod(val.shape[3:]) == c.n_kv_heads * c.head_dim
                assert val.ndim == {"slot": 5, "paged": 4}[layout]
        finally:
            eng.stop()
