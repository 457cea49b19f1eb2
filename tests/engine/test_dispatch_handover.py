"""The hand-over of a dispatch: one packed upload, a key derived in the program.

What the host sends the device for a dispatch goes as one int32 buffer of
per-lane rows (engine/lanes.py) beside the token rows, the page ids and,
when they changed, the block tables; the program mixes the dispatch's counter,
a row of that buffer, into the engine's one base key. These cases hold the
counts and the bytes (CPU, tiny sizes, no times):

(a) uploads a dispatch, by program, in both families and both KV layouts;
(b) no `jax.random.split` of a concrete key is left on the engine thread;
(c) every dispatch draws from its own key, a seed fixes the sampled bytes,
    and first tokens at temperature 1 follow the softmax of the logits;
(d) the packed rows come out of the program's unpacking bit for bit.
"""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.lanes import DECODE, PREFILL, VERIFY, dispatch_key
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.models import lfm2, llama, preset
from agentcontrolplane_tpu.models.llama import PRESETS

TINY = dataclasses.replace(PRESETS["tiny"], max_seq_len=128)
LFM2 = preset("lfm2-tiny")
FAMILIES = {
    "llama-slot": dict(config=TINY, kv_layout="slot"),
    "llama-paged": dict(config=TINY, kv_layout="paged", page_size=8),
    "lfm2-paged": dict(config=LFM2, kv_layout="paged", page_size=8, kv_pages=80),
}
_PARAMS: dict = {}


def build(family: str, **kw) -> Engine:
    opts = dict(FAMILIES[family])
    if opts["config"] is LFM2:
        if "lfm2" not in _PARAMS:
            _PARAMS["lfm2"] = lfm2.init_params(LFM2, jax.random.key(0))
        opts["params"] = _PARAMS["lfm2"]
    else:
        opts["tokenizer"] = ByteTokenizer()
    opts.update(max_slots=4, max_ctx=128, prefill_buckets=(16, 32, 64), width_buckets=(2, 4),
                decode_block_size=4, seed=0, mesh=jax.sharding.Mesh(jax.devices()[:1], ("tp",)))
    return Engine(**{**opts, **kw})


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 250, n)] for n in lengths]


PROGRAMS = ("_jit_prefill", "_jit_prefill_continue", "_jit_prefill_paged", "_jit_prefill_paged_continue",
            "_jit_decode", "_jit_decode_paged", "_jit_verify", "_jit_megastep")


def record_dispatches(eng: Engine) -> list:
    """Wrap every model program of ``eng``: one record a dispatch, holding
    the uploads the engine made since the dispatch before it (all of a
    dispatch's uploads come right before its call), and the arguments."""
    calls, seen = [], {"uploads": eng.stats()["perf"]["uploads"]}

    def wrap(name, real):
        def call(*args):
            now = eng.stats()["perf"]["uploads"]
            calls.append({"program": name, "uploads": now - seen["uploads"], "args": args,
                          # read before the call: a decode block's lanes are donated
                          "lanes": [np.asarray(a) for a in jax.tree_util.tree_leaves(args[2:])
                                    if getattr(a, "dtype", None) == jnp.int32 and a.ndim == 2]})
            seen["uploads"] = now
            out = real(*args)
            calls[-1]["out"] = out
            return out
        return call

    for name in PROGRAMS:
        if getattr(eng, name, None) is not None:
            setattr(eng, name, wrap(name, getattr(eng, name)))
    return calls


def closed_loop(eng: Engine, ps, sampling, rounds=2):
    """``rounds`` requests a caller, each sent from its caller's completion."""
    with eng.hold_admission():
        futures = [eng.submit(p, sampling) for p in ps]
    out = []
    for r in range(1, rounds):
        out += [f.result(300) for f in futures]
        futures = [eng.submit([1 + (t + r) % 249 for t in p], sampling) for p in ps]  # no prefix shared
    return out + [f.result(300) for f in futures]


# -- (a) uploads a dispatch ------------------------------------------------


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_dispatch_uploads_its_lanes_once(family):
    """Plain prefill <= 3 uploads (token rows, lanes, page ids), a dirty
    decode block <= 2 (lanes; block tables when they changed), a block that
    nothing dirtied none, and it runs the program the dirty block ran."""
    eng = build(family)
    calls = record_dispatches(eng)
    eng.start()
    try:
        closed_loop(eng, prompts(20, 9, 30), SamplingParams(temperature=0.7, max_tokens=21), rounds=3)
    finally:
        eng.stop()
    prefills = [c for c in calls if c["program"] in ("_jit_prefill", "_jit_prefill_paged")]
    decodes = [c for c in calls if c["program"] in ("_jit_decode", "_jit_decode_paged")]
    counts = (len(prefills), len(decodes), len(calls))
    assert counts[0] >= 3 and counts[1] >= 8 and counts[0] + counts[1] == counts[2], counts
    assert all(1 <= c["uploads"] <= 3 for c in prefills), [c["uploads"] for c in prefills]
    assert all(c["uploads"] <= 2 for c in decodes), [c["uploads"] for c in decodes]
    clean = dirty = 0
    for before, c in zip(decodes, decodes[1:]):
        fed_back = c["args"][2] is before["out"][3]  # the lanes are the carry handed back
        same_tables = family == "llama-slot" or c["args"][-1] is before["args"][-1]
        assert c["uploads"] == (not fed_back) + (not same_tables), (c["uploads"], fed_back, same_tables)
        clean += fed_back and same_tables
        dirty += not fed_back
    assert clean >= 2 and dirty >= 2, (clean, dirty)
    # one signature: the shapes a clean block feeds are those of a dirty one
    shapes = {tuple(a.shape for a in jax.tree_util.tree_leaves(c["args"][2:])) for c in decodes}
    assert len(shapes) <= len(eng.width_buckets), shapes
    perf = eng.stats()["perf"]
    assert perf["uploads"] == sum(c["uploads"] for c in calls) + 2  # + the two dummy tables of __init__
    assert perf["uploads"] / perf["blocks"] <= 6


@pytest.mark.parametrize("family,megastep", [("llama-paged", False), ("lfm2-paged", False),
                                             ("llama-slot", True), ("llama-paged", True)])
def test_a_continuation_uploads_at_most_four(family, megastep):
    """Chunked prefill: a chunk and a final chunk are continuations (token
    rows, lanes with their starts, page ids, their block tables); fused,
    each phase of the megastep keeps to its own bound."""
    eng = build(family, prefill_chunk=16, prefill_buckets=(16, 32), megastep=megastep)
    calls = record_dispatches(eng)
    eng.start()
    try:
        closed_loop(eng, prompts(70, 41, 12, seed=3), SamplingParams(temperature=0.7, max_tokens=9))
    finally:
        eng.stop()
    conts = [c for c in calls if c["program"].endswith("_continue")]
    fused = [c for c in calls if c["program"] == "_jit_megastep"]
    assert (fused if megastep else conts), {c["program"] for c in calls}
    assert all(c["uploads"] <= 4 for c in conts), [c["uploads"] for c in conts]
    for c in fused:
        _params, _cache, _key, _swaps, mids, plains, finals, dec, _aux, ver = c["args"]
        bound = 4 * (mids is not None) + 3 * (plains is not None) + 4 * (finals is not None) \
            + 2 * (dec is not None) + 3 * (ver is not None)
        assert c["uploads"] <= bound, (c["uploads"], bound)
    for c in calls:
        if "prefill" in c["program"]:
            assert c["uploads"] <= (4 if c["program"].endswith("_continue") else 3)


# -- (b) no key is split on the host ----------------------------------------


@pytest.mark.parametrize("family,kw", [
    ("llama-paged", {}),
    ("llama-slot", {}),
    ("lfm2-paged", {}),
    ("llama-paged", dict(prefill_buckets=(16, 32))),  # a prompt past the largest bucket spills
    ("llama-paged", dict(prefill_chunk=16, prefill_buckets=(16, 32), megastep=False)),
    ("llama-paged", dict(prefill_chunk=16, prefill_buckets=(16, 32))),
    ("llama-slot", dict(prefill_chunk=16, prefill_buckets=(16, 32), spec_len=3)),
    ("llama-paged", dict(spec_len=3, megastep=False)),
], ids=["paged", "slot", "lfm2", "spill", "chunked-split", "megastep", "megastep-spec-slot", "spec-split"])
def test_no_key_is_split_on_the_engine_thread(family, kw, monkeypatch):
    """`jax.random.split` of a concrete key raises on the engine thread (a
    traced one, inside a program being compiled, passes): a sampled and a
    greedy request complete all the same."""
    eng = build(family, **kw)
    real = jax.random.split

    def split(key, *a, **k):
        if threading.current_thread() is eng._thread and not isinstance(key, jax.core.Tracer):
            raise AssertionError("a key was split on the host")
        return real(key, *a, **k)

    monkeypatch.setattr(jax.random, "split", split)
    eng.start()
    try:
        # repetitive prompts, so that the n-gram drafter proposes where spec_len > 0
        ps = [([7, 8, 9, 10] * 18)[:70], [3, 4] * 10]
        for temperature in (0.7, 0.0):
            sp = SamplingParams(temperature=temperature, max_tokens=12)
            for r in closed_loop(eng, ps, sp, rounds=1):
                assert r.finish_reason == "length" and len(r.tokens) == 12
        if kw.get("spec_len"):
            assert eng.stats()["spec"]["verify_dispatches"] > 0
    finally:
        eng.stop()


# -- (c) keys ----------------------------------------------------------------


def key_of(call):
    """(n, chain) a program derives its key from, read off its lanes."""
    rows = DECODE if "decode" in call["program"] else PREFILL
    lanes = call["lanes"][1] if "prefill" in call["program"] else call["lanes"][0]
    assert lanes.shape[0] == len(rows.kinds)
    n = lanes[rows.index["n"]]
    assert (n == n[0]).all()
    return int(n[0]), int(lanes[rows.index["chain"], 0]) if rows is DECODE else -1


@pytest.mark.parametrize("family", ["llama-paged", "lfm2-paged"])
def test_every_dispatch_draws_from_its_own_key(family):
    eng = build(family)
    calls = record_dispatches(eng)
    eng.start()
    try:
        closed_loop(eng, prompts(20, 9, 30), SamplingParams(temperature=1.0, max_tokens=21))
    finally:
        eng.stop()
    keys = [key_of(c) for c in calls]
    assert len(set(keys)) == len(keys) >= 10, keys
    # the counter counts dispatches; a block nothing dirtied keeps its n and moves along its chain
    assert len({n for n, _ in keys}) < len(keys)
    assert any(chain > 0 for _, chain in keys)
    dirty = [n for n, chain in keys if chain <= 0]
    assert dirty == sorted(dirty) and len(set(dirty)) == len(dirty)
    base = jax.random.key(0)
    data = {bytes(np.asarray(jax.random.key_data(
        dispatch_key(base, jnp.asarray([n]), None if chain < 0 else jnp.asarray([chain]))))) for n, chain in keys}
    assert len(data) == len(keys)


def sampled(seed):
    eng = build("llama-paged", seed=seed)
    eng.start()
    try:
        sp = SamplingParams(temperature=1.0, max_tokens=24)
        return [eng.generate(p, sp).tokens for p in prompts(20, 9)]  # one at a time: one order of dispatches
    finally:
        eng.stop()


def test_a_seed_fixes_the_sampled_bytes_and_another_seed_moves_them():
    first, again, other = sampled(11), sampled(11), sampled(12)
    assert first == again
    assert first != other
    assert first[0][:8] != first[1][:8]  # and two dispatches of one engine do not repeat each other


DRAWS, TOP = 4096, 6
TOLERANCE = 0.035  # 4.5 standard deviations of a share of 0.5 over 4,096 draws


def test_first_tokens_at_temperature_one_follow_the_softmax():
    """4,096 first tokens of one prompt at temperature 1: each of the six
    likeliest tokens (and the rest together) is drawn with its softmax
    share within TOLERANCE. A reused key repeats one draw 4,096 times, a
    temperature read as an int flattens the shares: both read far outside."""
    params = llama.init_params(TINY, jax.random.key(3))
    params["lm_head"] = params["lm_head"] * 4.0  # a peaked distribution: top shares of 0.1-0.4
    prompt = prompts(12, seed=5)[0]
    logits = np.asarray(llama.forward(params, jnp.asarray([prompt]), TINY)[0, -1], dtype=np.float64)
    want = np.exp(logits - logits.max())
    want /= want.sum()
    top = np.argsort(want)[::-1][:TOP]
    assert want[top[0]] > 0.1 and want[top].sum() < 0.95, want[top]
    eng = build("llama-paged", params=params, max_slots=8, width_buckets=(8,))
    eng.start()
    try:
        sp = SamplingParams(temperature=1.0, max_tokens=1)
        firsts = []
        for _ in range(DRAWS // 512):
            with eng.hold_admission():
                futures = [eng.submit(prompt, sp) for _ in range(512)]
            firsts += [f.result(300).tokens[0] for f in futures]
    finally:
        eng.stop()
    got = np.bincount(firsts, minlength=TINY.vocab_size) / len(firsts)
    assert np.abs(got[top] - want[top]).max() < TOLERANCE, (got[top], want[top])
    assert abs(got[top].sum() - want[top].sum()) < TOLERANCE


# -- (d) the packed buffer ----------------------------------------------------

FLOATS = np.asarray([0.7, 0.0, 1.0, 1e-8, 3.4e38, -0.0, 0.1 + 0.2, np.float32(2) ** -126], dtype=np.float32)
INTS = np.asarray([0, -1, 1, -2**31, 2**31 - 1, 151_936, -7, 4], dtype=np.int32)
BOOLS = np.asarray([True, False, False, True, True, False, True, False])


@pytest.mark.parametrize("rows", [PREFILL, DECODE, VERIFY], ids=["prefill", "decode", "verify"])
def test_packed_rows_come_out_of_the_programs_unpacking_bit_for_bit(rows):
    """float32 temps and top_ps, booleans and negative ints (-1 snapshots,
    a slot out of range, the largest and smallest int32) through
    `pack` on the host and `unpack` under jit."""
    given = {}
    for i, (name, kind) in enumerate(rows.kinds.items()):
        source = {np.float32: FLOATS, np.int32: INTS, np.bool_: BOOLS}[kind.type]
        given[name] = np.roll(source, i)
    lanes = rows.pack(8, **given)
    assert lanes.dtype == np.int32 and lanes.shape == (len(rows.kinds), 8)
    got = jax.jit(rows.unpack)(jnp.asarray(lanes))
    for name, kind in rows.kinds.items():
        out = np.asarray(got[name])
        assert out.dtype == kind, (name, out.dtype)
        np.testing.assert_array_equal(out.view(np.uint8), given[name].view(np.uint8), err_msg=name)
    # a scalar stands for every lane, and every row has to be given
    one = dict(given, **{next(iter(given)): given[next(iter(given))][3]})
    assert (rows.pack(8, **one)[0] == rows.pack(8, **given)[0][3]).all()
    with pytest.raises(ValueError, match="lanes"):
        rows.pack(8, **{k: v for k, v in given.items() if k != "budgets"})


def test_the_decode_carry_keeps_what_the_program_only_reads():
    given = {name: np.roll({np.float32: FLOATS, np.int32: INTS, np.bool_: BOOLS}[kind.type], i)
             for i, (name, kind) in enumerate(DECODE.kinds.items())}
    lanes = jnp.asarray(DECODE.pack(8, **given))

    @jax.jit
    def block(lanes):
        ln = DECODE.unpack(lanes)
        return DECODE.update(lanes, tokens=ln["tokens"] + 1, active=~ln["active"], chain=ln["chain"] + 1)

    out = jax.jit(DECODE.unpack)(block(lanes))
    for name in DECODE.kinds:
        want = {"tokens": given["tokens"] + 1, "active": ~given["active"], "chain": given["chain"] + 1}.get(name, given[name])
        np.testing.assert_array_equal(np.asarray(out[name]).view(np.uint8), want.view(np.uint8), err_msg=name)
