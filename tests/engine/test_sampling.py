"""Bisection-threshold sampler vs an exact numpy nucleus/top-k oracle, and
vs the sampler that ran both searches for every batch.

The sampler replaces the two full-vocab sorts with threshold binary
searches (ops/sampling.py); these tests pin the masking semantics: a
sampled token must always lie inside the exact allowed set, and greedy
(temperature 0) must be untouched by the masks.

Since PR 39 each search runs only when a live lane of the batch asks for
it. ``always_both`` below is the sampler as it was, kept as the oracle: a
batch in which some live lane asks gets its tokens under the same key, lane
for lane; a batch in which none asks gets the plain categorical draw; a
dead lane's stale top-k / top-p turns nothing on; and the engine counts
the dispatches that asked (``stats()["sampling"]``).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from agentcontrolplane_tpu.engine import engine as engine_module
from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams, sample_lanes
from agentcontrolplane_tpu.engine.lanes import PREFILL
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.models.llama import PRESETS
from agentcontrolplane_tpu.ops import sampling
from agentcontrolplane_tpu.ops.sampling import masks_wanted, sample, speculative_accept


def _exact_allowed(logits: np.ndarray, top_k: int, top_p: float) -> set:
    """Oracle: indices surviving top-k (keep k largest, ties kept) then
    top-p (keep tokens whose strictly-greater-prob mass is < top_p)."""
    V = logits.shape[0]
    x = logits.astype(np.float64).copy()
    if top_k > 0 and top_k < V:
        kth = np.sort(x)[::-1][top_k - 1]
        x[x < kth] = -np.inf
    e = np.exp(x - np.max(x[np.isfinite(x)]))
    e[~np.isfinite(x)] = 0.0
    p = e / e.sum()
    allowed = set()
    # mass of strictly-greater-probability tokens, per token
    for i in range(V):
        if p[i] <= 0:
            continue
        mass_above = p[p > p[i]].sum()
        if mass_above < top_p:
            allowed.add(i)
    return allowed


def test_sampled_tokens_stay_inside_exact_nucleus():
    rng = np.random.default_rng(0)
    V, S = 64, 4
    logits_np = rng.normal(scale=3.0, size=(S, V)).astype(np.float32)
    logits = jnp.asarray(logits_np)
    temps = jnp.asarray([0.7, 1.3, 0.9, 2.0])
    top_ks = jnp.asarray([0, 5, 3, 8], dtype=jnp.int32)
    top_ps = jnp.asarray([0.8, 1.0, 0.5, 0.9])
    allowed = [
        _exact_allowed(logits_np[s], int(top_ks[s]), float(top_ps[s]))
        for s in range(S)
    ]
    for trial in range(64):
        toks = np.asarray(
            sample(logits, jax.random.key(trial), temps, top_ks, top_ps)
        )
        for s in range(S):
            assert int(toks[s]) in allowed[s], (
                f"slot {s} trial {trial}: token {toks[s]} outside exact "
                f"top_k={int(top_ks[s])}/top_p={float(top_ps[s])} set"
            )


def test_greedy_unaffected_by_masks():
    rng = np.random.default_rng(1)
    logits_np = rng.normal(size=(3, 128)).astype(np.float32)
    toks = np.asarray(
        sample(
            jnp.asarray(logits_np),
            jax.random.key(0),
            jnp.zeros(3),  # temperature 0 -> greedy
            jnp.asarray([4, 0, 1], dtype=jnp.int32),
            jnp.asarray([0.3, 0.01, 1.0]),
        )
    )
    np.testing.assert_array_equal(toks, logits_np.argmax(-1))


def test_top_k_one_is_greedy_even_at_high_temperature():
    rng = np.random.default_rng(2)
    logits_np = rng.normal(size=(2, 256)).astype(np.float32)
    for trial in range(16):
        toks = np.asarray(
            sample(
                jnp.asarray(logits_np),
                jax.random.key(trial),
                jnp.full((2,), 5.0),
                jnp.ones(2, dtype=jnp.int32),  # top_k=1
                jnp.ones(2),
            )
        )
        np.testing.assert_array_equal(toks, logits_np.argmax(-1))


# -- the searches run only when a live lane asks (PR 39) ------------------------

NEG_INF, ITERS = -1e30, 32


def always_both(logits, rng, temperature, top_k, top_p, wanted=None):
    """``ops.sampling.sample`` as it stood before PR 39, its two searches
    included: both thresholds for every batch, whatever it asks for."""
    logits = logits.astype(jnp.float32)
    S, V = logits.shape

    k = jnp.where(top_k > 0, top_k, V)
    lo, hi = jnp.min(logits, axis=-1), jnp.max(logits, axis=-1)

    def topk_turn(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        ok = jnp.sum(logits >= mid[:, None], axis=-1) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, hi = jax.lax.fori_loop(0, ITERS, topk_turn, (lo, hi))
    logits = jnp.where(logits < lo[:, None], NEG_INF, logits)

    probs = jax.nn.softmax(logits, axis=-1)
    lo, hi = jnp.zeros(S), jnp.max(probs, axis=-1)

    def topp_turn(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        ok = jnp.sum(jnp.where(probs > mid[:, None], probs, 0.0), axis=-1) < top_p
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    lo, hi = jax.lax.fori_loop(0, ITERS, topp_turn, (lo, hi))
    logits = jnp.where(probs < hi[:, None], NEG_INF, logits)

    greedy = jnp.argmax(logits, axis=-1)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(rng, logits / temp, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)


S, V, KEYS = 8, 1024, 24
TEMPS = jnp.asarray([0.7, 0.0, 1.3, 0.7, 0.0, 2.0, 0.9, 1.0])  # two greedy lanes
# lane 3 (and lane 6) ask; the others set neither
BATCHES = {
    "top-k": ([0, 0, 0, 5, 0, 0, 40, 0], [1.0] * 8),
    "top-p": ([0] * 8, [1.0, 1.0, 1.0, 0.9, 1.0, 1.0, 0.5, 1.0]),
    "both": ([0, 0, 0, 5, 0, 0, 0, 0], [1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 0.9, 1.0]),
    "neither": ([0] * 8, [1.0] * 8),
}


def batch(name):
    top_k, top_p = BATCHES[name]
    logits = jnp.asarray(np.random.default_rng(7).normal(scale=3.0, size=(S, V)), jnp.float32)
    return logits, jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32)


@pytest.mark.parametrize("name", list(BATCHES))
def test_every_lane_of_a_batch_gets_the_token_it_got_under_the_same_key(name):
    """A lane that asks for a mask, beside lanes that set neither and
    greedy lanes: each gets the oracle's token. (A batch in which no lane
    asks for top-p no longer masks the tokens the search at 1.0 happened to
    drop, under 2^-32 of the row's largest probability; these keys draw none.)"""
    logits, top_k, top_p = batch(name)
    new, old = jax.jit(sample), jax.jit(always_both)
    for key in range(KEYS):
        key = jax.random.key(key)
        np.testing.assert_array_equal(new(logits, key, TEMPS, top_k, top_p),
                                      old(logits, key, TEMPS, top_k, top_p))


def test_a_batch_in_which_no_lane_asks_gets_the_plain_draw():
    logits, top_k, top_p = batch("neither")
    for key in range(KEYS):
        key = jax.random.key(key)
        plain = jnp.where(TEMPS <= 0.0, jnp.argmax(logits, -1),
                          jax.random.categorical(key, logits / jnp.maximum(TEMPS, 1e-6)[:, None], axis=-1))
        np.testing.assert_array_equal(sample(logits, key, TEMPS, top_k, top_p), plain)


def eqns(jaxpr, name):
    return [e for e in jaxpr.eqns if e.primitive.name == name]


def primitives(jaxpr, but=()) -> set:
    """The names of every primitive of ``jaxpr``, nested jaxprs included,
    the equations ``but`` apart."""
    out = set()
    for e in jaxpr.eqns:
        if e in but:
            continue
        out.add(e.primitive.name)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out |= primitives(sub)
    return out


def test_the_top_p_loop_is_inside_a_conditional_and_the_top_k_loop_turns_by_a_traced_count():
    logits, top_k, top_p = batch("both")
    jaxpr = jax.make_jaxpr(sample)(logits, jax.random.key(0), TEMPS, top_k, top_p).jaxpr
    (cond,), (loop,) = eqns(jaxpr, "cond"), eqns(jaxpr, "while")
    asked, skipped = (b.jaxpr for b in sorted(cond.params["branches"], key=lambda b: -len(b.jaxpr.eqns)))
    assert {"scan", "exp"} <= primitives(asked), "the softmax and its 32 turns are the asking branch"
    assert not skipped.eqns, "the skipping branch hands back the top-k threshold it was given"
    assert [v.aval.shape for v in cond.outvars] == [(S,)], "what leaves the conditional is a threshold a row"
    # a loop of 32 turns is a `scan`; one whose count is traced is a `while`, here of 0 or 32
    assert loop is not None and not eqns(jaxpr, "scan"), "the top-k loop's count of turns is traced"
    assert "exp" not in primitives(jaxpr, but=[cond]), "a softmax outside the conditional"
    np.testing.assert_array_equal(sampling._topk_threshold(logits, top_k, 0)[:, 0], logits.min(-1))


@pytest.mark.parametrize("stale", [dict(top_k=1), dict(top_p=0.01)], ids=["top-k", "top-p"])
def test_a_dead_lanes_stale_mask_turns_no_search_on(stale):
    """Lane 3 is dead and still carries a finished request's top_k = 1 (or
    top_p = 0.01): either search, had it run, would leave that lane its
    argmax. The live lanes ask for nothing, so the batch gets the plain
    draw, and the dead lane's (discarded) token is drawn like any other."""
    logits, top_k, top_p = batch("neither")
    top_k = top_k.at[3].set(stale.get("top_k", 0))
    top_p = top_p.at[3].set(stale.get("top_p", 1.0))
    temps = jnp.full((S,), 5.0)
    active = jnp.ones((S,), bool).at[3].set(False)
    assert [bool(w) for w in masks_wanted(top_k, top_p, active)] == [False, False]
    assert [bool(w) for w in masks_wanted(top_k, top_p)] == ["top_k" in stale, "top_p" in stale]
    argmax = int(jnp.argmax(logits[3]))
    gated = jax.jit(lambda key: sample(logits, key, temps, top_k, top_p, masks_wanted(top_k, top_p, active)))
    ungated = jax.jit(lambda key: sample(logits, key, temps, top_k, top_p))
    keys = [jax.random.key(i) for i in range(8)]
    assert all(int(ungated(key)[3]) == argmax for key in keys)
    assert any(int(gated(key)[3]) != argmax for key in keys)
    for key in keys:
        np.testing.assert_array_equal(
            gated(key), jax.random.categorical(key, logits / 5.0, axis=-1))


@pytest.mark.parametrize("name", list(BATCHES))
def test_speculative_accept_emits_the_tokens_it_emitted(name, monkeypatch):
    logits, top_k, top_p = batch(name)
    T = 4
    logits3 = jnp.stack([jnp.roll(logits, i, axis=1) for i in range(T)], axis=1)  # [S, T, V]
    inputs = jnp.asarray(np.random.default_rng(3).integers(0, V, (S, T)), jnp.int32)
    # the draft is the argmax where the lane is greedy, so some positions are accepted
    inputs = inputs.at[:, 1:].set(jnp.where((TEMPS <= 0)[:, None], jnp.argmax(logits3, -1)[:, :-1], inputs[:, 1:]))
    args = (logits3, inputs, jnp.full((S,), T, jnp.int32), jnp.ones((S,), bool).at[2].set(False))
    rest = (TEMPS, top_k, top_p, (5,), jnp.full((S,), 9, jnp.int32), jnp.asarray(False))
    new = [speculative_accept(*args, jax.random.key(i), *rest) for i in range(6)]
    monkeypatch.setattr(sampling, "sample", always_both)
    old = [speculative_accept(*args, jax.random.key(i), *rest) for i in range(6)]
    for got, want in zip(new, old):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)
    assert any(int(n) > 1 for got in new for n in got[1]), "no draft position was ever accepted"


@pytest.mark.parametrize("name", list(BATCHES))
def test_sample_lanes_draws_the_first_tokens_it_drew(name, monkeypatch):
    """A prefill group has no `active` row: every lane counts."""
    logits, top_k, top_p = batch(name)
    ln = PREFILL.unpack(jnp.asarray(PREFILL.pack(
        S, n=3, lengths=1, starts=0, slots=0, snap_at=-1, temps=np.asarray(TEMPS), top_ks=np.asarray(top_k),
        top_ps=np.asarray(top_p), con_states=0, constrained=False, budgets=4)))
    table, min_close = jnp.full((1, V), -1, jnp.int32), jnp.zeros((1,), jnp.int32)
    new = sample_lanes(logits, jax.random.key(5), ln, table, min_close)
    monkeypatch.setattr(engine_module, "sample", always_both)
    old = sample_lanes(logits, jax.random.key(5), ln, table, min_close)
    np.testing.assert_array_equal(new[0], old[0])


# -- the engine: the same streams, and the count of dispatches that asked --------

TINY = dataclasses.replace(PRESETS["tiny"], max_seq_len=128)


def serve(requests, **kw):
    """One paged engine on the tiny model; ``requests`` admitted as one
    group. Returns each request's tokens and the engine's sampling counts."""
    eng = Engine(config=TINY, tokenizer=ByteTokenizer(), kv_layout="paged", page_size=8, max_slots=4, max_ctx=128,
                 prefill_buckets=(16, 32), width_buckets=(2, 4), decode_block_size=4, seed=3,
                 mesh=jax.sharding.Mesh(jax.devices()[:1], ("tp",)), **kw)
    eng.start()
    try:
        with eng.hold_admission():
            futures = [eng.submit(prompt, sp) for prompt, sp in requests]
        return [f.result(300).tokens for f in futures], eng.stats()["sampling"]
    finally:
        eng.stop()


PROMPTS = [[int(t) for t in np.random.default_rng(i).integers(1, 250, 12)] for i in range(2)]


@pytest.mark.parametrize("spec_len", [0, 2], ids=["decode-blocks", "speculative"])
def test_a_request_with_top_p_beside_one_without_streams_what_it_streamed(spec_len, monkeypatch):
    requests = [(PROMPTS[0], SamplingParams(temperature=0.8, top_p=0.9, max_tokens=24)),
                (PROMPTS[1], SamplingParams(temperature=0.8, max_tokens=24))]
    new, counts = serve(requests, spec_len=spec_len)
    monkeypatch.setattr(engine_module, "sample", always_both)
    monkeypatch.setattr(sampling, "sample", always_both)
    old, _ = serve(requests, spec_len=spec_len)
    assert new == old and len(new[0]) == len(new[1]) == 24
    assert counts["topp_dispatches"] == counts["dispatches"] > 0 == counts["topk_dispatches"]


def test_the_counts_follow_masked_and_unmasked_dispatches():
    """A short request with top-k and top-p beside a long one without, then
    the long one alone: the prefill group and the blocks while the short one
    lives are counted as asking; the blocks after it, whose lane 1 is dead
    and still holds 5 and 0.9, are not."""
    requests = [(PROMPTS[0], SamplingParams(temperature=0.8, max_tokens=40)),
                (PROMPTS[1], SamplingParams(temperature=0.8, top_k=5, top_p=0.9, max_tokens=6))]
    tokens, counts = serve(requests)
    assert [len(t) for t in tokens] == [40, 6]
    # one prefill group, then blocks of 4: the short request lives through two of the long one's ten
    assert counts["dispatches"] == 11
    assert counts["topk_dispatches"] == counts["topp_dispatches"] == 3
    _, plain = serve([(PROMPTS[0], SamplingParams(temperature=0.8, max_tokens=16))])
    assert plain == {"dispatches": 5, "topk_dispatches": 0, "topp_dispatches": 0}
