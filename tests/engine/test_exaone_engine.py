"""K-EXAONE through the normal path, the engine: `Engine(config=ExaoneConfig)`
serves through `submit` with the model's own MTP module as the drafter. At
temperature 0 the drafting engine's tokens are the non-drafting reference's
(the family's plain forward pass, no cache, no draft), token for token:
short and long slots in one batch, budgets that cut inside a two-token step,
the context's edge, continuations, preemption and re-admission, streaming;
the drafter's counters; what the family refuses.

CPU, tiny sizes, float32, seeded weights (`exaone-tiny`: a window of 16,
pages of 8).
"""

import jax
import numpy as np
import pytest

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.invariants import verify_engine
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.models import exaone, preset
from agentcontrolplane_tpu.parallel.mesh import make_mesh
from agentcontrolplane_tpu.testing import greedy_reference


class NoStop(ByteTokenizer):
    stop_tokens = frozenset()


CFG = preset("exaone-tiny")
PAGE, WINDOW = 8, CFG.window
MAX_CTX = 256  # the engines' and the padded reference's
PARAMS = exaone.init_params(CFG, jax.random.key(0))
GREEDY = SamplingParams(temperature=0.0, max_tokens=24)


def stack_logits(params, tokens, config):
    """The family's plain forward pass, the stack's logits alone."""
    return exaone.forward(params, tokens, config)[0]


def reference(prompt, n):
    return greedy_reference(stack_logits, PARAMS, CFG, prompt, n, MAX_CTX)


def make_engine(**over):
    opts = dict(kv_layout="paged", page_size=PAGE, max_slots=4, max_ctx=MAX_CTX, prefill_buckets=(32, 64, 128),
                width_buckets=(4,), decode_block_size=4, tokenizer=NoStop(), check_invariants=True)
    eng = Engine(config=CFG, params=PARAMS, mesh=make_mesh({"tp": 1}, devices=jax.devices()[:1]), **{**opts, **over})
    eng.start()
    return eng


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=n).tolist() for n in lengths]


@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    yield eng
    eng.stop()


def test_greedy_tokens_with_the_drafter_are_the_references_and_the_drafter_is_counted(engine):
    """Six requests over four slots, under and past the 16-token window,
    budgets odd and even: the tokens are the full forward pass's, streamed
    as returned, and the device counted every step's draft."""
    ps = prompts(5, 12, 25, 40, 70, 100)
    budgets = (9, 30, 17, 40, 20, 33)
    streamed = [[] for _ in ps]
    futures = [engine.submit(p, SamplingParams(temperature=0.0, max_tokens=m), on_tokens=streamed[i].extend)
               for i, (p, m) in enumerate(zip(ps, budgets))]
    for p, m, f, st in zip(ps, budgets, futures, streamed):
        got = f.result(300)
        assert got.tokens == reference(p, m) and got.finish_reason == "length" and st == got.tokens
    st = engine.stats()
    d = st["drafter"]
    assert d["steps"] == engine.decode_steps and 0 < d["accepted"] <= d["proposed"]
    assert 1.0 <= d["tokens_per_step"] <= 2.0 and d["proposed"] <= d["tokens"] <= d["proposed"] + d["accepted"]
    # every token the decode blocks committed (each request's first is its prefill's) came from a verify step
    assert d["tokens"] == st["tokens_generated"] == sum(budgets) - 6
    assert st["window"]["pages_per_slot"] == WINDOW // PAGE + 1 and st["window"]["window_layers"] == CFG.n_window
    assert engine.cache["k"].shape[0] == CFG.n_full + 1 and st["moe"]["held"] == 16
    assert st["window"]["slots_holding"] == 0 and st["kv_pages"]["free"] == st["kv_pages"]["total"]
    assert verify_engine(engine) == []
    assert engine._jit_decode_paged.__wrapped__.__name__ == "decode_block"  # the name the trace readers match on
    assert engine._step_rows == 2 and engine._block_rows == 2 * 4 + 1


def test_a_budget_cuts_inside_a_step_and_the_host_and_device_agree(engine):
    """Budgets of 1..6 tokens: a kept draft's second token past the budget
    is not emitted, on the device and on the host alike (a later request in
    the same slot would read a wrong length otherwise)."""
    p = prompts(30, seed=5)[0]
    want = reference(p, 6)
    for m in range(1, 7):
        got = engine.submit(p, SamplingParams(temperature=0.0, max_tokens=m)).result(300)
        assert got.tokens == want[:m] and got.finish_reason == "length"
    assert verify_engine(engine) == []


def test_sampled_requests_keep_their_drafts_at_the_rate_of_the_overlap(engine):
    """Temperature 0.7 over 256 tokens of unit-variance logits: drafts are
    kept now and then (never at temperature 0 with random weights, where
    drafted and verified argmax disagree), every request runs to its budget,
    and a lane commits between one and two tokens a step."""
    before = engine.stats()["drafter"]
    futures = [engine.submit(p, SamplingParams(temperature=0.7, max_tokens=60)) for p in prompts(20, 33, 9, 50, seed=6)]
    assert [len(f.result(300).tokens) for f in futures] == [60] * 4
    after = engine.stats()["drafter"]
    put, kept = after["proposed"] - before["proposed"], after["accepted"] - before["accepted"]
    assert put > 100 and 0.05 < kept / put < 0.6
    assert verify_engine(engine) == []


def test_near_the_contexts_edge_the_tokens_are_still_the_references():
    """A context of 64: the engine keeps the prompt's tail that leaves the
    budget room, and the two-row steps run up to the last rows of the
    slot's table (a refused row past a committed one still has a page)."""
    eng = make_engine(max_ctx=64, prefill_buckets=(32, 64), max_slots=2, width_buckets=(2,))
    try:
        p = prompts(50, seed=8)[0]
        got = eng.submit(p, SamplingParams(temperature=0.0, max_tokens=40)).result(300)
        kept = p[len(p) - got.prompt_tokens:]
        assert got.finish_reason == "length" and got.prompt_tokens + len(got.tokens) >= 64 - 2
        assert got.tokens == greedy_reference(stack_logits, PARAMS, CFG, kept, len(got.tokens), 64)
        assert verify_engine(eng) == []
    finally:
        eng.stop()


@pytest.mark.parametrize("chunked", [False, True], ids=["spill", "prefill_chunk"])
def test_prompts_over_the_widest_bucket_go_through_continuations(chunked):
    """A 150-token prompt over buckets of at most 64: every continuation
    runs the pending MTP row of the chunk before it, so the first draft
    after the last chunk is the whole prompt's."""
    eng = make_engine(prefill_buckets=(32, 64), **({"prefill_chunk": 32} if chunked else {}))
    try:
        for p in prompts(150, 70, seed=3):
            assert eng.generate(p, GREEDY).tokens == reference(p, 24)
        assert verify_engine(eng) == []
    finally:
        eng.stop()


def test_preempt_and_resume_rebuild_ring_and_draft_and_reproduce_the_tokens():
    """An oversubscribed pool preempts; the resumed request's prefill writes
    its ring, its MTP pages and its pending row anew, in whichever slot it
    lands, and the tokens are the reference's."""
    eng = make_engine(kv_pages=30)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=40)
        ps = prompts(*[45] * 6, seed=1)
        with eng.hold_admission():
            futures = [eng.submit(p, sp) for p in ps]
        assert [f.result(300).tokens for f in futures] == [reference(p, 40) for p in ps]
        assert eng.preemptions >= 1
        assert eng.stats()["window"]["slots_holding"] == 0 and verify_engine(eng) == []
    finally:
        eng.stop()


REFUSED = {
    "slot-layout": (dict(kv_layout="slot"), "kv_layout='slot'"),
    "n-gram-speculation": (dict(spec_len=4), "the family drafts one row a step by itself"),
    "host-swap": (dict(host_kv_bytes=1 << 20), "host_kv_bytes > 0"),
    "int8-pages": (dict(quantize_kv=True), "quantize_kv"),
    "int8-weights": (dict(quantize="int8"), "weight-only int8"),
    "tensor-parallel": (dict(mesh=None), "tensor or context parallelism"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_each_option_the_family_does_not_serve_is_refused_in_words(case):
    over, words = REFUSED[case]
    opts = dict(config=CFG, params=PARAMS, kv_layout="paged", page_size=PAGE, max_slots=2, max_ctx=128,
                prefill_buckets=(32,), mesh=make_mesh({"tp": 1}, devices=jax.devices()[:1]))
    if case == "tensor-parallel":
        over = dict(mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match="the exaone family does not serve with") as e:
        Engine(**{**opts, **over})
    assert words in str(e.value)


def test_a_family_that_does_not_draft_keeps_its_one_token_block():
    """The other families' decode block is the one-token block it was: no
    new program, one row a step, a block's reservation its block size."""
    from agentcontrolplane_tpu.models import mellum

    cfg = preset("mellum-tiny")
    eng = Engine(config=cfg, params=mellum.init_params(cfg, jax.random.key(0)), kv_layout="paged", page_size=8,
                 max_slots=2, max_ctx=128, prefill_buckets=(32,), decode_block_size=4,
                 mesh=make_mesh({"tp": 1}, devices=jax.devices()[:1]))
    try:
        assert eng._step_rows == 1 and eng._block_rows == 4 and "drafter" not in eng.stats()
    finally:
        eng.stop()
