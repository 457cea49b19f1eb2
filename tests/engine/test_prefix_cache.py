"""Prefix KV cache: hits must be bit-identical to cold prefills.

Agent workloads re-send growing conversations with identical system
prompts; the engine snapshots prefix KV at bucket boundaries and, on a hit,
copies it into the slot and runs only the suffix (models/llama.py
prefill_continue)."""

import dataclasses

import pytest

import jax

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.models import preset
from agentcontrolplane_tpu.models.llama import PRESETS
from agentcontrolplane_tpu.parallel.mesh import make_mesh

CFG = dataclasses.replace(
    PRESETS["tiny"], vocab_size=512, max_seq_len=512, n_kv_heads=2
)


def _engine(prefix_entries: int) -> Engine:
    eng = Engine(
        config=CFG,
        tokenizer=ByteTokenizer(),
        mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]),
        max_slots=4,
        max_ctx=256,
        prefill_buckets=(64, 128, 256),
        decode_block_size=4,
        prefix_cache_entries=prefix_entries,
        seed=0,
    )
    eng.start()
    return eng


SYSTEM = "you are an agent with tools. " * 4  # > smallest bucket (64 bytes)


def test_hit_results_match_cold_engine():
    greedy = SamplingParams(temperature=0.0, max_tokens=12)
    cached = _engine(prefix_entries=4)
    cold = _engine(prefix_entries=0)
    try:
        prompts = [SYSTEM + "turn one", SYSTEM + "turn one plus more text"]
        # first generation seeds the cache; the second must hit it
        out_cached = [cached.generate(p, greedy).tokens for p in prompts]
        assert cached.stats()["prefix_cache"]["entries"] >= 1
        hits_before = cached.stats()["prefix_cache"]["hits"]
        out_cached.append(cached.generate(prompts[1], greedy).tokens)
        assert cached.stats()["prefix_cache"]["hits"] > hits_before

        out_cold = [cold.generate(p, greedy).tokens for p in prompts]
        out_cold.append(cold.generate(prompts[1], greedy).tokens)
        assert out_cached == out_cold
    finally:
        cached.stop()
        cold.stop()


def test_growing_conversation_reuses_prefix():
    """Multi-turn shape: each prompt extends the previous one (conversation
    re-sent in full). Later turns must hit and stay correct."""
    greedy = SamplingParams(temperature=0.0, max_tokens=8)
    cached = _engine(prefix_entries=4)
    cold = _engine(prefix_entries=0)
    try:
        convo = SYSTEM
        for turn in range(3):
            convo += f" user says thing {turn}. assistant replies."
            a = cached.generate(convo, greedy).tokens
            b = cold.generate(convo, greedy).tokens
            assert a == b, f"turn {turn} diverged under prefix caching"
        assert cached.stats()["prefix_cache"]["hits"] >= 1
    finally:
        cached.stop()
        cold.stop()


def test_forced_prefix_and_json_through_cache_hit():
    """tool_choice forcing + grammar must survive the hit path (constraint
    state is seeded past the forced prefix regardless of where the KV came
    from)."""
    import json

    prefix = tuple(ByteTokenizer().encode('{"name": "t", "arguments": {'))
    sp = SamplingParams(temperature=1.1, max_tokens=24, json_only=True, forced_prefix=prefix)
    eng = _engine(prefix_entries=4)
    try:
        r1 = eng.generate(SYSTEM + "call it", sp)
        r2 = eng.generate(SYSTEM + "call it", sp)  # hit
        assert eng.stats()["prefix_cache"]["hits"] >= 1
        for r in (r1, r2):
            obj = json.loads(r.text)
            assert obj["name"] == "t"
    finally:
        eng.stop()


def test_concurrent_mixed_hits_and_misses():
    greedy = SamplingParams(temperature=0.0, max_tokens=8)
    eng = _engine(prefix_entries=4)
    try:
        eng.generate(SYSTEM + "seed", greedy)  # seeds the SYSTEM prefix
        prompts = [SYSTEM + f"variant {i}" for i in range(3)] + ["totally different"]
        solo = [eng.generate(p, greedy).tokens for p in prompts]
        futs = [eng.submit(p, greedy) for p in prompts]
        burst = [f.result(timeout=300).tokens for f in futs]
        assert burst == solo
    finally:
        eng.stop()


def test_chunked_prefill_long_prompt():
    """Prompts longer than the largest prefill bucket run as several
    bounded continuation dispatches; greedy results must equal an engine
    whose buckets cover the prompt in one shot."""
    greedy = SamplingParams(temperature=0.0, max_tokens=8)
    small_buckets = Engine(
        config=CFG, tokenizer=ByteTokenizer(),
        mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]),
        max_slots=2, max_ctx=512, prefill_buckets=(64,),  # force chunking
        decode_block_size=4, prefix_cache_entries=0, seed=0,
    )
    big_buckets = Engine(
        config=CFG, tokenizer=ByteTokenizer(),
        mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]),
        max_slots=2, max_ctx=512, prefill_buckets=(64, 256),
        decode_block_size=4, prefix_cache_entries=0, seed=0,
    )
    small_buckets.start()
    big_buckets.start()
    try:
        prompt = "a long conversation transcript. " * 7  # ~220 tokens
        a = small_buckets.generate(prompt, greedy).tokens
        b = big_buckets.generate(prompt, greedy).tokens
        assert a == b
        # and chunking composes with the prefix cache
        cached = Engine(
            config=CFG, tokenizer=ByteTokenizer(),
            mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]),
            max_slots=2, max_ctx=512, prefill_buckets=(64,),
            decode_block_size=4, prefix_cache_entries=4, seed=0,
        )
        cached.start()
        try:
            c1 = cached.generate(prompt, greedy).tokens
            c2 = cached.generate(prompt + " more", greedy).tokens
            assert c1 == a
            assert cached.stats()["prefix_cache"]["hits"] >= 1
            assert c2 == big_buckets.generate(prompt + " more", greedy).tokens
        finally:
            cached.stop()
    finally:
        small_buckets.stop()
        big_buckets.stop()


# the pool whose pages an entry shares: a `k` and a `v` (the dense family on a
# tp=2 mesh), or the one leaf of a latent pool (models/kanana.py, held whole):
# an entry is page ids and refcounts, and names no leaf
POOLS = {
    "k_and_v": lambda: (CFG, make_mesh({"tp": 2}, devices=jax.devices()[:2])),
    "one_leaf": lambda: (preset("kanana-tiny"), make_mesh({"tp": 1}, devices=jax.devices()[:1])),
}
pools = pytest.mark.parametrize("pool", list(POOLS))


def _paged_engine(prefix_entries: int, pool: str = "k_and_v") -> Engine:
    config, mesh = POOLS[pool]()
    eng = Engine(
        config=config,
        tokenizer=ByteTokenizer(),
        mesh=mesh,
        max_slots=4,
        max_ctx=256,
        prefill_buckets=(64, 128, 256),
        decode_block_size=4,
        kv_layout="paged",
        page_size=16,
        prefix_cache_entries=prefix_entries,
        seed=0,
    )
    eng.start()
    return eng


@pools
def test_paged_hit_results_match_cold_engine(pool):
    """Paged layout shares prefix PAGES zero-copy (refcounted block-table
    references); hits must still be bit-identical to cold prefills."""
    greedy = SamplingParams(temperature=0.0, max_tokens=10)
    cached = _paged_engine(prefix_entries=4, pool=pool)
    cold = _paged_engine(prefix_entries=0, pool=pool)
    try:
        prompts = [SYSTEM + "turn one", SYSTEM + "turn one and then some"]
        out_cached = [cached.generate(p, greedy).tokens for p in prompts]
        assert cached.stats()["prefix_cache"]["entries"] >= 1
        assert cached.stats()["prefix_cache"]["hits"] >= 1  # prompt 2 reused prompt 1's pages
        out_cold = [cold.generate(p, greedy).tokens for p in prompts]
        assert out_cached == out_cold
    finally:
        cached.stop()
        cold.stop()


@pools
def test_paged_prefix_page_refcounts_conserved(pool):
    """Page accounting: after all requests drain, the only pages still out
    are exactly the cached entries' shared pages; disabling the cache (0
    entries) returns the pool to full."""
    greedy = SamplingParams(temperature=0.0, max_tokens=6)
    eng = _paged_engine(prefix_entries=2, pool=pool)
    initial_free = eng._allocator.free_count
    try:
        for i in range(5):  # several prompts; entries capped at 2 (LRU evicts)
            eng.generate(SYSTEM + f"variant {i}", greedy)
        import time as _time

        deadline = _time.monotonic() + 30
        while _time.monotonic() < deadline and eng.stats()["active_slots"]:
            _time.sleep(0.05)
        held = sum(
            len(e["pages"]) for e in eng._prefix_cache.values() if "pages" in e
        )
        assert held > 0
        assert eng._allocator.free_count == initial_free - held
        # evict everything (simulate) and the pool must be whole again
        with eng._prefix_lock:
            while eng._prefix_cache:
                _, old = eng._prefix_cache.popitem(last=False)
                eng._allocator.free(old["pages"])
        assert eng._allocator.free_count == initial_free
    finally:
        eng.stop()


@pools
def test_paged_entry_eviction_while_borrower_active_is_safe(pool):
    """An entry evicted while a sequence still references its pages must not
    free them out from under the borrower (refcounts): the borrower's
    output is unaffected and pages return only when it finishes."""
    eng = _paged_engine(prefix_entries=1, pool=pool)  # capacity 1: next save evicts
    cold = _paged_engine(prefix_entries=0, pool=pool)
    try:
        seed_prompt = SYSTEM + "base"
        eng.generate(seed_prompt, SamplingParams(temperature=0.0, max_tokens=4))
        # borrower: long generation that HITS the entry and keeps running
        borrower = eng.submit(
            seed_prompt + " extended turn", SamplingParams(temperature=0.0, max_tokens=48)
        )
        # a different prompt's save evicts the (capacity-1) entry mid-flight
        eng.generate("completely different " * 10, SamplingParams(temperature=0.0, max_tokens=4))
        got = borrower.result(timeout=120).tokens
        want = cold.generate(
            seed_prompt + " extended turn", SamplingParams(temperature=0.0, max_tokens=48)
        ).tokens
        assert got == want
    finally:
        eng.stop()
        cold.stop()


def test_paged_chunked_prefill_long_prompt():
    """Paged layout no longer requires buckets to reach max_ctx: long
    prompts spill through the paged continuation program; greedy equality
    vs a single-shot paged engine, and composes with the paged prefix
    cache."""
    greedy = SamplingParams(temperature=0.0, max_tokens=8)

    def paged(buckets, entries):
        e = Engine(
            config=CFG, tokenizer=ByteTokenizer(),
            mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]),
            max_slots=2, max_ctx=512, prefill_buckets=buckets,
            decode_block_size=4, kv_layout="paged", page_size=16,
            prefix_cache_entries=entries, seed=0,
        )
        e.start()
        return e

    small = paged((64,), 0)  # forces chunking
    big = paged((64, 512), 0)
    try:
        prompt = "a long paged conversation transcript. " * 7  # ~260 tokens
        a = small.generate(prompt, greedy).tokens
        b = big.generate(prompt, greedy).tokens
        assert a == b
        cached = paged((64,), 4)
        try:
            c1 = cached.generate(prompt, greedy).tokens
            c2 = cached.generate(prompt + " more", greedy).tokens
            assert c1 == a
            assert cached.stats()["prefix_cache"]["hits"] >= 1
            assert c2 == big.generate(prompt + " more", greedy).tokens
        finally:
            cached.stop()
    finally:
        small.stop()
        big.stop()
