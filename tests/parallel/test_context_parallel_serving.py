"""Context-parallel serving: the slot KV cache's ctx dim sharded over the
mesh's 'sp' axis (kv_cache_specs). No model-code change — XLA GSPMD turns
the decode/prefill softmax reductions over the sharded dim into per-shard
flash partials merged by [S, H_kv]-sized all-reduces. These tests pin
(a) numerics vs the replicated cache, (b) the compiled HLO containing NO
all-gather (the failure mode where GSPMD materializes the cache on every
rank), and (c) the full Engine producing identical greedy generations on
an sp x tp mesh vs tp-only.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
from agentcontrolplane_tpu.models.llama import (
    PRESETS,
    decode_step,
    init_kv_cache,
    init_params,
)
from agentcontrolplane_tpu.parallel.mesh import (
    kv_cache_shardings,
    make_mesh,
    param_shardings,
)

TINY = dataclasses.replace(PRESETS["tiny"], max_seq_len=256)


def test_decode_step_ctx_sharded_matches_replicated_and_no_allgather():
    cfg = TINY
    S, C = 8, 256
    mesh = make_mesh({"sp": 4, "tp": 2})
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    shape = init_kv_cache(cfg, S, C)["k"].shape
    cache = {
        "k": jnp.asarray(rng.normal(size=shape), dtype=cfg.dtype),
        "v": jnp.asarray(rng.normal(size=shape), dtype=cfg.dtype),
    }
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(S,)), dtype=jnp.int32)
    seq_lens = jnp.asarray(rng.integers(1, C - 1, size=(S,)), dtype=jnp.int32)

    ref_cache, ref_logits = jax.jit(
        lambda p, c, t, s: decode_step(p, c, t, s, cfg)
    )(params, cache, tokens, seq_lens)

    cp_shard = kv_cache_shardings(mesh)
    assert cp_shard["k"].spec == P(None, None, "sp", "tp", None)
    p_shard = param_shardings(mesh, cfg, params)
    rep = NamedSharding(mesh, P())
    step = jax.jit(
        lambda p, c, t, s: decode_step(p, c, t, s, cfg),
        in_shardings=(p_shard, cp_shard, rep, rep),
        out_shardings=(cp_shard, rep),
    )
    params_cp = jax.device_put(params, p_shard)
    cache_cp = {k: jax.device_put(cache[k], cp_shard[k]) for k in cache}
    compiled = step.lower(params_cp, cache_cp, tokens, seq_lens).compile()
    out_cache, out_logits = step(params_cp, cache_cp, tokens, seq_lens)

    np.testing.assert_allclose(
        np.asarray(out_logits), np.asarray(ref_logits), rtol=2e-2, atol=2e-2
    )
    np.testing.assert_allclose(
        np.asarray(out_cache["k"], dtype=np.float32),
        np.asarray(ref_cache["k"], dtype=np.float32),
        rtol=1e-3, atol=1e-4,
    )
    # the whole point: the sharded-softmax merge, not a cache all-gather.
    # The only acceptable gather is the [S, vocab] logits replication at
    # the root (out_shardings=replicated) — tiny. Anything within an order
    # of magnitude of the cache means GSPMD materialized it on every rank.
    import re

    cache_elems = int(np.prod(shape))
    for line in compiled.as_text().splitlines():
        if "all-gather" not in line:
            continue
        dims = re.search(r"\[([0-9,]+)\]", line)
        assert dims is not None, line
        elems = int(np.prod([int(x) for x in dims.group(1).split(",")]))
        assert elems < cache_elems // 16, f"cache-sized all-gather: {line.strip()[:160]}"


def _greedy_workload(eng: Engine) -> list[list[int]]:
    eng.start()
    try:
        futs = [
            eng.submit(
                [1 + i] * (24 + 5 * i),
                SamplingParams(temperature=0.0, max_tokens=16 + 2 * i),
            )
            for i in range(4)
        ]
        first = [f.result(timeout=300).tokens for f in futs]
        # second turn: extended prompts re-enter through the prefix cache /
        # continuation prefill against the sharded cache
        futs = [
            eng.submit(
                [1 + i] * (24 + 5 * i) + first[i][:4] + [2],
                SamplingParams(temperature=0.0, max_tokens=8),
            )
            for i in range(4)
        ]
        return first + [f.result(timeout=300).tokens for f in futs]
    finally:
        eng.stop()


def test_engine_sp_mesh_matches_tp_only():
    def build(mesh):
        return Engine(
            config=TINY,
            tokenizer=ByteTokenizer(),
            max_slots=4,
            max_ctx=256,
            prefill_buckets=(32, 64),
            decode_block_size=4,
            seed=0,
            mesh=mesh,
        )

    ref = _greedy_workload(build(make_mesh({"tp": 2}, devices=jax.devices()[:2])))
    cp = _greedy_workload(build(make_mesh({"sp": 4, "tp": 2})))
    assert cp == ref
    assert all(len(t) > 0 for t in ref)


def test_engine_rejects_bad_cp_configs():
    with pytest.raises(ValueError, match="context-parallel paged"):
        # sp must divide the page size (each rank holds a page slice)
        Engine(
            config=TINY, tokenizer=ByteTokenizer(), max_slots=2, max_ctx=256,
            kv_layout="paged", page_size=2, mesh=make_mesh({"sp": 4, "tp": 2}),
        )
    with pytest.raises(ValueError, match="divisible"):
        Engine(
            config=TINY, tokenizer=ByteTokenizer(), max_slots=2, max_ctx=254,
            mesh=make_mesh({"sp": 4, "tp": 2}),
        )


# -- paged + context parallelism (VERDICT r3 weak #4) ------------------------


def test_decode_step_paged_sp_sharded_matches_replicated_and_no_allgather():
    """The paged pools shard their WITHIN-PAGE dim over sp; decode must
    (a) match the replicated result and (b) compile with no pool-sized
    all-gather — prefix-page sharing composes with long-context sharding."""
    from agentcontrolplane_tpu.models.llama import decode_step_paged, init_paged_cache
    from agentcontrolplane_tpu.ops.paged import TRASH_PAGE

    cfg = TINY
    S, page_size, num_pages = 4, 16, 33
    max_pages = 256 // page_size
    mesh = make_mesh({"sp": 4, "tp": 2})
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    shape = init_paged_cache(cfg, num_pages, page_size)["k"].shape
    pages = {
        "k": jnp.asarray(rng.normal(size=shape), dtype=cfg.dtype),
        "v": jnp.asarray(rng.normal(size=shape), dtype=cfg.dtype),
    }
    tables = np.full((S, max_pages), TRASH_PAGE, dtype=np.int32)
    seq_lens = np.asarray([30, 7, 64, 45], dtype=np.int32)
    nxt = 1
    for s in range(S):
        for i in range(-(-int(seq_lens[s] + 1) // page_size)):
            tables[s, i] = nxt
            nxt += 1
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(S,)), dtype=jnp.int32)
    tables = jnp.asarray(tables)
    seq_lens_j = jnp.asarray(seq_lens)
    active = jnp.ones((S,), dtype=bool)

    fn = lambda p, pg, t, s, bt, a: decode_step_paged(p, pg, t, s, bt, a, cfg)
    ref_pages, ref_logits = jax.jit(fn)(
        params, pages, tokens, seq_lens_j, tables, active
    )

    assert len(shape) == 4  # [L, pages, P, H_kv * d]: the row's heads over tp, a page's rows over sp
    page_spec = NamedSharding(mesh, P(None, None, "sp", "tp"))
    pg_shard = {"k": page_spec, "v": page_spec}
    p_shard = param_shardings(mesh, cfg, params)
    rep = NamedSharding(mesh, P())
    step = jax.jit(
        fn,
        in_shardings=(p_shard, pg_shard, rep, rep, rep, rep),
        out_shardings=(pg_shard, rep),
    )
    pages_cp = {k: jax.device_put(pages[k], page_spec) for k in pages}
    params_cp = jax.device_put(params, p_shard)
    compiled = step.lower(
        params_cp, pages_cp, tokens, seq_lens_j, tables, active
    ).compile()
    out_pages, out_logits = step(params_cp, pages_cp, tokens, seq_lens_j, tables, active)

    np.testing.assert_allclose(
        np.asarray(out_logits), np.asarray(ref_logits), rtol=2e-2, atol=2e-2
    )
    np.testing.assert_allclose(
        np.asarray(out_pages["k"], dtype=np.float32),
        np.asarray(ref_pages["k"], dtype=np.float32),
        rtol=1e-3, atol=1e-4,
    )
    import re

    pool_elems = int(np.prod(shape))
    for line in compiled.as_text().splitlines():
        if "all-gather" not in line:
            continue
        dims = re.search(r"\[([0-9,]+)\]", line)
        assert dims is not None, line
        elems = int(np.prod([int(x) for x in dims.group(1).split(",")]))
        assert elems < pool_elems // 16, f"pool-sized all-gather: {line.strip()[:160]}"


def test_engine_paged_sp_mesh_matches_tp_only():
    """Full engine on an sp x tp mesh with PAGED KV (prefix cache on):
    greedy generations identical to the tp-only paged engine — including
    second-turn prompts that re-enter through shared prefix pages."""

    def build(mesh):
        return Engine(
            config=TINY,
            tokenizer=ByteTokenizer(),
            max_slots=4,
            max_ctx=256,
            prefill_buckets=(32, 64),
            decode_block_size=4,
            kv_layout="paged",
            page_size=16,
            seed=0,
            mesh=mesh,
        )

    ref = _greedy_workload(build(make_mesh({"tp": 2}, devices=jax.devices()[:2])))
    cp = _greedy_workload(build(make_mesh({"sp": 4, "tp": 2})))
    assert cp == ref
    assert all(len(t) > 0 for t in ref)
