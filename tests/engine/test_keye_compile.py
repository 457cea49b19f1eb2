"""The chip's compiler, here, for the keye family (`tests/engine/test_chip_compile.py`
has the other families' cases and the described-v5e fixture these use; a file
of their own, as `test_exaone_compile.py` is, so that neither file runs over
its budget): every program the cell `keyevl2-ep8-decode-sparse-long` runs
compiles for a described v5e at the cell's sizes: the engine's decode block at
16 lanes of 26,624 tokens, the prefill's attention kernel, the engine's own
prefill at both buckets with the mask's search once a tier, and the
continuation the prewarm runs."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.engine.test_chip_compile import PAGE, _computations, _ops, _resident, v5e  # noqa: F401 (v5e: the fixture)

# -- the keye family: rows chosen by a learned indexer, the pool's third leaf -------------------------------

_KEYE_SLOTS, _KEYE_PAGES, _KEYE_CTX = 16, 26625, 26624  # acpbench/configs/keye-vl2-30b-a3b-bf16-v5e1-ep8.json


def _keye(v5e, monkeypatch):
    """The benchmark's cut of the published config (8 layers, 16 of 128
    experts, an eighth of the vocabulary), abstract weights and the pool of
    two leaves placed on one described chip, the expert layer steered onto
    its kernel."""
    import dataclasses
    import functools

    from agentcontrolplane_tpu.models import experts, keye

    # the expert layer is `experts.routed_ff`: steered there
    monkeypatch.setattr(experts, "routed_experts", functools.partial(experts.routed_experts, kernel=True))
    c = dataclasses.replace(keye.PRESETS["keye-vl-2.0-30b-a3b"], n_layers=8, vocab_size=18992,
                            experts_held=tuple(range(16)))
    one_chip = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: keye.init_params(c, jax.random.key(0))))
    cache = place(jax.eval_shape(lambda: keye.init_paged_cache(c, _KEYE_PAGES, PAGE, max_slots=_KEYE_SLOTS)))
    vec = lambda *shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    return keye, c, params, cache, vec


def test_keye_decode_block_chooses_rows_and_copies_no_pool(v5e, monkeypatch):
    """The ENGINE's decode block (`make_decode_block` around the family's
    step through `models.programs`) at the cell's 16 lanes of 26,624 tokens:
    the resident set is the issue's arithmetic (1.71 GB of weights, 7.85 GB
    of K|V rows and the indexer's keys stored a lane tile wide), the pool is
    donated and no op copies a leaf of it (a layer's whole `ik` is 109 MB,
    `kv` 1.74 GB), a layer's chosen rows are one gather of 32-bit words, the block's temporaries (16 lanes' gathered index keys
    and chosen rows) a small fraction of it, and the choice is the kernel
    `index_select` (PR 62: a threshold over a lane's 26,624 scores in VMEM,
    two lane groups of eight): no `sort` and no `top_k` is left."""
    import re

    from agentcontrolplane_tpu import models
    from agentcontrolplane_tpu.engine import engine
    from agentcontrolplane_tpu.engine.lanes import DECODE

    keye, c, params, cache, vec = _keye(v5e, monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the step chooses its choice's kernel by the backend
    prog = models.programs(c)
    block = engine.make_decode_block(
        lambda p, pages, tokens, seq_lens, active, tables: prog.decode_step_paged(
            p, pages, tokens, seq_lens, tables, active, c, use_pallas=True),
        (), _KEYE_CTX, 16)
    key = jax.eval_shape(lambda: jax.random.key(0))
    S = _KEYE_SLOTS
    compiled = jax.jit(block, donate_argnums=(1, 2)).lower(
        params, cache, vec(len(DECODE.kinds), S), vec(*key.shape, dt=key.dtype), vec(1, 256), vec(1),
        vec(S, _KEYE_CTX // PAGE)).compile()
    text = compiled.as_text()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    pool = sum(cache[name].size * cache[name].dtype.itemsize for name in ("kv", "ik"))
    assert abs(weights - 1.71e9) < 0.01e9 and abs(pool - 7.85e9) < 0.01e9
    assert (cache["kv"].shape, cache["kv"].dtype) == ((8, _KEYE_PAGES, PAGE, 512), jnp.uint32)
    assert cache["ik"].shape == (8, _KEYE_PAGES, PAGE, 128)
    # the chosen rows of a layer are ONE gather of 16 lanes x 2,048 rows of 512 words (K | V), none of bfloat16 rows
    gathers = re.findall(r"= (\w+)\[(?:32768|16,2048),(\d+)\]\S* gather\(", text)
    assert gathers == [("u32", "512")], gathers
    for leaf in ("u32[8,{},16,512]", "bf16[8,{},16,128]"):
        assert not re.search(rf"= {re.escape(leaf.format(_KEYE_PAGES))}\S* copy\(", text), f"a copy of the leaf {leaf}"
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < pool // 20, f"temporaries {mem.temp_size_in_bytes / 1e6:.0f} MB"
    assert "moe_gmm" in text and "index_select" in text and "sparse_walk" in text
    assert len(re.findall(r'custom_call_target="tpu_custom_call"[^\n]*index_select', text)) >= 1
    assert not [line for line in text.splitlines() if "index_select" in line and (" sort(" in line or "top_k" in line)]
    assert 0.55 * 16e9 < _resident(compiled) < 0.65 * 16e9, f"{_resident(compiled) / 1e9:.2f} GB"


def test_keye_prefill_attention_kernel_compiles_at_the_cells_buckets(v5e):
    """`ops/pallas/masked_attention.py` through the chip's compiler at the
    cell's widest bucket (24,576 rows, 32 query heads over 4 KV heads of 128,
    the `[T, T]` int8 mask): Mosaic takes the int8 mask tile, the lane slice
    of the running maximum and the index maps that repeat the diagonal's
    block; the kernel states no VMEM limit and its temporaries are the
    heads-first copies of q and the result."""
    from agentcontrolplane_tpu.ops.pallas import masked_attention as ma

    one_chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    T, H, H_kv, d = 24576, 32, 4, 128
    compiled = jax.jit(ma.masked_attention).lower(
        sds((T, H, d), jnp.bfloat16), sds((T, H_kv, d), jnp.bfloat16), sds((T, H_kv, d), jnp.bfloat16),
        sds((T, T), jnp.int8)).compile()
    text = compiled.as_text()
    assert "masked_prefill_attention" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 * T * H * d * 2


@pytest.mark.parametrize("tokens, tiers", [(16384, 4), (24576, 6)])
def test_keye_prefill_compiles_under_the_engines_own_sampler_with_one_mask_body_a_tier(v5e, monkeypatch, tokens, tiers):
    """The ENGINE's prefill program (`prefill_and_sample` around the family's
    prefill through `models.programs`) at the cell's two buckets, one row:
    it fits beside the resident set (the `[T, T]` mask of a layer, 604 MB at
    24,576 rows, and a block's float32 scores are its temporaries), holds
    the attention kernel once (one layer body), and holds the mask's
    threshold search once a TIER of 4,096 rows (`keye.prompt_mask`: a
    `lax.map` over a tier's blocks), not once a block of query rows: the
    unrolled blocks were 25.8 s of this sandbox's compiler at 24,576 rows
    where the tiers are 13.4 (PERF.md, PR 59)."""
    from agentcontrolplane_tpu import models
    from agentcontrolplane_tpu.engine import engine
    from agentcontrolplane_tpu.engine.lanes import PREFILL

    import re

    keye, c, params, cache, vec = _keye(v5e, monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the prefill chooses its attention by the backend
    prog = models.programs(c)

    def prefill_and_sample(params, pages, toks, lanes, page_ids, key, table, min_close):
        ln = PREFILL.unpack(lanes)
        pages, logits = prog.prefill_paged_batch(params, pages, toks, ln["lengths"], page_ids, c)
        out, states = engine.sample_lanes(logits, key, ln, table, min_close)
        return pages, out, states

    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = jax.jit(prefill_and_sample, donate_argnums=(1,)).lower(
        params, cache, vec(1, tokens), vec(len(PREFILL.kinds), 1), vec(1, tokens // PAGE),
        vec(*key.shape, dt=key.dtype), vec(1, 256), vec(1)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"custom-call\(.*masked_prefill_attention", text)) == 1
    searches = {name for name, lines in _computations(text).items()
                if any(op == "while" and "index_select" in rest for _, _, op, rest in _ops(lines))}
    assert len(searches) == tiers, sorted(searches)
    assert keye.MASK_TIER * tiers == tokens
    assert _resident(compiled) < 15e9, f"{_resident(compiled) / 1e9:.2f} GB"


def test_keye_continuation_compiles_at_the_spills_rows_over_a_full_table(v5e, monkeypatch):
    """`prefill_paged_continue` at the 16,384-row continuation the engine's
    prewarm runs (a resumed request's tail over a slot's whole table of
    26,624 rows): the `kv` pages and the indexer's keys gathered, the queries' choice
    in blocks of 512 rows, the attention under it folded 2,048 keys at a
    time; it fits beside the resident set."""
    keye, c, params, cache, vec = _keye(v5e, monkeypatch)
    T = 16384
    compiled = jax.jit(
        lambda p, ca, toks, n, starts, ids, tables: keye.prefill_paged_continue(p, ca, toks, n, starts, ids, tables, c),
        donate_argnums=(1,),
    ).lower(params, cache, vec(1, T), vec(1), vec(1), vec(1, T // PAGE), vec(1, _KEYE_CTX // PAGE)).compile()
    assert "index_select" in compiled.as_text()
    assert _resident(compiled) < 15e9, f"{_resident(compiled) / 1e9:.2f} GB"
