"""The chip's compiler, here, for the dots family (`tests/engine/test_chip_compile.py`
has the other families' cases and the described-v5e fixture these use; a file
of its own, as `test_keye_compile.py` is): every program the cell
`dots3-ep16-decode-sparse-latent` runs compiles for a described v5e at the
cell's sizes: the engine's decode block at 32 lanes of 20,480 tokens, the
prefill's attention kernel at keys of 256 beside values of 128, the engine's
own prefill at the widest bucket, and the continuation the prewarm runs."""

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from tests.engine.test_chip_compile import PAGE, _resident, v5e  # noqa: F401 (v5e: the fixture)

_SLOTS, _PAGES, _CTX = 32, 40961, 20480  # acpbench/configs/dots3-note-prev-bf16-v5e1-ep16.json


def _dots(v5e, monkeypatch):
    """The benchmark's cut of the published config (5 of 46 layers, 16 of 256
    experts, an eighth of the vocabulary), abstract weights and the pool of
    three leaves placed on one described chip, the expert layer steered onto
    its kernel."""
    import dataclasses
    import functools

    from agentcontrolplane_tpu.models import dots, experts

    # the expert layer is `experts.routed_ff`: steered there
    monkeypatch.setattr(experts, "routed_experts", functools.partial(experts.routed_experts, kernel=True))
    c = dataclasses.replace(dots.PRESETS["dots3-note-prev"], layer_types=dots._pattern(5), vocab_size=19008,
                            experts_held=tuple(range(16)))
    one_chip = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: dots.init_params(c, jax.random.key(0))))
    cache = place(jax.eval_shape(lambda: dots.init_paged_cache(c, _PAGES, PAGE, max_slots=_SLOTS)))
    vec = lambda *shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    return dots, c, params, cache, vec


def test_dots_decode_block_chooses_latent_rows_and_copies_no_pool(v5e, monkeypatch):
    """The ENGINE's decode block (`make_decode_block` around the family's
    step through `models.programs`) at the cell's 32 lanes of 20,480 tokens:
    the resident set is the issue's arithmetic (5.15 GB of weights, 2.01 GB
    of latent rows and indexer keys on the page list, 0.12 GB of rings), the
    pool is donated and no op copies a leaf of it, a full layer's chosen rows
    are one gather of 640-wide latent rows, the leaves' names are in the
    program's text, and the choice is the kernel `index_select` at 64 heads'
    scores of 20,480 columns, four lanes groups of eight, beside what the
    block keeps (PR 62): no `sort` and no `top_k` of the context is left."""
    import re

    from agentcontrolplane_tpu import models
    from agentcontrolplane_tpu.engine import engine
    from agentcontrolplane_tpu.engine.lanes import DECODE

    dots, c, params, cache, vec = _dots(v5e, monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the step chooses its choice's kernel by the backend
    prog = models.programs(c)
    block = engine.make_decode_block(
        lambda p, pages, tokens, seq_lens, active, tables: prog.decode_step_paged(
            p, pages, tokens, seq_lens, tables, active, c, use_pallas=False),
        (), _CTX, 16)
    key = jax.eval_shape(lambda: jax.random.key(0))
    S = _SLOTS
    compiled = jax.jit(block, donate_argnums=(1, 2)).lower(
        params, cache, vec(len(DECODE.kinds), S), vec(*key.shape, dt=key.dtype), vec(1, 256), vec(1),
        vec(S, _CTX // PAGE)).compile()
    text = compiled.as_text()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    paged = sum(cache[name].size * cache[name].dtype.itemsize for name in ("kv", "ik"))
    rings = cache["wkv"].size * 2
    assert abs(weights - 5.15e9) < 0.01e9 and abs(paged - 2.01e9) < 0.01e9 and abs(rings - 0.124e9) < 0.002e9
    assert cache["kv"].shape == (2, _PAGES, PAGE, 640) and cache["ik"].shape == (2, _PAGES, PAGE, 128)
    assert cache["wkv"].shape == (3, (S + 1) * 34, PAGE, 1152)
    gathers = re.findall(r"= (\w+)\[(?:65536|32,2048),(\d+)\]\S* gather\(", text)
    assert ("bf16", "640") in gathers, gathers
    for leaf in (f"bf16[2,{_PAGES},16,640]", f"bf16[2,{_PAGES},16,128]", f"bf16[3,{(S + 1) * 34},16,1152]"):
        assert not re.search(rf"= {re.escape(leaf)}\S* copy\(", text), f"a copy of the leaf {leaf}"
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= paged + rings
    assert mem.temp_size_in_bytes < 1.5e9, f"temporaries {mem.temp_size_in_bytes / 1e6:.0f} MB"
    for name in ("moe_gmm", "index_select", "sparse_latent", "ring_latent", "attn_gate", "mla_absorb"):
        assert name in text, name
    assert len(re.findall(r'custom_call_target="tpu_custom_call"[^\n]*index_select', text)) >= 1
    assert not [line for line in text.splitlines() if "index_select" in line and (" sort(" in line or "top_k" in line)]
    assert 0.42 * 16e9 < _resident(compiled) < 0.60 * 16e9, f"{_resident(compiled) / 1e9:.2f} GB"


def test_the_masked_kernel_compiles_at_keys_of_256_beside_values_of_128(v5e):
    """`ops/pallas/masked_attention.py` through the chip's compiler at the
    cell's widest bucket and a group of this family's heads: 16,384 rows, 32
    heads each its own KV head, keys and queries of 192 padded to 256,
    values of 128, the scale the 192's."""
    from agentcontrolplane_tpu.ops.pallas import masked_attention as ma

    one_chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    T, H, dk, dv = 16384, 32, 256, 128
    assert ma.serves(T, dk, dv) and not ma.serves(T, 192, dv) and not ma.serves(T + 8, dk, dv)
    compiled = jax.jit(lambda q, k, v, m: ma.masked_attention(q, k, v, m, scale=192 ** -0.5)).lower(
        sds((T, H, dk), jnp.bfloat16), sds((T, H, dk), jnp.bfloat16), sds((T, H, dv), jnp.bfloat16),
        sds((T, T), jnp.int8)).compile()
    text = compiled.as_text()
    assert "masked_prefill_attention" in text and "tpu_custom_call" in text
    assert f"bf16[{H},1,{T},{dv}]" in text  # the result a head: values' width, not the keys'


def test_dots_prefill_compiles_under_the_engines_own_sampler_and_fits(v5e, monkeypatch):
    """The ENGINE's prefill program (`prefill_and_sample` around the family's
    prefill through `models.programs`, which hands the lanes' slots beside the
    page ids) at the cell's widest bucket, one row: it fits beside the
    resident set (the `[T, T]` mask of a full layer, a head group's expanded K
    and V and a block's float32 scores are its temporaries) and holds the
    attention kernel once a kind of full layer."""
    import re

    from agentcontrolplane_tpu import models
    from agentcontrolplane_tpu.engine import engine
    from agentcontrolplane_tpu.engine.lanes import PREFILL

    dots, c, params, cache, vec = _dots(v5e, monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the prefill chooses its attention by the backend
    prog = models.programs(c)
    tokens = 16384

    def prefill_and_sample(params, pages, toks, lanes, page_ids, key, table, min_close):
        ln = PREFILL.unpack(lanes)
        pages, logits = prog.prefill_paged_batch(params, pages, toks, ln["lengths"], (page_ids, (ln["slots"], ln["snap_at"])), c)
        out, states = engine.sample_lanes(logits, key, ln, table, min_close)
        return pages, out, states

    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = jax.jit(prefill_and_sample, donate_argnums=(1,)).lower(
        params, cache, vec(1, tokens), vec(len(PREFILL.kinds), 1),
        vec(1, tokens // PAGE), vec(*key.shape, dt=key.dtype), vec(1, 256), vec(1)).compile()
    text = compiled.as_text()
    assert 1 <= len(re.findall(r"custom-call\(.*masked_prefill_attention", text)) <= 2  # the dense and the expert full layer
    assert "moe_gmm" in text and "sparse_mask" in text and "mla_expand" in text
    assert _resident(compiled) < 15e9, f"{_resident(compiled) / 1e9:.2f} GB"


def test_dots_continuation_compiles_at_a_buckets_rows_over_a_full_table(v5e, monkeypatch):
    """`prefill_paged_continue` at the 12,288-row continuation the engine's
    prewarm runs (a resumed request's tail over a slot's whole table of 20,480
    rows): latent rows and the indexer's keys gathered, the queries' choice in
    blocks of 512 rows, a group of heads expanded and attended a block at a
    time; the sliding layers over their ring and the band of their own rows;
    it fits beside the resident set."""
    dots, c, params, cache, vec = _dots(v5e, monkeypatch)
    T = 12288
    compiled = jax.jit(
        lambda p, ca, toks, n, starts, ids, tables, slots, snap: dots.prefill_paged_continue(
            p, ca, toks, n, starts, ids, tables, (slots, snap), c),
        donate_argnums=(1,),
    ).lower(params, cache, vec(1, T), vec(1), vec(1), vec(1, T // PAGE), vec(1, _CTX // PAGE), vec(1), vec(1)).compile()
    text = compiled.as_text()
    assert "index_select" in text and "ring_latent" in text
    assert _resident(compiled) < 15e9, f"{_resident(compiled) / 1e9:.2f} GB"
