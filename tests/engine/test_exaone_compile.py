"""The chip's compiler, here, for the exaone family (`tests/engine/test_chip_compile.py`
has the other families' cases and the described-v5e fixture these use; a file
of their own so that neither file runs over its budget): the verify-and-draft
decode block at the benchmark's 64 lanes and its widest prefills compile for a
described v5e, copy no pool and fit beside the resident set; the one-row window
walk's kernel is op for op what it was before a verify step's rows rode one
query group."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.engine.test_chip_compile import PAGE, _kernel_vmem, _resident, v5e  # noqa: F401 (v5e: the fixture)


_EXAONE_SLOTS, _EXAONE_PAGES = 64, 24577  # acpbench/configs/k-exaone-236b-a23b-bf16-v5e1-ep8.json


def _exaone(v5e, monkeypatch):
    """The benchmark's cut of the published config (depth 5 and the MTP
    module, 16 of 128 experts, an eighth of the vocabulary), abstract
    weights and caches placed on one described chip, the expert layer
    steered onto its kernel."""
    import dataclasses
    import functools

    from agentcontrolplane_tpu.models import exaone, experts

    monkeypatch.setattr(experts, "routed_experts", functools.partial(experts.routed_experts, kernel=True))
    c = dataclasses.replace(exaone.PRESETS["k-exaone-236b-a23b"], layer_types=exaone._pattern(5), vocab_size=19200,
                            experts_held=tuple(range(16)))
    one_chip = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: exaone.init_params(c, jax.random.key(0))))
    cache = place(jax.eval_shape(lambda: exaone.init_paged_cache(c, _EXAONE_PAGES, PAGE, max_slots=_EXAONE_SLOTS)))
    vec = lambda *shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    return exaone, c, params, cache, vec


def test_exaone_decode_block_verifies_two_rows_and_copies_no_pool(v5e, monkeypatch):
    """64 lanes of the benchmark's cut, verify-and-draft steps in a loop as
    the engine's decode block nests them (its own sampler around each): both
    walks and the grouped matmuls are kernels, the pools and the drafter's
    state are aliased from argument to result, no op copies a pool, and the
    resident set is what the configuration's file says (about 11.9 GB)."""
    import re

    from agentcontrolplane_tpu.engine.engine import make_draft_block
    from agentcontrolplane_tpu.engine.lanes import DECODE

    exaone, c, params, cache, vec = _exaone(v5e, monkeypatch)
    S = _EXAONE_SLOTS
    block = make_draft_block(
        lambda p, ca, tok, n, active, sampler, tables: exaone.verify_step_paged(
            p, ca, tok, n, tables, active, sampler, c, use_pallas=True),
        (), 6144, 4)
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=SingleDeviceSharding(v5e[0]))
    compiled = jax.jit(block, donate_argnums=(1, 2)).lower(
        params, cache, vec(len(DECODE.kinds), S), key, vec(1, 256), vec(1), vec(S, 6144 // PAGE)).compile()
    text = compiled.as_text()
    assert "paged_window_walk" in text and "paged_page_walk" in text and "moe_gmm" in text
    # a lane's two rows in ONE query group of each walk: 64 lanes of 8 KV heads x 16 rows, and no walk of 128 lanes
    for walk in ("paged_page_walk", "paged_window_walk"):
        assert re.search(rf"%{walk}\S* = \(f32\[{S},8,16,128\]", text), f"{walk} is not {S} lanes of 16 rows a KV head"
    assert f"f32[{2 * S},8,8,128]" not in text
    # the grouped matmuls: none states a VMEM limit over the 16 MiB a kernel gets unasked (each what it holds and 2
    # MiB); and what the compiler stages in VMEM under them stays there: a layer's q weights `bf16[1,6144,8192]`,
    # 96 MiB, went back to HBM under kernels that claimed 55 MB, and took `attn_qkv` and `attn_out` 0.6 ms a step with
    # them (PR 53)
    vmem = _kernel_vmem(text)
    assert vmem and all(used <= asked <= 16 << 20 for asked, used in vmem), vmem
    staged = re.findall(r"= (bf16\[1,6144,8192\]\S*) fusion\(", text)
    assert all("S(1)" in layout for layout in staged), f"a layer's q weights staged outside VMEM: {staged}"
    pools = sum(cache[name].size * 2 for name in ("k", "v", "wk", "wv"))
    mem = compiled.memory_analysis()
    assert 3.3e9 < pools < 3.5e9 and mem.alias_size_in_bytes >= pools
    ring = 128 // PAGE + 1
    for pool in (rf"bf16\[2,{_EXAONE_PAGES},{PAGE},1024\]", rf"bf16\[4,{(S + 1) * ring},{PAGE},1024\]"):
        assert re.search(pool, text)
        assert not re.search(rf"= {pool}\S* copy\(", text), f"a copy of the whole pool {pool}"
    assert 0.7 * 16e9 < _resident(compiled) < 14e9, f"{_resident(compiled) / 1e9:.2f} GB"


@pytest.mark.parametrize("tokens", [3072, 6144])
def test_exaone_prefill_fits_beside_the_resident_set(v5e, monkeypatch, tokens):
    """The mix's widest prompt bucket and the bucket a resumed request takes
    (one row, as the engine prefills it), the MTP block over the prompt
    among it: under the chip's 16 GB beside weights and caches."""
    exaone, c, params, cache, vec = _exaone(v5e, monkeypatch)
    B, T = 1, tokens
    compiled = jax.jit(
        lambda p, ca, tok, n, ids, slots, snap: exaone.prefill_paged_batch(p, ca, tok, n, ids, (slots, snap), c),
        donate_argnums=(1,),
    ).lower(params, cache, vec(B, T), vec(B), vec(B, T // PAGE), vec(B), vec(B)).compile()
    assert "moe_gmm" in compiled.as_text()
    assert _resident(compiled) < 15e9, f"{_resident(compiled) / 1e9:.1f} GB"


def _kernel_ops(jaxpr) -> int:
    """Equations of a jaxpr, those of every loop and branch inside it among them."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    n += _kernel_ops(inner)
    return n


# (id, slots, query heads, KV heads, table entries = the ring, rows a lane, the kernel's equations)
_WINDOW_KERNELS = [
    ("mellum2-one-row", 32, 32, 4, 1024 // PAGE + 1, 1, 667),  # PR 51's count: `rows == 1` traces what it traced
    ("kexaone-one-row", 64, 64, 8, 128 // PAGE + 1, 1, 999),  # `decode_step_paged`'s, the same
    ("kexaone-two-rows", 64, 64, 8, 128 // PAGE + 1, 2, 1072),  # an edge a row: its select and compare a turn, a guard a KV head
]


@pytest.mark.parametrize("case", _WINDOW_KERNELS, ids=lambda c: c[0])
def test_the_window_walks_kernel_has_the_ops_it_had_at_one_row_a_lane(case):
    """The window branch of the walk's body takes a static case at more than
    one row a lane and no other: the one-row kernels' equation counts are the
    parent's (PR 51's tree, counted there), so `mellum2`'s and the drafter-less
    decode step's programs hold the ops they held."""
    from agentcontrolplane_tpu.ops.pallas import paged_attention as pa

    _, S, H, H_kv, ring, rows, ops = case
    sds = jax.ShapeDtypeStruct
    pages = sds((4096, PAGE, H_kv * 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, t, n, first: pa._paged_state(q, k, v, t, n, kv_heads=H_kv, starts=first, ring=ring, rows=rows))(
        sds((S, rows * H, 128), jnp.bfloat16), pages, pages, sds((S, ring), jnp.int32), sds((S,), jnp.int32),
        sds((S * rows,), jnp.int32))
    (call,) = (eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "pallas_call")
    assert call.params["name"] == "paged_window_walk" and _kernel_ops(call.params["jaxpr"]) == ops
