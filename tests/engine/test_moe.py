"""Mixture-of-Experts FFN (ops/moe.py) + the Mixtral-architecture family.

The grouped expert layer (its rows counted by expert, PR 49) must match the exact per-token reference,
drop no token however uneven the routing, and expert parallelism ('ep' mesh
axis) must be numerically transparent and must not all-gather the expert
weights.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from agentcontrolplane_tpu.models.llama import PRESETS, forward, init_params
from agentcontrolplane_tpu.ops.moe import moe_ffn_reference, route_topk, routed_experts
from agentcontrolplane_tpu.parallel.mesh import make_mesh, param_shardings

MOE = PRESETS["moe-tiny"]


def _weights(seed=0, E=4, D=64, F=128):
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(
        rng.normal(size=shape) * 0.05, dtype=jnp.float32
    )
    return mk(D, E), mk(E, D, F), mk(E, D, F), mk(E, F, D)


def test_route_topk_renormalizes_over_selection():
    logits = jnp.asarray([[1.0, 3.0, 2.0, -1.0]])
    idx, w = route_topk(logits, 2)
    assert sorted(np.asarray(idx[0]).tolist()) == [1, 2]
    np.testing.assert_allclose(float(jnp.sum(w)), 1.0, rtol=1e-6)
    # softmax over the selected two logits only
    expect = np.exp([3.0, 2.0]) / np.exp([3.0, 2.0]).sum()
    np.testing.assert_allclose(np.sort(np.asarray(w[0]))[::-1], expect, rtol=1e-6)


def _grouped(x, router, w1, w3, w2, **kw):
    """Mixtral's flags through the grouped layer (softmax over the chosen,
    no bias, all experts held), as models/llama.py calls it."""
    return routed_experts(x, router, w1, w3, w2, 2, score="softmax", kernel=False, **kw)[0]


def test_moe_ffn_matches_per_token_reference():
    router, w1, w3, w2 = _weights()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(13, 64)), dtype=jnp.float32)
    out = _grouped(x, router, w1, w3, w2)
    ref = moe_ffn_reference(x, router, w1, w3, w2, experts_per_token=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_moe_drops_no_token_however_uneven_the_routing():
    """Every token sent to the same two experts (a router that sees one
    direction only): the capacity path dropped what overflowed an expert's
    share to the residual; the grouped layer has no share to overflow, and
    every row still matches the per-token reference."""
    router, w1, w3, w2 = _weights(seed=2)
    rng = np.random.default_rng(3)
    x = jnp.abs(jnp.asarray(rng.normal(size=(9, 64)), dtype=jnp.float32))
    router = jnp.zeros_like(router).at[:, 1].set(1.0).at[:, 3].set(0.5)
    out, counts = routed_experts(x, router, w1, w3, w2, 2, score="softmax", kernel=False)
    ref = moe_ffn_reference(x, router, w1, w3, w2, experts_per_token=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
    assert np.asarray(counts[:3]).tolist() == [18, 18, 2]  # pairs, landed, experts read
    assert np.asarray(counts[3:]).tolist() == [0, 9, 0, 9]


def test_forward_moe_tiny_finite_and_deterministic():
    params = init_params(MOE, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, MOE.vocab_size, size=(2, 16)),
        dtype=jnp.int32,
    )
    logits = forward(params, tokens, MOE)
    assert logits.shape == (2, 16, MOE.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    logits2 = forward(params, tokens, MOE)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits2))


def test_forward_moe_batch_independent():
    """No capacity, no drops: a row's logits must not depend on what else
    is in the batch (serving correctness: solo == batched)."""
    params = init_params(MOE, jax.random.key(0))
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.integers(1, MOE.vocab_size, size=(1, 12)), dtype=jnp.int32)
    b = jnp.asarray(rng.integers(1, MOE.vocab_size, size=(1, 12)), dtype=jnp.int32)
    solo = forward(params, a, MOE)
    batched = forward(params, jnp.concatenate([a, b]), MOE)
    np.testing.assert_allclose(
        np.asarray(solo[0]), np.asarray(batched[0]), rtol=2e-4, atol=2e-4
    )


def test_expert_parallel_forward_matches_replicated_no_weight_allgather():
    """ep2 x tp2: expert-sharded forward == replicated forward, and the
    compiled HLO contains no expert-weight-sized all-gather (each rank
    computes only its own experts' batches)."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    params = init_params(MOE, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(1, MOE.vocab_size, size=(2, 16)),
        dtype=jnp.int32,
    )
    ref = jax.jit(lambda p, t: forward(p, t, MOE))(params, tokens)

    mesh = make_mesh({"ep": 2, "tp": 2}, devices=jax.devices()[:4])
    p_sh = param_shardings(mesh, MOE, params)
    rep = NamedSharding(mesh, P())
    fn = jax.jit(
        lambda p, t: forward(p, t, MOE),
        in_shardings=(p_sh, rep),
        out_shardings=rep,
    )
    params_ep = jax.device_put(params, p_sh)
    compiled = fn.lower(params_ep, tokens).compile()
    out = fn(params_ep, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)

    # an expert stack is [L, E, D, F]; one layer's experts = E*D*F elements.
    # Anything that size being all-gathered means GSPMD replicated the
    # expert weights instead of dispatching tokens to them.
    expert_elems = MOE.n_experts * MOE.dim * MOE.ffn_dim
    for line in compiled.as_text().splitlines():
        # the op itself, not an op that merely reads an all-gather's result
        if not re.search(r"= \S+ all-gather(-start)?\(", line):
            continue
        dims = re.search(r"\[([0-9,]+)\]", line)
        assert dims is not None, line
        elems = int(np.prod([int(x) for x in dims.group(1).split(",")]))
        assert elems < expert_elems // 2, f"expert-sized all-gather: {line.strip()[:160]}"


def test_moe_serves_through_the_engine():
    """The MoE family drops into the serving engine unchanged (the MLP swap
    lives inside attn_mlp): greedy generation, both KV layouts, identical
    tokens."""
    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer

    cfg = dataclasses.replace(MOE, vocab_size=512)
    outs = {}
    for layout in ("slot", "paged"):
        eng = Engine(
            config=cfg,
            tokenizer=ByteTokenizer(),
            mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]),
            max_slots=2,
            max_ctx=64,
            prefill_buckets=(32, 64),
            decode_block_size=4,
            kv_layout=layout,
            page_size=8,
            seed=0,
        )
        eng.start()
        try:
            outs[layout] = eng.generate(
                "hello moe", SamplingParams(temperature=0.0, max_tokens=8)
            ).tokens
        finally:
            eng.stop()
    assert outs["slot"] == outs["paged"]
    assert len(outs["slot"]) >= 1


def test_mixtral_logits_match_hf():
    """Weight mapping + MoE forward pinned against HF transformers'
    MixtralForCausalLM on a tiny random checkpoint (the same exactness
    contract as the llama/qwen/gemma families)."""
    torch = pytest.importorskip("torch")
    from transformers import MixtralConfig, MixtralForCausalLM

    from agentcontrolplane_tpu.engine.weights import params_from_state_dict
    from agentcontrolplane_tpu.models.llama import LlamaConfig

    tiny = LlamaConfig(
        vocab_size=256,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_dim=128,
        max_seq_len=128,
        rope_theta=10000.0,
        n_experts=4,
        experts_per_token=2,
        dtype=jnp.float32,
    )
    hf_config = MixtralConfig(
        vocab_size=tiny.vocab_size,
        hidden_size=tiny.dim,
        num_hidden_layers=tiny.n_layers,
        num_attention_heads=tiny.n_heads,
        num_key_value_heads=tiny.n_kv_heads,
        intermediate_size=tiny.ffn_dim,
        num_local_experts=tiny.n_experts,
        num_experts_per_tok=tiny.experts_per_token,
        rms_norm_eps=tiny.norm_eps,
        rope_theta=tiny.rope_theta,
        max_position_embeddings=tiny.max_seq_len,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = MixtralForCausalLM(hf_config).eval()
    params = params_from_state_dict(model.state_dict(), tiny)
    assert params["layers"]["w1"].shape == (2, 4, 64, 128)
    assert params["layers"]["router"].shape == (2, 64, 4)
    tokens = np.random.default_rng(0).integers(0, tiny.vocab_size, size=(2, 13))
    with __import__("torch").no_grad():
        hf_logits = model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(forward(params, jnp.asarray(tokens, dtype=jnp.int32), tiny))
    np.testing.assert_allclose(ours, hf_logits, rtol=3e-4, atol=3e-4)


def test_mixtral_config_from_hf(tmp_path):
    import json

    from agentcontrolplane_tpu.engine.weights import config_from_hf

    cfg = {
        "model_type": "mixtral",
        "vocab_size": 32000,
        "hidden_size": 4096,
        "num_hidden_layers": 32,
        "num_attention_heads": 32,
        "num_key_value_heads": 8,
        "intermediate_size": 14336,
        "num_local_experts": 8,
        "num_experts_per_tok": 2,
        "rope_theta": 1000000.0,
        "max_position_embeddings": 32768,
    }
    p = tmp_path / "config.json"
    p.write_text(json.dumps(cfg))
    c = config_from_hf(str(p))
    assert c.n_experts == 8 and c.experts_per_token == 2
    assert c.ffn_dim == 14336


def test_moe_train_step_over_dp_ep_mesh():
    """The trainer takes the MoE family unchanged: one dp2 x ep2 x tp2
    train step produces a finite loss that matches the unsharded step."""
    import optax

    from agentcontrolplane_tpu.train.trainer import Trainer

    cfg = dataclasses.replace(MOE, vocab_size=128)
    batch = np.random.default_rng(0).integers(1, cfg.vocab_size, size=(4, 16))

    def one_step(mesh_axes):
        mesh = make_mesh(mesh_axes, devices=jax.devices()[: int(np.prod(list(mesh_axes.values())))])
        tr = Trainer(config=cfg, mesh=mesh, optimizer=optax.adamw(1e-3))
        params, opt = tr.init(jax.random.key(0))
        tokens, mask = tr.shard_batch(batch)
        _, _, loss = tr.train_step(params, opt, tokens, mask)
        return float(loss)

    sharded = one_step({"dp": 2, "ep": 2, "tp": 2})
    base = one_step({"dp": 1, "tp": 1})
    assert np.isfinite(sharded)
    np.testing.assert_allclose(sharded, base, rtol=2e-3)


def test_moe_serves_on_expert_parallel_mesh():
    """Engine over serving_mesh(ep=2): greedy tokens identical to tp-only."""
    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer

    cfg = dataclasses.replace(MOE, vocab_size=512)

    def run(mesh):
        eng = Engine(
            config=cfg, tokenizer=ByteTokenizer(), mesh=mesh,
            max_slots=2, max_ctx=64, prefill_buckets=(32, 64),
            decode_block_size=4, seed=0,
        )
        eng.start()
        try:
            return eng.generate(
                "expert parallel", SamplingParams(temperature=0.0, max_tokens=8)
            ).tokens
        finally:
            eng.stop()

    ref = run(make_mesh({"tp": 2}, devices=jax.devices()[:2]))
    ep = run(make_mesh({"ep": 2, "tp": 2}, devices=jax.devices()[:4]))
    assert ep == ref and len(ref) >= 1


def test_moe_int8_quantization():
    """Weight-only int8 applies per expert stack ([L, E, D, F] tensors;
    per-channel scales over the contraction dim) and the expert layer
    dequantizes transparently — outputs close to bf16."""
    from agentcontrolplane_tpu.ops.quant import quantize

    router, w1, w3, w2 = _weights(seed=5)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(11, 64)), dtype=jnp.float32)
    ref = _grouped(x, router, w1, w3, w2)
    out = _grouped(x, router, quantize(w1), quantize(w3), quantize(w2))
    assert quantize(w1).q.shape == (4, 64, 128)
    assert quantize(w1).scale.shape == (4, 1, 128)  # per-channel over D
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=0.1, atol=0.05)


@pytest.mark.parametrize("n", [21, 1100])
def test_moe_grouped_matches_reference_across_tile_boundaries(n):
    """Groups are padded to whole row tiles (16 rows at decode sizes, 128
    once a prefill brings 2,048 pairs): with groups that end inside a tile
    the result must still match the exact per-token reference, and padding
    rows must add nothing."""
    router, w1, w3, w2 = _weights(seed=7)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(n, 64)), dtype=jnp.float32)
    ref = moe_ffn_reference(x, router, w1, w3, w2, experts_per_token=2)
    out = _grouped(x, router, w1, w3, w2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# -- the grouped matmul's plan, by counting (PR 49) ----------------------------


def _group_rows_double(key, Eh, tm, M, k):
    """The plan as a stable sort and a loop write it: each held expert's
    pairs in their own order, its group padded to whole tiles, a dead
    tile given the last live tile's expert (the last held expert where no
    pair landed at all)."""
    key = np.asarray(key)
    order = np.argsort(key, kind="stable")
    dest, row_token, live = np.full(len(key), M), np.zeros(M, np.int64), np.zeros(M, bool)
    tile_expert, counts, row = [], [], 0
    for e in range(Eh):
        members = [p for p in order if key[p] == e]
        for p in members:
            dest[p], row_token[row], live[row] = row, p // k, True
            row += 1
        row = -(-row // tm) * tm
        tile_expert += [e] * (-(-len(members) // tm))
        counts.append(len(members))
    n_live = len(tile_expert)
    tile_expert += [tile_expert[-1] if tile_expert else Eh - 1] * (M // tm - n_live)
    return dest, row_token, np.asarray(tile_expert), n_live, np.asarray(counts), live


def _keys(case: str, pairs: int, Eh: int):
    rng = np.random.default_rng(len(case) * 1000 + pairs)
    if case == "held-out-of-the-middle":  # 64 experts, those held are 24..24+Eh: most pairs are not here
        chosen = rng.integers(0, 64, pairs)
        return np.where((chosen >= 24) & (chosen < 24 + Eh), chosen - 24, Eh)
    if case == "an-expert-no-pair-chose":
        return rng.choice([e for e in range(Eh + 1) if e not in (1, Eh - 1)], pairs)
    if case == "every-pair-absent":  # and every row `valid` False: `routed_experts` writes Eh for both
        return np.full(pairs, Eh)
    if case == "one-expert-takes-all":
        return np.full(pairs, Eh - 2)
    return rng.integers(0, Eh + 1, pairs)  # "even"


@pytest.mark.parametrize("pairs,Eh,k,tm", [
    (6, 8, 2, 16),  # under one tile
    (128, 8, 4, 16), (96, 8, 6, 16), (256, 16, 8, 16),  # the three cells' decode rows
    (2046, 8, 2, 16), (2048, 8, 2, 128),  # either side of the inverse's rule, at `row_tile`'s tiles
    (2046, 3, 2, 128), (2048, 3, 2, 16), (512, 16, 8, 128),  # and each form at the other tile
])
@pytest.mark.parametrize("case", ["even", "held-out-of-the-middle", "an-expert-no-pair-chose", "every-pair-absent",
                                  "one-expert-takes-all"])
def test_group_rows_counts_what_a_stable_sort_would_lay_out(case, pairs, Eh, k, tm):
    from agentcontrolplane_tpu.ops.moe import group_rows

    M = -(-pairs // tm) * tm + Eh * tm
    key = _keys(case, pairs, Eh)
    dest, row_token, tile_expert, n_live, counts = (
        np.asarray(a) for a in jax.jit(group_rows, static_argnums=(1, 2, 3, 4))(jnp.asarray(key, jnp.int32), Eh, tm, M, k))
    want_dest, want_token, want_expert, want_live, want_counts, live = _group_rows_double(key, Eh, tm, M, k)
    np.testing.assert_array_equal(dest, want_dest)
    np.testing.assert_array_equal(tile_expert, want_expert)
    assert n_live.tolist() == [want_live]
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_array_equal(row_token[live], want_token[live])
    assert not row_token[~live].any(), "a row no pair landed on reads token 0"


# sha256 (16 hex digits) of y's and the counts' bytes as the PARENT of PR 49 (58bc506: argsort, two scatters, a
# searchsorted) computed them on these operands. Every operand is a small integer or a binary fraction and `act` is
# the identity, so each product and sum of the matmuls is exact in float32 in any order: the XLA path and the
# interpreted kernel agree to the bit, and nothing but a changed plan (or router) moves a digest.
_FROZEN = {  # tokens, k, E, held, score, rows valid -> digest
    "32x4-8-of-64": ((32, 4, 64, tuple(range(8)), "sigmoid", "four-fifths"), "aa536b2dc7faa79a"),  # 63 of 100 landed
    "16x6-8-of-128-middle": ((16, 6, 128, tuple(range(56, 64)), "sigmoid", "four-fifths"), "75a015bde5626d09"),  # 20 of 72
    "32x8-16-of-64": ((32, 8, 64, tuple(range(16, 32)), "sigmoid", "four-fifths"), "ec0ecb04db8bdbec"),  # 151 of 200
    "600x2-all-held": ((600, 2, 8, None, "softmax", "four-fifths"), "078d67cf78858c59"),  # 960 of 960
    "2048x4-8-of-64": ((2048, 4, 64, tuple(range(8)), "sigmoid", "four-fifths"), "ef082103353c3034"),  # 4,153 of 6,552
    "32x4-no-row-valid": ((32, 4, 64, tuple(range(8)), "sigmoid", "none"), "e94801d91141191e"),  # 0 of 0
}


@pytest.mark.parametrize("path", ["xla", "interpreted-kernel"])
@pytest.mark.parametrize("name", list(_FROZEN))
def test_routed_experts_is_bit_for_bit_what_the_sorted_plan_gave(name, path):
    import hashlib

    (N, k, E, held, score, valid), digest = _FROZEN[name]
    Eh, D, F = E if held is None else len(held), 64, 128
    rng = np.random.default_rng(49)
    ints = lambda lo, hi, *shape: jnp.asarray(rng.integers(lo, hi + 1, shape), jnp.float32)  # noqa: E731
    x, router, bias = ints(-2, 2, N, D), ints(-8, 8, D, E) / 64, ints(-4, 4, E) / 256
    w1, w3, w2 = ints(-1, 1, 2 * Eh, D, F), ints(-1, 1, 2 * Eh, D, F), ints(-1, 1, 2 * Eh, F, D)  # two layers' experts
    if held is not None:
        bias = bias.at[jnp.asarray(held)].add(0.25)  # the choice leans to the held: about half the pairs land
    ok = jnp.arange(N) % 5 != 0 if valid == "four-fifths" else jnp.zeros((N,), bool)
    y, stats = routed_experts(x, router, w1, w3, w2, k, held=held, score=score, bias=bias if score == "sigmoid" else None,
                              valid=ok, act=lambda v: v, expert_base=Eh, kernel=False, interpret=path != "xla")
    assert hashlib.sha256(np.asarray(y).tobytes() + np.asarray(stats).tobytes()).hexdigest()[:16] == digest


# -- the grouped matmul as a stream (PR 55) -------------------------------------

# (id, K, N, weights, row tile, (chunk, depth)): the five expert cells' two kernels at a decode step's rows and a
# prefill's, and CPU-test widths with no whole-lane-tile divisor (the width itself, one chunk)
_PLANS = [
    ("kexaone-up-decode", 6144, 2048, 2, 16, (128, 3)), ("kexaone-down-decode", 2048, 6144, 1, 16, (1536, 2)),
    ("kexaone-up-prefill", 6144, 2048, 2, 128, (128, 3)), ("kexaone-down-prefill", 2048, 6144, 1, 128, (1024, 2)),
    ("mellum2-up-decode", 2304, 896, 2, 16, (128, 3)), ("mellum2-down-decode", 896, 2304, 1, 16, (2304, 3)),
    ("mellum2-up-prefill", 2304, 896, 2, 128, (128, 3)), ("mellum2-down-prefill", 896, 2304, 1, 128, (2304, 2)),
    ("lfm2-up-decode", 2048, 1536, 2, 16, (768, 2)), ("lfm2-down-decode", 1536, 2048, 1, 16, (2048, 2)),
    ("lfm2-up-prefill", 2048, 1536, 2, 128, (512, 3)), ("lfm2-down-prefill", 1536, 2048, 1, 128, (1024, 3)),
    ("kanana2-up-decode", 2048, 768, 2, 16, (768, 2)), ("kanana2-down-decode", 768, 2048, 1, 16, (2048, 3)),
    ("kanana2-up-prefill", 2048, 768, 2, 128, (384, 3)), ("kanana2-down-prefill", 768, 2048, 1, 128, (2048, 3)),
    ("nemotron3s-up-decode", 1024, 2688, 1, 16, (2688, 2)), ("nemotron3s-down-decode", 2688, 1024, 1, 16, (1024, 2)),
    ("nemotron3s-up-prefill", 1024, 2688, 1, 128, (896, 3)), ("nemotron3s-down-prefill", 2688, 1024, 1, 128, (1024, 2)),
    ("cpu-tiny", 64, 96, 2, 16, (96, 3)), ("cpu-odd", 200, 320, 1, 16, (320, 3)),
]


@pytest.mark.parametrize("case", _PLANS, ids=lambda c: c[0])
def test_the_chunk_is_as_wide_as_two_units_fit_beside_a_runs_rows(case):
    """`chunk_plan`, the rule alone: the widest whole-lane-tile divisor of
    the width of which two units (a chunk of every weight, `K` whole) fit
    the VMEM a kernel gets unasked beside a run's rows, two output tiles,
    the float32 products and 2 MiB for the compiler; a third unit where
    that fits too. What a call states (`vmem_bytes`) is never over that
    default."""
    from agentcontrolplane_tpu.ops.pallas import moe_gmm

    _, K, N, weights, tm, want = case
    default = moe_gmm._DEFAULT_VMEM_BYTES
    assert (default, moe_gmm._COMPILER_BYTES, moe_gmm._RUN_ROWS) == (16 << 20, 2 << 20, 256)
    chunk, depth = moe_gmm.chunk_plan(K, N, weights, 2, tm)
    assert (chunk, depth) == want and N % chunk == 0 and (chunk % 128 == 0 or chunk == N) and 2 <= depth <= 3
    stated = lambda c, d: moe_gmm.vmem_bytes(K, weights, 2, tm, c, d)  # noqa: E731
    assert stated(chunk, depth) <= default and (depth == 3 or stated(chunk, 3) > default)
    rows = moe_gmm.held_tiles(tm)
    assert rows == {16: 16, 128: 2}[tm], "a run's rows: 256 of them"
    assert stated(chunk, depth) == depth * weights * K * chunk * 2 + rows * tm * K * 2 + (4 + 4 * (weights + 1)) * tm * chunk + (2 << 20)
    assert all(stated(c, 2) > default for c in range(chunk + 128, N + 1, 128) if N % c == 0), "a wider chunk fits"
    assert moe_gmm.chunk_plan(K, N, weights, 2, tm, 128)[0] == (128 if N % 128 == 0 else N), "a caller's cap holds"


# (id, K, N, row tile, tiles of each held expert in turn (0: idle), dead tiles after them, the cap on the chunk): the
# five cells' two shapes cut small, and what a stream can get wrong
_STREAMS = [
    ("kexaone-one-tile-runs-under-a-long-dead-bound", 384, 256, 16, (1, 1, 2, 1), 15, 128),
    ("mellum2-seven-chunks", 96, 896, 16, (1, 2, 1, 1), 4, 128),
    ("lfm2-three-chunks-every-tile-live", 128, 384, 16, (2, 1, 1, 3), 0, 128),
    ("kanana2-one-chunk", 128, 128, 16, (1, 1, 0, 1), 3, None),
    ("nemotron3s-one-chunk-many-experts", 64, 256, 16, (1, 0, 1, 1, 0, 0, 1, 1), 9, None),
    ("no-tile-live", 128, 256, 16, (0, 0, 0), 5, 128),
    ("one-tile-live", 128, 256, 16, (0, 1, 0), 5, 128),
    ("an-idle-expert-between-two-live", 128, 256, 16, (2, 0, 1), 2, 128),
    ("a-run-of-three-tiles-of-128-rows", 64, 256, 128, (3, 1), 2, 128),
    ("a-run-longer-than-the-rows-held", 32, 256, 16, (1, 19, 2), 1, 128),
    ("a-width-with-no-lane-tile-divisor", 40, 96, 16, (1, 2), 1, None),
]


@pytest.mark.parametrize("entry", ["gmm", "gmm_swiglu", "gmm_act"])
@pytest.mark.parametrize("case", _STREAMS, ids=lambda c: c[0])
def test_the_stream_gives_ragged_dots_bits_on_every_live_row(case, entry):
    """The kernel walks the live tiles as one stream of (run, column chunk)
    units, the second layer of a stack of two (`tile_expert` carries the
    layer's base): against `ragged_dot` over the same groups, bit for bit
    on every row of a live tile. The operands are small integers, so each
    sum is exact in any order: what this holds is the stream's indexing
    (runs, chunks, the ring, the rows kept and refilled), not the host's
    rounding. Rows past the live tiles are not written and are compared
    with nothing."""
    from agentcontrolplane_tpu.ops.pallas import moe_gmm

    _, K, N, tm, tiles, dead, tn = case
    E, live = len(tiles), sum(tiles)
    rng = np.random.default_rng(55)
    M = (live + dead) * tm
    experts = np.repeat(np.arange(E), tiles)
    tile_expert = jnp.asarray(np.concatenate([experts, np.full(dead, experts[-1] if live else E - 1)]) + E, jnp.int32)
    n_live = jnp.asarray([live], jnp.int32)
    x = jnp.asarray(rng.integers(-2, 3, (M, K)), jnp.bfloat16)
    ws = [jnp.asarray(rng.integers(-1, 2, (2 * E, K, N)), jnp.bfloat16) for _ in range(1 + (entry == "gmm_swiglu"))]
    act = lambda v: jnp.maximum(v, 0)  # noqa: E731 (exact, where silu is the host's exp)
    kw = {} if entry == "gmm" else {"act": act}
    got = np.asarray(jax.jit(lambda *a: getattr(moe_gmm, entry)(*a, tile_expert, n_live, tm, tn=tn, interpret=True, **kw))(x, *ws))
    chunk, _ = moe_gmm.chunk_plan(K, N, len(ws), 2, tm, tn)
    assert got.shape == (M, N) and N // chunk == {384: 3, 896: 7}.get(N, 2 if tn else 1)
    groups = jnp.asarray(np.concatenate([np.asarray(tiles) * tm, [dead * tm]]), jnp.int32)  # the dead rows: a group of their own
    dot = lambda w: jax.lax.ragged_dot(  # noqa: E731
        x, jnp.concatenate([w[E:], w[:1]]), groups, preferred_element_type=jnp.float32)
    want = {"gmm": lambda: dot(ws[0]), "gmm_act": lambda: act(dot(ws[0])), "gmm_swiglu": lambda: act(dot(ws[0])) * dot(ws[1])}[entry]()
    np.testing.assert_array_equal(got[: live * tm], np.asarray(want.astype(jnp.bfloat16))[: live * tm])
    assert not live or np.abs(got[: live * tm].astype(np.float32)).max() > 4


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_stream_through_the_rows_plan_with_a_narrow_and_the_widest_chunk(dtype):
    """Through `group_rows`' own plan, dead tiles and an expert no row chose
    among them: three chunks of 128 and one of 384 agree bit for bit on the
    live rows, in float32 (the full-precision contract) and bfloat16."""
    from agentcontrolplane_tpu.ops.moe import group_rows
    from agentcontrolplane_tpu.ops.pallas import moe_gmm

    E, K, N, tm, k, pairs = 4, 128, 384, 16, 2, 40
    rng = np.random.default_rng(53)
    key = jnp.asarray(rng.choice([0, 2, 3, E], pairs), jnp.int32)  # expert 1 is chosen by no pair; E: not held here
    M = -(-pairs // tm) * tm + E * tm
    _, row_token, tile_expert, n_live, counts = group_rows(key, E, tm, M, k)
    live = int(n_live[0]) * tm
    assert int(counts[1]) == 0 and live < M, "the case holds an idle expert and dead tiles"
    x = jnp.asarray(rng.integers(-2, 3, (pairs // k, K)), dtype)[row_token]
    w = jnp.asarray(rng.integers(-1, 2, (E, K, N)), dtype)
    run = lambda tn: np.asarray(jax.jit(lambda *a: moe_gmm.gmm(*a, tile_expert, n_live, tm, tn=tn, interpret=True))(x, w))  # noqa: E731
    narrow, widest = run(128), run(None)
    assert moe_gmm.chunk_plan(K, N, 1, x.dtype.itemsize, tm)[0] == N
    assert narrow[:live].tobytes() == widest[:live].tobytes()
    want = jax.lax.ragged_dot(x, w, -(-counts // tm) * tm, preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(widest[:live], np.asarray(want.astype(dtype))[:live])


def test_the_chips_parity_case_runs_interpreted_with_an_idle_expert_and_dead_tiles():
    """`engine.kernel_parity.expert_matmul_parity` (what `chip_smoke.py` and
    the hardware test compile at `exaone`'s widths) at a tiny size through
    the interpreted kernel: column tiles of 256 and 128, the second layer of
    a stack, held expert 1 idle, every fifth token routed nowhere."""
    from agentcontrolplane_tpu.engine.kernel_parity import expert_matmul_parity

    got = expert_matmul_parity(3, tokens=20, k=2, experts=8, held=4, hidden=128, width=256, layers=2, interpret=True)
    assert got["ok"] and got["shape"] == (20, 128) and got["pairs_by_expert"][1] == 0, got
    assert sum(got["pairs_by_expert"]) == 32, "16 of 20 tokens route, two choices each, all to held experts"
