"""Real-TPU validation, opt-in via ACP_TEST_TPU=1.

These run against the directly attached chip (NOT the CPU backend the rest
of the suite is held to): the compiled Pallas page walk vs the XLA
reference on-device at TPU-shaped tile sizes — GQA, MQA, MHA, the
Qwen2.5-7B geometry, bf16 and int8 pages — and a slot-vs-paged engine
equivalence on hardware. The parity helper is the one `chip_smoke.py`
uses (`engine/kernel_parity.py`).

    ACP_TEST_TPU=1 python -m pytest tests/engine/test_tpu_hardware.py -q
"""

import os

import pytest

pytestmark = pytest.mark.skipif(
    not os.environ.get("ACP_TEST_TPU"),
    reason="set ACP_TEST_TPU=1 to run against the real TPU",
)


@pytest.fixture(scope="module")
def tpu():
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip(f"no TPU backend (got {jax.default_backend()})")
    return jax.devices()[0]


# (id, seed, geometry, page dtype, int8 pages, serving hot-path form)
_WALKS = [
    ("mha-f32", 0, dict(S=8, H=8, H_kv=8, d=128), "float32", False, False),
    ("gqa-f32-page32", 1,
     dict(S=4, H=32, H_kv=8, d=128, P=32, max_pages=4, num_pages=64),
     "float32", False, False),
    # head_dim 256 (gemma's head_dim_override) with MQA — the engine's
    # Pallas gate admits head_dim % 128 == 0
    ("gemma-mqa-f32", 2,
     dict(S=4, H=8, H_kv=1, d=256, max_pages=4, num_pages=32),
     "float32", False, False),
    ("mha-f32-plus-new", 3, dict(S=8, H=8, H_kv=8, d=128), "float32", False, True),
    ("qwen2.5-7b-bf16", 4, dict(S=8, H=28, H_kv=4, d=128), "bfloat16", False, True),
    ("qwen2.5-7b-int8", 5, dict(S=8, H=28, H_kv=4, d=128), "bfloat16", True, True),
    ("llama3-8b-int8", 6, dict(S=8, H=32, H_kv=8, d=128), "bfloat16", True, True),
    ("mha-f32-int8", 7, dict(S=8, H=8, H_kv=8, d=128), "float32", True, False),
]


@pytest.mark.parametrize("case", _WALKS, ids=lambda c: c[0])
def test_compiled_page_walk_matches_reference(tpu, case):
    """The DMA-pipelined kernel, COMPILED on hardware (not interpret mode),
    must agree with the XLA gather reference."""
    import jax.numpy as jnp

    from agentcontrolplane_tpu.engine.kernel_parity import (
        make_paged_case,
        page_walk_parity,
    )

    _, seed, geometry, dtype, int8, plus_new = case
    got = page_walk_parity(
        make_paged_case(seed, dtype=jnp.dtype(dtype), int8=int8, **geometry),
        plus_new=plus_new,
    )
    assert got["ok"], got


@pytest.mark.parametrize("int8", [False, True], ids=["bf16-pages", "int8-pages"])
@pytest.mark.parametrize("order", ["empty-first", "empty-between-long", "every-slot-empty", "last-turns-of-G-1-pages"])
def test_compiled_stream_across_slots_matches_reference(tpu, order, int8):
    """The walk's one stream of turns COMPILED: slots of 0 to 3 x depth + 1
    turns with empty ones first, between and last, every page no table
    names NaN. A wait on a fetch that was never started hangs here, where
    the interpreter (`tests/engine/test_paged_stream.py`) cannot."""
    import jax.numpy as jnp

    from ._paged_cases import PAGES, stream_case, stream_parity

    c = stream_case(PAGES[order], jnp.bfloat16, H=8, Hkv=2, d=128, int8=int8)
    for plus_new in (False, True):
        stream_parity(c, plus_new, 2e-2, interpret=False)


_LATENT_TURNS = {  # turns a slot; the first is `kernel_parity.LATENT_TURNS`, what `chip_smoke.py` runs
    "empty-first-between-and-last": {},
    "empty-last": {"turns": (10, 4, 3, 2, 1, 0)},
    "every-slot-empty": {"turns": (0, 0, 0)},
    "one-slot-alone": {"turns": (4,)},
}


@pytest.mark.parametrize("order", list(_LATENT_TURNS))
def test_compiled_latent_walk_matches_reference(tpu, order):
    """The latent walk COMPILED at its own geometry (a pool of one leaf: 32
    pages a turn at page 16, 32 heads on a row of 640): slots of 0 to 10
    turns, empty ones first, between and last, every page no slot's rows
    reach NaN. A wait on a fetch never started hangs here."""
    from agentcontrolplane_tpu.engine.kernel_parity import latent_walk_parity, make_latent_case

    got = latent_walk_parity(make_latent_case(9, **_LATENT_TURNS[order]))
    assert got["pages_per_turn"] == 32 and got["ok"], got


@pytest.mark.parametrize("lanes, columns, coarse", [(16, 26624, False), (16, 26624, True), (32, 20480, True)],
                         ids=["keyevl2", "keyevl2-ties", "dots3-ties"])
def test_compiled_index_select_chooses_top_ks_set(tpu, lanes, columns, coarse):
    """The indexer's choice COMPILED at the two sparse cells' sizes (2,048 of
    a lane's 26,624 and 20,480 scores; lanes of 0 rows, of 1,024, and of the
    whole width among them) against `jax.lax.top_k`'s set on the same chip,
    on random scores (no lane tied) and on scores rounded until whole runs
    of columns tie at every threshold (every long lane tied, the earlier
    columns win)."""
    from agentcontrolplane_tpu.engine.kernel_parity import index_select_parity

    got = index_select_parity(9, lanes=lanes, columns=columns, coarse=coarse)
    assert got["ok"], got
    assert (got["lanes_tied"] >= lanes - 2) if coarse else got["lanes_tied"] == 0, got


@pytest.mark.parametrize("window", [False, True], ids=["pages", "ring"])
def test_compiled_verify_walk_matches_reference(tpu, window):
    """A verify step's two rows a lane in ONE query group of the walk,
    COMPILED at the geometry that runs it (64 / 8 heads of 128, a window of
    128 over pages of 16): lanes of 0 to 6,000 rows, the two rows' edges in
    one page and in two, every page outside the walk NaN."""
    from agentcontrolplane_tpu.engine.kernel_parity import make_verify_case, verify_walk_parity

    got = verify_walk_parity(make_verify_case(9), window=window)
    assert got["ok"] and got["shape"] == (8, 2, 64, 128), got


@pytest.mark.parametrize("case", ["widest", "kexaone-decode", "nemotron3s-decode", "mellum2-prefill"])  # kernel_parity.EXPERT_CASES
def test_compiled_grouped_matmul_matches_ragged_dot(tpu, case):
    """The routed experts' layer through the `moe_gmm` kernels, COMPILED,
    against `ragged_dot` over the same plan, an idle expert and dead tiles
    among it: at the widest geometry served with most tiles live (`exaone`'s
    decode step: 1,280 rows in tiles of 16, 6,144 and 2,048 wide, sixteen
    chunks an expert's gate and up), and as a stream under a long dead bound:
    `kexaone`'s and `nemotron3s`'s decode steps with the share of the pairs
    an unsteered router lands here (about 17 of 80 and 64 of 240 tiles
    live), and a prefill's runs of 128-row tiles."""
    from agentcontrolplane_tpu.engine.kernel_parity import EXPERT_CASES, expert_matmul_parity

    kw = EXPERT_CASES[case]
    got = expert_matmul_parity(9, **kw)
    assert got["ok"] and got["shape"] == (kw["tokens"], kw["hidden"]), got
    assert case == "widest" or got["live_tiles"] < got["tiles"] // 2, got


def test_engine_slot_and_paged_agree_on_tpu(tpu):
    """Greedy decode through BOTH kv layouts on hardware must produce the
    same tokens (the paged path uses the compiled Pallas kernel: engine
    _use_pallas is True on the tpu backend)."""
    import dataclasses

    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
    from agentcontrolplane_tpu.models.llama import PRESETS

    # hardware-native geometry (head_dim 128) so the paged engine takes the
    # compiled Pallas path — the tiny CPU config's head_dim 16 would fall
    # back to the XLA reference and test nothing new here
    cfg = dataclasses.replace(
        PRESETS["tiny"], vocab_size=512, dim=512, n_heads=4, n_kv_heads=2,
        head_dim_override=128,
    )
    results = {}
    for layout in ("slot", "paged"):
        eng = Engine(
            config=cfg,
            tokenizer=ByteTokenizer(),
            max_slots=2,
            max_ctx=128,
            prefill_buckets=(64, 128),
            decode_block_size=8,
            kv_layout=layout,
            seed=0,
        )
        assert layout == "slot" or eng._use_pallas, "paged on TPU must compile Pallas"
        eng.start()
        try:
            results[layout] = eng.generate(
                "the quick brown fox", SamplingParams(temperature=0.0, max_tokens=24)
            ).tokens
        finally:
            eng.stop()
    assert results["slot"] == results["paged"]


def test_paged_engine_chunked_spec_int8kv_identity_on_tpu(tpu):
    """The paged-only machinery on hardware in one engine: chunked prefill
    through the fused megastep, the speculative verify pass, and the int8
    page walk (quantize_kv) — greedy output must be byte-identical with
    chunking + speculation on and off (the repo's standing invariant; the
    quantized cache is on both sides, so it does not relax it)."""
    import dataclasses

    from agentcontrolplane_tpu.engine.engine import Engine, SamplingParams
    from agentcontrolplane_tpu.engine.tokenizer import ByteTokenizer
    from agentcontrolplane_tpu.models.llama import PRESETS

    cfg = dataclasses.replace(
        PRESETS["tiny"], vocab_size=512, dim=512, n_heads=4, n_kv_heads=2,
        head_dim_override=128, max_seq_len=512,
    )
    prompt = "the quick brown fox jumps over the lazy dog. " * 5  # > one chunk
    results = {}
    for name, knobs in (
        ("plain", {}),
        ("chunked+spec", {"prefill_chunk": 64, "spec_len": 4}),
    ):
        eng = Engine(
            config=cfg, tokenizer=ByteTokenizer(), max_slots=2, max_ctx=512,
            prefill_buckets=(64, 128, 256), decode_block_size=8,
            kv_layout="paged", quantize_kv=True, seed=0, **knobs,
        )
        assert eng._use_pallas, "paged on TPU must compile Pallas"
        eng.start()
        try:
            results[name] = eng.generate(
                prompt, SamplingParams(temperature=0.0, max_tokens=24)
            ).tokens
            stats = eng.stats()
        finally:
            eng.stop()
    assert results["plain"] == results["chunked+spec"]
    assert stats["kv_layout"] == "paged"
