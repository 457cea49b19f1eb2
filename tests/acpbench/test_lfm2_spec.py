"""The LFM2 configuration's file, mix, cell, kernel arithmetic and readers:
what `test_spec.py` asserts of the Qwen files, for this family's own facts."""

import json
import os
import types

import pytest

from acpbench import spec
from acpbench.kernels import moe_gmm
from acpbench.layer_metrics import _moe, expert_layer_ms_per_step, moe_experts_read_share, moe_gmm_roofline
from acpbench.layer_metrics import page_walk_roofline_attn_layers

BENCH = spec.benchmark()
NAME, CELL = "lfm2-24b-a2b-bf16-v5e1-ep8", "lfm2-ep8-decode-saturated"
CONF = next(c for c in BENCH["configs"] if c["name"] == NAME)
FILE = spec.load_json(os.path.join(spec.ROOT, CONF["file"]))
# https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json as the catalog has it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
}
PATTERN = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 + ["full_attention", "conv"]


def test_the_file_keeps_every_published_key_and_cuts_only_the_experts_held():
    assert FILE["source"] == CONF["source"] and FILE["reduced"] == CONF["reduced"] == ["num_experts_held"]
    assert {k: FILE[k] for k in PUBLISHED} == PUBLISHED and FILE["layer_types"] == PATTERN
    assert FILE["num_experts_held"] == 8 and "eight" in FILE["deployment"] and "num_experts_held" in FILE["reduced_why"]
    assert {"head_dim", "tie_word_embeddings", "tokenizer"} <= set(FILE["assumed"])
    assert "quantize" not in FILE["engine"] and "bfloat16" in FILE["precision"]["weights"]
    assert set(FILE["engine"]) - {"kv_layout"} <= set(FILE["engine_departures"]) | {
        "max_slots", "max_ctx", "kv_pages", "page_size", "prefill_batch_max"}
    program = spec.family(FILE).program_config(FILE)
    assert (program.dim, program.n_heads, program.n_kv_heads, program.head_dim) == (2048, 32, 8, 64)
    assert (program.ffn_dim, program.expert_ffn_dim, program.n_experts, program.experts_per_token) == (11776, 1536, 64, 4)
    assert program.held == tuple(range(8)) and program.n_layers == 40 and program.n_attention == 10
    assert any(w["config"] == NAME for w in BENCH["workloads"])


def test_the_resident_set_is_over_a_quarter_of_the_chip():
    """The issue's arithmetic, from the file: weights at 2 bytes, the pages
    of 10 attention layers, the conv state of 30."""
    d, f, fd, v = FILE["hidden_size"], FILE["moe_intermediate_size"], FILE["intermediate_size"], FILE["vocab_size"]
    conv, attn = 3 * d * d + 3 * d + d * d, 2 * d * d + 2 * d * 512
    experts = 38 * FILE["num_experts_held"] * 3 * d * f
    rest = 30 * conv + 10 * attn + 2 * 3 * d * fd + 38 * d * 64 + v * d
    pages = FILE["engine"]["kv_pages"] * FILE["engine"]["page_size"] * 10 * 2 * 8 * 64 * 2
    state = 2 * 30 * FILE["engine"]["max_slots"] * 2 * d * 2
    assert 7.4e9 < 2 * (experts + rest) < 7.6e9 and 0.6e9 < pages < 0.7e9
    assert 2 * (experts + rest) + pages + state > 0.25 * 16e9


def test_the_mix_is_decode_heavys_but_for_the_traced_slice():
    found = spec.cell(BENCH, CELL)
    other = spec.cell(BENCH, "q7b-decode-saturated")["mix"]
    own = ("why", "who", "trace_seconds", "trace_seconds_why")
    assert {k: v for k, v in found["mix"].items() if k not in own} == {k: v for k, v in other.items() if k not in own}
    assert found["mix"]["trace_seconds"] < other["trace_seconds"] and found["workload"]["chips"] == 1


def test_the_cell_joins_the_lists_the_issue_names_and_brings_four_metrics():
    names = {m["name"] for m in spec.metrics_for(BENCH, CELL, "per_layer")}
    joined = {"batch_occupancy", "preemptions", "gap_p50_ms.saturated", "decode_step_ms.throughput",
              "host_ms_per_block", "idle_named_share"} | {f"idle_ms_per_block.{p}" for p in (
                  "admit", "launch", "fetch", "commit", "publish")}
    new = {"moe_gmm_roofline", "moe_experts_read_share", "page_walk_roofline.attn_layers", "expert_layer_ms_per_step"}
    assert names == joined | new and "page_walk_roofline" not in names
    for m in BENCH["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_per_chip"
    assert {m["name"] for m in spec.metrics_for(BENCH, CELL, "end_to_end")} == {"tokens_per_s_per_chip", "setup_s"}


def test_grouped_matmul_arithmetic():
    kw = {"hidden": 2048, "width": 1536}
    expert = 3 * 2048 * 1536 * 2
    assert moe_gmm.bytes_moved(1, 0, **kw) == expert and round(expert / 1e6, 1) == 18.9
    assert moe_gmm.bytes_moved(0, 1, **kw) == 2 * 2048 * 2 and moe_gmm.flops(1, **kw) == 2 * 3 * 2048 * 1536
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    # a decode step's layer: bound by bytes; ten thousand rows an expert: by operations
    assert moe_gmm.least_seconds(7, 16, peaks=peaks, **kw) == pytest.approx(moe_gmm.bytes_moved(7, 16, **kw) / 819e9)
    assert moe_gmm.least_seconds(1, 10000, peaks=peaks, **kw) == pytest.approx(moe_gmm.flops(10000, **kw) / 197e12)


def _run(stats, ops=None):
    trace = None if ops is None else {
        "op_intervals": [ops], "modules": {"jit_decode_block": {"n": 1.0, "s": 0.01}}, "ops": {},
        "windows": [(0, 10**7)], "slice_s": (0.0, 0.01)}
    return types.SimpleNamespace(stats=stats, trace=trace, config=FILE, device_kind="TPU v5e", records=[],
                                 traced=(0.0, 1.0), cell={"workload": {"name": CELL}})


def test_the_new_readers_give_nothing_on_a_program_without_the_counters():
    """A parent commit's `stats()` has no `moe` block and its trace no
    `moe_gmm` op: each reader returns None and the line leaves it out."""
    plain = {"decode_steps": 8, "max_slots": 32, "decode_block_size": 8}
    old = _run({e: dict(plain) for e in ("open", "close", "trace_start", "trace_stop")},
               ops=[(0, 1000, "%fusion.1 = f32[32,64]{1,0} fusion()")])
    for reader in (moe_gmm_roofline, moe_experts_read_share, expert_layer_ms_per_step):
        assert reader.read(old) is None
    assert page_walk_roofline_attn_layers.read(_run(old.stats)) is None


def test_the_new_readers_find_the_expert_layers_in_a_trace():
    moe = lambda n: {"experts": 64, "held": 8, "experts_per_token": 4, "decode": {  # noqa: E731
        "expert_layers": 38 * n, "pairs_routed": 128 * 38 * n, "pairs_held": 16 * 38 * n, "experts_read": 7 * 38 * n,
        "tokens_per_held_expert": [2 * 38 * n] * 8}}
    snap = lambda n: {"decode_steps": n, "max_slots": 32, "decode_block_size": 8, "moe": moe(n)}  # noqa: E731
    stats = {"open": snap(0), "trace_start": snap(80), "trace_stop": snap(160), "close": snap(800)}
    layer = [(0, 10, "%fusion.7 = f32[32,64]{1,0:T(8,128)} fusion(%p)"), (10, 30, "%sort.3 = s32[128]{0} sort(%k)"),
             (30, 130, "%moe_gmm.30 = bf16[256,1536]{1,0} custom-call(%x)"), (130, 135, "%fusion.9 = bf16[256,1536]{1,0} fusion()"),
             (135, 185, "%moe_gmm.31 = bf16[256,2048]{1,0} custom-call(%h)"), (185, 200, "%fusion.11 = bf16[32,2048]{1,0} fusion()")]
    prefill = [(200, 900, "%moe_gmm.30 = bf16[3072,1536]{1,0} custom-call(%x)")]
    run = _run(stats, ops=layer + prefill)
    kernel_s, layer_s = _moe.decode_expert_seconds(run)
    assert kernel_s == pytest.approx(150e-9) and layer_s == pytest.approx(185e-9)
    assert moe_experts_read_share.read(run) == pytest.approx(100 * 7 / 8)
    assert expert_layer_ms_per_step.read(run) == pytest.approx(185e-9 * 1e3 / 8)
    steps = 8
    least = moe_gmm.least_seconds(7 * 38 * steps, 16 * 38 * steps, hidden=2048, width=1536,
                                  peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})
    assert moe_gmm_roofline.read(run) == pytest.approx(100 * least / 150e-9)
    # the host's step count a block behind the device's counters when the slice closed: the same reading
    late = _run(dict(stats, trace_stop=dict(snap(160), decode_steps=152)), ops=layer + prefill)
    assert moe_gmm_roofline.read(late) == pytest.approx(100 * least / 150e-9)


def test_the_family_is_found_by_name_and_documents_its_controls():
    family = spec.family(FILE)
    assert family.__name__ == "acpbench.families.lfm2"
    for name in ("int8", "nobias", "nonorm", "capacity", "quantize_kv", "zero_state"):
        assert name in family.__doc__
    with pytest.raises(ValueError, match="bfloat16 weights only"):
        family.weights(dict(FILE, engine=dict(FILE["engine"], quantize="int8")), None, None, 0)
    assert json.dumps(FILE)  # plain JSON all the way down
