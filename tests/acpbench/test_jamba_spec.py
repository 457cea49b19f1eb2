"""The Jamba configuration's file, mix, cell, kernel arithmetic and readers:
what `test_spec.py` asserts of the Qwen files and `test_lfm2_spec.py` of the
LFM2 file, for this family's own facts."""

import json
import os
import types

import pytest

from acpbench import spec
from acpbench.families import jamba_study
from acpbench.kernels import ssm
from acpbench.layer_metrics import _ssm, ssm_scan_roofline, ssm_update_ms_per_step, ssm_update_roofline

BENCH = spec.benchmark()
NAME, CELL = "jamba2-3b-bf16-v5e1", "jamba2-decode-saturated"
CONF = next(c for c in BENCH["configs"] if c["name"] == NAME)
FILE = spec.load_json(os.path.join(spec.ROOT, CONF["file"]))
# https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json as the catalog has it
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1,
    "rms_norm_eps": 1e-06, "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536,
}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
DIMS = {"d_inner": 5120, "d_state": 16}


def test_the_file_keeps_every_published_key_and_cuts_nothing():
    assert FILE["source"] == CONF["source"] and FILE["reduced"] == CONF["reduced"] == []
    assert {k: FILE[k] for k in PUBLISHED} == PUBLISHED
    assert FILE["layer_types"] == ["full_attention" if i % 14 == 7 else "mamba" for i in range(28)]
    assert {"layer_types", "head_dim", "ssm_state_dtype", "tokenizer"} <= set(FILE["assumed"])
    assert "262,144" in FILE["assumed"]["context"] and "one v5e chip holds" in FILE["deployment"]
    assert "quantize" not in FILE["engine"] and "bfloat16" in FILE["precision"]["weights"]
    assert "float32" in FILE["precision"]["ssm_state"]
    assert set(FILE["engine"]) - {"kv_layout"} <= set(FILE["engine_departures"]) | {
        "max_slots", "max_ctx", "kv_pages", "page_size", "prefill_batch_max"}
    e = FILE["engine"]
    assert (e["max_slots"], e["max_ctx"], e["kv_pages"], e["page_size"]) == (128, 2048, 16385, 16)
    assert e["prefill_buckets"] == [128, 256, 512] and e["width_buckets"] == [16, 128] and e["prefill_batch_max"] == 4
    assert e["decode_block_size"] in (16, 32)
    program = spec.family(FILE).program_config(FILE)
    assert (program.dim, program.n_heads, program.n_kv_heads, program.head_dim) == (2560, 20, 1, 128)
    assert (program.ffn_dim, program.d_inner, program.d_state, program.d_conv, program.dt_rank) == (8192, 5120, 16, 4, 160)
    assert (program.n_layers, program.n_attention, program.n_mamba) == (28, 2, 26)
    assert program.state_bytes_per_slot == 9_318_400
    assert sum(w["config"] == NAME for w in BENCH["workloads"]) == 1


def test_the_resident_set_is_over_a_quarter_of_the_chip():
    """The issue's arithmetic, from the file's shapes: weights at 2 bytes,
    state and its snapshot over slots + 1 rows, the pages of 2 layers."""
    d, f, v = FILE["hidden_size"], FILE["intermediate_size"], FILE["vocab_size"]
    di, n, r = FILE["mamba_expand"] * d, FILE["mamba_d_state"], FILE["mamba_dt_rank"]
    mamba = d * 2 * di + 4 * di + di + di * (r + 2 * n) + r * di + di + n * di + di + di * d
    attn = 2 * d * 20 * 128 + 2 * d * 128
    params = 26 * (mamba + 3 * d * f) + 2 * (attn + 3 * d * f) + v * d
    assert 41.2e6 < mamba < 41.3e6 and 3.02e9 < params < 3.04e9
    slots = FILE["engine"]["max_slots"] + 1
    state = 2 * slots * 26 * (n * di * 4 + 3 * di * 2)
    pages = FILE["engine"]["kv_pages"] * FILE["engine"]["page_size"] * 2 * 2 * 128 * 2
    assert 2.39e9 < state < 2.41e9 and 0.26e9 < pages < 0.28e9
    assert 0.5 * 16e9 < 2 * params + state + pages < 0.6 * 16e9


def test_the_mix_is_decode_heavys_but_for_callers_answers_and_the_traced_slice():
    found = spec.cell(BENCH, CELL)
    other = spec.cell(BENCH, "q7b-decode-saturated")["mix"]
    own = ("why", "who", "clients", "answer_tokens", "trace_seconds", "trace_seconds_why")
    assert {k: v for k, v in found["mix"].items() if k not in own} == {k: v for k, v in other.items() if k not in own}
    mix = found["mix"]
    assert mix["clients"] == FILE["engine"]["max_slots"] == 128 and mix["trace_seconds"] == 3
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 512, "max": 1536}
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] <= FILE["engine"]["max_ctx"]
    assert found["workload"]["chips"] == 1 and found["workload"]["traffic"] == "decode-heavy-ssm"


def test_the_cell_reports_at_least_the_metrics_the_issue_names():
    names = {m["name"] for m in spec.metrics_for(BENCH, CELL, "per_layer")}
    joined = {"batch_occupancy", "preemptions", "gap_p50_ms.saturated", "decode_step_ms.throughput", "host_ms_per_block",
              "idle_named_share", "uploads_per_block", "page_walk_roofline.attn_layers"} | {
                  f"idle_ms_per_block.{p}" for p in ("admit", "launch", "fetch", "commit", "publish")}
    new = {"ssm_update_roofline", "ssm_scan_roofline", "ssm_update_ms_per_step"}
    assert names >= joined | new and "page_walk_roofline" not in names  # a superset: a later PR's metric may join
    for m in BENCH["per_layer"]:
        if m["name"] in new:
            assert CELL in m["workloads"] and m["moves"] == "tokens_per_s_per_chip" and m["source"] == "device_trace"
    assert {m["name"] for m in spec.metrics_for(BENCH, CELL, "end_to_end")} >= {"tokens_per_s_per_chip", "setup_s"}


def test_recurrence_arithmetic():
    lane = (2 * 16 * 5120 + 3 * 5120 + 2 * 16) * 4
    assert ssm.update_bytes(1, **DIMS) == lane == 716_928
    step = ssm.update_bytes(128 * 26, **DIMS)  # a decode step of the cell: 2.39 GB, 2.9 ms of bytes
    assert 2.38e9 < step < 2.39e9 and ssm.least_seconds(step, PEAKS) == pytest.approx(step / 819e9)
    assert 2.9e-3 < ssm.least_seconds(step, PEAKS) < 3.0e-3
    assert ssm.scan_bytes(1, 0, **DIMS) == (3 * 5120 + 32) * 4 and ssm.scan_bytes(0, 1, **DIMS) == 3 * 16 * 5120 * 4
    assert ssm.scan_bytes(10, 2, **DIMS) == 10 * ssm.scan_bytes(1, 0, **DIMS) + 2 * ssm.scan_bytes(0, 1, **DIMS)
    # the scan's own bound is the vector unit's: ~20 operations a byte it must move
    assert ssm.scan_flops(1, **DIMS) == 8 * 16 * 5120 and ssm.scan_flops(1, **DIMS) / ssm.scan_bytes(1, 0, **DIMS) > 10


def _run(stats, ops=None):
    trace = None if ops is None else {
        "op_intervals": [ops], "modules": {"jit_decode_block": {"n": 1.0, "s": 0.01}}, "ops": {},
        "windows": [(0, 10**7)], "slice_s": (0.0, 0.01)}
    return types.SimpleNamespace(stats=stats, trace=trace, config=FILE, device_kind="TPU v5e", records=[],
                                 traced=(0.0, 1.0), cell={"workload": {"name": CELL}})


READERS = (ssm_update_roofline, ssm_scan_roofline, ssm_update_ms_per_step)


def test_the_new_readers_give_nothing_on_a_program_without_the_counters_or_kernels():
    """A parent commit's `stats()` has no `ssm` block and its trace no
    `ssm_update` or `ssm_scan` op: each reader returns None and the line
    leaves it out; so do all three without a trace."""
    plain = {"decode_steps": 8, "max_slots": 128, "decode_block_size": 16}
    stats = {e: dict(plain) for e in ("open", "close", "trace_start", "trace_stop")}
    old = _run(stats, ops=[(0, 1000, "%fusion.1 = f32[128,64]{1,0} fusion()")])
    for reader in READERS:
        assert reader.read(old) is None and reader.read(_run(stats)) is None
    other = types.SimpleNamespace(**{**vars(old), "config": {"hidden_size": 64}})  # another family's file
    assert _ssm.sizes(other) is None and all(reader.read(other) is None for reader in READERS[:2])


def test_the_new_readers_find_the_kernels_in_a_trace():
    ssm_ = lambda steps, pre: {"state_bytes_per_slot": 9318400, "decode": {  # noqa: E731
        "mamba_layers": 26 * steps, "rows": 26 * 120 * steps, "tokens": 26 * 120 * steps, "chunks": 26 * 120 * steps},
        "prefill": {"mamba_layers": 26 * pre, "rows": 26 * 2 * pre, "tokens": 26 * 600 * pre, "chunks": 26 * 6 * pre}}
    snap = lambda steps, pre: {"decode_steps": steps, "max_slots": 128, "decode_block_size": 16,  # noqa: E731
                               "ssm": ssm_(steps, pre)}
    stats = {"open": snap(0, 0), "trace_start": snap(160, 3), "trace_stop": snap(320, 7), "close": snap(1600, 40)}
    conv = "%fusion.40 = bf16[26,129,15360]{2,1,0:T(8,128)(2,1)} fusion(%p)"
    layer = [(0, 50, conv), (50, 60, "%fusion.41 = f32[128,192]{1,0} fusion()"),
             (60, 4060, "%ssm_update.5 = (f32[128,5120]{1,0}, f32[26,129,16,5120]{3,2,1,0}) custom-call(%s)")]
    prefill = [(5000, 9000, "%ssm_scan.3 = (f32[2,512,5120]{2,1,0}, f32[2,16,5120]{2,1,0}) custom-call(%d)"),
               (9000, 9100, conv.replace("fusion.40", "scatter.9")), (9100, 9200, conv.replace("fusion.40", "scatter.10"))]
    second = [(s + 10000, e + 10000, op) for s, e, op in layer]
    run = _run(stats, ops=layer + prefill + second)
    assert _ssm.kernel_events(run, r"ssm_update") == (2, pytest.approx(8000e-9))
    assert _ssm.decode_conv_seconds(run) == pytest.approx(100e-9)  # the two decode convs; the prefill's commits apart
    # 26 of the file's 28 layers step a state; the update's calls at the two attention layers pass through
    least = ssm.least_seconds(ssm.update_bytes(120 * 2 * 26 / 28, **DIMS), PEAKS)
    assert ssm_update_roofline.read(run) == pytest.approx(100 * least / 8000e-9)
    least = ssm.least_seconds(ssm.scan_bytes(600, 2, **DIMS), PEAKS)
    assert ssm_scan_roofline.read(run) == pytest.approx(100 * least / 4000e-9)
    assert ssm_update_ms_per_step.read(run) == pytest.approx(8100e-9 * 1e3 / 16)
    # the host's step count a block behind the device's counters when the slice closed: the same readings
    late = _run(dict(stats, trace_stop=dict(snap(320, 7), decode_steps=304)), ops=layer + prefill + second)
    assert ssm_update_roofline.read(late) == ssm_update_roofline.read(run)


def test_the_family_is_found_by_name_and_documents_its_controls():
    family = spec.family(FILE)
    assert family.__name__ == "acpbench.families.jamba"
    for name in ("int8", "bf16", "h_bf16", "nonorm", "nobias", "zero_state", "state_swap", "quantize_kv"):
        assert name in family.__doc__
    with pytest.raises(ValueError, match="no control 'int4'"):
        family.reference_logits(FILE, {}, [[0]], [[0]], lower="int4")
    with pytest.raises(ValueError, match="bfloat16 weights only"):
        family.weights(dict(FILE, engine=dict(FILE["engine"], quantize="int8")), None, None, 0)
    with pytest.raises(ValueError, match="num_experts 1"):
        family.program_config(dict(FILE, num_experts=16))
    # the family's own numbers on the stored state: documented, limited in the file, read by the study
    assert set(FILE["check"]["state_limits"]) == {"state_rel_rms", "state_16bit_share"}
    assert all(name in family.__doc__ and name in jamba_study.NUMBERS for name in FILE["check"]["state_limits"])
    assert set(jamba_study.CACHE) == {"program", "h_bf16", "zero_state", "state_swap", "kv_int8"}
    assert all(name[4:] in family.jamba_reference.CONTROLS for name in jamba_study.REFERENCE)
    with pytest.raises(SystemExit, match="unknown readings"):
        jamba_study.main(["--readings", "ref_fp4"])
    assert json.dumps(FILE)  # plain JSON all the way down
