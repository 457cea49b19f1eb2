"""The Nemotron 3 Super configuration's file, mix, cell, the new counts'
arithmetic and readers: what `test_exaone_spec.py` asserts of the K-EXAONE
file, for this family's own facts; and the harness end to end on the CPU at
a tiny size, the recurrence's and the experts' counters read over the window.
Lists that later PRs append to are asserted to INCLUDE, never to equal."""

import json
import os
import types

import jax
import pytest

from acpbench import device_scopes, run as runner
from acpbench import spec
from acpbench.families import nemotron_h_reference, nemotron_h_study
from acpbench.kernels import latent_gmm, moe_gmm, ssd
from acpbench.layer_metrics import (
    latent_gmm_roofline, latent_moe_ms_per_step, ssd_scan_roofline, ssd_update_ms_per_step, ssd_update_roofline,
)
from acpbench.systems.engine import CompileCounter, System

BENCH = spec.benchmark()
NAME, CELL = "nemotron3-super-120b-a12b-bf16-v5e1-ep8", "nemotron3s-ep8-decode-state"
CONF = next(c for c in BENCH["configs"] if c["name"] == NAME)
FILE = spec.load_json(os.path.join(spec.ROOT, CONF["file"]))
DATA = os.path.join(os.path.dirname(__file__), "data")
CUT = {"num_hidden_layers", "hybrid_override_pattern", "num_experts_held", "vocab_size", "num_nextn_predict_layers",
       "mtp_hybrid_override_pattern"}
# https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json, as the catalog has it
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376, "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 22, "num_hidden_layers": 88,
    "num_key_value_heads": 2, "num_logits_to_keep": 1, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000, "routed_scaling_factor": 5,
    "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}


def test_the_file_cuts_no_width_and_names_every_cut():
    assert FILE["source"] == CONF["source"] and set(FILE["reduced"]) == set(CONF["reduced"]) == CUT
    assert {k: FILE[k] for k in PUBLISHED if k not in CUT} == {k: v for k, v in PUBLISHED.items() if k not in CUT}
    # the cuts: depth (the published list's first 11 characters), experts held, vocabulary rows, no MTP module
    assert (FILE["num_hidden_layers"], FILE["num_experts_held"], FILE["vocab_size"]) == (11, 64, 16384)
    assert FILE["hybrid_override_pattern"] == PUBLISHED["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert (FILE["num_nextn_predict_layers"], FILE["mtp_hybrid_override_pattern"]) == (0, "")
    assert 131072 // 8 == 16384 and 512 // 8 == 64 and FILE["num_experts"] == FILE["n_routed_experts"] == 512
    whole = PUBLISHED["hybrid_override_pattern"]
    assert (len(whole), whole.count("M"), whole.count("E"), whole.count("*")) == (88, 40, 40, 8)
    cut = FILE["hybrid_override_pattern"]
    assert (cut.count("M"), cut.count("E"), cut.count("*")) == (5, 5, 1)  # the whole list's ratio, 40 : 40 : 8
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size", "moe_latent_size", "head_dim", "mamba_head_dim",
              "moe_shared_expert_intermediate_size", "ssm_state_size", "num_experts_per_tok", "n_groups", "mamba_num_heads",
              "num_attention_heads", "num_key_value_heads", "conv_kernel", "chunk_size", "expand", "n_routed_experts")
    assert not CUT & set(widths) and not any(k.endswith(("_dim", "_rank")) for k in CUT)
    assert set(FILE["reduced_why"]) == {"num_hidden_layers", "num_experts_held", "vocab_size", "num_nextn_predict_layers"}
    assert "rolled back" in FILE["reduced_why"]["num_nextn_predict_layers"]
    assert {"in_proj_order", "latent_projections", "gated_norm", "time_step_limit", "router_bias", "position_encoding",
            "tokenizer", "layer_types"} <= set(FILE["assumed"])
    assert "eight v5e chips" in FILE["deployment"] and "share each layer" in FILE["deployment"]
    assert "quantize" not in FILE["engine"] and "spec_len" not in FILE["engine"]
    assert "float32, STORED" in FILE["precision"]["ssm_state"] and "float32" in FILE["precision"]["router"]
    named = " ".join(FILE["engine_departures"])
    assert all(key in named for key in set(FILE["engine"]) - {"page_size"}), named
    e = FILE["engine"]
    assert (e["max_slots"], e["max_ctx"], e["kv_pages"], e["page_size"]) == (128, 4096, 128 * 256 + 1, 16)
    assert e["prefill_buckets"][-1] == 2048 and e["width_buckets"][-1] == 128 and e["prefill_batch_max"] == 1
    assert (e["prefix_cache_entries"], e["prefix_dedup"], e["park_max_s"]) == (0, False, 0)
    c = FILE["check"]
    assert c["prefill_bucket"] + c["decode_steps"] > FILE["chunk_size"]  # the compared rows span a chunk's edge
    assert set(c["state_limits"]) == {"state_rel_rms", "state_16bit_share"}
    program = spec.family(FILE).program_config(FILE)
    assert (program.dim, program.n_heads, program.n_kv_heads, program.head_dim) == (4096, 32, 2, 128)
    assert (program.mamba_heads, program.mamba_head_dim, program.d_state, program.n_groups, program.d_conv) == (128, 64, 128, 8, 4)
    assert (program.n_layers, program.n_mamba, program.n_moe, program.n_attention) == (11, 5, 5, 1)
    assert (program.n_experts, program.experts_per_token, len(program.held)) == (512, 22, 64)
    assert (program.latent_dim, program.expert_ffn_dim, program.shared_ffn_dim, program.vocab_size) == (1024, 2688, 5376, 16384)
    assert program.routed_scaling_factor == 5.0 and program.norm_eps == 1e-5 and not program.tie_embeddings
    assert FILE["layer_types"] == [{"M": "mamba", "E": "moe", "*": "full_attention"}[ch] for ch in cut]
    assert sum(w["config"] == NAME for w in BENCH["workloads"]) >= 1
    with pytest.raises(ValueError, match="serves num_nextn_predict_layers=0 only"):
        spec.family(FILE).program_config(dict(FILE, num_nextn_predict_layers=1))
    with pytest.raises(ValueError, match="not num_hidden_layers characters"):
        spec.family(FILE).program_config(dict(FILE, num_hidden_layers=12))


def test_the_resident_set_is_the_issues_arithmetic():
    d, H, P, G, N = FILE["hidden_size"], FILE["mamba_num_heads"], FILE["mamba_head_dim"], FILE["n_groups"], FILE["ssm_state_size"]
    di = H * P
    channels = di + 2 * G * N
    assert (di, channels, di + channels + H) == (8192, 10240, 18560)
    mamba = d * (di + channels + H) + (FILE["conv_kernel"] + 1) * channels + 3 * H + di + di * d + d
    attn = 2 * d * 32 * 128 + 2 * d * 2 * 128 + d
    expert = 2 * FILE["moe_latent_size"] * FILE["moe_intermediate_size"]
    outside = d * 512 + 512 + 2 * d * FILE["moe_latent_size"] + 2 * d * FILE["moe_shared_expert_intermediate_size"] + d
    assert [round(n / 1e6, 2) for n in (mamba, attn, outside)] == [109.64, 35.66, 54.53] and round(expert / 1e6, 3) == 5.505
    whole = 40 * mamba + 8 * attn + 40 * (outside + 512 * expert) + 2 * 131072 * d + d
    assert round(whole / 1e9, 2) == 120.67
    active = 40 * mamba + 8 * attn + 40 * (outside + 22 * expert) + 131072 * d  # the head counted, the embedding's gather not
    assert 12.1e9 < active < 12.3e9
    params = 5 * mamba + attn + 5 * (outside + FILE["num_experts_held"] * expert) + 2 * FILE["vocab_size"] * d + d
    assert round(params / 1e6) == 2752 and round(2 * params / 1e9, 2) == 5.50
    slot = 5 * (H * P * N * 4 + (FILE["conv_kernel"] - 1) * channels * 2)
    assert slot == 21_278_720 == spec.family(FILE).program_config(FILE).state_bytes_per_slot
    e = FILE["engine"]
    state = 2 * (e["max_slots"] + 1) * slot
    pool = e["kv_pages"] * e["page_size"] * 2 * 2 * 128 * 2
    assert round(state / 1e9, 2) == 5.49 and round(pool / 1e9, 2) == 0.54
    assert 0.6 * 16e9 < 2 * params + state + pool < 0.75 * 16e9


def test_the_mix_is_what_the_issue_names():
    found = spec.cell(BENCH, CELL)
    mix = found["mix"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == FILE["engine"]["max_slots"] == 128
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 512, "max": 2048}
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 512, "max": 1536}
    assert (mix["temperature"], mix["prompt_vocab"], mix["ramp_s"], mix["warmup_seconds"], mix["trace_seconds"]) == (0.7, 256, 12, 4, 3)
    assert "top_k" not in mix and "top_p" not in mix and FILE["ignore_stop_tokens"]
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] == 3584 <= FILE["engine"]["max_ctx"]
    assert mix["prompt_tokens"]["max"] <= max(FILE["engine"]["prefill_buckets"]) and mix["prompt_vocab"] <= FILE["vocab_size"]
    assert found["workload"]["chips"] == 1 and found["workload"]["traffic"] == "decode-heavy-ssd"
    assert len(found["workload"]["why"]) <= 200 and len(CONF["why"]) <= 200 and "5.5 rows" in found["workload"]["why"]


def test_the_cell_reports_what_reads_it_truly_and_not_what_would_not():
    names = {m["name"] for m in spec.metrics_for(BENCH, CELL, "per_layer")}
    joined = {"batch_occupancy", "preemptions", "gap_p50_ms.saturated", "decode_step_ms.throughput", "host_ms_per_block",
              "idle_named_share", "uploads_per_block", "moe_experts_read_share", "page_walk_roofline.attn_layers",
              "step_ms.attn", "step_ms.mixer", "step_ms.ffn", "step_ms.head", "step_ms.sample", "step_ms.other",
              "device_named_share"} | {f"idle_ms_per_block.{p}" for p in ("admit", "launch", "fetch", "commit", "publish")}
    new = {"ssd_update_roofline": ("%", "kernels"), "ssd_update_ms_per_step": ("ms", "programs"),
           "ssd_scan_roofline": ("%", "kernels"), "latent_gmm_roofline": ("%", "kernels"),
           "latent_moe_ms_per_step": ("ms", "programs")}
    assert names >= joined | set(new)
    # their counts are another model's (three matrices at the hidden width; Mamba-1's state); the new leaves are
    # no part of `device_scopes.LEAVES` and would be filed as glue
    assert not names & {"moe_gmm_roofline", "expert_layer_ms_per_step", "ssm_update_roofline", "ssm_scan_roofline",
                        "ssm_update_ms_per_step", "glue_ms_per_step"}
    assert not {"latent_down", "latent_up", "moe_shared", "ssd_gate_norm"} & set(device_scopes.LEAVES)
    assert {"ssm_update", "ssm_scan", "moe_gmm"} <= set(device_scopes.KERNELS)  # the names the program gives its kernels
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (unit, layer) in new.items():
        m = by_name[name]
        assert CELL in m["workloads"] and m["moves"] == "tokens_per_s_per_chip"  # a later cell may join the list
        assert (m["unit"], m["source"], m["layer"]) == (unit, "device_trace", layer)
    assert {m["name"] for m in spec.metrics_for(BENCH, CELL, "end_to_end")} >= {"tokens_per_s_per_chip", "setup_s"}
    assert {c["name"] for c in BENCH["configs"]} >= {NAME} and {w["name"] for w in BENCH["workloads"]} >= {CELL}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 4)
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 64 * 1024


DIMS = dict(heads=128, head_dim=64, d_state=128, n_groups=8)


def test_the_recurrences_counts_are_the_state_once_in_and_once_out():
    lane = ssd.update_bytes(1, **DIMS)
    assert lane == (2 * 128 * 64 * 128 + 2 * 8192 + 128 + 2 * 1024) * 4 and 8.38e6 < lane < 8.47e6  # 4 MiB each way
    step = ssd.update_bytes(128 * 5, **DIMS)
    assert 6.5e-3 < ssd.least_seconds(step, 0.0, {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}) < 6.7e-3  # the issue's 6.6 ms
    token, row = ssd.scan_bytes(1, 0, **DIMS), ssd.scan_bytes(0, 1, **DIMS)
    assert token == (2 * 8192 + 128 + 2048) * 4 and row == 3 * 4 * 2 ** 20
    flops = ssd.scan_flops(1, **DIMS)
    assert flops == 2 * 8 * 128 * 64 + 2 * 8192 * 64 + 4 * 8192 * 128 and 5.3e6 < flops < 5.5e6
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    assert ssd.least_seconds(token * 2048, flops * 2048, peaks) == token * 2048 / 819e9  # bound by bytes at these sizes


def test_the_latent_experts_count_is_two_matrices_at_the_latent_width():
    kw = dict(latent=1024, width=2688)
    assert latent_gmm.bytes_moved(1, 0, **kw) == 2 * 1024 * 2688 * 2 == 11_010_048
    assert latent_gmm.bytes_moved(0, 1, **kw) == 2 * 1024 * 2 and latent_gmm.flops(1, **kw) == 4 * 1024 * 2688
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    # a decode step: 63.8 of 64 experts read a layer, 352 rows: the weights' bytes, 4.3 ms over the five layers
    least = latent_gmm.least_seconds(5 * 63.8, 5 * 352, peaks=peaks, **kw)
    assert 4.2e-3 < least < 4.4e-3 and least == latent_gmm.bytes_moved(5 * 63.8, 5 * 352, **kw) / 819e9
    # the accepted count at this file's keys reads six times as much: the list this cell stays off
    theirs = moe_gmm.bytes_moved(1, 0, hidden=FILE["hidden_size"], width=FILE["moe_intermediate_size"])
    assert theirs / latent_gmm.bytes_moved(1, 0, **kw) == 6.0


def _run(stats, ops=None, intervals=None, config=FILE):
    trace = None if ops is None else {
        "op_intervals": [intervals or []], "modules": {"jit_decode_block": {"n": 2.0, "s": 0.8}}, "ops": ops,
        "windows": [(0, 10**9)], "slice_s": (0.0, 1.0)}
    return types.SimpleNamespace(stats=stats, trace=trace, config=config, device_kind="TPU v5e", records=[],
                                 traced=(0.0, 1.0), cell={"workload": {"name": CELL}})


READERS = (ssd_update_roofline, ssd_update_ms_per_step, ssd_scan_roofline, latent_gmm_roofline, latent_moe_ms_per_step)


def test_the_new_readers_give_nothing_on_a_program_without_the_kernels():
    """A parent commit's stats have no `ssm` or `moe` group and its trace none
    of the kernels; another family's file has none of the sizes: each reader
    returns None and the line leaves the metric out; so do all without a trace."""
    plain = {"decode_steps": 8, "max_slots": 128, "decode_block_size": 16}
    stats = {e: dict(plain) for e in ("open", "close", "trace_start", "trace_stop")}
    old = _run(stats, ops={"paged_page_walk.8": 0.2, "fusion.1": 0.1}, intervals=[(0, 100, "%fusion.1 = f32[8,8]{1,0} fusion()")])
    for reader in READERS:
        assert reader.read(old) is None and reader.read(_run(stats)) is None, reader.__name__
    jamba = spec.load_json(os.path.join(spec.ROOT, "acpbench/configs/jamba2-3b-bf16-v5e1.json"))
    counted = {"mamba_layers": 10, "rows": 1280, "tokens": 1280, "chunks": 1280}
    ssm_stats = {e: dict(plain, ssm={"decode": {k: v * i for k, v in counted.items()}, "prefill": {k: v * i for k, v in counted.items()}})
                 for i, e in enumerate(("open", "trace_start", "trace_stop", "close"))}
    kernel = [(0, 1000, "%ssm_update.3 = (f32[128,1,8192]{2,1,0}) custom-call()"), (2000, 9000, "%ssm_scan.1 = f32[1,2048,8192]{2,1,0} custom-call()")]
    theirs = _run(ssm_stats, ops={"ssm_update.3": 1e-6}, intervals=kernel, config=jamba)
    assert all(reader.read(theirs) is None for reader in READERS[:3])  # Mamba-1's file: not this count's sizes
    ours = _run(ssm_stats, ops={"ssm_update.3": 1e-6}, intervals=kernel)
    lanes = 128  # rows / mamba_layers of the traced slice
    assert ssd_update_roofline.read(ours) == pytest.approx(100 * ssd.update_bytes(lanes, **DIMS) / 819e9 / 1e-6)
    assert ssd_update_ms_per_step.read(ours) == pytest.approx(1e-6 * 1e3 / 32)  # two blocks of 16 steps in the slice
    assert ssd_scan_roofline.read(ours) == pytest.approx(
        100 * ssd.least_seconds(ssd.scan_bytes(128, 128, **DIMS), ssd.scan_flops(128, **DIMS), {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}) / 7e-6)


def test_the_family_is_found_by_name_and_documents_its_controls():
    family = spec.family(FILE)
    assert family.__name__ == "acpbench.families.nemotron_h"
    for name in ("int8", "bf16", "recurrence_bf16", "decay_quotient", "latent_skip", "h_bf16", "zero_state", "state_swap",
                 "free_routing", "state_rel_rms", "state_16bit_share"):
        assert name in family.__doc__
    with pytest.raises(ValueError, match="bfloat16 weights only"):
        family.weights(dict(FILE, engine=dict(FILE["engine"], quantize="int8")), None, None, 0)
    assert set(nemotron_h_study.CACHE) == {"program", "h_bf16", "zero_state", "state_swap", "recurrence_bf16", "free_routing"}
    assert [name[4:] for name in nemotron_h_study.REFERENCE] == list(nemotron_h_reference.CONTROLS)
    assert set(FILE["check"]["limits"]) == {"logit_rel_rms", "cache_excess", "greedy_regret", "stream_mismatch"}
    source = open(nemotron_h_reference.__file__).read()
    assert "import agentcontrolplane" not in source and "from agentcontrolplane" not in source
    assert json.dumps(FILE)  # plain JSON all the way down


# -- the harness end to end on the CPU at a tiny size; nothing here is a device metric ----------------


@pytest.fixture(scope="module")
def rehearsal():
    config = spec.load_json(os.path.join(DATA, "tiny-config-nemotron-h.json"))
    cell = {"workload": {"name": CELL, "chips": 1}, "config": config,
            "mix": spec.load_json(os.path.join(DATA, "tiny-closed.json"))}
    counter = CompileCounter()
    system = System(config, 2**31 + 11)
    try:
        runner.warm_up(system, cell, 5, counter)
        before = counter.count
        run = runner.measure(system, cell, 5, 2.0, False, "")
        run.setup_s, run.device_kind = 1.0, jax.devices()[0].device_kind
        compiled = counter.count - before
        check = runner.output_check(system, cell, 5)
    finally:
        system.stop()
    return run, compiled, check


def test_nothing_compiles_after_the_warm_up(rehearsal):
    assert rehearsal[1] == 0


def test_requests_are_counted_none_fails_and_none_is_cut_short(rehearsal):
    run = rehearsal[0]
    attempted, failed = runner.count_requests(run)
    assert attempted >= 3 and failed == 0
    ended = [r for r in run.records if r.end_t is not None and not r.censored]
    assert ended and all(r.finish == "length" and r.n_tokens == r.max_tokens for r in ended)


def test_counters_are_read_and_device_metrics_are_not(rehearsal):
    run = rehearsal[0]
    got = runner.read_metrics(BENCH, "per_layer", run)
    for m in spec.metrics_for(BENCH, CELL, "per_layer"):
        assert (m["name"] in got) == (m["source"] != "device_trace"), m["name"]
    assert 0 < got["batch_occupancy"]["value"] <= 100 and 0 < got["moe_experts_read_share"]["value"] <= 100
    got = runner.read_metrics(BENCH, "end_to_end", run)
    assert set(got) >= {"tokens_per_s_per_chip", "setup_s"} and all(v["value"] > 0 for v in got.values())


def test_both_groups_of_counters_count_over_the_window(rehearsal):
    stats = rehearsal[0].stats
    steps = stats["close"]["decode_steps"] - stats["open"]["decode_steps"]
    a, b = stats["open"]["ssm"], stats["close"]["ssm"]
    assert a["state_bytes_per_slot"] == 5 * (8 * 8 * 16 * 4 + 3 * 128 * 2)
    ran = b["decode"]["mamba_layers"] - a["decode"]["mamba_layers"]
    # a snapshot taken while a block is in flight reads the device's counters a block (4 steps) ahead of the host's count
    assert steps > 0 and ran % 5 == 0 and abs(ran - 5 * steps) <= 5 * 4, (ran, steps)
    assert b["prefill"]["tokens"] > a["prefill"]["tokens"] and b["prefill"]["rows"] > a["prefill"]["rows"]
    a, b = stats["open"]["moe"], stats["close"]["moe"]
    assert (b["experts"], b["held"], b["experts_per_token"]) == (16, 4, 3)
    layers = b["decode"]["expert_layers"] - a["decode"]["expert_layers"]
    assert layers % 5 == 0 and abs(layers - 5 * steps) <= 5 * 4
    routed, held = (b["decode"][k] - a["decode"][k] for k in ("pairs_routed", "pairs_held"))
    assert 0 < held < routed and routed % 3 == 0


def test_outputs_agree_with_the_reference(rehearsal, capsys):
    ok, lines = rehearsal[2]
    assert ok, lines
    for name in ("logit_rel_rms=", "cache_excess=", "greedy_regret="):
        assert any(line.startswith(name) and "limit=" in line for line in lines)
    assert any(line.startswith("stream_mismatch=0 limit=0 ok") for line in lines)
