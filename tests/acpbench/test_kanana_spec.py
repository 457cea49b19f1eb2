"""The Kanana-2 configuration's file, mix, cell, latent-walk arithmetic and
readers: what `test_mellum_spec.py` asserts of the Mellum2 file, for this
family's own facts; and the harness end to end on the CPU at a tiny size."""

import json
import os
import types

import jax
import pytest

from acpbench import device_scopes, run as runner
from acpbench import spec
from acpbench.families import kanana_reference, kanana_study
from acpbench.kernels import latent_walk
from acpbench.layer_metrics import latent_absorb_ms_per_step, latent_walk_ms_per_step, latent_walk_roofline
from acpbench.systems.engine import CompileCounter, System

BENCH = spec.benchmark()
NAME, CELL = "kanana2-30b-a3b-bf16-v5e1-ep16", "kanana2-ep16-decode-long"
CONF = next(c for c in BENCH["configs"] if c["name"] == NAME)
FILE = spec.load_json(os.path.join(spec.ROOT, CONF["file"]))
DATA = os.path.join(os.path.dirname(__file__), "data")
# https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json as the catalog has it
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "kv_lora_rank": 512, "max_position_embeddings": 32768, "model_type": "deepseek_v3",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128, "n_shared_experts": 2,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256,
}
SIZES = {"page_size": 16, "row_values": 576, "n_layers": 48}


def test_the_file_keeps_every_published_key_and_cuts_only_the_experts_held():
    assert FILE["source"] == CONF["source"] and FILE["reduced"] == CONF["reduced"] == ["num_experts_held"]
    assert {k: FILE[k] for k in PUBLISHED} == PUBLISHED
    assert FILE["num_experts"] == FILE["n_routed_experts"] == 128  # the readers' name for it: an alias and no cut
    assert FILE["num_experts_held"] == 8 and "8 of 128" in FILE["reduced_why"]["num_experts_held"]
    assert {"rope_interleave", "rms_norm_weight", "tokenizer", "renormalisation", "shared_expert", "not_read"} <= set(FILE["assumed"])
    assert "sixteen v5e chips" in FILE["deployment"] and "share each layer" in FILE["deployment"]
    assert "quantize" not in FILE["engine"] and "bfloat16" in FILE["precision"]["weights"]
    assert "float32" in FILE["precision"]["router"] and "latent rows" in FILE["precision"]["kv_pages"]
    named = " ".join(FILE["engine_departures"])
    assert all(key in named for key in set(FILE["engine"]) - {"page_size"}), named
    e = FILE["engine"]
    assert (e["max_slots"], e["max_ctx"], e["kv_pages"], e["page_size"]) == (16, 5120, 16 * 320 + 1, 16)
    assert e["prefill_buckets"][-1] == e["max_ctx"] and e["width_buckets"] == [16] and e["prefill_batch_max"] == 1
    assert "prefix_cache_entries" not in e and "prefix_dedup" not in e  # the CLI's defaults: they serve this family
    c = FILE["check"]
    assert c["prefill_bucket"] in e["prefill_buckets"] and c["min_prompt"] >= 2560  # twenty turns of 128 rows and more
    program = spec.family(FILE).program_config(FILE)
    assert (program.dim, program.n_heads, program.kv_lora_rank, program.qk_nope_head_dim) == (2048, 32, 512, 128)
    assert (program.qk_rope_head_dim, program.v_head_dim, program.row_width, program.row_stored) == (64, 128, 576, 640)
    assert (program.n_layers, program.first_dense, program.ffn_dim, program.expert_ffn_dim) == (48, 1, 6144, 768)
    assert (program.n_experts, program.experts_per_token, len(program.held), program.shared_width) == (128, 6, 8, 1536)
    assert program.routed_scaling_factor == 2.448 and program.rope_theta == 1e6 and program.max_seq_len == 32768
    assert not program.tie_embeddings and program.vocab_size == 128256
    assert sum(w["config"] == NAME for w in BENCH["workloads"]) == 1
    with pytest.raises(ValueError, match="num_experts is n_routed_experts"):
        spec.family(FILE).program_config(dict(FILE, num_experts=64))


def test_the_resident_set_is_over_a_quarter_of_the_chip():
    """The issue's arithmetic, from the file's shapes: weights at 2 bytes a
    parameter and the latent pool, a row as the chip stores it."""
    d, v, f, held = FILE["hidden_size"], FILE["vocab_size"], FILE["moe_intermediate_size"], FILE["num_experts_held"]
    h, r, rope = FILE["num_attention_heads"], FILE["kv_lora_rank"], FILE["qk_rope_head_dim"]
    attn = d * h * FILE["qk_head_dim"] + d * (r + rope) + r * h * (FILE["qk_nope_head_dim"] + FILE["v_head_dim"]) + h * FILE["v_head_dim"] * d
    shared = 3 * d * FILE["n_shared_experts"] * f
    beside = attn + d * FILE["n_routed_experts"] + shared
    assert 26.3e6 < attn < 26.4e6 and 36.0e6 < beside < 36.1e6
    dense = attn + 3 * d * FILE["intermediate_size"]
    params = dense + 47 * (beside + held * 3 * d * f) + 2 * v * d
    whole = dense + 47 * (beside + 128 * 3 * d * f) + 2 * v * d
    assert 4.05e9 < params < 4.07e9 and 30.6e9 < whole < 30.7e9
    e = FILE["engine"]
    row, stored = (r + rope) * 2, 640 * 2  # bytes a token and layer: the values, and as the chip's tiling stores them
    pool = 48 * e["kv_pages"] * e["page_size"] * stored
    assert row == 1152 and 5.0e9 < pool < 5.1e9
    assert 32 * (192 + 128) * 2 == 20480 and 20480 / row > 17  # what per-head K and V of 32 heads would be
    assert 0.8 * 16e9 < 2 * params + pool < 0.85 * 16e9


def test_the_mix_is_what_the_issue_names():
    found = spec.cell(BENCH, CELL)
    mix = found["mix"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == FILE["engine"]["max_slots"] == 16
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 2048, "max": 4096}
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 512, "max": 1024}
    assert (mix["temperature"], mix["prompt_vocab"], mix["ramp_s"], mix["warmup_seconds"]) == (0.7, 256, 12, 8)
    assert (mix["drain_limit_s"], mix["requests_per_client"], mix["shape_seed"], mix["trace_seconds"]) == (5, 16, 44, 2)
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] <= FILE["engine"]["max_ctx"]
    assert mix["prompt_tokens"]["max"] <= max(FILE["engine"]["prefill_buckets"])
    assert found["workload"]["chips"] == 1 and found["workload"]["traffic"] == "decode-long-latent"
    assert len(found["workload"]["why"]) <= 200 and len(CONF["why"]) <= 200


def test_the_cell_reports_at_least_the_metrics_the_issue_names():
    names = {m["name"] for m in spec.metrics_for(BENCH, CELL, "per_layer")}
    joined = {"batch_occupancy", "preemptions", "gap_p50_ms.saturated", "decode_step_ms.throughput", "host_ms_per_block",
              "idle_named_share", "uploads_per_block", "moe_gmm_roofline", "moe_experts_read_share", "step_ms.attn",
              "step_ms.ffn", "step_ms.head", "step_ms.sample", "step_ms.other", "device_named_share"} | {f"idle_ms_per_block.{p}" for p in ("admit", "launch", "fetch", "commit", "publish")}
    new = {"latent_walk_roofline": ("device_trace", "kernels"), "latent_walk_ms_per_step": ("device_trace", "programs"),
           "latent_absorb_ms_per_step": ("device_trace", "programs")}
    assert names >= joined | set(new)  # a superset: a later PR's metric may join
    # ISSUE 44 listed `glue_ms_per_step` too: its reader knows no `paged_latent_walk` and would count the kernel's
    # 9.4 ms a step as glue, so the cell stays off that list until `device_scopes.KERNELS` has it (PERF.md section 7)
    assert "paged_latent_walk" in device_scopes.KERNELS or "glue_ms_per_step" not in names
    assert not names & {"step_ms.mixer", "page_walk_roofline", "page_walk_roofline.attn_layers", "window_walk_roofline"}
    by_name = {m["name"]: m for m in BENCH["per_layer"]}  # entries looked up by name, not by place
    for name, (source, layer) in new.items():
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_per_chip"
        assert (m["source"], m["layer"]) == (source, layer)
    assert by_name["latent_walk_roofline"]["unit"] == "%" and by_name["latent_walk_roofline"]["better"] == "higher"
    assert {c["name"] for c in BENCH["configs"]} >= {NAME} and {w["name"] for w in BENCH["workloads"]} >= {CELL}
    assert {m["name"] for m in spec.metrics_for(BENCH, CELL, "end_to_end")} >= {"tokens_per_s_per_chip", "setup_s"}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 4)
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 64 * 1024


def test_latent_walk_arithmetic():
    row = 576 * 2  # a token and layer: one read, the least row
    assert latent_walk.bytes_per_step([1], **SIZES) == 48 * 16 * row  # one token: one page a layer, read once
    assert latent_walk.bytes_per_step([16], **SIZES) == latent_walk.bytes_per_step([1], **SIZES)
    assert latent_walk.bytes_per_step([0, 17], **SIZES) == 48 * 32 * row
    step = latent_walk.bytes_per_step([3450] * 16, **SIZES)  # the cell: ~3.06 GB, 3.7 ms of bytes
    assert 3.0e9 < step < 3.1e9
    flops = latent_walk.flops_per_step([3450] * 16, heads=32, row_values=576, latent_values=512, n_layers=48)
    assert flops == 16 * 3450 * 2 * 32 * (576 + 512) * 48 and 180e9 < flops < 190e9
    assert 55 < flops / step < 65  # 60 operations a byte: the first walk not bound by bytes alone
    assert latent_walk.flops_per_step([0], heads=32, row_values=576, latent_values=512, n_layers=48) == 0


def _run(stats, ops=None, records=()):
    trace = None if ops is None else {
        "op_intervals": [[]], "modules": {"jit_decode_block": {"n": 2.0, "s": 0.8}}, "ops": ops,
        "windows": [(0, 10**9)], "slice_s": (0.0, 1.0)}
    return types.SimpleNamespace(stats=stats, trace=trace, config=FILE, device_kind="TPU v5e", records=list(records),
                                 traced=(0.0, 1.0), cell={"workload": {"name": CELL}})


READERS = (latent_walk_roofline, latent_walk_ms_per_step, latent_absorb_ms_per_step)


def test_the_new_readers_give_nothing_on_a_program_without_the_kernel_or_scope():
    """A parent commit's trace has no `paged_latent_walk` op and no
    `mla_absorb` path: each reader returns None and the line leaves it out;
    so do all three without a trace."""
    plain = {"decode_steps": 8, "max_slots": 16, "decode_block_size": 16}
    stats = {e: dict(plain) for e in ("open", "close", "trace_start", "trace_stop")}
    old = _run(stats, ops={"paged_page_walk.8": 0.2, "fusion.1": 0.1})
    for reader in READERS:
        assert reader.read(old) is None and reader.read(_run(stats)) is None
    other = types.SimpleNamespace(**{**vars(old), "config": {"hidden_size": 64, "engine": {"page_size": 16}}})
    assert latent_walk_roofline.read(other) is None  # another family's file


def test_the_new_readers_find_the_latent_walk_in_a_trace():
    snap = lambda steps: {"decode_steps": steps, "max_slots": 16, "decode_block_size": 16}  # noqa: E731
    stats = {"open": snap(0), "trace_start": snap(160), "trace_stop": snap(192), "close": snap(1600)}
    live = [types.SimpleNamespace(first_t=0.0, last_t=2.0, prompt_len=n, blocks=[]) for n in (5000, 3000)]
    ops = {"paged_latent_walk.3": 0.064, "paged_page_walk.8": 0.2, "fusion.9": 0.3}
    run = _run(stats, ops=ops, records=live)
    assert latent_walk_ms_per_step.read(run) == pytest.approx(0.064 * 1e3 / 32)  # 2 blocks of 16 steps
    by_bytes = latent_walk.bytes_per_step([5000, 3000], **SIZES) / 819e9
    by_ops = latent_walk.flops_per_step([5000, 3000], heads=32, row_values=576, latent_values=512, n_layers=48) / 197e12
    assert by_bytes > by_ops  # at the published peaks the bytes are the greater
    assert latent_walk_roofline.read(run) == pytest.approx(100 * by_bytes * 32 / 0.064)


def test_the_absorb_reader_sums_the_scopes_ops_inside_decode_runs():
    Op = device_scopes.Op
    base = "jit(decode_block)/while/body/closed_call/acp.attn/"
    tables = {"/device:TPU:0": {
        (7, "%fusion.1 = x"): Op(base + "mla_absorb/dot_general", "", "convolution fusion"),
        (7, "%fusion.2 = x"): Op(base + "latent_walk/mul", "", "loop fusion"),
        (7, "%fusion.3 = x"): Op(base + "attn_qkv/dot;" + base + "mla_absorb/concatenate", "", "loop fusion"),
        (9, "%fusion.1 = x"): Op("jit(prefill_and_sample)/acp.attn/mla_absorb/dot_general", "", "convolution fusion"),
    }}
    runs = [("/device:TPU:0", [(0, 1000, "jit_decode_block", 7), (1000, 2000, "jit_prefill_and_sample", 9)])]
    ops = [[(10, 110, "%fusion.1 = x"), (200, 250, "%fusion.2 = x"), (300, 330, "%fusion.3 = x"),
            (1100, 1900, "%fusion.1 = x"), (5000, 6000, "%fusion.1 = x")]]
    assert latent_absorb_ms_per_step.seconds(ops, runs, tables) == pytest.approx(130e-9)
    assert latent_absorb_ms_per_step.seconds(ops, runs, {}) == 0.0


def test_the_family_is_found_by_name_and_documents_its_controls():
    family = spec.family(FILE)
    assert family.__name__ == "acpbench.families.kanana"
    for name in ("int8_matmul_inputs", "bf16", "scale_128", "rope_all", "kv_norm_off", "k_pe_unroped", "shared_off",
                 "route_scale_off", "bias_off", "kv_int8", "free_routing"):
        assert name in family.__doc__
    with pytest.raises(ValueError, match="no control 'int4'"):
        family.reference_logits(FILE, {"embed": 0}, [[0]], [[0]], lower="int4")
    with pytest.raises(ValueError, match="bfloat16 weights only"):
        family.weights(dict(FILE, engine=dict(FILE["engine"], quantize="int8")), None, None, 0)
    assert set(kanana_study.CACHE) == {"program", "kv_int8", "free_routing"}
    assert all(name[4:] in kanana_reference.CONTROLS for name in kanana_study.REFERENCE)
    assert {"ref_int8_matmul_inputs", "ref_scale_128", "ref_rope_all", "ref_kv_norm_off", "ref_k_pe_unroped",
            "ref_shared_off", "ref_route_scale_off", "ref_bias_off"} <= set(kanana_study.REFERENCE)
    with pytest.raises(SystemExit, match="unknown readings"):
        kanana_study.main(["--readings", "ref_fp4"])
    assert set(FILE["check"]["limits"]) == {"logit_rel_rms", "cache_excess", "greedy_regret", "stream_mismatch"}
    assert json.dumps(FILE)  # plain JSON all the way down


# -- the harness end to end on the CPU at a tiny size; nothing here is a device metric ----------------


@pytest.fixture(scope="module")
def rehearsal():
    config = spec.load_json(os.path.join(DATA, "tiny-config-kanana.json"))
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
    assert set(got) == {"tokens_per_s_per_chip", "setup_s"} and all(v["value"] > 0 for v in got.values())


def test_the_latent_counters_count_over_the_window(rehearsal):
    stats = rehearsal[0].stats
    a, b = stats["open"]["latent"], stats["close"]["latent"]
    assert (b["row_values"], b["layers"], b["row_bytes_stored"]) == (40, 4, 128 * 2)  # bfloat16, a lane tile stored
    steps = stats["close"]["decode_steps"] - stats["open"]["decode_steps"]
    # a snapshot taken while a block is in flight reads the device's counters a block (4 steps) ahead of the host's count
    ran = b["decode"]["steps"] - a["decode"]["steps"]
    assert steps > 0 and abs(ran - steps) <= 4, (ran, steps)
    assert b["decode"]["rows_read"] > a["decode"]["rows_read"] and b["decode"]["rows_expanded"] == 0
    assert b["prefill"]["rows_expanded"] > 0 and stats["close"]["moe"]["shared_width"] == 32


def test_outputs_agree_with_the_reference(rehearsal):
    ok, lines = rehearsal[2]
    assert ok, lines
    for name in ("logit_rel_rms=", "cache_excess=", "greedy_regret="):
        assert any(line.startswith(name) and "limit=" in line for line in lines)
    assert any(line.startswith("stream_mismatch=0 limit=0 ok") for line in lines)
