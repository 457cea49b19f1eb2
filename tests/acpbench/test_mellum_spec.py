"""The Mellum2 configuration's file, mix, cell, window-walk arithmetic and
readers: what `test_spec.py` asserts of the Qwen files and `test_jamba_spec.py`
of the Jamba file, for this family's own facts; and the harness end to end on
the CPU at a tiny size, as `test_jamba_harness_cpu.py` makes it for Jamba."""

import json
import os
import types

import jax
import pytest

from acpbench import run as runner
from acpbench import spec
from acpbench.families import mellum_study
from acpbench.kernels import window_walk
from acpbench.layer_metrics import window_rows_share, window_walk_ms_per_step, window_walk_roofline
from acpbench.systems.engine import CompileCounter, System

BENCH = spec.benchmark()
NAME, CELL = "mellum2-12b-a2.5b-bf16-v5e1-ep4", "mellum2-ep4-decode-short-long"
CONF = next(c for c in BENCH["configs"] if c["name"] == NAME)
FILE = spec.load_json(os.path.join(spec.ROOT, CONF["file"]))
DATA = os.path.join(os.path.dirname(__file__), "data")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json as the catalog has it
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum", "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304, "use_sliding_window": True,
}
SIZES = {"window": 1024, "page_size": 16, "kv_heads": 4, "head_dim": 128, "n_layers": 21}


def test_the_file_keeps_every_published_key_and_cuts_only_the_experts_held():
    assert FILE["source"] == CONF["source"] and FILE["reduced"] == CONF["reduced"] == ["num_experts_held"]
    assert {k: FILE[k] for k in PUBLISHED} == PUBLISHED
    assert FILE["num_experts_held"] == 16 and "16 of 64" in FILE["reduced_why"]["num_experts_held"]
    assert {"qk_norm", "window_edge", "head_dim", "tokenizer", "rms_norm_weight", "not_modelled"} <= set(FILE["assumed"])
    assert "MTP head" in FILE["assumed"]["not_modelled"] and "four v5e chips" in FILE["deployment"]
    assert "quantize" not in FILE["engine"] and "bfloat16" in FILE["precision"]["weights"]
    assert "float32" in FILE["precision"]["router"]
    named = " ".join(FILE["engine_departures"])
    assert all(key in named for key in set(FILE["engine"]) - {"page_size"}), named
    e = FILE["engine"]
    assert (e["max_slots"], e["max_ctx"], e["kv_pages"], e["page_size"]) == (32, 8192, 32 * 512 + 1, 16)
    assert e["prefill_buckets"] == [1024, 2048, 4096, 6144, 8192] and e["prefill_buckets"][-1] == e["max_ctx"] and e["width_buckets"] == [16, 32]
    assert (e["prefix_cache_entries"], e["prefix_dedup"], e["park_max_s"]) == (0, False, 0)
    c = FILE["check"]
    assert c["prefill_bucket"] == 2048 and c["min_prompt"] >= 1100 > FILE["sliding_window"]  # every compared row past the window
    program = spec.family(FILE).program_config(FILE)
    assert (program.dim, program.n_heads, program.n_kv_heads, program.head_dim) == (2304, 32, 4, 128)
    assert (program.expert_ffn_dim, program.n_experts, program.experts_per_token, len(program.held)) == (896, 64, 8, 16)
    assert (program.n_layers, program.n_window, program.n_full, program.window) == (28, 21, 7, 1024)
    assert program.yarn == (16.0, 8192, 32.0, 1.0, 1.2772588722239782) and program.rope_theta == 500000.0
    assert not program.tie_embeddings and program.vocab_size == 98304
    assert sum(w["config"] == NAME for w in BENCH["workloads"]) == 1


def test_the_resident_set_is_over_a_quarter_of_the_chip():
    """The issue's arithmetic, from the file's shapes: weights at 2 bytes a
    parameter, the full layers' pages, a ring a slot (and one more) of the
    window layers'."""
    d, v, f, held = FILE["hidden_size"], FILE["vocab_size"], FILE["moe_intermediate_size"], FILE["num_experts_held"]
    h, kv, hd = FILE["num_attention_heads"], FILE["num_key_value_heads"], FILE["head_dim"]
    attn = 2 * d * h * hd + 2 * d * kv * hd
    layer = attn + d * FILE["num_experts"] + held * 3 * d * f
    params = 28 * layer + 2 * v * d
    assert 21.2e6 < attn < 21.3e6 and 3.82e9 < params < 3.84e9
    whole = 28 * (attn + d * 64 + 64 * 3 * d * f) + 2 * v * d
    assert 12.1e9 < whole < 12.2e9
    e = FILE["engine"]
    row = 2 * kv * hd * 2  # K and V of a token and layer, bytes
    full = 7 * e["kv_pages"] * e["page_size"] * row
    ring = FILE["sliding_window"] // e["page_size"] + 1
    window = 21 * (e["max_slots"] + 1) * ring * e["page_size"] * row
    assert ring == 65 and 3.75e9 < full < 3.77e9 and 1.46e9 < window < 1.48e9
    # a slot at a context of 8,192: 162 MB in two caches against 470 MB kept whole
    assert 7 * 8192 * row + 21 * ring * 16 * row < 0.35 * 28 * 8192 * row
    assert 0.75 * 16e9 < 2 * params + full + window < 0.85 * 16e9


def test_the_mix_is_what_the_issue_names():
    found = spec.cell(BENCH, CELL)
    mix = found["mix"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == FILE["engine"]["max_slots"] == 32
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 256, "max": 6144}
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 512, "max": 1536}
    assert (mix["temperature"], mix["prompt_vocab"], mix["ramp_s"], mix["warmup_seconds"]) == (0.7, 256, 12, 8)
    assert (mix["drain_limit_s"], mix["requests_per_client"], mix["shape_seed"], mix["trace_seconds"]) == (5, 16, 25, 2)
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] <= FILE["engine"]["max_ctx"]
    assert mix["prompt_tokens"]["max"] <= max(FILE["engine"]["prefill_buckets"])
    assert found["workload"]["chips"] == 1 and found["workload"]["traffic"] == "decode-short-long"
    assert len(found["workload"]["why"]) <= 200 and len(CONF["why"]) <= 200


def test_the_cell_reports_at_least_the_metrics_the_issue_names():
    names = {m["name"] for m in spec.metrics_for(BENCH, CELL, "per_layer")}
    joined = {"batch_occupancy", "preemptions", "gap_p50_ms.saturated", "decode_step_ms.throughput", "host_ms_per_block",
              "idle_named_share", "uploads_per_block", "moe_gmm_roofline", "moe_experts_read_share",
              "expert_layer_ms_per_step", "page_walk_roofline.attn_layers"} | {
                  f"idle_ms_per_block.{p}" for p in ("admit", "launch", "fetch", "commit", "publish")}
    new = {"window_walk_roofline": ("device_trace", "kernels"), "window_walk_ms_per_step": ("device_trace", "programs"),
           "window_rows_share": ("program_counter", "KV manager")}
    assert names >= joined | set(new) and "page_walk_roofline" not in names  # a superset: a later PR's metric may join
    for m in BENCH["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_per_chip"
            assert (m["source"], m["layer"]) == new[m["name"]]
    assert BENCH["per_layer"][-3]["name"] == "window_walk_roofline"  # new entries at the end of their list
    assert BENCH["configs"][-1]["name"] == NAME and BENCH["workloads"][-1]["name"] == CELL
    assert {m["name"] for m in spec.metrics_for(BENCH, CELL, "end_to_end")} >= {"tokens_per_s_per_chip", "setup_s"}
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 64 * 1024


def test_window_walk_arithmetic():
    row = 4 * 128 * 2 * 2  # K and V of a token and layer
    assert window_walk.rows_read(10, 1024) == 10 and window_walk.rows_read(5000, 1024) == 1024
    assert window_walk.bytes_per_step([1], **SIZES) == 21 * 16 * row  # one token: one page a layer
    assert window_walk.bytes_per_step([1024], **SIZES) == window_walk.bytes_per_step([8000], **SIZES) == 21 * 1024 * row
    assert window_walk.bytes_per_step([0, 17], **SIZES) == 21 * 32 * row
    step = window_walk.bytes_per_step([3900] * 30 + [500] * 2, **SIZES)  # the cell: ~1.3 GB, 1.6 ms of bytes
    assert 1.3e9 < step < 1.4e9
    assert window_walk.flops_per_step([5000], window=1024, heads=32, head_dim=128, n_layers=21) == 4 * 1024 * 32 * 128 * 21


def _run(stats, ops=None, records=()):
    trace = None if ops is None else {
        "op_intervals": [[]], "modules": {"jit_decode_block": {"n": 2.0, "s": 0.8}}, "ops": ops,
        "windows": [(0, 10**9)], "slice_s": (0.0, 1.0)}
    return types.SimpleNamespace(stats=stats, trace=trace, config=FILE, device_kind="TPU v5e", records=list(records),
                                 traced=(0.0, 1.0), cell={"workload": {"name": CELL}})


READERS = (window_walk_roofline, window_walk_ms_per_step, window_rows_share)


def test_the_new_readers_give_nothing_on_a_program_without_the_kernel_or_counters():
    """A parent commit's `stats()` has no `window` block and its trace no
    `paged_window_walk` op: each reader returns None and the line leaves it
    out; so do all three without a trace."""
    plain = {"decode_steps": 8, "max_slots": 32, "decode_block_size": 16}
    stats = {e: dict(plain) for e in ("open", "close", "trace_start", "trace_stop")}
    old = _run(stats, ops={"paged_page_walk.8": 0.2, "fusion.1": 0.1})
    for reader in READERS:
        assert reader.read(old) is None and reader.read(_run(stats)) is None
    other = types.SimpleNamespace(**{**vars(old), "config": {"hidden_size": 64, "engine": {"page_size": 16}}})
    assert window_walk_roofline.read(other) is None  # another family's file


def test_the_new_readers_find_the_window_walk_in_a_trace():
    window = lambda steps: {"window": 1024, "window_layers": 21, "full_layers": 7, "pages_per_slot": 65,  # noqa: E731
                            "decode": {"steps": steps, "rows_read": 900 * 32 * steps, "rows_unwindowed": 3600 * 32 * steps,
                                       "slots_past_window": 29 * steps},
                            "prefill": {"steps": 1, "rows_read": 10, "rows_unwindowed": 20, "slots_past_window": 0}}
    snap = lambda steps: {"decode_steps": steps, "max_slots": 32, "decode_block_size": 16, "window": window(steps)}  # noqa: E731
    stats = {"open": snap(0), "trace_start": snap(160), "trace_stop": snap(192), "close": snap(1600)}
    live = [types.SimpleNamespace(first_t=0.0, last_t=2.0, prompt_len=n, blocks=[]) for n in (5000, 300)]
    ops = {"paged_window_walk.3": 0.064, "paged_page_walk.8": 0.2, "fusion.9": 0.3}
    run = _run(stats, ops=ops, records=live)
    assert window_walk_ms_per_step.read(run) == pytest.approx(0.064 * 1e3 / 32)  # 2 blocks of 16 steps
    least = window_walk.bytes_per_step([5000, 300], **SIZES) * 32 / 819e9
    assert window_walk_roofline.read(run) == pytest.approx(100 * least / 0.064)
    assert window_rows_share.read(run) == pytest.approx(25.0)
    assert window_rows_share.read(_run(stats)) == pytest.approx(25.0)  # a counter: read without a trace too


def test_the_family_is_found_by_name_and_documents_its_controls():
    family = spec.family(FILE)
    assert family.__name__ == "acpbench.families.mellum"
    for name in ("int8", "bf16", "window_off", "one_rope", "nonorm", "window_minus_page", "kv_int8", "free_routing"):
        assert name in family.__doc__
    with pytest.raises(ValueError, match="no control 'int4'"):
        family.reference_logits(FILE, {}, [[0]], [[0]], lower="int4")
    with pytest.raises(ValueError, match="bfloat16 weights only"):
        family.weights(dict(FILE, engine=dict(FILE["engine"], quantize="int8")), None, None, 0)
    assert set(mellum_study.CACHE) == {"program", "window_minus_page", "kv_int8", "free_routing"}
    assert all(name[4:] in family.mellum_reference.CONTROLS for name in mellum_study.REFERENCE)
    assert {"ref_int8", "ref_window_off", "ref_one_rope"} <= set(mellum_study.REFERENCE)
    with pytest.raises(SystemExit, match="unknown readings"):
        mellum_study.main(["--readings", "ref_fp4"])
    assert set(FILE["check"]["limits"]) == {"logit_rel_rms", "cache_excess", "greedy_regret", "stream_mismatch"}
    assert json.dumps(FILE)  # plain JSON all the way down


# -- the harness end to end on the CPU at a tiny size; nothing here is a device metric ----------------


@pytest.fixture(scope="module")
def rehearsal():
    config = spec.load_json(os.path.join(DATA, "tiny-config-mellum.json"))
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
    assert 0 < got["batch_occupancy"]["value"] <= 100 and 0 < got["window_rows_share"]["value"] <= 100
    assert 0 < got["moe_experts_read_share"]["value"] <= 100
    got = runner.read_metrics(BENCH, "end_to_end", run)
    assert set(got) == {"tokens_per_s_per_chip", "setup_s"} and all(v["value"] > 0 for v in got.values())


def test_the_windows_counters_count_over_the_window(rehearsal):
    stats = rehearsal[0].stats
    a, b = stats["open"]["window"], stats["close"]["window"]
    assert (b["window"], b["window_layers"], b["full_layers"], b["pages_per_slot"]) == (32, 6, 2, 5)
    steps = stats["close"]["decode_steps"] - stats["open"]["decode_steps"]
    # a snapshot taken while a block is in flight reads the device's counters a block (4 steps) ahead of the host's count
    ran = b["decode"]["steps"] - a["decode"]["steps"]
    assert steps > 0 and abs(ran - steps) <= 4, (ran, steps)
    assert b["decode"]["rows_unwindowed"] - a["decode"]["rows_unwindowed"] >= b["decode"]["rows_read"] - a["decode"]["rows_read"] > 0
    assert b["slots_holding"] <= stats["close"]["max_slots"]


def test_outputs_agree_with_the_reference(rehearsal):
    ok, lines = rehearsal[2]
    assert ok, lines
    for name in ("logit_rel_rms=", "cache_excess=", "greedy_regret="):
        assert any(line.startswith(name) and "limit=" in line for line in lines)
    assert any(line.startswith("stream_mismatch=0 limit=0 ok") for line in lines)
