"""The K-EXAONE configuration's file, mix, cell, verify-walk arithmetic and
readers: what `test_kanana_spec.py` asserts of the Kanana-2 file, for this
family's own facts; and the harness end to end on the CPU at a tiny size,
the drafter's counters read over the window."""

import json
import os
import types

import jax
import pytest

from acpbench import device_scopes, run as runner
from acpbench import spec
from acpbench.families import exaone_reference, exaone_study, exaone_weights
from acpbench.kernels import page_walk, verify_walk, window_walk
from acpbench.layer_metrics import mtp_accept_share, mtp_draft_ms_per_step, mtp_tokens_per_step, verify_walk_roofline
from acpbench.systems.engine import CompileCounter, System

BENCH = spec.benchmark()
NAME, CELL = "k-exaone-236b-a23b-bf16-v5e1-ep8", "kexaone-ep8-decode-mtp"
CONF = next(c for c in BENCH["configs"] if c["name"] == NAME)
FILE = spec.load_json(os.path.join(spec.ROOT, CONF["file"]))
DATA = os.path.join(os.path.dirname(__file__), "data")
CUT = {"num_hidden_layers", "layer_types", "mlp_layer_types", "sliding_windows", "num_experts_held", "vocab_size"}
# https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json as the catalog has it, but the lists
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 6144, "intermediate_size": 18432,
    "max_position_embeddings": 262144, "model_type": "exaone_moe", "moe_intermediate_size": 2048,
    "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0], "n_group": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 8, "num_nextn_predict_layers": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "sliding_window": 128, "sliding_window_pattern": "LLLG", "tie_word_embeddings": False,
    "topk_group": 1, "vocab_size": 153600,
}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


def test_the_file_cuts_no_width_and_names_every_cut():
    assert FILE["source"] == CONF["source"] and set(FILE["reduced"]) == set(CONF["reduced"]) == CUT
    assert {k: FILE[k] for k in PUBLISHED if k not in CUT} == {k: v for k, v in PUBLISHED.items() if k not in CUT}
    # the cuts: depth (and with it the three lists, the published lists' first five), experts held, vocabulary rows
    assert (FILE["num_hidden_layers"], FILE["num_experts_held"], FILE["vocab_size"]) == (5, 16, 19200)
    assert FILE["layer_types"] == (PERIOD * 12)[:5] == ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    assert FILE["mlp_layer_types"] == ["dense"] + ["sparse"] * 4 and FILE["sliding_windows"] == [128, 128, 128, 0, 128]
    assert 153600 // 8 == 19200 and 128 // 8 == 16
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim", "num_attention_heads",
              "num_key_value_heads", "num_experts_per_tok", "num_experts", "sliding_window")
    assert not CUT & set(widths) and not any(k.endswith(("_dim", "_rank")) for k in CUT)
    assert set(FILE["reduced_why"]) == {"num_hidden_layers", "num_experts_held", "vocab_size"}
    assert "one layer pass in six here and one in 49" in FILE["reduced_why"]["num_hidden_layers"]
    assert {"norm_placement", "qk_norm", "rope_on_window_layers_only", "window_edge", "router_bias", "mtp_block",
            "acceptance", "tokenizer"} <= set(FILE["assumed"])
    assert str(exaone_weights.HEAD_GAIN) in FILE["assumed"]["acceptance"]
    assert "eight v5e chips" in FILE["deployment"] and "share each layer" in FILE["deployment"]
    assert "quantize" not in FILE["engine"] and "spec_len" not in FILE["engine"]
    assert "float32" in FILE["precision"]["router"] and "float32" in FILE["precision"]["accept"]
    named = " ".join(FILE["engine_departures"])
    assert all(key in named for key in set(FILE["engine"]) - {"page_size"}), named
    e = FILE["engine"]
    assert (e["max_slots"], e["max_ctx"], e["kv_pages"], e["page_size"]) == (64, 6144, 64 * 384 + 1, 16)
    assert e["prefill_buckets"][-1] == e["max_ctx"] and e["width_buckets"] == [64] and e["decode_block_size"] == 16
    assert (e["prefix_cache_entries"], e["prefix_dedup"], e["park_max_s"]) == (0, False, 0)
    c = FILE["check"]
    assert c["prefill_bucket"] in e["prefill_buckets"] and c["min_prompt"] > 2 * FILE["sliding_window"]
    assert c["engine_tokens"] > e["decode_block_size"]  # greedy requests span two blocks of nearly all refused drafts
    program = spec.family(FILE).program_config(FILE)
    assert (program.dim, program.n_heads, program.n_kv_heads, program.head_dim) == (6144, 64, 8, 128)
    assert (program.n_layers, program.n_window, program.n_full, program.first_dense) == (5, 4, 1, 1)
    assert (program.ffn_dim, program.expert_ffn_dim, program.window, program.vocab_size) == (18432, 2048, 128, 19200)
    assert (program.n_experts, program.experts_per_token, len(program.held), program.shared_width) == (128, 8, 16, 2048)
    assert program.routed_scaling_factor == 2.5 and program.rope_theta == 1e6 and program.norm_eps == 1e-5
    assert sum(w["config"] == NAME for w in BENCH["workloads"]) >= 1
    with pytest.raises(ValueError, match="serves num_nextn_predict_layers=1 only"):
        spec.family(FILE).program_config(dict(FILE, num_nextn_predict_layers=2))
    with pytest.raises(ValueError, match="mlp_layer_types is not"):
        spec.family(FILE).program_config(dict(FILE, mlp_layer_types=["sparse"] * 5))


def test_the_resident_set_is_the_issues_arithmetic():
    d, f, h, kv, hd = FILE["hidden_size"], FILE["moe_intermediate_size"], 64, 8, 128
    attn = 2 * d * h * hd + 2 * d * kv * hd
    expert = 3 * d * f
    assert round(attn / 1e6, 2) == 113.25 and round(expert / 1e6, 2) == 37.75
    sparse = attn + FILE["num_experts_held"] * expert + expert + d * FILE["num_experts"]
    whole = attn + 128 * expert + expert + d * 128
    dense = attn + 3 * d * FILE["intermediate_size"]
    mtp = dense + 2 * d * d
    head = 2 * FILE["vocab_size"] * d
    assert 755e6 < sparse < 757e6 and 4.97e9 < whole < 4.99e9 and 452e6 < dense < 454e6 and 528e6 < mtp < 529e6
    params = dense + 4 * sparse + mtp + head
    assert 4.23e9 < params < 4.25e9
    e = FILE["engine"]
    token = 2 * kv * hd * 2  # K and V of one cache layer
    pool = 2 * e["kv_pages"] * e["page_size"] * token  # layer 3 and the MTP block
    rings = 4 * (e["max_slots"] + 1) * (128 // 16 + 1) * e["page_size"] * token
    assert 3.2e9 < pool < 3.25e9 and 0.14e9 < rings < 0.16e9
    assert 10e9 < 2 * params + pool + rings < 0.76 * 16e9


def test_the_mix_is_what_the_issue_names():
    found = spec.cell(BENCH, CELL)
    mix = found["mix"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == FILE["engine"]["max_slots"] == 64
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 512, "max": 3072}
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 1024, "max": 3072}
    assert (mix["temperature"], mix["prompt_vocab"], mix["ramp_s"], mix["warmup_seconds"]) == (0.7, 256, 12, 8)
    assert "top_k" not in mix and "top_p" not in mix
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] <= FILE["engine"]["max_ctx"]
    assert mix["prompt_tokens"]["min"] > FILE["sliding_window"]  # every slot past the window from its first step
    assert mix["prompt_tokens"]["max"] <= max(FILE["engine"]["prefill_buckets"])
    assert found["workload"]["chips"] == 1 and found["workload"]["traffic"] == "decode-self-draft"
    assert len(found["workload"]["why"]) <= 200 and len(CONF["why"]) <= 200


def test_the_cell_reports_what_reads_it_truly_and_not_what_would_not():
    names = {m["name"] for m in spec.metrics_for(BENCH, CELL, "per_layer")}
    joined = {"preemptions", "gap_p50_ms.saturated", "decode_step_ms.throughput", "host_ms_per_block",
              "idle_named_share", "uploads_per_block", "moe_experts_read_share", "window_walk_ms_per_step",
              "window_walk_roofline", "step_ms.attn", "step_ms.ffn", "step_ms.head", "step_ms.sample", "step_ms.other",
              "device_named_share"} | {f"idle_ms_per_block.{p}" for p in ("admit", "launch", "fetch", "commit", "publish")}
    new = {"mtp_accept_share": ("%", "program_counter", "scheduler"), "mtp_tokens_per_step": ("count", "program_counter", "scheduler"),
           "mtp_draft_ms_per_step": ("ms", "device_trace", "programs"), "verify_walk_roofline": ("%", "device_trace", "kernels")}
    assert names >= joined | set(new)
    # two tokens a step read 170% of a share that cannot pass 100; the new leaves are no part of `device_scopes.LEAVES`
    # and would be filed as glue; the full layers' count knows no MTP layer; the expert reader takes a decode step's
    # rows for a prefill's at two rows a lane (PERF.md section 7)
    assert not names & {"batch_occupancy", "glue_ms_per_step", "page_walk_roofline.attn_layers", "page_walk_roofline",
                        "moe_gmm_roofline", "step_ms.mixer"}
    assert not {"mtp_in_proj", "mtp_block", "mtp_head", "spec_accept"} & set(device_scopes.LEAVES)
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (unit, source, layer) in new.items():
        m = by_name[name]
        assert CELL in m["workloads"] and m["moves"] == "tokens_per_s_per_chip"  # a later cell may join the list
        assert (m["unit"], m["source"], m["layer"]) == (unit, source, layer)
    assert {m["name"] for m in spec.metrics_for(BENCH, CELL, "end_to_end")} >= {"tokens_per_s_per_chip", "setup_s"}
    # looked up by name, never by place or count: a later PR appends after these and must not fail here
    assert {c["name"] for c in BENCH["configs"]} >= {NAME} and {w["name"] for w in BENCH["workloads"]} >= {CELL}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 4)
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 64 * 1024


SIZES = dict(window=128, page_size=16, kv_heads=8, head_dim=128)


def test_verify_walk_arithmetic_counts_each_lanes_rows_once():
    common = {k: v for k, v in SIZES.items() if k != "window"}
    one = verify_walk.bytes_per_step([4000], window_layers=4, full_layers=2, **SIZES)
    assert one == (window_walk.bytes_per_step([4000], n_layers=4, **SIZES)
                   + page_walk.bytes_per_step([4000], n_layers=2, **common))
    page = 16 * 8 * 128 * 2 * 2  # K and V of one page of one layer
    assert one == 4 * 8 * page + 2 * 250 * page  # a ring's window: 8 pages; the context: 250
    assert verify_walk.bytes_per_step([0, 100], window_layers=4, full_layers=2, **SIZES) == (4 + 2) * 7 * page
    step = verify_walk.bytes_per_step([3800] * 64, window_layers=4, full_layers=2, **SIZES)
    assert 1.9e9 < step < 2.2e9  # the cell: ~2 GB a step, 2.5 ms of bytes; two lanes a slot read twice that


def _run(stats, ops=None, records=(), config=FILE):
    trace = None if ops is None else {
        "op_intervals": [[]], "modules": {"jit_decode_block": {"n": 2.0, "s": 0.8}}, "ops": ops,
        "windows": [(0, 10**9)], "slice_s": (0.0, 1.0)}
    return types.SimpleNamespace(stats=stats, trace=trace, config=config, device_kind="TPU v5e", records=list(records),
                                 traced=(0.0, 1.0), cell={"workload": {"name": CELL}})


def test_the_new_readers_give_nothing_on_a_program_without_a_drafter():
    """A parent commit's stats have no `drafter` and its trace no `mtp_*`
    path; another family's file has no MTP module: each reader returns None
    and the line leaves the metric out; so do all four without a trace."""
    plain = {"decode_steps": 8, "max_slots": 64, "decode_block_size": 16}
    stats = {e: dict(plain) for e in ("open", "close", "trace_start", "trace_stop")}
    old = _run(stats, ops={"paged_page_walk.8": 0.2, "fusion.1": 0.1})
    for reader in (mtp_accept_share, mtp_tokens_per_step, mtp_draft_ms_per_step):
        assert reader.read(old) is None and reader.read(_run(stats)) is None
    assert verify_walk_roofline.read(_run(stats)) is None
    mellum = spec.load_json(os.path.join(spec.ROOT, "acpbench/configs/mellum2-12b-a2.5b-bf16-v5e1-ep4.json"))
    assert verify_walk_roofline.read(_run(stats, ops={"paged_page_walk.8": 0.2}, config=mellum)) is None


def test_the_counter_readers_take_the_drafters_deltas_over_the_window():
    snap = lambda put, kept, tokens: {"decode_steps": 0, "max_slots": 64, "decode_block_size": 16,  # noqa: E731
                                      "drafter": {"proposed": put, "accepted": kept, "tokens": tokens}}
    stats = {"open": snap(1000, 900, 1900), "close": snap(11000, 7900, 18700)}
    run = _run(stats)
    assert mtp_accept_share.read(run) == pytest.approx(70.0)
    assert mtp_tokens_per_step.read(run) == pytest.approx(1.68)
    assert mtp_accept_share.read(_run({"open": snap(5, 1, 6), "close": snap(5, 1, 6)})) is None  # no step in the window


def test_the_walk_reader_holds_both_kernels_time_against_the_bytes_needed_once():
    snap = lambda steps: {"decode_steps": steps, "max_slots": 64, "decode_block_size": 16}  # noqa: E731
    stats = {"open": snap(0), "trace_start": snap(160), "trace_stop": snap(192), "close": snap(1600)}
    live = [types.SimpleNamespace(first_t=0.0, last_t=2.0, prompt_len=n, blocks=[]) for n in (5000, 3000)]
    ops = {"paged_page_walk.3": 0.040, "paged_window_walk.4": 0.024, "fusion.9": 0.3}
    need = verify_walk.bytes_per_step([5000, 3000], window_layers=4, full_layers=2, **SIZES) / 819e9
    assert verify_walk_roofline.read(_run(stats, ops=ops, records=live)) == pytest.approx(100 * need * 32 / 0.064)


def test_the_family_is_found_by_name_and_documents_its_controls():
    family = spec.family(FILE)
    assert family.__name__ == "acpbench.families.exaone"
    for name in ("int8", "bf16", "rope_on_full", "window_off", "nonorm", "bias_off", "route_scale_off", "shared_off",
                 "mtp_prev_hidden_off", "window_minus_page", "draft_row_kept", "kv_int8", "free_routing"):
        assert name in family.__doc__
    with pytest.raises(ValueError, match="bfloat16 weights only"):
        family.weights(dict(FILE, engine=dict(FILE["engine"], quantize="int8")), None, None, 0)
    assert set(exaone_study.CACHE) == {"program", "window_minus_page", "draft_row_kept", "kv_int8", "free_routing"}
    assert all(name[4:] in exaone_reference.CONTROLS for name in exaone_study.REFERENCE)
    assert {"ref_int8", "ref_rope_on_full", "ref_window_off", "ref_nonorm", "ref_bias_off", "ref_route_scale_off",
            "ref_shared_off"} <= set(exaone_study.REFERENCE)
    assert set(FILE["check"]["limits"]) == {"logit_rel_rms", "cache_excess", "greedy_regret", "stream_mismatch"}
    assert "import agentcontrolplane" not in open(exaone_reference.__file__).read()
    assert json.dumps(FILE)  # plain JSON all the way down


# -- the harness end to end on the CPU at a tiny size; nothing here is a device metric ----------------


@pytest.fixture(scope="module")
def rehearsal():
    config = spec.load_json(os.path.join(DATA, "tiny-config-exaone.json"))
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
    assert 20 < got["mtp_accept_share"]["value"] < 100 and 1.0 < got["mtp_tokens_per_step"]["value"] < 2.0
    got = runner.read_metrics(BENCH, "end_to_end", run)
    assert set(got) >= {"tokens_per_s_per_chip", "setup_s"} and all(v["value"] > 0 for v in got.values())


def test_the_drafters_counters_count_over_the_window(rehearsal):
    stats = rehearsal[0].stats
    a, b = stats["open"]["drafter"], stats["close"]["drafter"]
    steps = stats["close"]["decode_steps"] - stats["open"]["decode_steps"]
    ran = b["steps"] - a["steps"]  # a snapshot in flight reads the device a block (4 steps) ahead of the host
    assert steps > 0 and abs(ran - steps) <= 4, (ran, steps)
    put, kept, tokens = (b[k] - a[k] for k in ("proposed", "accepted", "tokens"))
    assert 0 < kept < put and put < tokens <= put + kept
    assert stats["close"]["window"]["pages_per_slot"] == 3 and stats["close"]["moe"]["shared_width"] == 32


def test_outputs_agree_with_the_reference(rehearsal):
    ok, lines = rehearsal[2]
    assert ok, lines
    for name in ("logit_rel_rms=", "cache_excess=", "greedy_regret="):
        assert any(line.startswith(name) and "limit=" in line for line in lines)
    assert any(line.startswith("stream_mismatch=0 limit=0 ok") for line in lines)
