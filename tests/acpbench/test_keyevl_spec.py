"""The Keye-VL-2.0 configuration's file against the catalog's keys, its cuts
and their arithmetic, the mix, the cell, the two kernels' counts and the six
readers on recorded spans: what `test_exaone_spec.py` asserts of the K-EXAONE
file, for this family's own facts; and the harness end to end on the CPU at
a tiny size, the indexer's counters read over the window. Every entry of
`BENCHMARK.json` is looked up by name: no place and no count of a list is
asserted, so a later PR appends after these."""

import json
import os
import types

import jax
import pytest

from acpbench import device_scopes, run as runner
from acpbench import spec
from acpbench.device_scopes import Op
from acpbench.families import keyevl_reference, keyevl_study
from acpbench.kernels import index_scores, masked_attention, page_walk, sparse_walk
from acpbench.layer_metrics import (
    _sparse, index_score_ms_per_step, index_score_roofline, index_select_ms_per_step, masked_prefill_attention_roofline,
    prefill_attention_ms_per_ktok, prefill_mask_ms_per_ktok, sparse_rows_share, sparse_walk_ms_per_step,
    sparse_walk_roofline,
)
from acpbench.systems.engine import CompileCounter, System

BENCH = spec.benchmark()
NAME, CELL = "keye-vl2-30b-a3b-bf16-v5e1-ep8", "keyevl2-ep8-decode-sparse-long"
CONF = next(c for c in BENCH["configs"] if c["name"] == NAME)
FILE = spec.load_json(os.path.join(spec.ROOT, CONF["file"]))
DATA = os.path.join(os.path.dirname(__file__), "data")
CUT = {"num_hidden_layers", "num_experts_held", "vocab_size"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json as the catalog has it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "max_position_embeddings": 262144, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "KeyeVL2", "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"}, "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}


def test_the_file_keeps_every_published_key_and_cuts_no_width():
    if os.path.exists(CATALOG):  # the catalog itself, where the guide is installed
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert row["config"] == PUBLISHED and row["source_url"] == CONF["source"]
    assert FILE["source"] == CONF["source"] and set(FILE["reduced"]) == set(CONF["reduced"]) == CUT
    assert {k: FILE[k] for k in PUBLISHED if k not in CUT} == {k: v for k, v in PUBLISHED.items() if k not in CUT}
    assert (FILE["num_hidden_layers"], FILE["num_experts_held"], FILE["vocab_size"]) == (8, 16, 18992)
    assert 151936 // 8 == 18992 and 128 // 8 == 16 and 48 // 6 == 8
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim", "num_attention_heads",
              "num_key_value_heads", "num_experts_per_tok", "num_experts", "sa_config", "rope_scaling")
    assert not CUT & set(widths) and not any(k.endswith(("_dim", "_rank")) for k in CUT)
    assert set(FILE["reduced_why"]) == CUT
    for key, published in (("num_hidden_layers", "48"), ("num_experts_held", "128"), ("vocab_size", "151,936")):
        assert published in FILE["reduced_why"][key], key
    assert {"qk_norm", "indexer_norm", "indexer_rope", "indexer_rotation_and_fp8", "chunk_sizes", "tie_break",
            "rms_norm_weight", "tokenizer", "vision_tower", "not_read"} <= set(FILE["assumed"])
    assert "eight v5e chips share each layer" in FILE["deployment"] and "pipeline stages" in FILE["deployment"]
    assert "quantize" not in FILE["engine"] and "float32" in FILE["precision"]["index_scores"]
    named = " ".join(FILE["engine_departures"])
    assert all(key in named for key in set(FILE["engine"]) - {"page_size"}), named
    e = FILE["engine"]
    assert (e["max_slots"], e["max_ctx"], e["kv_pages"], e["page_size"]) == (16, 26624, 16 * 1664 + 1, 16)
    assert e["prefill_buckets"] == [16384, 24576] and e["width_buckets"] == [16] and e["decode_block_size"] == 16
    assert e["prefill_batch_max"] == 1 and (e["prefix_cache_entries"], e["prefix_dedup"]) == (0, False)
    assert all(b % 1024 == 0 and b % 512 == 0 for b in e["prefill_buckets"])  # whole blocks of the mask and of the kernel
    c = FILE["check"]
    assert c["prefill_bucket"] in e["prefill_buckets"] and c["min_prompt"] >= 12288 and c["decode_steps"] == 16
    # several live lanes (a crossed table shows between two requests), and every engine token inside the bucket
    assert c["sequences"] >= 2 and c["engine_tokens"] <= c["decode_steps"]
    assert set(c["limits"]) == {"logit_rel_rms", "cache_excess", "greedy_regret", "stream_mismatch"}
    assert set(c["select_limits"]) == {"select_miss_prefill", "select_miss_decode", "missed_weight", "select_cache_miss"}
    program = spec.family(FILE).program_config(FILE)
    assert (program.dim, program.n_heads, program.n_kv_heads, program.head_dim) == (2048, 32, 4, 128)
    assert (program.n_layers, program.vocab_size, len(program.held), program.n_experts) == (8, 18992, 16, 128)
    assert (program.expert_ffn_dim, program.experts_per_token, program.rope_theta) == (768, 8, 1e7)
    assert (program.index_heads, program.index_head_dim, program.index_topk, program.ik_stored) == (16, 64, 2048, 128)
    assert program.mrope_section == (16, 24, 24) and program.max_seq_len == 262144
    with pytest.raises(ValueError, match="serves mlp_only_layers"):
        spec.family(FILE).program_config(dict(FILE, mlp_only_layers=[0]))
    with pytest.raises(ValueError, match="one indexer key head"):
        spec.family(FILE).program_config(dict(FILE, sa_config=dict(FILE["sa_config"], indexer_num_kv_heads=2)))


def test_the_resident_set_is_the_issues_arithmetic_and_over_a_quarter_of_the_chip():
    d, f, h, kv, hd = FILE["hidden_size"], FILE["moe_intermediate_size"], 32, 4, 128
    sa = FILE["sa_config"]
    indexer = d * sa["indexer_num_heads"] * sa["indexer_head_dim"] + d * sa["indexer_head_dim"] + d * sa["indexer_num_heads"]
    outside = 2 * d * h * hd + 2 * d * kv * hd + d * FILE["num_experts"] + indexer
    expert = 3 * d * f
    assert round(indexer / 1e6, 2) == 2.26 and round(outside / 1e6, 1) == 21.4 and round(expert / 1e6, 2) == 4.72
    whole = 48 * (outside + 128 * expert) + 2 * 151936 * d
    assert 30.5e9 < whole < 30.7e9
    params = FILE["num_hidden_layers"] * (outside + FILE["num_experts_held"] * expert) + 2 * FILE["vocab_size"] * d
    assert 852e6 < params < 854e6
    e = FILE["engine"]
    token = 2 * kv * hd * 2 + 128 * 2  # K, V and the indexer's key as the chip stores it, a layer
    assert token == 2304
    pool = e["kv_pages"] * e["page_size"] * token * FILE["num_hidden_layers"]
    assert 7.8e9 < pool < 7.9e9 and 0.55 * 16e9 < 2 * params + pool < 0.62 * 16e9
    import jax.numpy as jnp

    from agentcontrolplane_tpu import models

    program = spec.family(FILE).program_config(FILE)
    assert models.page_bytes(program, e["page_size"]) * e["kv_pages"] == pool
    weights = jax.eval_shape(lambda: models.programs(program).init_params(program, jax.random.key(0)))
    assert abs(sum(x.size for x in jax.tree_util.tree_leaves(weights)) - params) < 0.2e6  # the norms' weights
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree_util.tree_leaves(weights))


def test_the_mix_is_what_the_issue_names():
    found = spec.cell(BENCH, CELL)
    mix = found["mix"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == FILE["engine"]["max_slots"] == 16
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 12288, "max": 24576}
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 1024, "max": 2048}
    assert (mix["temperature"], mix["prompt_vocab"]) == (0.7, 256) and "top_k" not in mix and "top_p" not in mix
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] <= FILE["engine"]["max_ctx"]
    assert mix["prompt_tokens"]["min"] > FILE["sa_config"]["topk"]  # every decode row leaves rows out
    assert mix["prompt_tokens"]["max"] <= max(FILE["engine"]["prefill_buckets"]) and FILE["ignore_stop_tokens"]
    assert found["workload"]["chips"] == 1 and found["workload"]["traffic"] == "decode-sparse-long"
    assert len(found["workload"]["why"]) <= 200 and len(CONF["why"]) <= 200


def test_the_cell_joins_the_lists_the_issue_names_and_brings_nine_metrics():
    names = {m["name"] for m in spec.metrics_for(BENCH, CELL, "per_layer")}
    joined = {"preemptions", "decode_step_ms.throughput", "host_ms_per_block", "idle_named_share", "uploads_per_block",
              "moe_experts_read_share", "step_ms.attn", "step_ms.ffn", "step_ms.head", "step_ms.sample", "step_ms.other",
              "device_named_share"} | {f"idle_ms_per_block.{p}" for p in ("admit", "launch", "fetch", "commit", "publish")}
    # the review round's: the cell runs `moe_gmm` through `mellum`'s expert layer and is a saturated cell as the others
    joined |= {"moe_gmm_roofline", "batch_occupancy", "gap_p50_ms.saturated"}
    # `expert_layer_ms_per_step` finds a layer's start by the router's output shape, f32[lanes, experts]: here that
    # is f32[16, 128], which the 128-wide index keys of 16 lanes have too, and it read 15.7 of a step's 16.1 ms
    # (PR 58's builder's chip run): the cell stays off that list
    assert "expert_layer_ms_per_step" not in names
    new = {"sparse_walk_roofline": ("%", "device_trace", "kernels", "higher"),
           "index_score_roofline": ("%", "device_trace", "kernels", "higher"),
           "sparse_walk_ms_per_step": ("ms", "device_trace", "programs", "lower"),
           "index_score_ms_per_step": ("ms", "device_trace", "programs", "lower"),
           "index_select_ms_per_step": ("ms", "device_trace", "programs", "lower"),
           "sparse_rows_share": ("%", "program_counter", "KV manager", "lower"),
           # the prefill, two fifths of the window: its mask's making and the kernel that attends under it
           "prefill_mask_ms_per_ktok": ("ms", "device_trace", "programs", "lower"),
           "prefill_attention_ms_per_ktok": ("ms", "device_trace", "programs", "lower"),
           "masked_prefill_attention_roofline": ("%", "device_trace", "kernels", "higher")}
    assert names >= joined | set(new)
    # no page walk runs here and the new leaves are no part of `device_scopes.LEAVES` (their time would read as glue)
    assert not names & {"page_walk_roofline", "page_walk_roofline.attn_layers", "glue_ms_per_step", "step_ms.mixer",
                        "latent_walk_roofline", "window_walk_roofline"}
    assert not set(_sparse.LEAVES) & set(device_scopes.LEAVES)
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (unit, source, layer, better) in new.items():
        m = by_name[name]
        assert CELL in m["workloads"] and m["moves"] == "tokens_per_s_per_chip"  # a later cell may join the list
        assert (m["unit"], m["source"], m["layer"], m["better"]) == (unit, source, layer, better)
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        spec.reader("per_layer", name)  # its reader is found by name
    assert {m["name"] for m in spec.metrics_for(BENCH, CELL, "end_to_end")} >= {"tokens_per_s_per_chip", "setup_s"}
    assert {c["name"] for c in BENCH["configs"]} >= {NAME} and {w["name"] for w in BENCH["workloads"]} >= {CELL}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 4)
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 64 * 1024


SIZES = dict(topk=2048, kv_heads=4, head_dim=128, n_layers=8)


def test_the_kernels_count_the_least_row_not_the_stored_width_or_a_page():
    # chosen rows x 2 x 512 values x 2 B a lane and layer; a lane under topk chooses what it has, its own row among it
    assert sparse_walk.bytes_per_step([19000], **SIZES) == 2048 * 2 * 512 * 2 * 8
    assert sparse_walk.bytes_per_step([100, 0, 5000], **SIZES) == (101 + 2048) * 2 * 512 * 2 * 8
    assert sparse_walk.flops_per_step([19000], topk=2048, heads=32, head_dim=128, n_layers=8) == 2048 * 4 * 32 * 128 * 8
    # context rows x 64 values x 2 B and 2 x 16 x 64 operations a row: not the 128 lanes the chip stores
    assert index_scores.bytes_per_step([19000, 0, 1000], index_head_dim=64, n_layers=8) == 20000 * 64 * 2 * 8
    assert index_scores.flops_per_step([19000], index_heads=16, index_head_dim=64, n_layers=8) == 19000 * 2 * 16 * 64 * 8
    # the issue's step at 16 lanes of ~19k rows: 0.54 GB chosen, 0.31 GB of index keys, where a dense walk reads 5.0
    lanes = [19000] * 16
    walk, index = sparse_walk.bytes_per_step(lanes, **SIZES), index_scores.bytes_per_step(lanes, index_head_dim=64, n_layers=8)
    dense = page_walk.bytes_per_step(lanes, page_size=16, kv_heads=4, head_dim=128, n_layers=8)
    assert round(walk / 1e9, 2) == 0.54 and round(index / 1e9, 2) == 0.31 and round(dense / 1e9, 1) == 5.0
    assert 0.1 < walk / dense < 0.12


def _run(stats, leaves=None, records=(), config=FILE):
    trace = None if leaves is None else {
        "op_intervals": [[]], "modules": {"jit_decode_block": {"n": 2.0, "s": 0.8}}, "ops": {},
        "windows": [(0, 10**9)], "slice_s": (0.0, 1.0)}
    run = types.SimpleNamespace(stats=stats, trace=trace, config=config, device_kind="TPU v5e", records=list(records),
                                traced=(0.0, 1.0), cell={"workload": {"name": CELL}})
    if leaves is not None:
        run.sparse_leaves = (32, leaves) if leaves else None  # what `_sparse.leaf_seconds` keeps on the run
    return run


def test_the_new_readers_give_nothing_on_a_program_without_the_leaves_or_counters():
    """A parent commit's stats have no `sparse` and its trace no `index_*`
    path; another family's file has no `sa_config`: each reader returns None
    and does not raise, and the line leaves the metric out."""
    plain = {"decode_steps": 8, "max_slots": 16, "decode_block_size": 16}
    stats = {e: dict(plain) for e in ("open", "close", "trace_start", "trace_stop")}
    traced = (sparse_walk_roofline, index_score_roofline, sparse_walk_ms_per_step, index_score_ms_per_step,
              index_select_ms_per_step)
    for reader in traced:
        assert reader.read(_run(stats)) is None  # no trace
        assert reader.read(_run(stats, leaves={})) is None  # a trace with none of the leaves
    assert sparse_rows_share.read(_run(stats)) is None
    kanana = spec.load_json(os.path.join(spec.ROOT, "acpbench/configs/kanana2-30b-a3b-bf16-v5e1-ep16.json"))
    other = _run(stats, config=kanana)
    other.trace = {"op_intervals": [[]], "modules": {}, "ops": {}, "windows": [], "slice_s": (0.0, 1.0)}
    assert all(reader.read(other) is None for reader in traced)


def test_the_leaves_are_found_by_path_inside_decode_runs():
    base = "jit(decode_block)/while/body/closed_call/while/body/closed_call/acp.attn/"
    tables = {"/device:TPU:0": {
        (7, "%fusion.1 = x"): Op(base + "index_scores/gather", "", "loop fusion"),
        (7, "%fusion.2 = x"): Op(base + "index_scores/dot_general", "", "convolution fusion"),
        (7, "%fusion.3 = x"): Op(base + "index_select/top_k", "", "custom-call"),
        (7, "%fusion.4 = x"): Op(base + "sparse_walk/gather;" + base + "sparse_walk/select_n", "", "loop fusion"),
        (7, "%fusion.5 = x"): Op(base + "index_proj/dot_general", "", "convolution fusion"),
        (7, "%fusion.6 = x"): Op(base + "attn_qkv/dot_general", "", "convolution fusion"),
        (9, "%fusion.1 = x"): Op("jit(prefill_and_sample)/acp.attn/prefill_attention/index_scores/dot", "", "convolution fusion"),
    }}
    runs = [("/device:TPU:0", [(0, 1000, "jit_decode_block", 7), (1000, 2000, "jit_prefill_and_sample", 9)])]
    ops = [[(10, 110, "%fusion.1 = x"), (200, 250, "%fusion.2 = x"), (300, 330, "%fusion.3 = x"),
            (400, 460, "%fusion.4 = x"), (500, 505, "%fusion.5 = x"), (600, 700, "%fusion.6 = x"),
            (1100, 1900, "%fusion.1 = x")]]
    found = _sparse.by_leaf(ops, runs, tables, _sparse.LEAVES)
    assert found == pytest.approx({"index_proj": 5e-9, "index_scores": 150e-9, "index_select": 30e-9, "sparse_walk": 60e-9})


def test_the_readers_hold_each_leafs_time_against_what_it_must_read():
    snap = lambda steps: {"decode_steps": steps, "max_slots": 16, "decode_block_size": 16}  # noqa: E731
    stats = {"open": snap(0), "trace_start": snap(160), "trace_stop": snap(192), "close": snap(1600)}
    live = [types.SimpleNamespace(first_t=0.0, last_t=2.0, prompt_len=n, blocks=[]) for n in (20000, 14000)]
    leaves = {"index_proj": 0.001, "index_scores": 0.020, "index_select": 0.012, "sparse_walk": 0.040}
    run = _run(stats, leaves=leaves, records=live)
    assert sparse_walk_ms_per_step.read(run) == pytest.approx(40 / 32)
    assert index_score_ms_per_step.read(run) == pytest.approx(20 / 32)
    assert index_select_ms_per_step.read(run) == pytest.approx(12 / 32)
    need = sparse_walk.bytes_per_step([20000, 14000], **SIZES) / 819e9
    assert sparse_walk_roofline.read(run) == pytest.approx(100 * need * 32 / 0.040)
    need = index_scores.bytes_per_step([20000, 14000], index_head_dim=64, n_layers=8) / 819e9
    assert index_score_roofline.read(run) == pytest.approx(100 * need * 32 / 0.020)
    assert 0 < sparse_walk_roofline.read(run) < 100 and 0 < index_score_roofline.read(run) < 100


def test_the_prefill_readers_hold_the_mask_and_the_kernel_against_the_prompts_of_the_slice():
    """Two prompts whose first token came inside the slice (a third came
    after it): the mask's three leaves and the kernel's seconds over their
    34,000 tokens, and the kernel against the KEPT pairs of the real tokens
    (a query's 2,048 chosen rows, the least any implementation must attend
    over) at the bf16 peak; the leaves are summed over the PREFILL runs
    (`prefills_as_decode` hands them to `by_leaf` under the decode block's
    name and the decode runs under none)."""
    snap = {"decode_steps": 0, "max_slots": 16, "decode_block_size": 16}
    stats = {e: dict(snap) for e in ("open", "close", "trace_start", "trace_stop")}
    records = [types.SimpleNamespace(first_t=t, last_t=9.0, prompt_len=n, blocks=[]) for t, n in
               ((0.2, 20000), (0.7, 14000), (1.5, 24000))]
    run = _run(stats, leaves={"index_scores": 0.001}, records=records)
    run.sparse_mask = {"index_scores": 0.30, "index_select": 0.25, "sparse_mask": 0.05}
    run.trace["ops"] = {"%masked_prefill_attention.3 = bf16[4,8,24576,128]": 0.5, "%fusion.7 = x": 3.0}
    assert prefill_mask_ms_per_ktok.read(run) == pytest.approx(600 / 34)
    assert prefill_attention_ms_per_ktok.read(run) == pytest.approx(500 / 34)
    kept = 2 * (2048 * 2049 // 2) + (20000 - 2048 + 14000 - 2048) * 2048
    flops = kept * 4 * 128 * 32 * 8
    assert masked_attention.pairs([20000, 14000], 2048) == kept and masked_attention.pairs([100], 2048) == 100 * 101 // 2
    assert masked_attention.flops([20000, 14000], topk=2048, heads=32, head_dim=128, n_layers=8) == flops
    assert masked_prefill_attention_roofline.read(run) == pytest.approx(100 * flops / 197e12 / 0.5)
    assert 0 < masked_prefill_attention_roofline.read(run) < 100
    # the rows once and no mask (an implementation's, not the least): far under the operations' time
    sizes = dict(heads=32, kv_heads=4, head_dim=128, n_layers=8)
    assert masked_attention.bytes_([1000], **sizes) == 1000 * 72 * 128 * 2 * 8
    assert masked_attention.bytes_([24576], **sizes) / 819e9 < 0.2 * masked_attention.flops([24576], topk=2048, heads=32, head_dim=128, n_layers=8) / 197e12
    # the prompts are those whose prefill RAN in the slice: two whole prefill runs, ended at 0.65 s and 1.45 s on the
    # benchmark's clock, are the requests whose first tokens came next after each (0.7 s and, past the slice's end,
    # 1.5 s); the request whose first token came at 0.2 s was prefilled before the slice began
    run.trace["windows"] = [(5_000_000_000, 6_000_000_000)]
    runs = [("/device:TPU:0", [(4_200_000_000, 4_900_000_000, "jit_prefill_and_sample", 9),  # cut by the slice's start
                               (5_100_000_000, 5_650_000_000, "jit_prefill_and_sample", 9),
                               (5_650_000_000, 5_900_000_000, "jit_decode_block", 7),
                               (5_950_000_000, 6_450_000_000, "jit_prefill_and_sample", 9)])]
    assert _sparse.prefill_ends(run, runs) == pytest.approx([0.65])  # the last is cut by the slice's end
    run.sparse_prefill_ends = [0.65, 1.45]
    assert _sparse.prompt_lengths(run) == [14000, 24000]
    del run.sparse_prefill_said  # said again, with the pairs
    assert prefill_mask_ms_per_ktok.read(run) == pytest.approx(600 / 38)
    run.sparse_prefill_ends = [0.65, 9.5]  # a run no request follows: the window's rule again
    assert _sparse.prompt_lengths(run) == [20000, 14000]
    del run.sparse_prefill_ends
    # nothing to read: no kernel in the slice, no mask leaves, no prompt in the slice, another family's file
    run.trace["ops"] = {"%fusion.7 = x": 3.0}
    assert prefill_attention_ms_per_ktok.read(run) is None and masked_prefill_attention_roofline.read(run) is None
    bare = _run(stats, leaves={"index_scores": 0.001}, records=records)
    bare.sparse_mask = None
    assert prefill_mask_ms_per_ktok.read(bare) is None and prefill_mask_ms_per_ktok.read(_run(stats)) is None
    runs = [("/device:TPU:0", [(0, 10, "jit_decode_block", 7), (10, 20, "jit_prefill_and_sample", 9), (20, 30, "jit_other", 3)])]
    assert [name for _s, _e, name, _p in _sparse.prefills_as_decode(runs)[0][1]] == ["other", "decode_block", "other"]


def test_the_counter_reader_takes_the_sparse_deltas_over_the_window():
    snap = lambda chosen, dense: {"decode_steps": 0, "sparse": {"decode": {"rows_chosen": chosen, "rows_dense": dense}}}  # noqa: E731
    run = _run({"open": snap(1000, 9000), "close": snap(1000 + 2048 * 50, 9000 + 19000 * 50)})
    assert sparse_rows_share.read(run) == pytest.approx(100 * 2048 / 19000)
    assert sparse_rows_share.read(_run({"open": snap(5, 6), "close": snap(5, 6)})) is None  # no step in the window


def test_the_family_is_found_by_name_and_documents_its_controls():
    family = spec.family(FILE)
    assert family.__name__ == "acpbench.families.keyevl"
    for name in ("int8", "bf16", "bf16_rest", "recent", "w_one", "topk_half", "index_rope_off", "index_norm_off", "dense",
                 "ik_int8", "kv_int8", "ik_crossed", "pages_crossed"):
        assert name in family.__doc__
    with pytest.raises(ValueError, match="no control 'int4'"):
        family.reference_logits(FILE, {"embed": 0}, [[0]], [[0]], lower="int4")
    with pytest.raises(ValueError, match="bfloat16 weights only"):
        family.weights(dict(FILE, engine=dict(FILE["engine"], quantize="int8")), None, None, 0)
    # the controls of the CHOICE are read with the fault planted in the program, judged as a run is; the reference
    # stands in the program's place under the controls of precision alone, given the same choices
    planted = {"planted_" + fault for fault in ("recent", "w_one", "topk_half", "index_rope_off", "index_norm_off", "dense")}
    assert set(keyevl_study.CACHE) == {"ik_int8", "kv_int8", "ik_crossed"} | planted and keyevl_study.CONFIG == NAME
    assert set(keyevl_study.REFERENCE) == {"ref_int8", "ref_bf16", "ref_bf16_rest"}
    assert all(fault in keyevl_reference.CONTROLS for fault in family.CHOICE_FAULTS)
    with pytest.raises(ValueError, match="no fault 'rope_twice'"):
        family.cache_readings(FILE, family.program_config(FILE), None, None, {}, False, indexer="rope_twice")
    with pytest.raises(SystemExit, match="unknown readings"):
        keyevl_study.main(["--readings", "ref_fp4"])
    text = open(keyevl_reference.__file__).read()
    assert "import agentcontrolplane_tpu" not in text and "from agentcontrolplane_tpu" not in text
    assert 'HI = jax.lax.Precision.HIGHEST' in text and "float32" in keyevl_reference.__doc__
    assert json.dumps(FILE)  # plain JSON all the way down


# -- the harness end to end on the CPU at a tiny size; nothing here is a device metric ----------------


@pytest.fixture(scope="module")
def rehearsal():
    config = spec.load_json(os.path.join(DATA, "tiny-config-keye.json"))
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
    assert 0 < got["sparse_rows_share"]["value"] < 100 and 0 < got["moe_experts_read_share"]["value"] <= 100
    got = runner.read_metrics(BENCH, "end_to_end", run)
    assert set(got) == {"tokens_per_s_per_chip", "setup_s"} and all(v["value"] > 0 for v in got.values())


def test_the_sparse_counters_count_over_the_window(rehearsal):
    stats = rehearsal[0].stats
    a, b = stats["open"]["sparse"], stats["close"]["sparse"]
    assert (b["topk"], b["layers"], b["ik_row_bytes_stored"]) == (8, 3, 128 * 2)  # bfloat16, a lane tile stored
    steps = stats["close"]["decode_steps"] - stats["open"]["decode_steps"]
    # a snapshot taken while a block is in flight reads the device's counters a block (4 steps) ahead of the host's count
    ran = b["decode"]["steps"] - a["decode"]["steps"]
    assert steps > 0 and abs(ran - steps) <= 4, (ran, steps)
    chosen, dense = (b["decode"][k] - a["decode"][k] for k in ("rows_chosen", "rows_dense"))
    assert 0 < chosen < dense and chosen <= ran * 4 * 8 * 3  # at most lanes x topk x layers a step


def test_outputs_agree_with_the_reference_and_the_choices_are_held_to_their_limits(rehearsal, capsys):
    ok, lines = rehearsal[2]
    for name in ("logit_rel_rms=", "cache_excess=", "greedy_regret="):
        assert any(line.startswith(name) and "limit=" in line for line in lines)
    assert any(line.startswith("stream_mismatch=0 limit=0 ok") for line in lines)
    assert lines[0] == "finite=True" and ok, lines
