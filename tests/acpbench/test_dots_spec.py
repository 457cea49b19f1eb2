"""The dots3-note-prev configuration's file against the catalog's keys, its
cuts and their arithmetic, the mix, the cell, the kernels' counts and the
eight readers on recorded spans: what `test_keyevl_spec.py` asserts of the
Keye-VL file, for this family's own facts; and the harness end to end on the
CPU at a tiny size, the indexer's and the rings' counters read over the
window. Every entry of `BENCHMARK.json` is looked up by name, and the cell is
asserted to be IN the lists it joins: no place and no count of a list is
asserted, so a later PR appends after these."""

import json
import os
import types

import jax
import pytest

from acpbench import run as runner
from acpbench import spec
from acpbench.device_scopes import Op
from acpbench.families import dots_reference, dots_study
from acpbench.kernels import index_scores, ring_latent, sparse_latent
from acpbench.layer_metrics import (
    _dots, latent_index_ms_per_step, latent_index_score_roofline, latent_mask_prefill_ms_per_ktok, ring_latent_ms_per_step,
    ring_latent_roofline, sparse_latent_ms_per_step, sparse_latent_roofline, sparse_latent_rows_share,
)
from acpbench.layer_metrics._loops import by_leaf
from acpbench.systems.engine import CompileCounter, System

BENCH = spec.benchmark()
NAME, CELL = "dots3-note-prev-bf16-v5e1-ep16", "dots3-ep16-decode-sparse-latent"
CONF = next(c for c in BENCH["configs"] if c["name"] == NAME)
FILE = spec.load_json(os.path.join(spec.ROOT, CONF["file"]))
DATA = os.path.join(os.path.dirname(__file__), "data")
CUT = {"num_hidden_layers", "layer_types", "num_experts_held", "vocab_size"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json as the catalog has it
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False, "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048,
    "intermediate_size": 13824, "kv_lora_rank": 512, "layer_types": ["full_attention"] * 2 + PERIOD * 11,
    "max_position_embeddings": 524288, "model_type": "dots3_note", "moe_intermediate_size": 1536, "moe_layer_freq": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 46, "num_key_value_heads": 128, "q_lora_rank": 1024,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid", "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64, "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64, "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152064,
}


def test_the_file_keeps_every_published_key_and_cuts_no_width():
    if os.path.exists(CATALOG):  # the catalog itself, where the guide is installed
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "dots3-note-prev")
        assert row["config"] == PUBLISHED and row["source_url"] == CONF["source"]
    assert FILE["source"] == CONF["source"] and set(FILE["reduced"]) == set(CONF["reduced"]) == CUT
    assert {k: FILE[k] for k in PUBLISHED if k not in CUT} == {k: v for k, v in PUBLISHED.items() if k not in CUT}
    assert (FILE["num_hidden_layers"], FILE["num_experts_held"], FILE["vocab_size"]) == (5, 16, 19008)
    # the published list's first five: the dense full layer, then one whole period of expert layers (1 full : 3 sliding)
    assert FILE["layer_types"] == PUBLISHED["layer_types"][:5] == ["full_attention"] * 2 + ["sliding_attention"] * 3
    assert 152064 // 8 == 19008 and 256 // 16 == 16 and FILE["num_experts_held"] >= FILE["num_experts_per_tok"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in CUT)
    assert set(FILE["reduced_why"]) == CUT
    for key, published in (("num_hidden_layers", "46"), ("num_experts_held", "256"), ("vocab_size", "152,064")):
        assert published in FILE["reduced_why"][key], key
    assert "1.4 times" in FILE["reduced_why"]["num_hidden_layers"]  # what the cut distorts, said in the file
    assumed = FILE["assumed"]
    assert {"attention_gate", "qkv_lora_rescale", "indexer", "indexer_rope", "indexer_rotation_and_fp8", "tie_break",
            "window_edge", "latent_norms", "rope", "router", "tokenizer", "towers_and_mtp", "not_read"} <= set(assumed)
    for key, control in (("attention_gate", "gate_off"), ("qkv_lora_rescale", "rescale_off"), ("indexer", "index_norm_off"),
                         ("indexer_rope", "index_rope_off"), ("window_edge", "ring_short")):
        assert control in assumed[key], key  # each assumed form names the control that sees it
    assert "arXiv:2505.06708" in assumed["attention_gate"] and "LongCat-Flash" in assumed["qkv_lora_rescale"]
    assert "4 x 4 v5e slice" in FILE["deployment"] and "pipeline stages" in FILE["deployment"]
    assert "not served" in FILE["deployment"] and "quantize" not in FILE["engine"]
    named = " ".join(FILE["engine_departures"])
    assert all(key in named for key in set(FILE["engine"]) - {"page_size"}), named
    e = FILE["engine"]
    assert (e["max_slots"], e["max_ctx"], e["kv_pages"], e["page_size"]) == (32, 20480, 32 * 1280 + 1, 16)
    assert e["prefill_buckets"] == [12288, 16384] and e["width_buckets"] == [32] and e["decode_block_size"] == 16
    assert e["prefill_batch_max"] == 1 and (e["prefix_cache_entries"], e["prefix_dedup"]) == (0, False)
    assert all(b % 512 == 0 for b in e["prefill_buckets"])  # whole blocks of the mask, of the kernel and of the band
    c = FILE["check"]
    assert c["prefill_bucket"] in e["prefill_buckets"] and c["decode_steps"] == 16
    # every compared row lies past the choice's count and the window, so both the choice and the ring's wrap are compared
    assert c["min_prompt"] > FILE["index_topk"] + FILE["sliding_window_size"]
    assert c["sequences"] >= 2 and c["engine_tokens"] <= c["decode_steps"]
    assert set(c["limits"]) == {"logit_rel_rms", "cache_excess", "greedy_regret", "stream_mismatch"}
    assert set(c["select_limits"]) == {"select_miss_prefill", "select_miss_decode", "missed_weight", "select_cache_miss"}
    program = spec.family(FILE).program_config(FILE)
    assert (program.dim, program.n_heads, program.swa_n_heads, program.n_layers) == (5120, 128, 64, 5)
    assert (program.full.row_width, program.full.row_stored, program.swa.row_width, program.swa.row_stored) == (576, 640, 1088, 1152)
    assert (program.vocab_size, len(program.held), program.n_experts, program.experts_per_token) == (19008, 16, 256, 8)
    assert (program.index_heads, program.index_head_dim, program.index_topk, program.sliding_window_size) == (64, 128, 2048, 513)
    assert (program.rope_theta, program.swa_rope_theta, program.max_seq_len) == (8e7, 5e4, 524288)
    with pytest.raises(ValueError, match="serves attention_gate_type"):
        spec.family(FILE).program_config(dict(FILE, attention_gate_type="elementwise"))
    with pytest.raises(ValueError, match="serves apply_mla_qkv_lora_rescale"):
        spec.family(FILE).program_config(dict(FILE, apply_mla_qkv_lora_rescale=False))


def test_the_resident_set_is_the_issues_arithmetic_and_over_a_quarter_of_the_chip():
    d, f = FILE["hidden_size"], FILE["moe_intermediate_size"]

    def attention(prefix, indexer):
        H, nope, rope, v = (FILE[prefix + k] for k in ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
        qr, r = FILE[prefix + "q_lora_rank"], FILE[prefix + "kv_lora_rank"]
        n = d * qr + qr * H * (nope + rope) + d * (r + rope) + r * H * (nope + v) + H * v * d + d * H
        if indexer:
            n += qr * FILE["index_n_heads"] * FILE["index_head_dim"] + d * FILE["index_head_dim"] + d * FILE["index_n_heads"]
        return n

    full, sliding, expert = attention("", True), attention("swa_", False), 3 * d * f
    dense, beside = 3 * d * FILE["intermediate_size"], d * FILE["n_routed_experts"] + expert
    assert round(full / 1e6, 2) == 144.05 and round(sliding / 1e6, 1) == 90.8 and round(expert / 1e6, 2) == 23.59
    assert round(dense / 1e6, 1) == 212.3 and round(beside / 1e6, 1) == 24.9
    whole = 13 * full + 33 * sliding + dense + 45 * (beside + 256 * expert) + 2 * 152064 * d
    assert 279.4e9 < whole < 279.8e9
    held = FILE["num_experts_held"]
    params = (full + dense) + (full + beside + held * expert) + 3 * (sliding + beside + held * expert) + 2 * FILE["vocab_size"] * d
    assert 2.575e9 < params < 2.580e9
    e = FILE["engine"]
    token = (640 + 128) * 2  # a full layer's latent row and indexer key as the chip stores them
    pool = e["kv_pages"] * e["page_size"] * token * 2
    rings = (e["max_slots"] + 1) * 34 * e["page_size"] * 1152 * 2 * 3
    assert token == 1536 and 2.0e9 < pool < 2.02e9 and 0.12e9 < rings < 0.13e9
    assert 0.44 * 16e9 < 2 * params + pool + rings < 0.47 * 16e9
    import jax.numpy as jnp

    from agentcontrolplane_tpu import models

    program = spec.family(FILE).program_config(FILE)
    assert models.page_bytes(program, e["page_size"]) * e["kv_pages"] == pool  # the rings are no part of a page's cost
    weights = jax.eval_shape(lambda: models.programs(program).init_params(program, jax.random.key(0)))
    assert abs(sum(x.size for x in jax.tree_util.tree_leaves(weights)) - params) < 0.3e6  # the norms' weights and biases
    assert all(x.dtype == jnp.bfloat16 for k, x in jax.tree_util.tree_leaves_with_path(weights) if "router_bias" not in str(k))
    cache = jax.eval_shape(lambda: models.programs(program).init_paged_cache(program, e["kv_pages"], 16, max_slots=32))
    assert cache["wkv"].shape == (3, 33 * 34, 16, 1152) and cache["wkv"].size * 2 == rings


def test_the_mix_is_what_the_issue_names():
    found = spec.cell(BENCH, CELL)
    mix = found["mix"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == FILE["engine"]["max_slots"] == 32
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 8192, "max": 16384}
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 2048, "max": 4096}
    assert (mix["temperature"], mix["prompt_vocab"], mix["shape_seed"]) == (0.7, 256, 61)
    assert "top_k" not in mix and "top_p" not in mix
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] <= FILE["engine"]["max_ctx"]
    assert mix["prompt_tokens"]["min"] > FILE["index_topk"] + FILE["sliding_window_size"]  # every decode row leaves rows out
    assert mix["prompt_tokens"]["max"] <= max(FILE["engine"]["prefill_buckets"]) and FILE["ignore_stop_tokens"]
    assert all(key in mix for key in ("ramp_s_why", "trace_seconds_why", "warmup_seconds_why", "who"))
    assert found["workload"]["chips"] == 1 and found["workload"]["traffic"] == "decode-sparse-latent"
    assert len(found["workload"]["why"]) <= 200 and len(CONF["why"]) <= 200


def _replayed_tokens_per_s(mix, ramp_s, block_s, prefill_s=(0.5855, 0.8862), seconds=51.0):
    """The mix's one trace replayed on the program's measured times (PERF.md, PR 61, call 62c: a prefill by its bucket,
    a decode block of 16 steps; the engine prefills everything that waits, one prompt a dispatch, then runs a block),
    counted as the benchmark counts: the window's tokens a second."""
    from collections import deque

    from acpbench import metrics
    from acpbench.generators import closed_loop
    from acpbench.loadgen import Record

    clients = closed_loop.plan(dict(mix, prompt_vocab=2), 1, seconds, {})["clients"]
    cursor, records, waiting, slots, t = [0] * len(clients), [], deque(), {}, 0.0115

    def send(k, now):
        req = clients[k][cursor[k] % len(clients[k])]
        cursor[k] += 1
        records.append(Record(idx=len(records), due=now, sent=now, client=k, prompt_len=len(req["prompt"]), max_tokens=req["max_tokens"]))
        waiting.append(records[-1])

    for k in range(len(clients)):
        send(k, 0.0)
    window = (0.2 + ramp_s, 0.2 + ramp_s + seconds)
    while t < window[1] + 1:
        while waiting and len(slots) < len(clients):
            rec = waiting.popleft()
            t += prefill_s[rec.prompt_len > 12288]
            rec.first_t, rec.n_tokens = t, 1
            rec.blocks.append((t, 1))
            slots[rec.idx] = rec
        t += block_s
        for rec in list(slots.values()):
            n = min(16, rec.max_tokens - rec.n_tokens)
            rec.n_tokens += n
            rec.blocks.append((t, n))
            if rec.n_tokens >= rec.max_tokens:
                del slots[rec.idx]
                send(rec.client, t)
    return metrics.tokens_in_window(records, window) / seconds


@pytest.mark.parametrize("block_ms", [198.0, 199.5, 201.0])
def test_the_windows_edges_fall_inside_decode_stretches_so_a_delayed_trace_counts_the_same(block_ms):
    """The mix is one trace: a prefill 50-90 ms long or a slower seed only DELAYS it against the window. With both
    edges inside stretches of decode blocks a delay moves nothing; with one at a prefill's end (`ramp_s` 26, as first
    measured) 0.1 s moved the count 0.26%, and six seeds spread past half the metric's bound."""
    mix = spec.cell(BENCH, CELL)["mix"]
    at = lambda delay: _replayed_tokens_per_s(mix, mix["ramp_s"] - delay, block_ms / 1e3)  # noqa: E731
    counts = [at(delay) for delay in (-0.2, -0.1, 0.0, 0.1, 0.2, 0.3, 0.4)]
    assert max(counts) - min(counts) < 0.001 * counts[2], counts
    assert 1500 < counts[2] < 1700
    first = _replayed_tokens_per_s(mix, 26.0, block_ms / 1e3)
    assert abs(_replayed_tokens_per_s(mix, 26.0 - 0.2, block_ms / 1e3) - first) > 0.003 * first  # what 26 did


def test_the_cell_is_in_the_lists_it_joins_and_brings_eight_metrics():
    names = {m["name"] for m in spec.metrics_for(BENCH, CELL, "per_layer")}
    joined = {"batch_occupancy", "preemptions", "gap_p50_ms.saturated", "decode_step_ms.throughput", "host_ms_per_block",
              "idle_named_share", "uploads_per_block", "step_ms.attn", "step_ms.ffn", "step_ms.head", "step_ms.sample",
              "step_ms.other", "device_named_share", "moe_gmm_roofline", "moe_experts_read_share"}
    joined |= {f"idle_ms_per_block.{p}" for p in ("admit", "launch", "fetch", "commit", "publish")}
    # NOT the nine `setup_*` lists, where ISSUE 61 put it: `tests/acpbench/test_setup_metrics.py` pins each to the first
    # ten cells (ROADMAP W1 (b)); the run's `[setup]` line has the numbers all the same
    assert not names & {f"setup_{p}" for p in ("trace_s", "lower_s", "compile_s", "cache_load_s", "first_run_s",
                                               "engine_init_s", "prewarm_rest_s", "programs", "cache_misses")}
    new = {"sparse_latent_roofline": ("%", "device_trace", "kernels", "higher"),
           "sparse_latent_ms_per_step": ("ms", "device_trace", "programs", "lower"),
           "latent_index_score_roofline": ("%", "device_trace", "kernels", "higher"),
           "latent_index_ms_per_step": ("ms", "device_trace", "programs", "lower"),
           "ring_latent_roofline": ("%", "device_trace", "kernels", "higher"),
           "ring_latent_ms_per_step": ("ms", "device_trace", "programs", "lower"),
           "sparse_latent_rows_share": ("%", "program_counter", "KV manager", "lower"),
           "latent_mask_prefill_ms_per_ktok": ("ms", "device_trace", "programs", "lower")}
    assert names >= joined | set(new)
    # no kernel walks here, the new leaves are no part of `device_scopes.LEAVES` (their time would read as glue), and
    # the other sparse family's readers key on its own file's keys
    assert not names & {"page_walk_roofline", "glue_ms_per_step", "step_ms.mixer", "latent_walk_roofline", "window_walk_roofline",
                        "sparse_walk_roofline", "index_score_roofline", "sparse_rows_share", "window_rows_share"}
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


LATENT = dict(topk=2048, row_values=576, n_layers=2)
RING = dict(window=513, row_values=1088, n_layers=3)


def test_the_kernels_count_the_rows_values_not_the_stored_width_or_the_ring():
    # chosen rows x 576 values x 2 B a lane and full layer; a lane under topk chooses what it has, its own row among it
    assert sparse_latent.bytes_per_step([19000], **LATENT) == 2048 * 576 * 2 * 2
    assert sparse_latent.bytes_per_step([100, 0, 5000], **LATENT) == (101 + 2048) * 576 * 2 * 2
    flops = sparse_latent.flops_per_step([19000], heads=128, value_width=512, **LATENT)
    assert flops == 2048 * 2 * 128 * (576 + 512) * 2 and 4 * 128 * 544 == 2 * 128 * (576 + 512)
    assert 240 < flops / sparse_latent.bytes_per_step([19000], **LATENT) < 243  # on the chip's ridge of 240
    # min(len + 1, 513) rows of 1,088 values a sliding layer: not the 34 pages of the ring, not 1,152
    assert ring_latent.bytes_per_step([19000, 0, 100], **RING) == (513 + 101) * 1088 * 2 * 3
    assert ring_latent.flops_per_step([19000], heads=64, value_width=1024, **RING) == 513 * 2 * 64 * (1088 + 1024) * 3
    # the indexer at this file's sizes through the other sparse family's count: 128 values a key, 64 heads of 128
    assert index_scores.bytes_per_step([19000, 0, 1000], index_head_dim=128, n_layers=2) == 20000 * 128 * 2 * 2
    assert index_scores.flops_per_step([19000], index_heads=64, index_head_dim=128, n_layers=2) == 19000 * 2 * 64 * 128 * 2
    # the issue's step at 32 lanes of ~14k rows: chosen rows 0.15 GB, index keys 0.23 GB, rings 0.11 GB
    lanes = [14000] * 32
    assert round(sparse_latent.bytes_per_step(lanes, **LATENT) / 1e9, 2) == 0.15
    assert round(index_scores.bytes_per_step(lanes, index_head_dim=128, n_layers=2) / 1e9, 2) == 0.23
    assert round(ring_latent.bytes_per_step(lanes, **RING) / 1e9, 2) == 0.11


def _run(stats, leaves=None, records=(), config=FILE):
    trace = None if leaves is None else {
        "op_intervals": [[]], "modules": {"jit_decode_block": {"n": 2.0, "s": 0.8}}, "ops": {},
        "windows": [(0, 10**9)], "slice_s": (0.0, 1.0)}
    run = types.SimpleNamespace(stats=stats, trace=trace, config=config, device_kind="TPU v5e", records=list(records),
                                traced=(0.0, 1.0), cell={"workload": {"name": CELL}})
    if leaves is not None:
        run.dots_leaves = (32, leaves) if leaves else None  # what `_dots.leaf_seconds` keeps on the run
        run.dots_mask = None
    return run


TRACED = (sparse_latent_roofline, sparse_latent_ms_per_step, latent_index_score_roofline, latent_index_ms_per_step,
          ring_latent_roofline, ring_latent_ms_per_step, latent_mask_prefill_ms_per_ktok)


def test_the_new_readers_give_nothing_on_a_program_without_the_leaves_or_counters():
    """A parent commit's stats have no `sparse` beside `window` and its trace
    no `sparse_latent` path; another family's file has no `swa_kv_lora_rank`:
    each reader returns None and does not raise, and the line leaves the
    metric out."""
    plain = {"decode_steps": 8, "max_slots": 32, "decode_block_size": 16}
    stats = {e: dict(plain) for e in ("open", "close", "trace_start", "trace_stop")}
    for reader in TRACED:
        assert reader.read(_run(stats)) is None  # no trace
        assert reader.read(_run(stats, leaves={})) is None  # a trace with none of the leaves
    assert sparse_latent_rows_share.read(_run(stats)) is None
    keye_only = {e: {**plain, "sparse": {"decode": {"rows_chosen": 5 * i, "rows_dense": 9 * i}}} for i, e in enumerate(stats)}
    assert sparse_latent_rows_share.read(_run(keye_only)) is None  # the other sparse family's counters, no ring's
    for other in ("keye-vl2-30b-a3b-bf16-v5e1-ep8", "kanana2-30b-a3b-bf16-v5e1-ep16", "mellum2-12b-a2.5b-bf16-v5e1-ep4"):
        config = spec.load_json(os.path.join(spec.ROOT, f"acpbench/configs/{other}.json"))
        run = _run(stats, config=config)
        run.trace = {"op_intervals": [[]], "modules": {}, "ops": {}, "windows": [], "slice_s": (0.0, 1.0)}
        assert all(reader.read(run) is None for reader in TRACED), other


def test_the_leaves_are_found_by_path_inside_decode_runs():
    base = "jit(decode_block)/while/body/closed_call/pjit/acp.attn/"
    tables = {"/device:TPU:0": {
        (7, "%fusion.1 = x"): Op(base + "index_scores/gather", "", "loop fusion"),
        (7, "%fusion.2 = x"): Op(base + "index_scores/dot_general", "", "convolution fusion"),
        (7, "%fusion.3 = x"): Op(base + "index_select/top_k", "", "custom-call"),
        (7, "%fusion.4 = x"): Op(base + "sparse_latent/gather;" + base + "sparse_latent/select_n", "", "loop fusion"),
        (7, "%fusion.5 = x"): Op(base + "ring_latent/gather", "", "loop fusion"),
        (7, "%fusion.6 = x"): Op(base + "mla_absorb/dot_general", "", "convolution fusion"),
        (7, "%fusion.7 = x"): Op(base + "attn_qkv/dot_general", "", "convolution fusion"),
        (9, "%fusion.1 = x"): Op("jit(prefill_and_sample)/acp.attn/prefill_attention/sparse_mask/index_scores/dot", "", "convolution fusion"),
    }}
    runs = [("/device:TPU:0", [(0, 1000, "jit_decode_block", 7), (1000, 2000, "jit_prefill_and_sample", 9)])]
    ops = [[(10, 110, "%fusion.1 = x"), (200, 250, "%fusion.2 = x"), (300, 330, "%fusion.3 = x"),
            (400, 460, "%fusion.4 = x"), (500, 520, "%fusion.5 = x"), (600, 610, "%fusion.6 = x"), (700, 800, "%fusion.7 = x"),
            (1100, 1900, "%fusion.1 = x")]]
    found = by_leaf(ops, runs, tables, _dots.LEAVES)
    want = {"index_proj": 0.0, "index_scores": 150e-9, "index_select": 30e-9, "sparse_latent": 60e-9, "ring_latent": 20e-9,
            "mla_absorb": 10e-9, "attn_gate": 0.0}
    assert found == pytest.approx(want)


def test_the_readers_hold_each_leafs_time_against_what_it_must_read():
    snap = lambda steps: {"decode_steps": steps, "max_slots": 32, "decode_block_size": 16}  # noqa: E731
    stats = {"open": snap(0), "trace_start": snap(160), "trace_stop": snap(192), "close": snap(1600)}
    live = [types.SimpleNamespace(first_t=0.0, last_t=2.0, prompt_len=n, blocks=[]) for n in (16000, 9000)]
    leaves = {"index_proj": 0.001, "index_scores": 0.070, "index_select": 0.030, "sparse_latent": 0.100, "ring_latent": 0.009,
              "mla_absorb": 0.010, "attn_gate": 0.0005}
    run = _run(stats, leaves=leaves, records=live)
    assert sparse_latent_ms_per_step.read(run) == pytest.approx(100 / 32)
    assert latent_index_ms_per_step.read(run) == pytest.approx((70 + 30) / 32)
    assert ring_latent_ms_per_step.read(run) == pytest.approx(9 / 32)
    lens = [16000, 9000]
    need = max(sparse_latent.bytes_per_step(lens, **LATENT) / 819e9,
               sparse_latent.flops_per_step(lens, heads=128, value_width=512, **LATENT) / 197e12)
    assert sparse_latent_roofline.read(run) == pytest.approx(100 * need * 32 / 0.100)
    need = max(index_scores.bytes_per_step(lens, index_head_dim=128, n_layers=2) / 819e9,
               index_scores.flops_per_step(lens, index_heads=64, index_head_dim=128, n_layers=2) / 197e12)
    assert latent_index_score_roofline.read(run) == pytest.approx(100 * need * 32 / 0.070)
    need = ring_latent.bytes_per_step(lens, **RING) / 819e9
    assert ring_latent_roofline.read(run) == pytest.approx(100 * need * 32 / 0.009)
    for reader in (sparse_latent_roofline, latent_index_score_roofline, ring_latent_roofline):
        assert 0 < reader.read(run) < 100
    # the prefill's reader: the mask's three leaves and the kernel over the prompts whose first token came in the slice
    records = [types.SimpleNamespace(first_t=t, last_t=9.0, prompt_len=n, blocks=[]) for t, n in ((0.2, 16000), (0.7, 9000), (1.5, 12000))]
    run = _run(stats, leaves=leaves, records=records)
    run.dots_mask = {"index_scores": 0.05, "index_select": 0.02, "sparse_mask": 0.005}
    run.trace["ops"] = {"%masked_prefill_attention.3 = bf16[32,1,16384,128]": 0.6, "%fusion.7 = x": 3.0}
    assert latent_mask_prefill_ms_per_ktok.read(run) == pytest.approx((75 + 600) / 25)
    run.trace["ops"] = {"%fusion.7 = x": 3.0}
    assert latent_mask_prefill_ms_per_ktok.read(run) is None  # no kernel in the slice


def test_the_counter_reader_takes_the_sparse_deltas_of_a_program_that_counts_its_rings():
    snap = lambda chosen, dense, ring: {"decode_steps": 0, "sparse": {"decode": {"rows_chosen": chosen, "rows_dense": dense}},  # noqa: E731
                                        "window": {"decode": {"rows_read": ring}}}
    run = _run({"open": snap(1000, 9000, 7), "close": snap(1000 + 2048 * 50, 9000 + 14000 * 50, 7 + 513 * 50)})
    assert sparse_latent_rows_share.read(run) == pytest.approx(100 * 2048 / 14000)
    assert sparse_latent_rows_share.read(_run({"open": snap(5, 6, 7), "close": snap(5, 6, 7)})) is None  # no step in the window


def test_the_family_is_found_by_name_and_documents_its_controls():
    family = spec.family(FILE)
    assert family.__name__ == "acpbench.families.dots"
    for name in ("int8", "bf16", "bf16_rest", "bf16_free", "gate_off", "rescale_off", "recent", "dense", "index_rope_off",
                 "index_norm_off", "window_off", "shared_off", "ik_int8", "kv_int8", "wkv_int8", "ring_short", "ik_crossed"):
        assert name in family.__doc__, name
    with pytest.raises(ValueError, match="no control 'int4'"):
        family.reference_logits(FILE, {"embed": 0}, [[0]], [[0]], lower="int4")
    with pytest.raises(ValueError, match="bfloat16 weights only"):
        family.weights(dict(FILE, engine=dict(FILE["engine"], quantize="int8")), None, None, 0)
    planted = {"planted_" + fault for fault in ("recent", "topk_half", "index_rope_off", "index_norm_off")}
    assert set(dots_study.CACHE) == {"ik_int8", "kv_int8", "wkv_int8", "ring_minus_1", "ring_minus_page", "ik_crossed"} | planted
    assert dots_study.CONFIG == NAME
    assert {"ref_int8", "ref_bf16", "ref_bf16_rest", "ref_gate_off", "ref_rescale_off", "ref_bf16_free"} <= set(dots_study.REFERENCE)
    assert all(fault == "topk_half" or fault in dots_reference.CONTROLS for fault in family.CHOICE_FAULTS)
    with pytest.raises(SystemExit, match="unknown readings"):
        dots_study.main(["--readings", "ref_fp4"])
    text = open(dots_reference.__file__).read()
    assert "import agentcontrolplane_tpu" not in text and "from agentcontrolplane_tpu" not in text and "pallas" not in text
    assert 'HI = jax.lax.Precision.HIGHEST' in text and "float32" in dots_reference.__doc__
    assert json.dumps(FILE)  # plain JSON all the way down


def test_the_embeddings_rows_come_in_pairs_that_add_up_to_nothing():
    """`dots_weights`: row 2i + 1 is the negative of row 2i, so the rows of the traffic's prompt ids (an even range
    from 0) have no mean for layer 0's value rows to carry into every router (module text); nothing else of the
    draw is paired, and a seed still decides every row."""
    import jax.numpy as jnp
    import numpy as np

    from acpbench.families import dots_weights
    from agentcontrolplane_tpu.parallel.mesh import make_mesh

    tiny = spec.load_json(os.path.join(DATA, "tiny-config-dots.json"))
    program = spec.family(tiny).program_config(tiny)
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1])
    one, other = (dots_weights.make(program, mesh, seed) for seed in (61, 6_100_000_043))
    e = np.asarray(one["embed"].astype(jnp.float32))
    assert e.shape == (program.vocab_size, program.dim) and np.array_equal(e[1::2], -e[0::2][: len(e[1::2])])
    prompt_ids = min(program.vocab_size, spec.cell(BENCH, CELL)["mix"]["prompt_vocab"]) // 2 * 2
    assert np.abs(e[:prompt_ids].sum(0)).max() == 0.0 and np.abs(e[0]).max() > 0
    assert 0.8 < np.square(e).sum(-1).mean() < 1.25  # a row's size as before: normal times width**-0.5
    assert not np.array_equal(e, np.asarray(other["embed"].astype(jnp.float32)))
    head = np.asarray(one["lm_head"].astype(jnp.float32))
    assert not np.array_equal(head[:, 1::2], -head[:, 0::2])  # the head is its own draw: a token's logit and its pair's are not tied


@pytest.mark.parametrize("draw", range(6))
def test_the_selection_bias_moves_the_choice_and_leaves_the_held_experts_loads_to_no_seed(draw):
    """`dots_weights.BIAS_STD` beside 256 sigmoid scores of unit-variance logits, by the program's own router: the
    eight chosen differ with and without the bias for a fifth to a half of the rows; no expert's share of the rows
    leaves an even share by two fifths (the draw's 16,384 rows' own noise in it); and a decode step of 32 rows reads 62-65.5% of the 16 held experts whatever the
    draw (63.2% for an even router). At the 0.03 the cell was first measured with, that share ran from 52% to 62% by
    the seed, and the cell's tokens a second with it."""
    import jax.numpy as jnp
    import numpy as np

    from acpbench.families import dots_weights
    from agentcontrolplane_tpu.ops.moe import route_scores

    E, k, held, lanes = FILE["n_routed_experts"], FILE["num_experts_per_tok"], FILE["num_experts_held"], FILE["engine"]["max_slots"]
    logits = jax.random.normal(jax.random.key(61), (16384, E))
    bias = dots_weights.BIAS_STD * jax.random.normal(jax.random.key(draw), (E,))
    with_b, _ = route_scores(logits, k, "sigmoid", bias)
    without, _ = route_scores(logits, k, "sigmoid", None)
    moved = float(jnp.mean(jnp.any(jnp.sort(with_b, -1) != jnp.sort(without, -1), axis=-1)))
    assert 0.2 < moved < 0.5, moved
    load = np.bincount(np.asarray(with_b).ravel(), minlength=E) / (len(logits) * k / E)
    assert 0.6 < load.min() and load.max() < 1.4, (load.min(), load.max())
    steps = np.asarray(with_b).reshape(-1, lanes * k)
    read = np.mean([len(np.unique(row[row < held])) for row in steps]) / held
    assert 0.62 < read < 0.655, read


# -- the harness end to end on the CPU at a tiny size; nothing here is a device metric ----------------


@pytest.fixture(scope="module")
def rehearsal():
    config = spec.load_json(os.path.join(DATA, "tiny-config-dots.json"))
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
    assert 0 < got["sparse_latent_rows_share"]["value"] < 100 and 0 < got["moe_experts_read_share"]["value"] <= 100
    assert got["preemptions"]["value"] == 0
    got = runner.read_metrics(BENCH, "end_to_end", run)
    assert set(got) == {"tokens_per_s_per_chip", "setup_s"} and all(v["value"] > 0 for v in got.values())


def test_the_sparse_and_the_rings_counters_count_over_the_window(rehearsal):
    stats = rehearsal[0].stats
    a, b = stats["open"], stats["close"]
    assert (b["sparse"]["topk"], b["sparse"]["layers"], b["window"]["window"], b["window"]["window_layers"]) == (8, 2, 9, 3)
    steps = b["decode_steps"] - a["decode_steps"]
    # a snapshot taken while a block is in flight reads the device's counters a block (4 steps) ahead of the host's count
    ran = b["sparse"]["decode"]["steps"] - a["sparse"]["decode"]["steps"]
    assert steps > 0 and abs(ran - steps) <= 4, (ran, steps)
    chosen, dense = (b["sparse"]["decode"][k] - a["sparse"]["decode"][k] for k in ("rows_chosen", "rows_dense"))
    assert 0 < chosen < dense and chosen <= ran * 4 * 8 * 2  # at most lanes x topk x full layers a step
    read, whole = (b["window"]["decode"][k] - a["window"]["decode"][k] for k in ("rows_read", "rows_unwindowed"))
    assert 0 < read < whole and read <= ran * 4 * 9  # at most lanes x window a step, one sliding layer's


def test_outputs_agree_with_the_reference_and_the_choices_are_held_to_their_limits(rehearsal):
    ok, lines = rehearsal[2]
    for name in ("logit_rel_rms=", "cache_excess=", "greedy_regret="):
        assert any(line.startswith(name) and "limit=" in line for line in lines)
    assert any(line.startswith("stream_mismatch=0 limit=0 ok") for line in lines)
    assert lines[0] == "finite=True" and ok, lines
