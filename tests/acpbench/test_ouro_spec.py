"""The Ouro configuration's file, mix, cell, the loop's arithmetic and
readers: what `test_kanana_spec.py` asserts of the Kanana-2 file, for this
family's own facts; and the harness end to end on the CPU at a tiny size."""

import json
import os
import types

import jax
import pytest

from acpbench import device_scopes, run as runner
from acpbench import spec
from acpbench.families import ouro_reference, ouro_study
from acpbench.kernels import loop_weights, page_walk
from acpbench.layer_metrics import (
    _loops, loop_passes_per_token, loop_walk_ms_per_step, loop_walk_roofline, loop_weights_roofline,
)
from acpbench.systems.engine import CompileCounter, System

BENCH = spec.benchmark()
NAME, CELL = "ouro-2.6b-bf16-v5e1", "ouro-decode-reasoning"
CONF = next(c for c in BENCH["configs"] if c["name"] == NAME)
FILE = spec.load_json(os.path.join(spec.ROOT, CONF["file"]))
DATA = os.path.join(os.path.dirname(__file__), "data")
# https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json as the catalog has it
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
    "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152,
}
NEW = {"loop_walk_roofline": ("%", "higher", "device_trace", "kernels"),
       "loop_walk_ms_per_step": ("ms", "lower", "device_trace", "programs"),
       "loop_weights_roofline": ("%", "higher", "device_trace", "programs"),
       "loop_passes_per_token": ("count", "lower", "program_counter", "programs")}


def test_the_file_keeps_every_published_key_and_cuts_nothing():
    assert FILE["source"] == CONF["source"] and FILE["reduced"] == CONF["reduced"] == []
    assert {k: FILE[k] for k in PUBLISHED} == PUBLISHED
    assert {"cache_index", "output_norms", "loop_norm", "exit_gate", "all_loops_run", "head_dim", "tokenizer",
            "max_ctx"} <= set(FILE["assumed"])
    assert all("published modeling file" in FILE["assumed"][k] for k in
               ("cache_index", "output_norms", "loop_norm", "exit_gate"))
    assert "one v5e chip holds Ouro-2.6B whole" in FILE["deployment"] and "replicas" in FILE["deployment"]
    assert "quantize" not in FILE["engine"] and "bfloat16" in FILE["precision"]["weights"]
    assert "float32" in FILE["precision"]["activations"] and "192 cache layers" in FILE["precision"]["kv_pages"]
    named = " ".join(FILE["engine_departures"])
    assert all(key in named for key in set(FILE["engine"]) - {"page_size"}), named
    e = FILE["engine"]
    assert (e["max_slots"], e["max_ctx"], e["kv_pages"], e["page_size"]) == (8, 640, 8 * 40 + 1, 16)
    assert e["prefill_buckets"] == [128, 256] and e["width_buckets"] == [8] and e["prefill_batch_max"] == 2
    assert "prefix_cache_entries" not in e and "prefix_dedup" not in e  # the CLI's defaults: they serve this family
    c = FILE["check"]
    assert (c["sequences"], c["prefill_bucket"], c["min_prompt"], c["decode_steps"], c["engine_tokens"]) == (4, 256, 64, 8, 16)
    program = spec.family(FILE).program_config(FILE)
    assert (program.dim, program.n_layers, program.n_heads, program.n_kv_heads, program.head_dim) == (2048, 48, 16, 16, 128)
    assert (program.ffn_dim, program.vocab_size, program.max_seq_len, program.rope_theta) == (5632, 49152, 65536, 1e6)
    assert (program.loops, program.exit_threshold, program.cache_layers, program.post_norms) == (4, 1.0, 192, True)
    assert not program.tie_embeddings and not program.qkv_bias and program.norm_eps == 1e-6
    assert sum(w["config"] == NAME for w in BENCH["workloads"]) == 1
    with pytest.raises(ValueError, match="full_attention layers only"):
        spec.family(FILE).program_config(dict(FILE, layer_types=["sliding_attention"] * 48))
    with pytest.raises(ValueError, match="rope_scaling=None only"):
        spec.family(FILE).program_config(dict(FILE, rope_scaling={"type": "yarn"}))


def test_the_resident_set_is_over_four_fifths_of_the_chip():
    """The issue's arithmetic, from the file's shapes: weights at 2 bytes a
    parameter, and a pool four times as deep as the weights."""
    d, v, f, L, T = FILE["hidden_size"], FILE["vocab_size"], FILE["intermediate_size"], 48, FILE["total_ut_steps"]
    layer = 4 * d * d + 3 * d * f + 4 * d
    assert 51.3e6 < layer < 51.4e6
    params = L * layer + 2 * v * d + d + d + 1
    assert 2.66e9 < params < 2.67e9 and 5.33e9 < 2 * params < 5.35e9
    token = 2 * FILE["num_key_value_heads"] * FILE["head_dim"] * 2 * L * T
    assert token == 1_572_864 and token / 57_344 > 27  # a 7B Qwen2.5 token is 57,344 B
    e = FILE["engine"]
    pool = e["kv_pages"] * e["page_size"] * token
    assert 8.07e9 < pool < 8.09e9 and e["page_size"] * token == 25_165_824
    assert 0.83 * 16e9 < 2 * params + pool < 0.85 * 16e9
    rows = e["prefill_batch_max"] * max(e["prefill_buckets"])
    assert rows * token < 0.82e9 and rows <= 0.125 * e["kv_pages"] * e["page_size"]  # what the family's refusal holds it to


def test_the_mix_is_what_the_issue_names():
    found = spec.cell(BENCH, CELL)
    mix = found["mix"]
    assert mix["kind"] == "closed_loop" and mix["clients"] == FILE["engine"]["max_slots"] == 8
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 128, "max": 256}
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 256, "max": 384}
    assert (mix["temperature"], mix["prompt_vocab"], mix["ramp_s"], mix["warmup_seconds"]) == (0.7, 256, 12, 4)
    assert (mix["drain_limit_s"], mix["requests_per_client"], mix["trace_seconds"]) == (5, 16, 2)
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] <= FILE["engine"]["max_ctx"]
    assert mix["prompt_tokens"]["max"] <= max(FILE["engine"]["prefill_buckets"])
    assert found["workload"]["chips"] == 1 and found["workload"]["traffic"] == "decode-loop-reasoning"
    assert len(found["workload"]["why"]) <= 200 and len(CONF["why"]) <= 200


def test_the_cell_joins_the_lists_the_issue_names_and_brings_four_metrics():
    names = {m["name"] for m in spec.metrics_for(BENCH, CELL, "per_layer")}
    joined = {"batch_occupancy", "preemptions", "gap_p50_ms.saturated", "decode_step_ms.throughput", "host_ms_per_block",
              "idle_named_share", "uploads_per_block", "step_ms.attn", "step_ms.ffn", "step_ms.head", "step_ms.sample",
              "step_ms.other", "glue_ms_per_step", "device_named_share"} | {
                  f"idle_ms_per_block.{p}" for p in ("admit", "launch", "fetch", "commit", "publish")}
    assert names >= joined | set(NEW)  # a superset: a later PR's metric may join
    assert "paged_page_walk" in device_scopes.KERNELS  # its only kernel: `glue_ms_per_step` files it
    # each would count 48 walks' bytes over 192 walks' time
    assert not names & {"page_walk_roofline", "page_walk_roofline.attn_layers", "step_ms.mixer", "moe_gmm_roofline"}
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (unit, better, source, layer) in NEW.items():
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_per_chip"
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (unit, better, source, layer)
    assert [m["name"] for m in BENCH["per_layer"]][-4:] == list(NEW)  # appended, nothing before them moved
    assert BENCH["configs"][-1]["name"] == NAME and BENCH["workloads"][-1]["name"] == CELL
    assert {m["name"] for m in spec.metrics_for(BENCH, CELL, "end_to_end")} == {"tokens_per_s_per_chip", "setup_s"}
    assert len(BENCH["workloads"]) >= 8 and sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 64 * 1024


def test_the_loops_arithmetic():
    step = loop_weights.from_config(FILE)
    assert step == (4 * 48 * (4 * 2048 * 2048 + 3 * 2048 * 5632) + 2048 * 49152) * 2 and 19.92e9 < step < 19.94e9
    assert 24.2e-3 < step / 819e9 < 24.4e-3  # ms of bytes a step, whatever the batch
    assert loop_weights.from_config({**FILE, "total_ut_steps": 1}) < step / 3.8
    assert loop_weights.from_config({**FILE, "engine": {"quantize": "int8"}}) == step // 2
    sizes = dict(page_size=16, kv_heads=16, head_dim=128)
    one = page_walk.bytes_per_step([1], n_layers=192, **sizes)
    assert one == 25_165_824 == 16 * 1_572_864  # a page over 192 cache layers
    live = page_walk.bytes_per_step([350] * 8, n_layers=192, **sizes)  # the cell: 2,800 live tokens
    assert 4.4e9 < live < 4.5e9 and live == 4 * page_walk.bytes_per_step([350] * 8, n_layers=48, **sizes)


def _run(stats, ops=None, records=(), config=FILE):
    trace = None if ops is None else {
        "op_intervals": [[]], "modules": {"jit_decode_block": {"n": 4.0, "s": 1.6}}, "ops": ops,
        "windows": [(0, 10**9)], "slice_s": (0.0, 1.0)}
    return types.SimpleNamespace(stats=stats, trace=trace, config=config, device_kind="TPU v5e", records=list(records),
                                 traced=(0.0, 1.0), cell={"workload": {"name": CELL}})


def _snaps(**at):
    snap = lambda steps, tokens: {"decode_steps": steps, "max_slots": 8, "decode_block_size": 8,  # noqa: E731
                                  "loops": {"decode": {"tokens": tokens, "passes": 4 * tokens}}}
    return {edge: snap(*v) for edge, v in at.items()}


READERS = (loop_walk_roofline, loop_walk_ms_per_step, loop_weights_roofline, loop_passes_per_token)


def test_the_new_readers_give_nothing_where_there_is_nothing_to_read():
    """A parent commit has no `loops` counters; another family's file has no
    `total_ut_steps`; a run without a trace has no device time: each reader
    returns None and the line leaves the metric out."""
    plain = {"decode_steps": 8, "max_slots": 8, "decode_block_size": 8}
    stats = {e: dict(plain) for e in ("open", "close", "trace_start", "trace_stop")}
    for reader in READERS:
        assert reader.read(_run(stats)) is None
    other = {"hidden_size": 64, "engine": {"page_size": 16}}
    ops = {"paged_page_walk.8": 0.2, "fusion.1": 0.1}
    for reader in READERS[:3]:
        assert reader.read(_run(stats, ops=ops, config=other)) is None
    assert loop_walk_roofline.read(_run(stats, ops={"fusion.1": 0.1})) is None  # no kernel in the program
    assert loop_weights_roofline.read(_run(stats, ops=ops)) is None  # no trace file to read the scopes from


def test_the_walk_readers_count_a_walk_a_loop_and_layer():
    stats = _snaps(open=(0, 0), trace_start=(160, 1280), trace_stop=(192, 1536), close=(1600, 12800))
    live = [types.SimpleNamespace(first_t=0.0, last_t=2.0, prompt_len=n, blocks=[]) for n in (500, 300)]
    run = _run(stats, ops={"paged_page_walk.3": 0.4, "fusion.9": 0.3}, records=live)
    assert loop_walk_ms_per_step.read(run) == pytest.approx(0.4 * 1e3 / 32)  # 4 blocks of 8 steps
    least = page_walk.bytes_per_step([500, 300], page_size=16, kv_heads=16, head_dim=128, n_layers=192) / 819e9
    assert loop_walk_roofline.read(run) == pytest.approx(100 * least * 32 / 0.4)
    assert loop_passes_per_token.read(run) == 4.0


def test_the_leaf_reader_files_each_op_under_one_leaf_inside_decode_runs():
    Op = device_scopes.Op
    base = "jit(decode_block)/while/body/while/body/while/body/closed_call/"
    tables = {"/device:TPU:0": {
        (7, "%fusion.1 = x"): Op(base + "acp.attn/attn_qkv/dot_general", "", "convolution fusion"),
        (7, "%fusion.2 = x"): Op(base + "acp.attn/page_walk/mul", "", "loop fusion"),
        (7, "%fusion.3 = x"): Op(base + "acp.ffn/ffn_dense/dot;" + base + "acp.head/loop_norm/mul", "", "loop fusion"),
        (7, "%fusion.4 = x"): Op("jit(decode_block)/while/body/acp.head/head_product/acp.head/dot_general", "", "convolution fusion"),
        (7, "%fusion.5 = x"): Op(base + "acp.head/exit_gate/logistic", "", "loop fusion"),
        (9, "%fusion.1 = x"): Op("jit(prefill_and_sample)/acp.attn/attn_qkv/dot_general", "", "convolution fusion"),
    }}
    runs = [("/device:TPU:0", [(0, 1000, "jit_decode_block", 7), (1000, 2000, "jit_prefill_and_sample", 9)])]
    ops = [[(10, 110, "%fusion.1 = x"), (200, 250, "%fusion.2 = x"), (300, 330, "%fusion.3 = x"),
            (400, 460, "%fusion.4 = x"), (500, 505, "%fusion.5 = x"), (1100, 1900, "%fusion.1 = x")]]
    found = _loops.by_leaf(ops, runs, tables)
    assert found == pytest.approx({"attn_qkv": 100e-9, "attn_out": 0.0, "ffn_dense": 30e-9, "head_product": 60e-9,
                                   "loop_norm": 0.0, "exit_gate": 5e-9, "exit_select": 0.0})
    assert sum(found[name] for name in _loops.MATMUL_LEAVES) == pytest.approx(190e-9)
    assert not any(_loops.by_leaf(ops, runs, {}).values())


def test_the_family_is_found_by_name_and_documents_its_controls():
    family = spec.family(FILE)
    assert family.__name__ == "acpbench.families.ouro"
    for name in (*ouro_reference.CONTROLS, "kv_int8"):
        assert name in family.__doc__
    assert ouro_reference.FAULTS[:2] == ("loops_3", "shared_cache")
    with pytest.raises(ValueError, match="no control 'int4'"):
        family.reference_logits(FILE, {"embed": 0}, [[0]], [[0]], lower="int4")
    with pytest.raises(ValueError, match="bfloat16 weights only"):
        family.weights(dict(FILE, engine=dict(FILE["engine"], quantize="int8")), None, None, 0)
    assert set(ouro_study.CACHE) == {"program", "kv_int8"} and ouro_study.CONFIG == NAME
    assert set(ouro_study.REFERENCE) == {"ref_" + name for name in ouro_reference.CONTROLS}
    with pytest.raises(SystemExit, match="unknown readings"):
        ouro_study.main(["--readings", "ref_fp4"])
    assert set(FILE["check"]["limits"]) == {"logit_rel_rms", "cache_excess", "greedy_regret", "stream_mismatch"}
    text = open(ouro_reference.__file__).read()
    assert "agentcontrolplane_tpu" not in text.replace("`agentcontrolplane_tpu", "") and "import llama" not in text
    assert json.dumps(FILE)  # plain JSON all the way down


# -- the harness end to end on the CPU at a tiny size; nothing here is a device metric ----------------


@pytest.fixture(scope="module")
def rehearsal():
    config = spec.load_json(os.path.join(DATA, "tiny-config-ouro.json"))
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
    assert 0 < got["batch_occupancy"]["value"] <= 100 and got["loop_passes_per_token"]["value"] == 2.0  # the tiny twin's loops
    got = runner.read_metrics(BENCH, "end_to_end", run)
    assert set(got) == {"tokens_per_s_per_chip", "setup_s"} and all(v["value"] > 0 for v in got.values())


def test_the_loops_counters_count_over_the_window(rehearsal):
    stats = rehearsal[0].stats
    a, b = stats["open"]["loops"], stats["close"]["loops"]
    assert (b["loops"], b["layers"], b["cache_layers"]) == (2, 3, 6) and stats["close"]["model"]["cache_layers"] == 6
    tokens = b["decode"]["tokens"] - a["decode"]["tokens"]
    assert tokens > 0 and b["decode"]["passes"] - a["decode"]["passes"] == 2 * tokens
    assert b["decode"]["cache_rows"] - a["decode"]["cache_rows"] == 6 * tokens
    assert b["decode"]["exit_at"][0] == 0 and b["prefill"]["exit_at"][1] > 0  # the published threshold: the last loop


def test_outputs_agree_with_the_reference(rehearsal):
    ok, lines = rehearsal[2]
    assert ok, lines
    for name in ("logit_rel_rms=", "cache_excess=", "greedy_regret="):
        assert any(line.startswith(name) and "limit=" in line for line in lines)
    assert any(line.startswith("stream_mismatch=0 limit=0 ok") for line in lines)
