"""`acpbench/device_scopes.py`: the op table read from the wire format of two
chip recordings, the attribution on a hand-built table, and the metrics that
read it."""

import os
import types

import pytest

from acpbench import device_scopes, spec, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = os.path.join(DATA, "small_spans.xplane.pb")  # the tiny engine on a v5e, before the scopes (PR 26)
NEW = os.path.join(DATA, "small_scopes.xplane.pb")  # the same recording made on the scoped tree (PR 42)
DECODE, CONTINUE = 9617605591198821176, 8131827333177531684  # programs of the old recording
METRICS = ["step_ms.attn", "step_ms.mixer", "step_ms.ffn", "step_ms.head", "step_ms.sample", "step_ms.other",
           "glue_ms_per_step", "device_named_share"]


@pytest.fixture(scope="module")
def old_table():
    return device_scopes.op_table(OLD)


def test_the_op_table_is_read_from_the_wire_format(old_table):
    """One device plane, 525 ops; each keyed by its program and the event
    name `ProfileData` gives, with the path, source and category the
    profiler kept in the event's metadata."""
    assert list(old_table) == ["/device:TPU:0"]
    table = old_table["/device:TPU:0"]
    assert len(table) == 525
    by_name = {(program, event.split(" = ")[0]): op for (program, event), op in table.items()}
    assert by_name[(DECODE, "%add_maximum_fusion.2")] == device_scopes.Op(
        "jit(decode_block)/while/body/closed_call/while/body/closed_call/max:",
        "/root/repo/agentcontrolplane_tpu/ops/paged.py:196", "loop fusion")
    assert by_name[(CONTINUE, "%broadcast_add_fusion")] == device_scopes.Op(
        "jit(paged_continue_and_sample)/jit(_gumbel)/jit(_uniform)/iota_2x32_shape:",
        "/root/repo/agentcontrolplane_tpu/ops/sampling.py:159", "loop fusion")
    # one name in two programs: the program is part of the key
    assert by_name[(DECODE, "%add_multiply_fusion.4")].tf_op.startswith("jit(decode_block)/")
    assert by_name[(CONTINUE, "%add_multiply_fusion.4")].tf_op.startswith("jit(paged_continue_and_sample)/")


def test_the_ops_with_no_path_are_the_compilers_own(old_table):
    table = old_table["/device:TPU:0"]
    bare = [op.category for op in table.values() if not op.tf_op]
    assert len(bare) == 525 - 347
    assert bare.count("copy-start") == bare.count("copy-done") == 61
    assert {"while", "custom-call", "data formatting"} <= set(bare)
    assert all(op.category for op in table.values())


def test_the_event_names_are_profile_datas(old_table):
    """The join needs nothing but the name `trace_reduce` already keeps."""
    import jax

    profile = jax.profiler.ProfileData.from_file(OLD)
    reduced = trace_reduce.reduce_profile(profile)
    runs = device_scopes.module_runs(profile)
    assert len(runs) == len(reduced["op_intervals"]) == 1 and runs[0][0] == "/device:TPU:0"
    programs = {program for _, _, _, program in runs[0][1]}
    assert {DECODE, CONTINUE} <= programs
    names = {name for _, _, name in reduced["op_intervals"][0]}
    known = {event for _, event in old_table["/device:TPU:0"]}
    assert names <= known


def test_an_unscoped_recording_reads_as_unnamed_and_says_so(old_table):
    import jax

    profile = jax.profiler.ProfileData.from_file(OLD)
    reduced = trace_reduce.reduce_profile(profile)
    found = device_scopes.attribute(reduced["op_intervals"], device_scopes.module_runs(profile), old_table)
    assert {top for _, top in found["top"]} == {"unnamed"}
    assert sum(found["top"].values()) == pytest.approx(sum(reduced["ops"].values()), rel=1e-12)
    assert found["programs"]["scoped"] == [] and "jit_decode_block" in found["programs"]["unscoped"]
    found.update(steps=16.0, prompt_tokens=0, reader_s=0.0)
    assert "compiled before the scopes" in device_scopes.line(found, reduced)
    assert device_scopes.named_share(found) == 0.0


# -- a hand-built table ----------------------------------------------------------------------------------

STEP = "jit(decode_block)/while/body/closed_call/"
LAYER = STEP + "while/body/closed_call/"
TABLE = {"/device:TPU:0": {
    (7, "%fusion.1 = bf16[4,64] fusion()"): device_scopes.Op(LAYER + "acp.attn/attn_qkv/dot_general:", "llama.py:428", "convolution fusion"),
    (7, "%fusion.2 = f32[4] fusion()"): device_scopes.Op(LAYER + "acp.attn/attn_qkv/reduce_sum:", "norms.py:12", "loop fusion"),
    (7, "%paged_page_walk.3 = f32[4] custom-call()"): device_scopes.Op("", "", "custom-call"),
    (7, "%fusion.4 = bf16[4,64] fusion()"): device_scopes.Op(LAYER + "acp.ffn/moe_sort/sort:", "moe.py:190", "loop fusion"),
    (7, "%fusion.5 = f32[4,256] fusion()"): device_scopes.Op(STEP + "acp.head/dot_general:", "llama.py:395", "convolution fusion"),
    (7, "%fusion.6 = s32[4] fusion()"): device_scopes.Op(STEP + "acp.sample/argmax:", "sampling.py:35", "loop fusion"),
    (7, "%copy.7 = bf16[2,4] copy()"): device_scopes.Op("", "", "data formatting"),
    # a commit under a window layer's own name, inside a layer: the innermost acp.* wins
    (7, "%fusion.8 = bf16[4] fusion()"): device_scopes.Op(LAYER + "acp.attn/window_commit/acp.commit/scatter:", "mellum.py:270", "loop fusion"),
    # a merged instruction: the first path that names a layer
    (7, "%fusion.9 = bf16[4] fusion()"): device_scopes.Op(STEP + "acp.commit/reshape;acp.commit/squeeze", "lfm2.py:400", "loop fusion"),
    (9, "%fusion.1 = bf16[4,64] fusion()"): device_scopes.Op("jit(prefill_and_sample)/while/body/closed_call/acp.ffn/ffn_dense/dot_general:", "llama.py:470", "convolution fusion"),
}}
RUNS = [("/device:TPU:0", [(1000, 2000, "jit_decode_block", 7), (3000, 4000, "jit_prefill_and_sample", 9),
                           (5000, 5100, "jit_saved_state", 11)])]
OPS = [[
    (1000, 1100, "%fusion.1 = bf16[4,64] fusion()"),
    (1100, 1130, "%fusion.2 = f32[4] fusion()"),
    (1130, 1330, "%paged_page_walk.3 = f32[4] custom-call()"),
    (1330, 1380, "%fusion.4 = bf16[4,64] fusion()"),
    (1380, 1480, "%fusion.5 = f32[4,256] fusion()"),
    (1480, 1500, "%fusion.6 = s32[4] fusion()"),
    (1500, 1510, "%copy.7 = bf16[2,4] copy()"),
    (1510, 1520, "%fusion.8 = bf16[4] fusion()"),
    (1520, 1525, "%fusion.9 = bf16[4] fusion()"),
    (3000, 3400, "%fusion.1 = bf16[4,64] fusion()"),  # the same name in another program
    (4500, 4600, "%copy.7 = bf16[2,4] copy()"),  # outside every run
    (5000, 5050, "%fusion.77 = f32[2] fusion()"),  # a program the table does not know
]]


def test_every_op_second_lands_in_one_scope_of_one_phase():
    found = device_scopes.attribute(OPS, RUNS, TABLE)
    ns = {key: round(s * 1e9) for key, s in found["top"].items()}
    assert ns == {("decode", "attn"): 100 + 30 + 200, ("decode", "ffn"): 50, ("decode", "head"): 100,
                  ("decode", "sample"): 20, ("decode", "unnamed"): 10, ("decode", "commit"): 10 + 5,
                  ("prefill", "ffn"): 400, ("other", "unnamed"): 100 + 50}
    assert sum(ns.values()) == sum(e - s for s, e, _ in OPS[0])
    leaves = {key: round(s * 1e9) for key, s in found["leaf"].items()}
    assert leaves == {("decode", "attn_qkv"): 130, ("decode", "page_walk"): 200, ("decode", "moe_sort"): 50,
                      ("decode", "window_commit"): 10, ("prefill", "ffn_dense"): 400}
    # a kernel with no path is filed by the name the program gave it, and equals trace_reduce's seconds of that name
    assert {k: round(s * 1e9) for k, s in found["kernel"].items()} == {("decode", "paged_page_walk"): 200}
    # glue: under attn, mixer, ffn in decode runs, neither kernel nor matmul
    assert round(found["glue"]["decode"] * 1e9) == 30 + 50
    assert {k: round(s * 1e9) for k, s in found["unnamed_by_category"].items()} == {
        ("decode", "data formatting"): 10, ("other", "not in the table"): 100 + 50}
    assert found["programs"] == {"scoped": ["jit_decode_block", "jit_prefill_and_sample"], "unscoped": []}
    assert device_scopes.named_share(found) == pytest.approx(100 * (1075 - 160) / 1075)


def test_the_innermost_scope_wins_and_the_last_leaf():
    assert device_scopes.top_level(LAYER + "acp.attn/window_commit/acp.commit/scatter:") == "commit"
    assert device_scopes.top_level(STEP + "while/body/dynamic_slice:") is None
    assert device_scopes.top_level("x/add;while/body/closed_call/acp.ffn/mul") == "ffn"
    assert device_scopes.leaf(LAYER + "acp.attn/prefill_attention/full_gather/gather:") == "full_gather"
    assert device_scopes.leaf(LAYER + "acp.mixer/short_conv/conv_in_proj/dot_general:") == "conv_in_proj"
    assert device_scopes.leaf(STEP + "acp.head/dot_general:") is None
    assert device_scopes.classify("%moe_gmm.72 = bf16[256,1536] custom-call()", None) == ("ffn", "moe_gmm", True)
    assert device_scopes.classify("%fusion.3 = f32[2] fusion()", None) == ("unnamed", None, False)
    named = device_scopes.Op(LAYER + "acp.attn/window_walk/pallas_call:", "", "custom-call")
    assert device_scopes.classify("%paged_window_walk.2 = f32[4] custom-call()", named) == ("attn", "window_walk", True)
    nonsense = device_scopes.Op(STEP + "acp.nonsense/add:", "", "loop fusion")
    assert device_scopes.classify("%fusion.3 = f32[2] fusion()", nonsense)[0] == "unnamed"


def test_the_harness_keeps_its_own_copy_of_the_vocabulary():
    """A program PR that renames a scope cannot move the yardstick in
    silence: this test says so."""
    from agentcontrolplane_tpu.observability import scopes

    assert tuple(scopes.LAYERS) == device_scopes.TOP_LEVELS
    assert scopes.PREFIX == device_scopes.PREFIX
    assert set(device_scopes.GLUE_LEVELS) < set(device_scopes.TOP_LEVELS)
    assert {top for top, _ in device_scopes.KERNELS.values()} <= set(device_scopes.TOP_LEVELS)
    assert {low for _, low in device_scopes.KERNELS.values()} <= set(device_scopes.LEAVES)


def _run(found):
    """A traced run whose analysis is already there."""
    return types.SimpleNamespace(trace={"modules": {}}, device_scopes=found)


@pytest.mark.parametrize("name", METRICS)
def test_a_metric_gives_none_without_a_trace_or_without_scopes(name):
    entry = next(m for m in spec.benchmark()["per_layer"] if m["name"] == name)
    assert entry["source"] == "device_trace" and entry["moves"] == "tokens_per_s_per_chip"
    read = spec.reader("per_layer", name).read
    assert read(types.SimpleNamespace(trace=None)) is None
    assert read(_run(None)) is None  # a parent commit: traced, no scope found


def test_the_six_step_metrics_partition_the_decode_steps_op_seconds():
    found = device_scopes.attribute(OPS, RUNS, TABLE)
    found.update(steps=2.0, prompt_tokens=0, reader_s=0.0)
    run = _run(found)
    parts = {name: spec.reader("per_layer", name).read(run) for name in METRICS[:6]}
    assert parts == {"step_ms.attn": pytest.approx(330e-6 / 2), "step_ms.mixer": 0.0, "step_ms.ffn": pytest.approx(50e-6 / 2),
                     "step_ms.head": pytest.approx(100e-6 / 2), "step_ms.sample": pytest.approx(20e-6 / 2),
                     "step_ms.other": pytest.approx(25e-6 / 2)}
    decode_ns = sum(e - s for s, e, _ in OPS[0] if s < 2000)
    assert sum(parts.values()) == pytest.approx(decode_ns / 1e6 / 2)
    assert spec.reader("per_layer", "glue_ms_per_step").read(run) == pytest.approx(80e-6 / 2)
    assert spec.reader("per_layer", "device_named_share").read(run) == pytest.approx(100 * 915 / 1075)
    reduced = {"modules": {"jit_decode_block": {"n": 1.0, "s": 1e-6}}, "ops": {"paged_page_walk.3_f32_4_": 200e-9}}
    text = device_scopes.line(found, reduced)
    assert "equal trace_reduce's: True" in text
    assert '"attn": 0.0002' in text and "llama.py:428" not in text  # a matmul is no glue
    assert "norms.py:12" in text and "moe.py:190" in text


# -- the scoped recording ------------------------------------------------------------------------------------

@pytest.mark.skipif(not os.path.exists(NEW), reason="the recording of the scoped tree is not in this checkout")
def test_the_scoped_recording_names_the_tiny_engines_decode_ops():
    """`python -m acpbench.record_spans` unedited, on the scoped tree, on a
    v5e: every program of the engine carries the scopes, the decode block's
    op seconds fall under all six parts, and most of them have a name."""
    import jax

    profile = jax.profiler.ProfileData.from_file(NEW)
    reduced = trace_reduce.reduce_profile(profile)
    found = device_scopes.attribute(reduced["op_intervals"], device_scopes.module_runs(profile),
                                    device_scopes.op_table(NEW))
    assert "jit_decode_block" in found["programs"]["scoped"] and not found["programs"]["unscoped"]
    decode = {top: s for (phase, top), s in found["top"].items() if phase == "decode"}
    assert {"attn", "ffn", "head", "sample", "commit", "embed"} <= set(decode)
    assert "mixer" not in decode
    assert decode["attn"] > decode["head"] > 0
    assert sum(found["top"].values()) == pytest.approx(sum(reduced["ops"].values()), rel=1e-9)
    assert device_scopes.named_share(found) > 60
    leaves = {low for phase, low in found["leaf"] if phase == "decode"}
    assert {"attn_qkv", "page_walk", "attn_out", "ffn_dense"} <= leaves
