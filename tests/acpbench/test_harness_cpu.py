"""The harness end to end on the CPU at a tiny size: weights, engine,
warm-up, ramp, window, drain, readers, output check. One chip's path and
the four-chip path on virtual devices. Nothing here is a device metric:
the command itself refuses a CPU (test_run_refuses_cpu.py)."""

import os

import jax
import pytest

from acpbench import run as runner
from acpbench import spec, study
from acpbench.systems.engine import CompileCounter, System

DATA = os.path.join(os.path.dirname(__file__), "data")
PARTS = {"program_config", "weights", "reference_logits", "cached_logits"}


BENCH = spec.benchmark()
CASES = [
    pytest.param((config, mix, workload, tp), id=f"{mix}-tp{tp}-{family}")
    for family, config in (("llama", "tiny-config"), ("recording", "tiny-config-recording"))
    for mix, workload, tp in (("tiny-open", "q7b-chat-mixed", 1), ("tiny-closed", "q32b-tp4-decode", 4))
]


@pytest.fixture(scope="module")
def a_second_family():
    """`recording`, a family this test brings as files only: a package
    added to the list `spec.family` searches, and a configuration's file
    that names it. Yields its record of calls."""
    from .data.families import recording

    spec.FAMILY_PACKAGES.append(recording.__package__)
    try:
        yield recording.CALLS
    finally:
        spec.FAMILY_PACKAGES.remove(recording.__package__)


@pytest.fixture(scope="module", params=CASES)
def rehearsal(request, a_second_family):
    file, mix, workload, tp = request.param
    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} (virtual) devices")
    config = spec.load_json(os.path.join(DATA, file + ".json"))
    config["engine"]["tensor_parallelism"] = tp
    a_second_family.clear()
    cell = {"workload": {"name": workload, "chips": tp}, "config": config,
            "mix": spec.load_json(os.path.join(DATA, mix + ".json"))}
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
    return run, compiled, check, list(a_second_family)


def test_nothing_compiles_after_the_warm_up(rehearsal):
    assert rehearsal[1] == 0


def test_requests_are_counted_and_none_fails(rehearsal):
    run = rehearsal[0]
    attempted, failed = runner.count_requests(run)
    assert attempted >= 3 and failed == 0
    assert all(r.error is None for r in run.records if not r.censored)
    assert set(run.stats) >= {"open", "close"}


def test_no_answer_is_cut_short_by_a_stop_token(rehearsal):
    """With `ignore_stop_tokens` the seed cannot change the work: at a
    vocabulary of 512 a sampled answer would else meet one of the byte
    tokenizer's two stop tokens about once in 256 tokens."""
    ended = [r for r in rehearsal[0].records if r.end_t is not None and not r.censored]
    assert ended and all(r.finish == "length" and r.n_tokens == r.max_tokens for r in ended)


def test_the_cells_end_to_end_metrics_are_read(rehearsal):
    run = rehearsal[0]
    got = runner.read_metrics(BENCH, "end_to_end", run)
    want = {m["name"] for m in spec.metrics_for(BENCH, run.cell["workload"]["name"], "end_to_end")}
    assert set(got) == want
    assert all(v["value"] > 0 for v in got.values())


def test_counters_are_read_and_device_metrics_are_not(rehearsal):
    run = rehearsal[0]
    got = runner.read_metrics(BENCH, "per_layer", run)
    per_layer = {m["name"]: m for m in spec.metrics_for(BENCH, run.cell["workload"]["name"], "per_layer")}
    for name, m in per_layer.items():
        if m["source"] == "device_trace":
            assert name not in got  # no trace on a CPU: the reader finds nothing and says nothing
        else:
            assert name in got
    if "batch_occupancy" in got:
        assert 0 < got["batch_occupancy"]["value"] <= 100


def test_outputs_agree_with_the_reference(rehearsal):
    ok, lines = rehearsal[2]
    assert ok, lines
    assert any(line.startswith("logit_rel_rms=") and "limit=" in line for line in lines)
    assert any(line.startswith("cache_excess=") for line in lines)
    # the cell's own path: greedy requests through submit, every token looked up in the reference
    assert any(line.startswith("greedy_regret=") and "limit=" in line for line in lines)
    assert any(line.startswith("stream_mismatch=0 limit=0 ok") for line in lines)


def test_every_part_comes_from_the_family_the_file_names(rehearsal):
    """`System`, `warm_up`, `measure` and `output_check` reach the
    program's config, the weights, the reference and the cache check
    through the module the configuration's file names, and through no
    other: the recording family sees all four parts called when it is
    named, and nothing when `llama` is."""
    run, calls = rehearsal[0], rehearsal[3]
    assert set(calls) == (PARTS if run.config["family"] == "recording" else set())
    if calls:
        assert calls[:2] == ["program_config", "weights"] and calls.count("weights") == 1
        assert calls.count("reference_logits") == 2  # the sample's logits, then the engine's tokens


@pytest.mark.parametrize("control,parts", [
    ("program", PARTS), ("kv_int8", PARTS), ("ref_fp8", PARTS - {"cached_logits"}),
])
def test_study_reads_through_the_family_too(a_second_family, control, parts):
    config = spec.load_json(os.path.join(DATA, "tiny-config-recording.json"))
    a_second_family.clear()
    numbers = study.readings(config, 3, control, use_pallas=False)
    assert set(a_second_family) == parts and numbers["finite"]


def test_a_family_no_package_has_is_an_error():
    with pytest.raises(ModuleNotFoundError, match="no-such-family"):
        spec.family({"family": "no-such-family"})


def test_only_families_know_the_programs_models():
    """Outside `acpbench/families/` no file of the harness imports the
    program's model modules or builds its config, and `spec.py` finds a
    family by the name a file gives and knows none itself."""
    here = os.path.join(spec.ROOT, "acpbench")
    families = {name[:-3] for name in os.listdir(os.path.join(here, "families")) if name.endswith(".py")}
    seen = 0
    for folder, _, names in os.walk(here):
        if os.path.basename(folder) in ("families", "__pycache__"):
            continue
        for name in names:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(folder, name)) as f:
                text = f.read()
            seen += 1
            for banned in ("agentcontrolplane_tpu.models", "LlamaConfig", "llama_kwargs"):
                assert banned not in text, (name, banned)
    assert seen > 20
    with open(os.path.join(here, "spec.py")) as f:
        text = f.read().lower()
    assert "llama" in families and not any(name.split("_")[0] in text for name in families if name != "__init__")
    with open(os.path.join(here, "systems", "engine.py")) as f:
        assert "quantize=" not in f.read()  # the weight precision comes from the file's engine block
