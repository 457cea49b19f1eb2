"""BENCHMARK.json against its contract's letter, and the harness finding
what later PRs add as new files, with no file edited."""

import json
import os
import re
import textwrap

import pytest

from acpbench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = spec.benchmark()


def _names():
    for table in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[table]:
            yield f"{table}.name", entry["name"]
    for w in BENCH["workloads"]:
        yield "config", w["config"]
        yield "traffic", w["traffic"]
    for c in BENCH["configs"]:
        for key in c["reduced"]:
            yield "reduced", key


@pytest.mark.parametrize("where,name", sorted(set(_names())))
def test_names_hold_only_the_allowed_characters(where, name):
    assert NAME.match(name), (where, name)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
    else:
        assert metric["moves"] in e2e and metric["layer"]
        assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        moved = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
        assert set(metric.get("workloads", cells)) <= set(moved.get("workloads", cells))


def test_the_file_keeps_to_its_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for entry in BENCH["workloads"] + BENCH["configs"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"] and "\t" not in entry["why"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files_and_reports(cell):
    found = spec.cell(BENCH, cell["name"])
    assert found["config"]["engine"]["kv_layout"] == "paged"
    assert found["config"]["engine"].get("tensor_parallelism", 1) == cell["chips"]
    assert hasattr(spec.generator(found["mix"]["kind"]), "plan")
    e2e = spec.metrics_for(BENCH, cell["name"], "end_to_end")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert spec.metrics_for(BENCH, cell["name"], "per_layer")
    for table in ("end_to_end", "per_layer"):
        for m in spec.metrics_for(BENCH, cell["name"], table):
            if m["name"] != "setup_s":
                assert callable(spec.reader(table, m["name"]).read)


# what the contract calls a width: `reduced` may name none, in any family
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj\w*|head)_size$|_dim$|_rank$|expan|experts_per_tok")


def _llama_facts(conf, file):
    """The Qwen files' own: nothing cut at all, head width 128, a qkv bias,
    weight-only int8."""
    assert conf["reduced"] == []
    program = spec.family(file).program_config(file)
    assert program.dim // program.n_heads == 128 and program.qkv_bias is True
    assert file["engine"]["quantize"] == "int8" and "int8" in file["precision"]["weights"]


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_files_cut_no_width(conf):
    file = spec.load_json(os.path.join(spec.ROOT, conf["file"]))
    assert conf["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert file["source"] == conf["source"] and file["reduced"] == conf["reduced"]
    assert not [key for key in conf["reduced"] if WIDTH.search(key)]
    assert any(c["config"] == conf["name"] for c in BENCH["workloads"])
    if file["family"] == "llama":  # another family's own facts: a test file of its own (`test_lfm2_spec.py`)
        _llama_facts(conf, file)


def test_every_llama_configuration_is_held_to_the_llama_facts():
    """The per-family assertion has something to bite on: the two Qwen
    files name `llama`, and a width in `reduced` is seen."""
    files = {c["name"]: spec.load_json(os.path.join(spec.ROOT, c["file"])) for c in BENCH["configs"]}
    assert {n for n, f in files.items() if f["family"] == "llama"} >= {"qwen2.5-32b-int8-v5e4", "qwen2.5-7b-int8-v5e1"}
    for key in ("hidden_size", "head_dim", "kv_lora_rank", "num_experts_per_tok", "ssm_state_size", "intermediate_size"):
        assert WIDTH.search(key), key
    for key in ("num_hidden_layers", "num_experts_held", "vocab_size", "max_position_embeddings"):
        assert not WIDTH.search(key), key
    cut = dict(BENCH["configs"][0], reduced=["num_hidden_layers"])
    with pytest.raises(AssertionError):
        _llama_facts(cut, files[cut["name"]])


def test_gap_is_judged_in_no_closed_loop_cell():
    gap = next(m for m in BENCH["end_to_end"] if m["name"] == "gap_p50_ms")
    for name in gap["workloads"]:
        assert spec.cell(BENCH, name)["mix"]["kind"] == "open_loop"


def test_new_files_are_found_without_editing_any(tmp_path, monkeypatch):
    """A later PR's configuration, mix, generator kind and per-layer metric:
    new files plus entries in BENCHMARK.json, nothing else touched."""
    import acpbench.generators
    import acpbench.layer_metrics

    root = tmp_path
    (root / "acpbench" / "configs").mkdir(parents=True)
    conf = dict(spec.load_json(os.path.join(spec.ROOT, BENCH["configs"][0]["file"])), deployment="a later PR's")
    (root / "acpbench" / "configs" / "later.json").write_text(json.dumps(conf))
    gens, mets, traffic = tmp_path / "gens", tmp_path / "mets", tmp_path / "traffic"
    for d in (gens, mets, traffic):
        d.mkdir()
    (gens / "bursty.py").write_text(textwrap.dedent("""
        def plan(mix, seed, seconds, config):
            return {"mode": "open", "ramp_s": 0, "requests": [
                {"due_s": 0.0, "prompt": [seed % 7] * mix["n"], "max_tokens": 4, "temperature": 0.0}]}
    """))
    (mets / "queue_wait_ms.py").write_text("def read(run):\n    return 1.5 if run.records else None\n")
    (traffic / "later-mix.json").write_text(json.dumps({"kind": "bursty", "n": 5}))
    monkeypatch.setattr(acpbench.generators, "__path__", list(acpbench.generators.__path__) + [str(gens)])
    monkeypatch.setattr(acpbench.layer_metrics, "__path__", list(acpbench.layer_metrics.__path__) + [str(mets)])
    monkeypatch.setattr(spec, "TRAFFIC_DIRS", spec.TRAFFIC_DIRS + [str(traffic)])
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "later", "source": conf["source"], "file": "acpbench/configs/later.json",
                             "reduced": [], "why": "later"})
    bench["workloads"].append({"name": "later.cell", "config": "later", "traffic": "later-mix", "chips": 1,
                               "why": "later"})
    bench["per_layer"].append({"name": "queue_wait_ms", "unit": "ms", "better": "lower", "source": "program_span",
                               "layer": "scheduler", "moves": "ttft_p90_ms", "workloads": ["later.cell"]})
    cell = spec.cell(bench, "later.cell", root=str(root))
    assert cell["config"]["deployment"] == "a later PR's"
    plan = spec.generator(cell["mix"]["kind"]).plan(cell["mix"], 3, 1.0, cell["config"])
    assert plan["requests"][0]["prompt"] == [3] * 5
    names = [m["name"] for m in spec.metrics_for(bench, "later.cell", "per_layer")]
    assert names == ["queue_wait_ms"]
    run = type("Run", (), {"records": [1]})()
    assert spec.reader("per_layer", "queue_wait_ms").read(run) == 1.5


@pytest.mark.parametrize("flag,stops", [(True, 0), (False, 2)], ids=["ignored", "as-the-program-has-them"])
def test_a_configuration_decides_whether_stop_tokens_end_an_answer(flag, stops):
    from acpbench.systems.engine import tokenizer

    t = tokenizer({"ignore_stop_tokens": flag})
    assert len(t.stop_tokens) == stops and t.vocab_size == 264 and t.decode(t.encode("a<b")) == "a<b"


@pytest.mark.parametrize("conf", [c["file"] for c in spec.benchmark()["configs"]])
def test_every_configuration_of_the_benchmark_ignores_stop_tokens(conf):
    """Else `--seed` changes the work: which answers random weights cut short."""
    assert spec.load_json(os.path.join(spec.ROOT, conf))["ignore_stop_tokens"] is True
