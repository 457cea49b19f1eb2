"""Finds a cell's files by the names `BENCHMARK.json` gives them. No cell,
configuration, mix or metric is known here by name."""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRAFFIC_DIRS = [os.path.join(HERE, "traffic")]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell, its configuration's file and its mix's file."""
    for w in bench["workloads"]:
        if w["name"] == workload:
            conf = next(c for c in bench["configs"] if c["name"] == w["config"])
            return {
                "workload": w,
                "config": load_json(os.path.join(root, conf["file"])),
                "mix": load_json(_first_existing(w["traffic"] + ".json", TRAFFIC_DIRS)),
            }
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def _first_existing(name: str, dirs: list[str]) -> str:
    for d in dirs:
        if os.path.exists(os.path.join(d, name)):
            return os.path.join(d, name)
    raise FileNotFoundError(f"{name} in none of {dirs}")


def generator(kind: str):
    return importlib.import_module(f"acpbench.generators.{kind}")


def metrics_for(bench: dict, workload: str, table: str) -> list[dict]:
    """The entries of `end_to_end` or `per_layer` this cell reports."""
    out = []
    for m in bench[table]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        out.append(m)
    return out


def reader(table: str, name: str):
    """The module that reads one metric: acpbench/<table>/<name>.py, with
    the characters a module may not have ('.', '-') written as '_'."""
    package = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[table]
    return importlib.import_module(f"acpbench.{package}.{name.replace('.', '_').replace('-', '_')}")


LLAMA_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "ffn_dim", "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "max_position_embeddings": "max_seq_len", "tie_word_embeddings": "tie_embeddings",
}


def llama_kwargs(config: dict) -> dict:
    """The source's `config.json` keys under the program's names, plus what
    the file states under `llama_config` in the program's own names."""
    kw = {ours: config[theirs] for theirs, ours in LLAMA_FIELDS.items() if theirs in config}
    kw.update(config.get("llama_config", {}))
    return kw


def model_sizes(config: dict) -> dict:
    """What the plain reference needs, from the source's keys alone."""
    return {
        "n_heads": config["num_attention_heads"], "n_kv_heads": config["num_key_value_heads"],
        "norm_eps": config["rms_norm_eps"], "rope_theta": config["rope_theta"],
    }
