"""Finds a cell's files by the names `BENCHMARK.json` gives them, and a
configuration's model family by the name its file gives. No cell,
configuration, mix, metric or family is known here by name."""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRAFFIC_DIRS = [os.path.join(HERE, "traffic")]
FAMILY_PACKAGES = ["acpbench.families"]


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


def family(config: dict):
    """The module the configuration's file names under `family`: the
    program's config, the seeded weights, the plain reference and the cache
    check (acpbench/families/__init__.py has the interface)."""
    name = config["family"]
    for package in FAMILY_PACKAGES:
        try:
            return importlib.import_module(f"{package}.{name}")
        except ModuleNotFoundError as e:
            if e.name != f"{package}.{name}":
                raise
    raise ModuleNotFoundError(f"family {name!r} in none of {FAMILY_PACKAGES}")


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
