"""`models/` is families over mechanism modules over `ops/`, arrows one way
(`docs/serving-engine.md`, "Adding a family"): an `ast` walk over the package's
import statements, no module imported. A family file imports mechanism modules
and `ops/`, never another family, but for the two arrows declared below, each
with its reason beside the import; a mechanism module imports no family; and no
underscore name crosses a module boundary inside `models/` but the one the
benchmark plants on by attribute name."""

import ast
from pathlib import Path

import pytest

MODELS = Path(__file__).resolve().parents[2] / "agentcontrolplane_tpu" / "models"
FAMILIES = ("dots", "exaone", "jamba", "kanana", "keye", "lfm2", "llama", "mellum", "nemotron_h", "ouro")
MECHANISMS = ("experts", "recurrent", "stack", "window")
# importer -> the one family it may import, and why the importing file says so
DECLARED = {"ouro": "llama", "dots": "keye"}
# (importer, module, name): `acpbench/families/dots.py _planted` sets `_layer_norm` on both modules by that name
PLANTED = {("dots", "keye", "_layer_norm")}


def siblings(name: str) -> list[tuple[str, str, int]]:
    """(module of `models/`, name imported from it or "" for the module itself, line) of `name`.py's imports."""
    found = []
    for node in ast.walk(ast.parse((MODELS / f"{name}.py").read_text())):
        if isinstance(node, ast.ImportFrom) and node.level == 1:
            if node.module is None:  # from . import llama
                found += [(a.name, "", node.lineno) for a in node.names]
            else:
                found += [(node.module.split(".")[0], a.name, node.lineno) for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0 and (node.module or "").startswith("agentcontrolplane_tpu.models"):
            found += [((node.module.split(".") + [a.name])[2], a.name, node.lineno) for a in node.names]
    return found


def test_every_file_of_models_is_a_family_or_a_mechanism():
    assert sorted(p.stem for p in MODELS.glob("*.py")) == sorted(FAMILIES + MECHANISMS + ("__init__",))


@pytest.mark.parametrize("family", FAMILIES)
def test_a_family_imports_no_family_but_a_declared_one(family):
    others = {module for module, _, _ in siblings(family) if module in FAMILIES}
    assert others == ({DECLARED[family]} if family in DECLARED else set())
    if family in DECLARED:
        lines = (MODELS / f"{family}.py").read_text().splitlines()
        first = min(line for module, _, line in siblings(family) if module == DECLARED[family])
        above = [ln for ln in lines[first - 6:first - 1] if ln.startswith("#")]
        assert any("the one family this file imports" in ln for ln in above), "the arrow's reason stands above the import"


@pytest.mark.parametrize("mechanism", MECHANISMS)
def test_a_mechanism_module_imports_no_family(mechanism):
    assert not [module for module, _, _ in siblings(mechanism) if module in FAMILIES]


@pytest.mark.parametrize("name", FAMILIES + MECHANISMS + ("__init__",))
def test_no_private_name_crosses_a_module_of_models(name):
    crossing = {(name, module, what) for module, what, _ in siblings(name) if what.startswith("_")}
    for node in ast.walk(ast.parse((MODELS / f"{name}.py").read_text())):  # and none is reached as `module._name`
        if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) and node.attr.startswith("_"):
            if node.value.id in FAMILIES + MECHANISMS and not node.attr.startswith("__"):
                crossing.add((name, node.value.id, node.attr))
    assert crossing == {p for p in PLANTED if p[0] == name}
