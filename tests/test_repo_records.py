"""What a document names, the tree has: every repository path, ``make``
target, ``python -m`` module and ``ACP_*`` environment variable in the
README, the Makefile, the CI workflow and each page under docs/.

One case a document, so a stale page names itself. The history files
(CHANGES.md, PERF.md's Findings, ROADMAP.md, docs/history/) tell what the
tree WAS and are not held to this.
"""

from __future__ import annotations

import functools
import importlib.util
import pathlib
import re
import subprocess

import pytest

REPO = pathlib.Path(__file__).parent.parent
DOCUMENTS = ["README.md", "Makefile", ".github/workflows/ci.yml"] + sorted(
    str(p.relative_to(REPO)) for p in (REPO / "docs").glob("*.md")
)

_listed = subprocess.run(
    ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
    cwd=REPO, capture_output=True, text=True,
).stdout.split()
FILES = {f for f in _listed if (REPO / f).exists()} or {
    str(p.relative_to(REPO)) for p in REPO.rglob("*") if p.is_file() and ".git" not in p.parts
}
DIRS = {str(d) for f in FILES for d in pathlib.PurePosixPath(f).parents} - {"."}
BASENAMES = {pathlib.PurePosixPath(f).name for f in FILES}
TOP = {f.split("/")[0] for f in FILES if "/" in f}

# what a page may name and this tree never holds: files a run writes or a
# user brings, and the reference repository's own (its `acp/` and
# `acp-example/` directories, its CI workflow)
NOT_THIS_TREE = {
    "acplint-findings.json",  # make lint-acp / the CI artifact
    "trace.json", "f.json", "fleet.json",  # `acp-tpu trace export -o ...`
    "sft.jsonl",  # `acp-tpu train --data ...`
    "config.json", "model.safetensors.index.json",  # a published checkpoint's
    "go-ci.yml",
}
REFERENCE_DIRS = ("acp/", "acp-example/")

PATH = re.compile(r"(?<![\w./<{$-])((?:\.github/|[A-Za-z_][\w.-]*/)*[\w.-]+\.(?:py|md|json|jsonl|ya?ml|toml|sh)|(?:[A-Za-z_.][\w.-]*/)+)(?![\w/*<{])")
MAKE = re.compile(r"(?m)(?:^\s*|[`(]\s*)make ([a-z][a-z0-9-]*)")  # in code, not in prose
MODULE = re.compile(r"(?:python3?|\$\(PY\))\s+-m\s+([A-Za-z_][\w.]*)")
ENV = re.compile(r"\bACP_[A-Z][A-Z0-9_]*[A-Z0-9]\b")


def text_of(name: str) -> str:
    text = (REPO / name).read_text()
    return re.sub(r"https?://\S+", " ", text)


def path_exists(token: str, beside: pathlib.PurePosixPath) -> bool:
    token = token.rstrip("/")
    for base in ("", "agentcontrolplane_tpu/", f"{beside}/" if str(beside) != "." else ""):
        if base + token in FILES or base + token in DIRS:
            return True
    # a bare file name (`faults.py`, `engine.py`) stands for the one in the tree
    return "/" not in token and token in BASENAMES


@functools.cache
def makefile_targets() -> set[str]:
    return set(re.findall(r"^([A-Za-z][\w-]*):", (REPO / "Makefile").read_text(), re.M))


@functools.cache
def source_text() -> str:
    """Everything that can read an environment variable: code, the Makefile,
    the workflows and the deployment's files (not this file, not prose)."""
    keep = (".py", ".yml", ".yaml", ".toml", ".sh", ".json")
    return "\n".join(
        (REPO / f).read_text(errors="ignore") for f in sorted(FILES)
        if (f.endswith(keep) or f == "Makefile" or f.startswith("deploy/"))
        and not f.endswith(".md") and f != "tests/test_repo_records.py"
    )


@pytest.mark.parametrize("name", DOCUMENTS)
def test_what_the_document_names_exists(name):
    text = text_of(name)
    beside = pathlib.PurePosixPath(name).parent
    missing = []
    for token in sorted(set(PATH.findall(text))):
        if token in NOT_THIS_TREE or token.startswith(("/", "~") + REFERENCE_DIRS):
            continue
        if path_exists(token, beside):
            continue
        if token.endswith("/") and token.split("/")[0] not in TOP:
            continue  # a URL path or a route (`v1/tasks/`), not the tree's
        missing.append(f"path {token}")
    targets = makefile_targets()
    missing += [f"make target {t}" for t in sorted(set(MAKE.findall(text))) if t not in targets]
    if name == "Makefile":
        phony = re.search(r"^\.PHONY:((?:.*\\\n)*.*)", text, re.M).group(1).replace("\\\n", " ").split()
        missing += [f"make target {t} (.PHONY)" for t in phony if t not in targets]
    for module in sorted(set(MODULE.findall(text))):
        try:
            found = importlib.util.find_spec(module) is not None
        except ModuleNotFoundError:
            found = False
        if not found:
            missing.append(f"module {module}")
    names = sorted(set(ENV.findall(text)))
    missing += [f"environment variable {v}" for v in names if v not in source_text()]
    assert not missing, f"{name} names what the tree does not have: {missing}"
