"""Docs-drift guard: user-facing docs must reference real code.

MIGRATION.md and README.md are the user-switch surface, ARCHITECTURE.md
the description of the system — every backticked repo path or
``pytorch_operator_tpu.*`` module they name must exist, or the docs rot silently as code moves (the same cannot-drift
principle the CRD generator applies to the API schema).
"""

import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
PKG = REPO / "pytorch_operator_tpu"

# Upstream-reference paths that legitimately do not exist in this tree
# (they describe the Kubeflow operator being migrated FROM).
UPSTREAM = {
    "examples/smoke-dist/dist_sendrecv.py",
    "pkg/apis/pytorch/v1/types.go",
}

PATH_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_/.\-*]*\.(py|md|yaml|yml|json|cc)$")


def _backtick_spans(text: str):
    return re.findall(r"`([^`\n]+)`", text)


def _module_refs(text: str):
    """Dotted modules appearing anywhere (incl. inside command lines)."""
    return set(re.findall(r"pytorch_operator_tpu(?:\.[A-Za-z0-9_]+)+", text))


def _resolves(path_str: str) -> bool:
    for base in (REPO, PKG):
        if "*" in path_str:
            if list(base.glob(path_str)):
                return True
        elif (base / path_str).exists():
            return True
    return False


@pytest.mark.parametrize("doc", ["MIGRATION.md", "README.md", "ARCHITECTURE.md"])
def test_doc_paths_exist(doc):
    text = (REPO / doc).read_text()
    missing = []
    for span in _backtick_spans(text):
        span = span.strip()
        if span in UPSTREAM or not PATH_RE.match(span):
            continue
        if not _resolves(span):
            missing.append(span)
    assert missing == [], f"{doc} references nonexistent paths: {missing}"


@pytest.mark.parametrize("doc", ["MIGRATION.md", "README.md", "ARCHITECTURE.md"])
def test_doc_modules_importable(doc):
    text = (REPO / doc).read_text()
    missing = []
    for mod in sorted(_module_refs(text)):
        # Resolve as a file path (no import: docs may name workload
        # modules whose import costs a jax load).
        rel = Path(*mod.split(".")[1:])
        if not (
            (PKG / rel).with_suffix(".py").exists()
            or (PKG / rel / "__init__.py").exists()
        ):
            missing.append(mod)
    assert missing == [], f"{doc} references nonexistent modules: {missing}"
