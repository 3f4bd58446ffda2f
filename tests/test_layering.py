"""Imports point one way: only ``repro.models`` imports ``repro.kernels``.
Each kernel on the train path is chosen in one place (attention in
``models.attention``, the SSD core in ``models.ssm``); no other package
reaches down to the kernels."""
import ast
import os

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src", "repro")
PACKAGES = ["core", "cluster", "configs", "data", "launch", "optim",
            "serve", "tracking", "train"]


def _imported_modules(path):
    """Every module a file imports, function-local imports included."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


@pytest.mark.parametrize("package", PACKAGES)
def test_only_models_imports_kernels(package):
    root = os.path.join(SRC, package)
    files = [os.path.join(d, f) for d, _, fs in os.walk(root)
             for f in fs if f.endswith(".py")]
    assert files, root
    found = [(os.path.relpath(p, SRC), m) for p in files
             for m in _imported_modules(p)
             if m == "repro.kernels" or m.startswith("repro.kernels.")]
    assert not found, found
