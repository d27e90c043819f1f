"""Lower layers do not import the serving layer above them.

``serving/`` sits on the executor, so a module of the kernels, the exec
graph or the staging path that imported it would close an import cycle
(and make a kernel's choice depend on serving state)."""

import ast
import os

import pytest

PKG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "pixie_tpu"
)


def _modules(rel):
    path = os.path.join(PKG, rel)
    if os.path.isfile(path):
        return [path]
    return [
        os.path.join(root, f)
        for root, _, files in os.walk(path)
        for f in sorted(files)
        if f.endswith(".py")
    ]


def _imports(path):
    """Every module name ``path`` imports, at any depth of its body."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


@pytest.mark.parametrize("rel", ["ops", "exec", "parallel/staging.py"])
def test_lower_layer_does_not_import_serving(rel):
    modules = _modules(rel)
    assert modules, rel
    bad = [
        (os.path.relpath(p, PKG), name)
        for p in modules
        for name in _imports(p)
        if name == "pixie_tpu.serving" or name.startswith("pixie_tpu.serving.")
    ]
    assert not bad, bad
