"""Layering: the packages the models are built from know nothing of the
layers above them. A kernel's block policy that imports telemetry, or a
layer that reaches into the planner, makes the hot path depend on code
whose job is to watch it."""
import ast
import os

import pytest

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "paddle_tpu")

LOWER = ["core", "tensor", "autograd", "amp", "nn", "ops", "quant", "moe",
         "models"]
UPPER = {"telemetry", "analysis", "planner", "fleet", "resilience"}


def _modules(package):
    """(dotted module name, path) of every module of paddle_tpu.<package>,
    be it a directory or a single file."""
    root = os.path.join(PKG, package)
    if os.path.isfile(root + ".py"):
        yield f"paddle_tpu.{package}", root + ".py"
    for dirpath, _, files in os.walk(root):
        for f in sorted(files):
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                rel = os.path.relpath(path, os.path.dirname(PKG))[:-3]
                yield rel.replace(os.sep, "."), path


def _imports(module, path):
    """Absolute dotted targets of every import statement in the file,
    wherever it stands (function bodies included)."""
    is_pkg = path.endswith("__init__.py")
    here = module.rsplit(".", 1)[0] if is_pkg else module
    for node in ast.walk(ast.parse(open(path).read(), path)):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield node.lineno, a.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                parent = here.split(".")
                parent = parent[:len(parent) - node.level + (1 if is_pkg else 0)]
                base = ".".join(parent + ([base] if base else []))
            yield node.lineno, base
            # `from .. import telemetry` names the package in the alias
            for a in node.names:
                yield node.lineno, f"{base}.{a.name}"


@pytest.mark.parametrize("package", LOWER)
def test_lower_layers_do_not_import_the_layers_above(package):
    modules = list(_modules(package))
    assert modules, f"paddle_tpu.{package} has no modules"
    found = []
    for module, path in modules:
        for lineno, target in _imports(module, path):
            parts = target.split(".")
            if parts[0] == "paddle_tpu" and len(parts) > 1 \
                    and parts[1] in UPPER:
                found.append(f"{os.path.relpath(path, PKG)}:{lineno} "
                             f"imports {target}")
    assert not found, "\n".join(found)
