"""The arrows between the package's low layers, frozen where they already
point one way: what each unit may import of `distributed_ddpg_tpu` outside
itself. A unit is a top-level module or sub-package; every `import` in it
counts, those inside function bodies too (a deferred import is still a
dependency). The table holds what the tree holds, so a new arrow out of a low
layer fails here and a repaired one is recorded here (ROADMAP D11: `ops`,
which imports `learner`, and `replay`, which imports `parallel`, are its next
rows)."""

import ast
from pathlib import Path

import pytest

import distributed_ddpg_tpu

PACKAGE = Path(distributed_ddpg_tpu.__file__).parent
NAME = PACKAGE.name

MAY_IMPORT = {
    "trace": set(),
    "envs": set(),
    "types": {"trace"},
    "models": {"trace"},
    "metrics": {"trace"},
    "obs": {"trace"},
    "checkpoint": {"config", "faults", "trace", "types"},
}


def _files(unit):
    module = PACKAGE / f"{unit}.py"
    return [module] if module.exists() else sorted((PACKAGE / unit).rglob("*.py"))


def _imported_units(path):
    """Top-level units of the package that `path` imports, by any spelling:
    `import pkg.a.b`, `from pkg.a import b`, `from pkg import a`, and the
    relative forms."""
    here = path.relative_to(PACKAGE).with_suffix("").parts
    found = set()
    for node in ast.walk(ast.parse(path.read_text(encoding="utf-8"))):
        if isinstance(node, ast.Import):
            targets = [alias.name.split(".") for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = (
                [NAME, *here[: len(here) - node.level]] if node.level
                else []
            ) + (node.module.split(".") if node.module else [])
            targets = [base + [alias.name] for alias in node.names]
        else:
            continue
        found.update(t[1] for t in targets if t[0] == NAME and len(t) > 1)
    return found


@pytest.mark.parametrize("unit", sorted(MAY_IMPORT))
def test_a_low_layer_imports_only_what_lies_under_it(unit):
    files = _files(unit)
    assert files, unit
    outside = {
        (str(path.relative_to(PACKAGE)), other)
        for path in files
        for other in _imported_units(path) - {unit}
        # `from pkg import name` of a name `pkg/__init__.py` defines, not a unit
        if (PACKAGE / other).is_dir() or (PACKAGE / f"{other}.py").exists()
    }
    assert {other for _, other in outside} <= MAY_IMPORT[unit], sorted(outside)
