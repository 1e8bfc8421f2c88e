"""Every ``repro`` import in the package and the examples resolves.

One case per module file under ``src/repro`` and per example script,
found on the file system. Each case parses its file and collects every
``repro`` import, lazy ones inside function bodies too (those fail only
when the function is called). It checks that each target module exists
and that each name a ``from repro.x import y`` asks for is an attribute
or a submodule of ``repro.x``. A package module other than a
``__main__`` is then imported whole.
"""
import ast
import importlib
import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
FILES = sorted((SRC / "repro").rglob("*.py")) + sorted(
    (ROOT / "examples").glob("*.py")
)


def _module_name(path: Path) -> str | None:
    """Dotted name of a package module; None for an example script."""
    if SRC not in path.parents:
        return None
    parts = path.relative_to(SRC).with_suffix("").parts
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def _repro_imports(tree: ast.AST, package: str | None):
    """``(module, names)`` for every import of ``repro`` in ``tree``;
    ``names`` is empty for a plain ``import repro.x``."""
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found += [
                (alias.name, ()) for alias in node.names
                if alias.name.split(".")[0] == "repro"
            ]
        elif isinstance(node, ast.ImportFrom):
            module = node.module or ""
            if node.level:
                module = importlib.util.resolve_name(
                    "." * node.level + module, package
                )
            if module.split(".")[0] == "repro":
                found.append((module, tuple(a.name for a in node.names)))
    return found


def _has_submodule(module: str, name: str) -> bool:
    try:
        return importlib.util.find_spec(f"{module}.{name}") is not None
    except ModuleNotFoundError:
        return False


@pytest.mark.parametrize(
    "path", FILES, ids=[str(p.relative_to(ROOT)) for p in FILES]
)
def test_repro_imports_resolve(path):
    name = _module_name(path)
    if name is None:
        package = None
    elif path.name == "__init__.py":
        package = name
    else:
        package = name.rpartition(".")[0]
    tree = ast.parse(path.read_text(), filename=str(path))
    for module, names in _repro_imports(tree, package):
        assert importlib.util.find_spec(module) is not None, (
            f"{path.name} imports missing module {module}"
        )
        if not names:
            continue
        target = importlib.import_module(module)
        for n in names:
            assert n == "*" or hasattr(target, n) or _has_submodule(
                module, n
            ), f"{path.name}: {module} has no {n}"
    if name is not None and not name.endswith("__main__"):
        importlib.import_module(name)
