"""W401/W402/W403 · layering / import boundary.

The package DAG in :data:`contracts.ALLOWED_EDGES` is the declarative
source of truth for who may import whom inside ``repro`` (the old
hand-rolled scan in ``tests/test_import_boundary.py`` now delegates
here).  Function-local (lazy) imports count: an edge is an edge, lazy or
not — lazy edges that are *intended* (the ``core -> pipeline`` shim) are
listed in the DAG like any other.

* **W401** — a module in package P imports ``repro.Q`` with Q outside
  ``ALLOWED_EDGES[P]``.  The north-star edge this guards: ``core`` (and
  everything below it) never imports ``api``; ``store`` imports only
  the stdlib-only recorder ``obs``.
* **W402** — a facade file (examples, fleet benchmarks) imports a
  ``repro`` module outside the public surface (``repro.api`` /
  ``repro.fleet``).
* **W403** — ``repro.legacy`` imported outside ``tests/`` /
  ``benchmarks/``: the frozen pre-refactor surface exists only for
  characterization tests and the throughput benchmark.
"""
from __future__ import annotations

import ast

from . import contracts
from .core import Finding, LintContext, SourceFile

RULES = {
    "W401": "package imports outside its allowed DAG edges",
    "W402": "facade file imports past the public repro.api/repro.fleet "
            "surface",
    "W403": "repro.legacy imported outside tests/ and benchmarks/",
}


def _imports(sf: SourceFile):
    """Yield ``(dotted_module, lineno)`` for every import in the file,
    with relative imports resolved against the file's own module."""
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, node.lineno
        elif isinstance(node, ast.ImportFrom):
            if node.level == 0:
                if node.module:
                    yield node.module, node.lineno
                continue
            base = (sf.module or "").split(".")
            # a module's level-1 relative import resolves against its
            # package: drop the module segment plus (level - 1) parents.
            # For a package __init__ the module IS the package, so one
            # fewer segment comes off.
            if base:
                drop = node.level - 1 if sf.path.endswith("__init__.py") \
                    else node.level
                anchor = base[:len(base) - drop]
                mod = ".".join(anchor + ([node.module]
                                         if node.module else []))
                if mod:
                    yield mod, node.lineno


def _target_package(module: str) -> str | None:
    """Top-level repro package a dotted import lands in, else None."""
    parts = module.split(".")
    if parts[0] != "repro":
        return None
    return parts[1] if len(parts) > 1 else "repro"


def run_pass(ctx: LintContext) -> list[Finding]:
    findings: list[Finding] = []
    facades = set(contracts.FACADE_FILES)
    for sf in ctx.files:
        if sf.tree is None:
            continue
        is_facade = sf.path in facades
        pkg = sf.package
        for module, lineno in _imports(sf):
            target = _target_package(module)
            if target is None:
                continue
            # W403 first: legacy has one rule for the whole repo
            if target == "legacy":
                if not sf.path.startswith(contracts.LEGACY_ALLOWED_DIRS):
                    findings.append(Finding(
                        "W403", sf.path, lineno,
                        "repro.legacy is the frozen pre-refactor surface; "
                        "only tests/ and benchmarks/ may import it",
                        hint="use repro.api (MinosSession) instead"))
                continue
            if is_facade:
                if not (module in contracts.FACADE_ALLOWED or any(
                        module.startswith(a + ".")
                        for a in contracts.FACADE_ALLOWED)):
                    findings.append(Finding(
                        "W402", sf.path, lineno,
                        f"facade file imports {module}; facades consume "
                        f"only {' / '.join(contracts.FACADE_ALLOWED)}",
                        hint="re-export what you need through repro.api "
                             "or drop the file from FACADE_FILES with a "
                             "rationale"))
                continue
            if pkg is None or pkg == "repro" or target == "repro":
                continue  # tests/benchmarks may import anything non-legacy
            if target == pkg:
                continue
            allowed = contracts.ALLOWED_EDGES.get(pkg)
            if allowed is None:
                findings.append(Finding(
                    "W401", sf.path, lineno,
                    f"package {pkg!r} has no entry in ALLOWED_EDGES",
                    hint="declare the package's allowed imports in "
                         "lint/contracts.py"))
            elif target not in allowed:
                findings.append(Finding(
                    "W401", sf.path, lineno,
                    f"illegal package edge {pkg} -> {target} "
                    f"(allowed: {', '.join(sorted(allowed)) or 'none'})",
                    hint="invert the dependency or add the edge to "
                         "ALLOWED_EDGES with a rationale"))
    return findings
