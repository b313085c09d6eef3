"""standalone-contract: stdlib-only module level, no package-relative imports.

The gate scripts (perf_ledger, numerics_audit, roofline_report,
twin_report, trace_summary, palint itself) must run without jax — on a
laptop holding just the ledger, or beside a process that holds the chip
(a chip belongs to one process, and importing jax on a TPU host takes it).
That only works because the modules they load keep their MODULE LEVEL stdlib-only
and free of package-relative imports: ``utils/roofline.py`` established
the contract (scripts/roofline_report.py path-loads it), ``utils/slo.py``,
``utils/retry.py``, ``utils/faults.py``, ``utils/lockcheck.py`` and
``fleet/twin.py`` adopted it, and ``bench.py``'s module level is the
reason scripts/perf_ledger.py can ``import bench`` jax-free.

This pass machine-checks the contract for those modules plus ALL of
``scripts/``:

- module-level ``import``/``from`` must resolve to the stdlib or to
  ``bench`` (itself a checked standalone module);
- package-relative imports (``from . import x`` / ``from ..utils import``)
  are banned at module level for the declared-standalone package modules
  (a path-loaded module has no package to be relative to);
- function-level imports are exempt — that IS the graceful-degradation
  pattern the contract prescribes.

TPU-side scripts (bench_kernels, bench_sampler_loop, …) already keep jax behind
function level, so the whole directory holds the contract uniformly.
"""

from __future__ import annotations

import ast
import sys

NAME = "standalone-contract"
DOC = "standalone-loadable modules: stdlib-only module level"

# Package modules that DECLARE the standalone contract (each one's
# docstring says so; scripts load them by file path). scripts/ and
# bench.py are added wholesale by run().
DECLARED = (
    "comfyui_parallelanything_tpu/utils/roofline.py",
    "comfyui_parallelanything_tpu/utils/slo.py",
    "comfyui_parallelanything_tpu/utils/retry.py",
    "comfyui_parallelanything_tpu/utils/faults.py",
    "comfyui_parallelanything_tpu/utils/lockcheck.py",
    "comfyui_parallelanything_tpu/utils/timeseries.py",
    "comfyui_parallelanything_tpu/utils/anomaly.py",
    "comfyui_parallelanything_tpu/fleet/twin.py",
)

# Non-stdlib module-level imports that are still standalone-safe: bench.py
# keeps its own module level jax-free (checked by this pass), which is what
# lets scripts/perf_ledger.py et al. `import bench` without touching jax.
ALLOWED_LOCAL = {"bench"}


def _stdlib() -> frozenset:
    names = getattr(sys, "stdlib_module_names", None)
    if names:  # 3.10+
        return frozenset(names) | {"__future__"}
    return frozenset({"__future__"})  # pragma: no cover - 3.10 floor


def run(ctx) -> list[dict]:
    stdlib = _stdlib()
    findings: list[dict] = []
    targets = [f for f in ctx.files
               if f.rel in DECLARED
               or f.rel == "bench.py"
               or f.rel.startswith("scripts/")]
    for f in targets:
        if f.tree is None:
            continue
        for node in _module_level_imports(f.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    top = alias.name.split(".")[0]
                    if top not in stdlib and top not in ALLOWED_LOCAL:
                        findings.append({
                            "path": f.rel, "line": node.lineno,
                            "code": "nonstd-import",
                            "message": (
                                f"module-level `import {alias.name}` breaks "
                                f"the standalone contract (stdlib-only — "
                                f"move under function level or path-load)"),
                        })
            elif isinstance(node, ast.ImportFrom):
                if node.level and node.level > 0:
                    findings.append({
                        "path": f.rel, "line": node.lineno,
                        "code": "relative-import",
                        "message": (
                            "module-level package-relative import — a "
                            "path-loaded standalone module has no package "
                            "to be relative to"),
                    })
                    continue
                top = (node.module or "").split(".")[0]
                if top and top not in stdlib and top not in ALLOWED_LOCAL:
                    findings.append({
                        "path": f.rel, "line": node.lineno,
                        "code": "nonstd-import",
                        "message": (
                            f"module-level `from {node.module} import …` "
                            f"breaks the standalone contract (pulls the "
                            f"package __init__ chain — path-load the module "
                            f"instead, the scripts/roofline_report.py "
                            f"pattern)"),
                    })
    return findings


def _module_level_imports(tree: ast.Module):
    """Imports in the module body, including inside top-level `if`/`try`
    blocks (those still execute at import time) — but NOT inside function
    or class bodies."""
    stack = list(tree.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            yield node
        elif isinstance(node, (ast.If, ast.Try, ast.With)):
            for field in ("body", "orelse", "finalbody", "handlers"):
                for child in getattr(node, field, []) or []:
                    if isinstance(child, ast.ExceptHandler):
                        stack.extend(child.body)
                    else:
                        stack.append(child)
