"""palint CLI — repo-native static analysis + lock-order discipline gate.

Thin entry point over the ``scripts/palint/`` pass package (engine and
passes are documented there). Stdlib-only and jax-free by the standalone
contract it enforces: this runs without a backend, in CI before the
38-minute suite (``scripts/ci_tier1.sh`` fast-fail), and on a laptop
holding just the checkout.

Usage:
    python scripts/palint.py              # findings + ledger/palint.json
    python scripts/palint.py --check     # exit 1 on any finding (CI gate)
    python scripts/palint.py --json      # machine-readable report
    python scripts/palint.py --env-table # regenerate the README PA_* table

Passes: standalone-contract, host-sync, recompile-hazard,
registry-consistency, lock-discipline, observability. Per-line pragmas:
``# palint: allow[<pass>] <justification>`` (stale or unjustified pragmas
are themselves findings). The runtime companion is ``utils/lockcheck.py``
(``PA_LOCKCHECK=1`` lock-acquisition-order graph).
"""

from __future__ import annotations

import importlib.util
import os
import sys


def _load_engine():
    """Load scripts/palint/__init__.py as a proper package by path — the
    scripts directory is not a package, and sys.path tricks would race the
    module/package name collision (palint.py vs palint/)."""
    here = os.path.dirname(os.path.abspath(__file__))
    pkg_dir = os.path.join(here, "palint")
    spec = importlib.util.spec_from_file_location(
        "pa_palint", os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules["pa_palint"] = mod
    spec.loader.exec_module(mod)
    return mod


if __name__ == "__main__":
    sys.exit(_load_engine().main())
