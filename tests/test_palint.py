"""palint (round 16): the repo-native static-analysis suite + lockcheck.

- each of the six passes fires on a positive fixture and stays quiet on
  the matching negative (standalone-contract, host-sync, recompile-hazard,
  registry-consistency, lock-discipline, observability);
- the pragma engine: `# palint: allow[pass] why` suppresses, an
  unjustified pragma is a finding, a stale pragma is a finding;
- the JSON report schema (`pa-palint/v1`) and the `--check` CLI gate on
  the REAL repo (green — every surviving convention violation is fixed or
  justified in-line);
- utils/lockcheck.py: a deliberate A→B / B→A acquisition cycle is
  detected (and a 3-lock transitive one), a clean consistent ordering is
  not, install() wraps repo-created locks only, uninstall() restores.

The engine is loaded by file path (its own standalone contract — no jax,
no package import), so this file runs even when the package can't import.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import threading
import _thread
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _load_engine():
    pkg_dir = REPO / "scripts" / "palint"
    spec = importlib.util.spec_from_file_location(
        "pa_palint_test", str(pkg_dir / "__init__.py"),
        submodule_search_locations=[str(pkg_dir)])
    mod = importlib.util.module_from_spec(spec)
    sys.modules["pa_palint_test"] = mod
    spec.loader.exec_module(mod)
    return mod


engine = _load_engine()


def _load_lockcheck():
    path = REPO / "comfyui_parallelanything_tpu" / "utils" / "lockcheck.py"
    spec = importlib.util.spec_from_file_location(
        "pa_lockcheck_test", str(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mini_repo(tmp_path: Path, files: dict[str, str]) -> Path:
    """A throwaway repo skeleton; keys are repo-relative paths."""
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return tmp_path


def _codes(findings, pass_name=None):
    return [f.code for f in findings
            if pass_name is None or f.pass_name == pass_name]


def lint(root: Path):
    findings, report = engine.lint(str(root))
    return findings, report


PKG = "comfyui_parallelanything_tpu"


# ---------------------------------------------------------------------------
# standalone-contract
# ---------------------------------------------------------------------------

class TestStandaloneContract:
    def test_module_level_jax_import_flagged(self, tmp_path):
        root = _mini_repo(tmp_path, {
            f"{PKG}/utils/roofline.py": "import json\nimport jax\n",
        })
        findings, _ = lint(root)
        codes = _codes(findings, "standalone-contract")
        assert codes == ["nonstd-import"]

    def test_relative_import_flagged(self, tmp_path):
        root = _mini_repo(tmp_path, {
            f"{PKG}/fleet/twin.py": "from ..utils import retry\n",
        })
        findings, _ = lint(root)
        assert _codes(findings, "standalone-contract") == ["relative-import"]

    def test_script_package_import_flagged(self, tmp_path):
        root = _mini_repo(tmp_path, {
            "scripts/myreport.py":
                f"from {PKG}.utils.roofline import walk_jaxpr\n",
        })
        findings, _ = lint(root)
        assert _codes(findings, "standalone-contract") == ["nonstd-import"]

    def test_clean_patterns_pass(self, tmp_path):
        root = _mini_repo(tmp_path, {
            # stdlib + function-level jax + `import bench`: all legal.
            f"{PKG}/utils/slo.py":
                "import json\nimport os\n\n"
                "def f():\n    import jax\n    return jax\n",
            "scripts/gate.py": "import bench\nimport argparse\n",
            "bench.py": "import json\n",
            # non-declared package modules may import anything.
            f"{PKG}/models/unet.py": "import jax\n",
        })
        findings, _ = lint(root)
        assert _codes(findings, "standalone-contract") == []

    def test_import_under_module_level_try_still_flagged(self, tmp_path):
        root = _mini_repo(tmp_path, {
            f"{PKG}/utils/retry.py":
                "try:\n    import numpy\nexcept ImportError:\n"
                "    numpy = None\n",
        })
        findings, _ = lint(root)
        assert _codes(findings, "standalone-contract") == ["nonstd-import"]


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

_TIMED_LOOP_BAD = """\
import time

def run(step, x, iters):
    t0 = time.perf_counter()
    for _ in range(iters):
        x = step(x)
        x.block_until_ready()
    return (time.perf_counter() - t0) / iters
"""

_TIMED_LOOP_OK = """\
import time

def run(step, x, iters):
    t0 = time.perf_counter()
    for _ in range(iters):
        x = step(x)
    force_ready(x)
    return (time.perf_counter() - t0) / iters
"""


class TestHostSync:
    def test_sync_inside_timed_loop_flagged(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/utils/metrics.py":
                                     _TIMED_LOOP_BAD})
        findings, _ = lint(root)
        assert "sync-in-hot-path" in _codes(findings, "host-sync")

    def test_boundary_sync_outside_loop_ok(self, tmp_path):
        # The closing force_ready sits between the stamps but outside the
        # loop — the StepTimer/chained_time honest-timing pattern.
        root = _mini_repo(tmp_path, {f"{PKG}/utils/metrics.py":
                                     _TIMED_LOOP_OK})
        findings, _ = lint(root)
        assert _codes(findings, "host-sync") == []

    def test_hot_path_transfer_flagged_and_jnp_asarray_ok(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/serving/bucket.py": (
            "import numpy as np\nimport jax.numpy as jnp\n\n"
            "class StepBucket:\n"
            "    def dispatch(self):\n"
            "        dev = jnp.asarray([1.0])\n"      # host→device: legal
            "        host = np.asarray(dev)\n"        # device→host: flagged
            "        return float(host[0])\n"         # float(subscript): flagged
        )})
        findings, _ = lint(root)
        codes = _codes(findings, "host-sync")
        assert codes.count("sync-in-hot-path") == 2

    def test_pragma_allows_boundary_block(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/serving/bucket.py": (
            "class StepBucket:\n"
            "    def dispatch(self, jax, x):\n"
            "        # palint: allow[host-sync] completion boundary\n"
            "        jax.block_until_ready(x)\n"
        )})
        findings, _ = lint(root)
        assert _codes(findings, "host-sync") == []
        # and the pragma is counted as used, not stale
        assert "stale-pragma" not in _codes(findings)


# ---------------------------------------------------------------------------
# recompile-hazard
# ---------------------------------------------------------------------------

class TestRecompileHazard:
    def test_dynamic_program_name_flagged(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/sampling/loops.py": (
            "def build(fn, n):\n"
            "    return instrument_jit(fn, f'loop:{n}')\n"
        )})
        findings, _ = lint(root)
        assert _codes(findings, "recompile-hazard") == [
            "dynamic-program-name"]

    def test_unhashable_static_and_mutable_default_flagged(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/sampling/loops.py": (
            "import jax\n\n"
            "def step(x, opts={}):\n"
            "    return x\n\n"
            "prog = jax.jit(step, static_argnames=('opts',))\n"
        )})
        findings, _ = lint(root)
        codes = _codes(findings, "recompile-hazard")
        assert "unhashable-static" in codes
        assert "mutable-default" in codes

    def test_static_argnums_resolution(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/parallel/stage.py": (
            "import jax\n\n"
            "def step(x, shape=[1, 2]):\n"
            "    return x\n\n"
            "prog = jax.jit(step, static_argnums=[1])\n"
        )})
        findings, _ = lint(root)
        assert "unhashable-static" in _codes(findings, "recompile-hazard")

    def test_stable_literal_name_ok(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/sampling/loops.py": (
            "def build(fn):\n"
            "    return instrument_jit(fn, 'loop:k', static_argnames=('n',))\n"
        )})
        findings, _ = lint(root)
        assert _codes(findings, "recompile-hazard") == []


# ---------------------------------------------------------------------------
# registry-consistency
# ---------------------------------------------------------------------------

class TestRegistryConsistency:
    def test_metric_family_check(self, tmp_path):
        root = _mini_repo(tmp_path, {
            f"{PKG}/utils/metrics.py":
                '"""Families: ``pa_good_*`` (x).\n"""\n',
            f"{PKG}/serving/bucket.py": (
                "def f(registry):\n"
                "    registry.counter('pa_good_x_total')\n"
                "    registry.gauge('pa_bad_thing', 1.0)\n"
            ),
        })
        findings, _ = lint(root)
        bad = [f for f in findings if f.code == "undocumented-metric"]
        assert len(bad) == 1 and "pa_bad_thing" in bad[0].message

    def test_env_table_both_directions(self, tmp_path):
        root = _mini_repo(tmp_path, {
            "README.md": "| `PA_DOCUMENTED` | x |\n| `PA_GHOST` | y |\n",
            f"{PKG}/server.py": (
                "import os\n"
                "A = os.environ.get('PA_DOCUMENTED')\n"
                "B = os.environ.get('PA_UNDOCUMENTED')\n"
            ),
        })
        findings, _ = lint(root)
        codes = _codes(findings, "registry-consistency")
        assert codes.count("undocumented-env") == 1
        assert codes.count("stale-env-doc") == 1

    def test_fault_sites_both_directions(self, tmp_path):
        root = _mini_repo(tmp_path, {
            f"{PKG}/utils/faults.py":
                "FAULT_SITES = {'real-site': 'x', 'dead-site': 'y'}\n",
            f"{PKG}/parallel/streaming.py": (
                "def f(faults):\n"
                "    faults.check('real-site', key='k')\n"
                "    faults.check('typo-site', key='k')\n"
            ),
        })
        findings, _ = lint(root)
        codes = _codes(findings, "registry-consistency")
        assert codes.count("unknown-fault-site") == 1
        assert codes.count("unfired-fault-site") == 1

    def test_span_category_vocabulary(self, tmp_path):
        root = _mini_repo(tmp_path, {
            "scripts/trace_summary.py":
                "SPAN_CATEGORIES = ('stream', 'ghost')\n",
            f"{PKG}/utils/tracing.py": (
                "def f(tracing):\n"
                "    tracing.record('x', 0, 1, cat='stream')\n"
                "    tracing.record('y', 0, 1, cat='mystery')\n"
            ),
        })
        findings, _ = lint(root)
        codes = _codes(findings, "registry-consistency")
        assert codes.count("unknown-span-category") == 1
        assert codes.count("stale-span-category") == 1

    def test_late_schema_drift(self, tmp_path):
        root = _mini_repo(tmp_path, {
            "bench.py": (
                "_LATE_SCHEMA_FIELDS = ('emitted_field', 'phantom_field')\n"
                "rec = {}\n"
                "rec['emitted_field'] = 1\n"
            ),
        })
        findings, _ = lint(root)
        drift = [f for f in findings if f.code == "late-schema-drift"]
        assert len(drift) == 1 and "phantom_field" in drift[0].message


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

_LOCKED_CLASS = """\
import threading


class Table:
    def __init__(self):
        self._lock = threading.Lock()
        self._rows = {{}}{ann}

    def put(self, k, v):
{body}
"""


class TestLockDiscipline:
    def test_unannotated_container_flagged(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/fleet/table.py":
                          _LOCKED_CLASS.format(
                              ann="",
                              body="        with self._lock:\n"
                                   "            self._rows[k] = v\n")})
        findings, _ = lint(root)
        assert _codes(findings, "lock-discipline") == [
            "unannotated-shared-attr"]

    def test_guarded_write_outside_lock_flagged(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/fleet/table.py":
                          _LOCKED_CLASS.format(
                              ann="  # guarded-by: _lock",
                              body="        self._rows[k] = v\n")})
        findings, _ = lint(root)
        assert _codes(findings, "lock-discipline") == ["unguarded-write"]

    def test_guarded_write_under_lock_ok(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/fleet/table.py":
                          _LOCKED_CLASS.format(
                              ann="  # guarded-by: _lock",
                              body="        with self._lock:\n"
                                   "            self._rows[k] = v\n")})
        findings, _ = lint(root)
        assert _codes(findings, "lock-discipline") == []

    def test_holds_annotation_and_mutator_calls(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/serving/table.py": (
            "import threading\n\n\n"
            "class Table:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._rows = {}  # guarded-by: _lock\n\n"
            "    def _put(self, k, v):  # palint: holds _lock\n"
            "        self._rows.update({k: v})\n\n"
            "    def drop(self, k):\n"
            "        self._rows.pop(k, None)\n"
        )})
        findings, _ = lint(root)
        # update() under holds is fine; pop() outside any lock is not.
        assert _codes(findings, "lock-discipline") == ["unguarded-write"]

    def test_condition_alias_covers_lock(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/serving/table.py": (
            "import threading\n\n\n"
            "class Table:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._cond = threading.Condition(self._lock)\n"
            "        self._rows = {}  # guarded-by: _lock\n\n"
            "    def put(self, k, v):\n"
            "        with self._cond:\n"
            "            self._rows[k] = v\n"
        )})
        findings, _ = lint(root)
        assert _codes(findings, "lock-discipline") == []

    def test_unguarded_reason_accepted(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/fleet/table.py":
                          _LOCKED_CLASS.format(
                              ann="  # unguarded: write-once pre-thread",
                              body="        self.other = v\n")})
        findings, _ = lint(root)
        assert _codes(findings, "lock-discipline") == []

    def test_unguarded_empty_reason_flagged(self, tmp_path):
        # `# unguarded:` with no reason would be a mute button — the engine
        # rejects it the way it rejects unjustified allow-pragmas.
        root = _mini_repo(tmp_path, {f"{PKG}/fleet/table.py":
                          _LOCKED_CLASS.format(
                              ann="  # unguarded:",
                              body="        self.other = v\n")})
        findings, _ = lint(root)
        assert "unjustified-annotation" in _codes(findings, "engine")

    def test_module_level_lock_and_global(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/serving/mod.py": (
            "import threading\n\n"
            "_batch_lock = threading.Lock()\n"
            "_counts = {}  # guarded-by: _batch_lock\n\n\n"
            "def good(k):\n"
            "    with _batch_lock:\n"
            "        _counts[k] = _counts.get(k, 0) + 1\n\n\n"
            "def bad(k):\n"
            "    _counts[k] = 0\n"
        )})
        findings, _ = lint(root)
        assert _codes(findings, "lock-discipline") == ["unguarded-write"]


# ---------------------------------------------------------------------------
# observability + pragma engine
# ---------------------------------------------------------------------------

class TestObservabilityAndPragmas:
    def test_print_and_time_flagged(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/utils/thing.py": (
            "import time\n\n"
            "def f():\n"
            "    print('hello')\n"
            "    return time.time()\n"
        )})
        findings, _ = lint(root)
        codes = _codes(findings, "observability")
        assert sorted(codes) == ["ad-hoc-time", "bare-print"]

    def test_scripts_exempt(self, tmp_path):
        root = _mini_repo(tmp_path, {"scripts/cli.py":
                                     "import time\nprint(time.time())\n"})
        findings, _ = lint(root)
        assert _codes(findings, "observability") == []

    def test_pragma_suppresses(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/utils/thing.py": (
            "def f():\n"
            "    # palint: allow[observability] CLI banner\n"
            "    print('hello')\n"
        )})
        findings, _ = lint(root)
        assert findings == []

    def test_unjustified_pragma_flagged(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/utils/thing.py": (
            "def f():\n"
            "    # palint: allow[observability]\n"
            "    print('hello')\n"
        )})
        findings, _ = lint(root)
        assert _codes(findings) == ["unjustified-pragma"]

    def test_stale_pragma_flagged(self, tmp_path):
        root = _mini_repo(tmp_path, {f"{PKG}/utils/thing.py": (
            "def f():\n"
            "    # palint: allow[observability] nothing here anymore\n"
            "    return 1\n"
        )})
        findings, _ = lint(root)
        assert _codes(findings) == ["stale-pragma"]


# ---------------------------------------------------------------------------
# report schema + the real repo gate (CLI, subprocess)
# ---------------------------------------------------------------------------

class TestReportAndRepoGate:
    def test_check_green_on_repo_and_report_schema(self, tmp_path):
        env = dict(os.environ, PA_LEDGER_DIR=str(tmp_path))
        proc = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "palint.py"),
             "--check", "--json"],
            capture_output=True, text=True, env=env, timeout=120,
        )
        assert proc.returncode == 0, (
            f"palint --check failed on the repo:\n{proc.stdout}\n"
            f"{proc.stderr}"
        )
        report = json.loads(proc.stdout)
        assert report["schema"] == "pa-palint/v1"
        assert report["ok"] is True and report["findings"] == []
        assert set(report["counts"]) == {
            "standalone-contract", "host-sync", "recompile-hazard",
            "registry-consistency", "lock-discipline", "observability",
        }
        assert report["files_scanned"] > 50
        # the ledger report landed under the redirect
        on_disk = json.loads((tmp_path / "palint.json").read_text())
        assert on_disk["schema"] == "pa-palint/v1"

    def test_check_exits_nonzero_on_violation(self, tmp_path):
        root = _mini_repo(tmp_path, {
            f"{PKG}/utils/thing.py": "print('x')\n",
            "scripts/.keep.py": "",
        })
        findings, report = lint(root)
        assert findings and report["ok"] is False

    def test_env_table_contains_inventory(self):
        proc = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "palint.py"),
             "--env-table"],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0
        assert "| `PA_LOCKCHECK` |" in proc.stdout
        assert "| `PA_FAULT_PLAN` |" in proc.stdout

    def test_env_table_preserves_readme_purposes(self, tmp_path):
        # The inventory comes from the code; the Purpose prose is preserved
        # from the committed README on regeneration, and a var the README
        # has never described gets a TODO row naming its read sites — so
        # "regenerate after adding a variable" never destroys the docs.
        root = _mini_repo(tmp_path, {
            f"{PKG}/utils/thing.py": (
                "import os\n\n"
                "A = os.environ.get('PA_OLD_VAR')\n"
                "B = os.environ.get('PA_NEW_VAR')\n"),
            "README.md": (
                "| Variable | Purpose |\n|---|---|\n"
                "| `PA_OLD_VAR` | the documented purpose |\n"),
        })
        table = engine.env_table(str(root))
        assert "| `PA_OLD_VAR` | the documented purpose |" in table
        assert "| `PA_NEW_VAR` | TODO: describe (read in thing.py) |" \
            in table

    def test_env_table_reproduces_committed_readme_table(self):
        # The README's committed table IS the generator's output today —
        # the drift gate the README documents.
        table = engine.env_table(str(REPO))
        readme = (REPO / "README.md").read_text()
        for row in table.splitlines()[2:]:
            assert row in readme, f"README env table drifted: {row}"
        assert "TODO: describe" not in table

    def test_engine_is_jax_free(self):
        env = dict(os.environ)
        code = (
            "import runpy, sys\n"
            "sys.argv = ['palint.py', '--env-table']\n"
            "try:\n"
            f"    runpy.run_path(r'{REPO}/scripts/palint.py',"
            " run_name='__main__')\n"
            "except SystemExit as e:\n"
            "    assert (e.code or 0) == 0, e.code\n"
            "assert 'jax' not in sys.modules, 'palint pulled jax'\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, env=env, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# utils/lockcheck.py — the runtime half
# ---------------------------------------------------------------------------

class TestLockcheck:
    def test_ab_ba_cycle_detected(self):
        lc = _load_lockcheck()
        A = lc.TrackedLock(_thread.allocate_lock(), "site:A", "Lock")
        B = lc.TrackedLock(_thread.allocate_lock(), "site:B", "Lock")

        def order_ab():
            with A:
                with B:
                    pass

        def order_ba():
            with B:
                with A:
                    pass

        # Two code paths with opposite orders, exercised from two threads
        # run to completion sequentially — no real deadlock ever fires, and
        # the graph still convicts the ORDER.
        for fn in (order_ab, order_ba):
            t = threading.Thread(target=fn)
            t.start()
            t.join()
        cyc = lc.cycles()
        assert len(cyc) == 1
        assert set(cyc[0]) == {"site:A", "site:B"}
        assert lc.report()["ok"] is False

    def test_clean_ordering_not_flagged(self):
        lc = _load_lockcheck()
        A = lc.TrackedLock(_thread.allocate_lock(), "site:A", "Lock")
        B = lc.TrackedLock(_thread.allocate_lock(), "site:B", "Lock")
        for _ in range(3):
            with A:
                with B:
                    pass
        assert lc.cycles() == []
        assert lc.report()["ok"] is True
        assert lc.edges() and lc.edges()[0]["count"] == 3

    def test_edge_attribution_names_acquiring_site(self):
        lc = _load_lockcheck()
        A = lc.TrackedLock(_thread.allocate_lock(), "site:A", "Lock")
        B = lc.TrackedLock(_thread.allocate_lock(), "site:B", "Lock")
        with A:
            with B:
                pass
        (edge,) = lc.edges()
        # The forensic `at` must name the ACQUIRING frame (this file), not
        # lockcheck's own __enter__/acquire plumbing — with-statements add
        # two lockcheck frames that a fixed _getframe depth would land on.
        assert edge["at"].startswith("test_palint.py:"), edge
        lc = _load_lockcheck()
        locks = {s: lc.TrackedLock(_thread.allocate_lock(), f"site:{s}",
                                   "Lock") for s in "ABC"}
        for first, second in (("A", "B"), ("B", "C"), ("C", "A")):
            with locks[first]:
                with locks[second]:
                    pass
        cyc = lc.cycles()
        assert len(cyc) == 1 and set(cyc[0]) == {
            "site:A", "site:B", "site:C"}

    def test_rlock_reentry_is_not_an_edge(self):
        lc = _load_lockcheck()
        R = lc.TrackedLock(_thread.allocate_lock(), "site:R", "RLock")
        # simulate reentrancy bookkeeping: same object acquired nested
        held = [R, R]
        with lc._graph_mutex:
            pass  # no edge was recorded for a self-pair
        A = lc.TrackedLock(_thread.allocate_lock(), "site:R", "Lock")
        B = lc.TrackedLock(_thread.allocate_lock(), "site:R", "Lock")
        with A:
            with B:  # distinct objects, SAME creation site: not an edge
                pass
        assert lc.edges() == [] and held

    def test_install_tracks_repo_locks_and_uninstall_restores(self):
        lc = _load_lockcheck()
        prev_lock, prev_rlock = threading.Lock, threading.RLock
        lc.install()
        try:
            tracked = threading.Lock()   # created HERE (tests/ = in-repo)
            assert type(tracked).__name__ == "TrackedLock"
            assert tracked.site.startswith("tests/test_palint.py")
            with tracked:
                assert tracked.locked()
            r = threading.RLock()
            with r:
                with r:  # reentrancy must hold through the proxy
                    pass
            cond = threading.Condition(threading.RLock())
            with cond:
                pass
        finally:
            lc.uninstall()
        assert threading.Lock is prev_lock
        assert threading.RLock is prev_rlock
