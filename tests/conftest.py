"""Test harness: force an 8-device virtual CPU platform before JAX initializes.

This is the multi-device-without-hardware story the reference lacks entirely
(SURVEY §4): `--xla_force_host_platform_device_count=8` gives every test a real 8-way
mesh on any machine, so the sharding path is exercised exactly as it would be on a
v5e-8, minus the ICI.
"""

import os

# Force the CPU platform: the test suite is defined over the virtual 8-device
# CPU mesh, whatever the machine has attached.
os.environ["JAX_PLATFORMS"] = "cpu"
# Everything a test appends to — the perf ledger, postmortem bundles, bench
# evidence files — goes to a per-process temp directory, so a run leaves the
# checkout clean. Tests that assert on those files set their own. Only the
# evidence root is set: the ledger follows it ($PA_LEDGER_DIR, where a test
# sets one, still wins), so a test that redirects PA_EVIDENCE_DIR alone
# takes its ledger along.
import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

_ARTIFACTS = tempfile.mkdtemp(prefix="pa-test-artifacts-")
atexit.register(shutil.rmtree, _ARTIFACTS, ignore_errors=True)
os.environ["PA_EVIDENCE_DIR"] = _ARTIFACTS
os.environ.pop("PA_LEDGER_DIR", None)
# Telemetry cost analysis re-lowers each instrumented program once at its
# first compile — valuable accounting on real runs, pure wall-clock overhead
# across a suite that compiles hundreds of tiny programs. Off by default
# here; the telemetry tests that assert FLOPs turn it back on per-test.
os.environ.setdefault("PA_TELEMETRY_COST", "0")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# PA_LOCKCHECK=1 (round 16): install the lock-acquisition-order tracker
# BEFORE jax/the package import so every module-level threading.Lock() in
# the package is born tracked. Path-loaded (utils/lockcheck.py is
# standalone by contract) precisely because importing the package here
# would create its locks un-tracked.
_lockcheck = None
if os.environ.get("PA_LOCKCHECK") == "1":
    import importlib.util as _ilu

    _lc_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "comfyui_parallelanything_tpu", "utils", "lockcheck.py",
    )
    _spec = _ilu.spec_from_file_location("pa_lockcheck_boot", _lc_path)
    _lockcheck = _ilu.module_from_spec(_spec)
    _spec.loader.exec_module(_lockcheck)
    _lockcheck.install()
    # ONE graph per process: later package imports of utils.lockcheck must
    # resolve to THIS instance (the installed factories close over its
    # edge dict), not a second execution of the file.
    import sys as _sys

    _sys.modules["comfyui_parallelanything_tpu.utils.lockcheck"] = _lockcheck

import jax  # noqa: E402

# This XLA CPU backend executes `default`-precision f32 matmuls at bf16 (matching TPU
# MXU behavior), but partitioned dots lower at full f32 — pin highest precision so
# sharded-vs-single equivalence tests compare at f32 tolerances.
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _no_lock_order_cycles():
    """Under PA_LOCKCHECK=1 every test ends with the lock-order graph
    acyclic — the interleaving-independent deadlock gate (a cycle is an
    ORDER fact: it fails here even when CI never schedules the deadlock).
    Attribution is per-test: the graph is cumulative (an edge from test A
    plus the reverse edge from test B is a real cross-path cycle), so the
    fixture snapshots the cycles already reported and fails only the test
    that closed a NEW one — the first offender goes red, not every test
    after it."""
    if _lockcheck is None:
        yield
        return
    before = {tuple(c) for c in _lockcheck.cycles()}
    yield
    new = [c for c in _lockcheck.cycles() if tuple(c) not in before]
    assert not new, (
        "lock-order cycle(s) recorded (potential deadlock): "
        + "; ".join(" -> ".join(c) for c in new)
    )


@pytest.fixture(autouse=True)
def _no_stale_interrupt():
    """The cooperative sampler interrupt (utils/progress.py) is process-wide
    state: a Cancel that races past its prompt's last checkpoint would poison
    whichever test runs the next workflow (observed as order-dependent
    Interrupted failures in the full suite). Every test ends flag-clean."""
    yield
    from comfyui_parallelanything_tpu.utils.progress import clear_interrupt

    clear_interrupt()
