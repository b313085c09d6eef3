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

# One compile cache a RUN, shared by every xdist worker and by the children the
# tests start (they inherit the variable): a tiny program six workers would
# each compile is compiled once and read back five times. It starts empty and
# goes with the run, so no run sees another's. Where the variable is set
# already, that directory is used as it is.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    _COMPILE_CACHE = tempfile.mkdtemp(prefix="pa-test-jax-cache-")
    atexit.register(shutil.rmtree, _COMPILE_CACHE, ignore_errors=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _COMPILE_CACHE

import jax  # noqa: E402

# The suite's programs are tiny and many: keep every one, whatever it took.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

# This XLA CPU backend executes `default`-precision f32 matmuls at bf16 (matching TPU
# MXU behavior), but partitioned dots lower at full f32 — pin highest precision so
# sharded-vs-single equivalence tests compare at f32 tolerances.
jax.config.update("jax_default_matmul_precision", "highest")

import faulthandler  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

# Far above the slowest test there is (the published-width decoder compile of
# test_compile_tpu_decoder.py: 240-360 s on a loaded machine) and far below the
# whole run's limit.
TEST_LIMIT_S = 600


# ``--dist loadfile`` hands whole files to workers one after another, so a long
# file handed out last IS the wall: the files that take longest (over 110 s in
# a whole run, PERF.md §7 "Tier-1's wall") go out first, the others in their
# order. A file that belongs here is one to cut before it is listed.
LONGEST_FIRST = (
    "test_compile_tpu_decoder.py", "test_stock_nodes_utility.py",
    "test_qk_prologue.py", "test_example_workflows.py",
    "test_zimage_reference.py", "test_controlnet.py",
    "test_flux_reference.py", "test_tpu_compile.py",
    "test_mmdit_reference.py",
    # its bench.py children read the run's compile cache: not among the first
    "test_bench_rungs.py",
    "test_wan_pipeline.py", "test_chip_smoke.py",
    "test_stock_nodes_video_unclip.py", "test_img2img.py",
    "test_pipelines.py", "test_fsdp.py", "test_telemetry.py",
)


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))  # stable


def pytest_configure(config):
    # xdist would re-sort the files by their NUMBER of tests, most first —
    # which hands the one-test file that holds the longest test out LAST.
    # Keep the order above (the attribute is there only where xdist is).
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


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


@pytest.fixture(autouse=True)
def _no_stale_degradation():
    """``pa_degradation_total`` (utils/degrade.py) is process-wide: a rung one
    test took on purpose would fail whichever test on the same worker next
    asserts that nothing degraded. Every test starts from zero."""
    from comfyui_parallelanything_tpu.utils.metrics import registry

    with registry._lock:
        registry._metrics.pop("pa_degradation_total", None)
    yield


@pytest.fixture(autouse=True)
def _a_limit_of_its_own():
    """A test that hangs fails alone, with every thread's stack on stderr, at
    ``TEST_LIMIT_S`` — not the whole run at its command's limit (rc 124,
    which counts only as far as the run got)."""
    if (threading.current_thread() is not threading.main_thread()
            or not hasattr(signal, "setitimer")):
        yield
        return

    def expired(signum, frame):
        pytest.fail(f"the test ran past its limit of {TEST_LIMIT_S} s",
                    pytrace=False)

    faulthandler.dump_traceback_later(TEST_LIMIT_S, exit=False)
    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture
def no_compile_cache():
    """The run's shared compile cache switched off for one test: one that
    counts the backend's compile events, which a hit in that cache does not
    raise. (``test_tpu_compile.py``'s ``topo`` does the same for a file: a
    compile for a described chip is written to the cache but cannot be read
    back without the chip.)"""
    from jax.experimental.compilation_cache import compilation_cache as cc

    previous = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", previous)
    cc.reset_cache()
