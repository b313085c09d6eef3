"""Resource telemetry: compile accounting, HBM watermarks, the perf ledger,
and failure postmortem bundles.

PR 3's span tracing answered "where did the time go"; this module answers the
other two production questions — "where did the bytes and compiles go" and
"did we regress":

- **Compile observability**: :class:`CompileRegistry` accounts every XLA
  compile in the process, per *program* (a stable human-readable name each
  instrumented jit site declares — ``loop:k:euler``, ``stream-stage[0:3)``,
  ``parallel-apply``). :func:`watch_compiles` registers ``jax.monitoring``
  listeners for backend-compile durations and persistent-cache hit/miss
  events; :func:`instrument_jit` wraps ``jax.jit`` so compiles occurring
  inside a program's calls attribute to that program, records a ``compile``
  span (utils/tracing.py) per compile, feeds ``pa_compile_*`` metrics, and —
  on a program's first compile — runs HLO ``cost_analysis()`` on the lowered
  program so the registry carries FLOPs/bytes-accessed per executable, and
  feeds the same analysis through ``utils/roofline.observe_program`` so every
  named program also carries a calibrated analytic time prediction
  (``pa_roofline_predicted_s``, the ``roofline`` health section).
- **Device memory telemetry**: :class:`HbmWatermark` (peak
  ``bytes_in_use`` across snapshots — the ``peak_hbm_bytes`` every bench
  line and ledger record carries) and :class:`MemoryMonitor` (the server's
  periodic sampler) over ``devices.memory.memory_snapshot``, whose CPU
  fallback is deterministic so off-hardware tests can assert the math.
- **Perf ledger**: every bench/dryrun/loadgen run appends one
  schema-versioned JSONL record to ``ledger/perf_ledger.jsonl``
  (:func:`append_ledger_record`); ``scripts/perf_ledger.py`` diffs the latest
  record per (rung, platform) against the banked evidence and exits nonzero
  on a step-time or peak-HBM regression — the CI regression gate.
- **Failure forensics**: :func:`write_postmortem` dumps a bundle (trace ring
  export, metrics snapshot, per-device memory stats, recent log records,
  error + traceback) into ``ledger/postmortem/<stamp>-<tag>/`` so the next
  flux_stream OOM is diagnosable after the fact.

Import discipline: this module imports only stdlib at module level — jax,
metrics, tracing, and devices.memory all load lazily inside functions — so
outer/driver processes can reason about the schema without touching jax
(they still must not import it through the package ``__init__``; bench.py's
outer process carries its own stdlib ledger-append twin for that reason).
"""

from __future__ import annotations

import json
import os
import re
import socket
import threading
import time
import traceback as _traceback

LEDGER_SCHEMA = "pa-perf-ledger/v1"
# v2 (fleet tier): adds top-level host_id / accepting / inflight_prompts —
# the fields a fleet router's scoreboard needs for placement and drain
# decisions without any extra endpoint. v1 consumers are unaffected: the
# additions are top-level keys, every v1 field is unchanged.
HEALTH_SCHEMA = "pa-health/v3"  # v3 adds host warm_keys; every v2 field intact
LEDGER_FILENAME = "perf_ledger.jsonl"

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "OOM", "Resource exhausted")


def looks_like_oom(err) -> bool:
    """Heuristic OOM classifier over an exception (or its string)."""
    text = f"{type(err).__name__}: {err}" if isinstance(err, BaseException) \
        else str(err)
    return any(m in text for m in _OOM_MARKERS)


def _loadavg_1m() -> float | None:
    try:
        return round(os.getloadavg()[0], 2)
    except (AttributeError, OSError):
        return None


# ---------------------------------------------------------------------------
# compile observability
# ---------------------------------------------------------------------------


class CompileRegistry:
    """Process-wide per-program compile accounting.

    Attribution is thread-local: an :class:`instrument_jit` wrapper pushes its
    program name around each call, and the jax.monitoring listeners charge
    whatever compile/cache events fire during that call to the innermost
    program on the calling thread's stack (``(unattributed)`` otherwise —
    library-internal jits like ``device_put`` land there)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._local = threading.local()
        # program name -> {"compiles", "compile_time_s", "cache_hits",
        #                  "cache_misses", "flops", "bytes_accessed"}
        self._programs: dict[str, dict] = {}  # guarded-by: _lock
        self._totals = {  # guarded-by: _lock
            "compiles": 0, "compile_time_s": 0.0,
            "cache_hits": 0, "cache_misses": 0,
        }

    # -- attribution --------------------------------------------------------

    def _stack(self) -> list:
        s = getattr(self._local, "stack", None)
        if s is None:
            s = self._local.stack = []
        return s

    def push_program(self, name: str) -> None:
        self._stack().append(name)

    def pop_program(self) -> None:
        s = self._stack()
        if s:
            s.pop()

    def current_program(self) -> str | None:
        s = self._stack()
        return s[-1] if s else None

    def _prog(self, name: str) -> dict:  # palint: holds _lock
        p = self._programs.get(name)
        if p is None:
            p = self._programs[name] = {
                "compiles": 0, "compile_time_s": 0.0,
                "cache_hits": 0, "cache_misses": 0,
                "flops": None, "bytes_accessed": None,
            }
        return p

    # -- event sinks (called from the jax.monitoring listeners) -------------

    def on_compile(self, dur_s: float) -> None:
        name = self.current_program() or "(unattributed)"
        with self._lock:
            self._totals["compiles"] += 1
            self._totals["compile_time_s"] += dur_s
            p = self._prog(name)
            p["compiles"] += 1
            p["compile_time_s"] += dur_s
        # Side channels outside the lock; both are no-ops when their layer is
        # off, and neither may ever break a compiling caller.
        try:
            from .metrics import registry

            registry.counter("pa_compile_total", labels={"program": name},
                             help="XLA backend compiles per program")
            registry.observe("pa_compile_seconds", dur_s,
                             labels={"program": name},
                             help="XLA backend compile wall time")
        except Exception:
            pass
        try:
            from . import tracing

            tracing.record(
                "compile", tracing.now_us() - dur_s * 1e6, dur_s * 1e6,
                cat="compile", program=name,
            )
        except Exception:
            pass

    def on_cache_event(self, hit: bool) -> None:
        key = "cache_hits" if hit else "cache_misses"
        name = self.current_program() or "(unattributed)"
        with self._lock:
            self._totals[key] += 1
            self._prog(name)[key] += 1
        try:
            from .metrics import registry

            registry.counter(f"pa_compile_{key}_total",
                             labels={"program": name},
                             help="persistent compilation cache "
                                  + ("hits" if hit else "misses"))
        except Exception:
            pass

    def record_cost(self, name: str, flops: float | None,
                    bytes_accessed: float | None) -> None:
        with self._lock:
            p = self._prog(name)
            if flops:
                p["flops"] = float(flops)
            if bytes_accessed:
                p["bytes_accessed"] = float(bytes_accessed)

    # -- read side ----------------------------------------------------------

    def compiles_of(self, name: str) -> int:
        with self._lock:
            p = self._programs.get(name)
            return p["compiles"] if p else 0

    def snapshot(self) -> dict:
        """Totals + per-program breakdown — the ``compile`` section of
        ``GET /health`` and the source of every bench line's
        ``compile_time_s`` / ``compile_cache_hits`` / ``compile_cache_misses``
        fields."""
        with self._lock:
            return {
                "compiles": self._totals["compiles"],
                "compile_time_s": round(self._totals["compile_time_s"], 4),
                "cache_hits": self._totals["cache_hits"],
                "cache_misses": self._totals["cache_misses"],
                "programs": {
                    n: dict(p) for n, p in sorted(self._programs.items())
                },
            }

    def reset(self) -> None:
        with self._lock:
            self._programs.clear()
            self._totals = {
                "compiles": 0, "compile_time_s": 0.0,
                "cache_hits": 0, "cache_misses": 0,
            }


compile_registry = CompileRegistry()

_watch_installed = False
_watch_lock = threading.Lock()


def _on_event_duration(event: str, duration: float, **_kw) -> None:
    # jax 0.4.x: '/jax/core/compile/backend_compile_duration'. Substring
    # match keeps this robust across the key's historical renames.
    if "backend_compile" in event:
        compile_registry.on_compile(float(duration))


def _on_event(event: str, **_kw) -> None:
    if event.endswith("/cache_hits"):
        compile_registry.on_cache_event(True)
    elif event.endswith("/cache_misses"):
        compile_registry.on_cache_event(False)


def watch_compiles() -> None:
    """Idempotently register the jax.monitoring listeners that feed
    :data:`compile_registry`. Listeners are process-global and permanent
    (jax offers no per-listener removal) but do nothing beyond dict updates,
    so installing them once at startup is free."""
    global _watch_installed
    if _watch_installed:  # lock-free fast path: called per instrumented jit
        return            # dispatch, so the mutex must not be in the hot path
    with _watch_lock:
        if _watch_installed:
            return
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(_on_event_duration)
        monitoring.register_event_listener(_on_event)
        _watch_installed = True


def compile_snapshot() -> dict:
    return compile_registry.snapshot()


class _InstrumentedJit:
    """``jax.jit`` plus per-program compile attribution. Call-compatible with
    the jitted callable it wraps; the per-call overhead when nothing compiles
    is two thread-local list ops and one dict read."""

    __slots__ = ("name", "_jit", "_cost_done")

    def __init__(self, fn, name: str, **jit_kwargs):
        import jax

        self.name = name
        self._jit = jax.jit(fn, **jit_kwargs)
        self._cost_done = False

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        watch_compiles()
        reg = compile_registry
        if not self._cost_done:
            # Fault site (utils/faults.py): an injected compile failure fires
            # before this program's FIRST observed compile, so the
            # compile→eager degradation rung (utils/degrade.py) is rehearsed
            # against the same callers a real XLA lowering error would hit.
            from . import faults

            act = faults.check("compile-fail", key=self.name)
            if act is not None:
                raise RuntimeError(
                    f"injected compile failure (program={self.name}, "
                    f"hit={act.hit})"
                )
        n0 = reg.compiles_of(self.name) if not self._cost_done else 0
        reg.push_program(self.name)
        try:
            out = self._jit(*args, **kwargs)
        finally:
            reg.pop_program()
        if not self._cost_done and reg.compiles_of(self.name) > n0:
            # First observed compile for this program: attach HLO cost
            # analysis (FLOPs / bytes accessed) from a lowering over abstract
            # avals — never the concrete buffers, which a donating program
            # may already have invalidated.
            self._cost_done = True
            self._analyze_cost(args, kwargs)
        return out

    def _analyze_cost(self, args, kwargs) -> None:
        if os.environ.get("PA_TELEMETRY_COST") == "0":
            return
        try:
            import jax

            def leaf(l):
                if isinstance(l, jax.core.Tracer):
                    raise _SkipCost  # nested trace: avals aren't concrete
                if hasattr(l, "shape") and hasattr(l, "dtype"):
                    return jax.ShapeDtypeStruct(l.shape, l.dtype)
                return l

            abs_args, abs_kwargs = jax.tree.map(leaf, (args, kwargs))
            cost = self._jit.lower(*abs_args, **abs_kwargs).cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else None
            cost = cost or {}
            compile_registry.record_cost(
                self.name, cost.get("flops"), cost.get("bytes accessed")
            )
            # Roofline prediction (utils/roofline.py): the same first-compile
            # cost analysis priced against the platform's analytic roofline —
            # mesh width/platform read off the CONCRETE args' shardings. Its
            # own flag (PA_ROOFLINE) and its own try/except: a broken
            # prediction must not cost the compile registry its FLOPs row.
            try:
                from . import roofline

                roofline.observe_program(
                    self.name, flops=cost.get("flops"),
                    bytes_accessed=cost.get("bytes accessed"),
                    args=(args, kwargs),
                )
            except Exception:
                pass
        except Exception:
            pass  # accounting must never break the program it accounts


class _SkipCost(Exception):
    pass


def instrument_jit(fn, name: str, **jit_kwargs) -> _InstrumentedJit:
    """The drop-in replacement for ``jax.jit`` at the repo's program-cache
    sites (sampling/compiled.py, parallel/{pipeline,streaming,orchestrator},
    models/api.py): same callable contract, compiles attributed to ``name``
    in :data:`compile_registry`."""
    return _InstrumentedJit(fn, name, **jit_kwargs)


# ---------------------------------------------------------------------------
# device memory telemetry
# ---------------------------------------------------------------------------


class HbmWatermark:
    """Peak device-memory watermark over explicit samples.

    ``sample()`` snapshots every device (``devices.memory.memory_snapshot``
    — deterministic CPU fallback included) and folds the max per-device
    ``bytes_in_use`` into ``peak_bytes``. bench.py samples per timed
    iteration, the streaming runner per stage (traced runs), the server's
    :class:`MemoryMonitor` periodically."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.peak_bytes = 0
        self.samples = 0
        self.last: list[dict] | None = None

    def sample(self, devices=None) -> list[dict]:
        from ..devices.memory import memory_snapshot

        snap = memory_snapshot(devices)
        # Fold in the backend's own peak_bytes_in_use where it exposes one:
        # transient within-step spikes (activation peaks between our samples)
        # are exactly what the watermark exists to catch, and the allocator's
        # running peak sees them when instantaneous bytes_in_use cannot. It
        # is process-lifetime monotone, so reset() cannot lower it — fresh
        # bench children start clean, which is where the number is banked.
        peak = max(
            (max(s["bytes_in_use"], s.get("peak_bytes_in_use") or 0)
             for s in snap),
            default=0,
        )
        with self._lock:
            self.peak_bytes = max(self.peak_bytes, peak)
            self.samples += 1
            self.last = snap
        try:
            from .metrics import registry

            registry.gauge("pa_hbm_peak_bytes", self.peak_bytes,
                           help="max per-device bytes_in_use observed this "
                                "run (the peak_hbm_bytes watermark)")
        except Exception:
            pass
        return snap

    def reset(self) -> None:
        with self._lock:
            self.peak_bytes = 0
            self.samples = 0
            self.last = None


watermark = HbmWatermark()


class MemoryMonitor:
    """Periodic HBM sampler (daemon thread): feeds the watermark and the
    ``pa_hbm_*`` gauges so ``GET /health`` / ``GET /metrics`` stay fresh
    between requests. Errors are swallowed — a failing memory probe must
    never take the serving host down with it."""

    def __init__(self, interval_s: float = 60.0):
        self.interval_s = max(1.0, float(interval_s))
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="pa-memory-monitor", daemon=True
        )

    def start(self) -> "MemoryMonitor":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                from ..devices.memory import publish_memory_gauges

                publish_memory_gauges()
                watermark.sample()
            except Exception:
                pass


# ---------------------------------------------------------------------------
# perf ledger
# ---------------------------------------------------------------------------


def ledger_dir() -> str:
    """``$PA_LEDGER_DIR`` > ``$PA_EVIDENCE_DIR/ledger`` (so mocked/dry runs
    redirect their ledger with their evidence) > ``<repo>/ledger`` — the repo
    root, never cwd: every reader (scripts/perf_ledger.py, bench's
    outer append) resolves there, and a record written to whatever
    directory the operator launched the server from would be invisible to
    the gate."""
    override = os.environ.get("PA_LEDGER_DIR")
    if override:
        return override
    evidence = os.environ.get("PA_EVIDENCE_DIR")
    if evidence:
        return os.path.join(evidence, "ledger")
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    return os.path.join(repo, "ledger")


def ledger_path() -> str:
    return os.path.join(ledger_dir(), LEDGER_FILENAME)


def append_ledger_record(record: dict, kind: str) -> str | None:
    """Append one schema-versioned record to the perf ledger; returns the
    ledger file path, or None when the append failed (best-effort by
    contract — a full disk must not kill the run it accounts).

    ``kind``: ``bench`` (a measured bench.py line), ``dryrun``
    (dryrun_multichip), ``loadgen`` (scripts/loadgen.py summary), ``error``
    (a failed attempt — never compared by the regression gate)."""
    rec = dict(record)
    rec["schema"] = LEDGER_SCHEMA
    rec["kind"] = kind
    # palint: allow[observability] ledger epoch STAMP, not a duration
    rec.setdefault("ts", time.time())
    try:
        rec.setdefault("host", socket.gethostname())
    except OSError:
        pass
    rec.setdefault("pid", os.getpid())
    path = ledger_path()
    # Slow-disk fault site (utils/faults.py): the sleep sits inside the
    # timed region so an injected fsync stall lands in
    # pa_disk_append_seconds{target=ledger} — the anomaly sentinel's
    # disk_append_p95 watch reads exactly this histogram.
    try:
        from . import faults
        slow = faults.check("slow-disk", key="ledger")
    except Exception:
        slow = None
    t0 = time.perf_counter()
    try:
        if slow is not None:
            slow.sleep()
        os.makedirs(ledger_dir(), exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError:
        path = None
    try:
        from .metrics import registry
        registry.histogram("pa_disk_append_seconds",
                           time.perf_counter() - t0,
                           labels={"target": "ledger"},
                           help="journal/ledger append wall time")
    except Exception:
        pass
    return path


# ---------------------------------------------------------------------------
# health snapshot (GET /health)
# ---------------------------------------------------------------------------


def health_snapshot(queue: dict | None = None,
                    host: dict | None = None) -> dict:
    """One JSON-able view of the process's resource state: devices, per-device
    HBM (+ utilization), peak watermark, compile/cache accounting, load
    average — the fields ``GET /health`` serves. Every section degrades to None independently (a
    wedged device backend must not blank the host-side sections). ``host``
    merges the pa-health/v3 fleet fields (host_id, accepting,
    inflight_prompts) top-level — the server passes its own identity/drain
    state; standalone callers omit it."""
    out: dict = {
        "schema": HEALTH_SCHEMA,
        # palint: allow[observability] health-document epoch STAMP
        "ts": time.time(),
        "loadavg_1m": _loadavg_1m(),
    }
    if host:
        out.update(host)
    try:
        from ..devices.discovery import available_devices

        out["devices"] = available_devices()
    except Exception:
        out["devices"] = None
    try:
        from ..devices.memory import memory_snapshot

        hbm = memory_snapshot()
        out["hbm"] = hbm
        utils = [s["utilization"] for s in hbm if s.get("utilization") is not None]
        out["hbm_utilization_max"] = max(utils) if utils else None
    except Exception:
        out["hbm"] = None
        out["hbm_utilization_max"] = None
    out["peak_hbm_bytes"] = watermark.peak_bytes or None
    out["compile"] = compile_snapshot()
    try:
        # Roofline attribution (utils/roofline.py): per-program calibrated
        # predictions priced from the compile registry's cost analysis —
        # the cost table the auto-parallel planner reads.
        from . import roofline

        out["roofline"] = roofline.programs.snapshot()
    except Exception:
        out["roofline"] = None
    try:
        # Auto-parallel planner (parallel/planner.py, round 18): the
        # process's last plan decision — chosen vs shadow hand plan,
        # divergence/win counters — the /health section the acceptance
        # gate and a capacity planner read routing decisions from.
        from ..parallel import planner

        out["plan"] = planner.snapshot()
    except Exception:
        out["plan"] = None
    try:
        # Numerics sentinel (utils/numerics.py): flag state, non-finite
        # event / quarantined-lane totals, last event, and the fingerprint
        # gate's last verdict (scripts/numerics_audit.py).
        from . import numerics

        out["numerics"] = numerics.sentinel.snapshot()
    except Exception:
        out["numerics"] = None
    try:
        # Cross-request compute reuse (round 17): the content-addressed
        # embed cache's hit/byte accounting (models/embed_cache.py) and the
        # batched decode tail's occupancy (serving/decode.py) — the /health
        # section a capacity planner reads the redundancy win from.
        from ..models.embed_cache import cache as _embed_cache
        from ..serving.decode import get_decode_queue as _get_dq
        from ..serving.scheduler import get_scheduler as _get_sched

        dq = _get_dq()
        sched = _get_sched()
        out["reuse"] = {
            "embed_cache": _embed_cache.stats(),
            "decode": dq.stats() if dq is not None else None,
            "serving": sched.reuse_stats() if sched is not None else None,
        }
    except Exception:
        out["reuse"] = None
    try:
        # Anomaly sentinel (utils/anomaly.py, round 22): active/fired
        # signal counts, the last event, and the history ring's budget —
        # the /health section the ops console and chaos verdicts read.
        from . import anomaly

        out["anomaly"] = anomaly.sentinel.snapshot()
    except Exception:
        out["anomaly"] = None
    if queue is not None:
        out["queue"] = queue
    return out


# ---------------------------------------------------------------------------
# failure postmortem bundles (the flight recorder's dump)
# ---------------------------------------------------------------------------


def write_postmortem(tag: str, error: BaseException | None = None,
                     extra: dict | None = None,
                     out_dir: str | None = None) -> str | None:
    """Dump a postmortem bundle and return its directory, or None when even
    creating the directory failed. Each artifact writes independently — a
    dead device backend loses ``memory.json``, never the trace or the logs.

    Layout (``<ledger>/postmortem/<UTC stamp>-<tag>/``):

    - ``error.json``   — tag, error type/message, traceback, loadavg, the
      compile snapshot, peak watermark, caller extras
    - ``trace.json``   — the span tracer's Chrome/Perfetto export (whatever
      the ring buffers still hold)
    - ``metrics.prom`` — the full Prometheus exposition at failure time
    - ``memory.json``  — per-device memory stats + watermark
    - ``logs.txt``     — the last K log records (utils/logging.py ring)
    """
    safe = re.sub(r"[^A-Za-z0-9._-]", "_", tag)[:80] or "failure"
    stamp = time.strftime("%Y%m%d-%H%M%S", time.gmtime())
    base = out_dir or os.path.join(ledger_dir(), "postmortem")
    path = os.path.join(base, f"{stamp}-{safe}")
    try:
        suffix = 1
        while os.path.exists(path):
            suffix += 1
            path = os.path.join(base, f"{stamp}-{safe}-{suffix}")
        os.makedirs(path)
    except OSError:
        return None

    def dump(filename: str, producer) -> None:
        try:
            payload = producer()
            with open(os.path.join(path, filename), "w") as f:
                if isinstance(payload, str):
                    f.write(payload)
                else:
                    json.dump(payload, f, indent=1, default=str)
        except Exception:
            pass

    def error_payload():
        info: dict = {
            "tag": tag,
            # palint: allow[observability] postmortem epoch STAMP
            "ts": time.time(),
            "loadavg_1m": _loadavg_1m(),
            "compile": compile_snapshot(),
            "peak_hbm_bytes": watermark.peak_bytes or None,
        }
        if error is not None:
            info["error_type"] = type(error).__name__
            info["error"] = str(error)[:4000]
            info["oom"] = looks_like_oom(error)
            info["traceback"] = "".join(
                _traceback.format_exception(
                    type(error), error, error.__traceback__
                )
            )[-16000:]
        if extra:
            info["extra"] = extra
        return info

    dump("error.json", error_payload)

    def trace_payload():
        from . import tracing

        return tracing.export()

    dump("trace.json", trace_payload)

    def metrics_payload():
        from .metrics import registry

        return registry.render()

    dump("metrics.prom", metrics_payload)

    def memory_payload():
        from ..devices.memory import memory_snapshot

        return {
            "devices": memory_snapshot(),
            "peak_hbm_bytes": watermark.peak_bytes or None,
            "samples": watermark.samples,
        }

    dump("memory.json", memory_payload)

    def logs_payload():
        from .logging import recent_log_records

        return "\n".join(recent_log_records()) + "\n"

    dump("logs.txt", logs_payload)
    return path
