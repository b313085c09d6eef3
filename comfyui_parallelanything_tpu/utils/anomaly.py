"""Online anomaly sentinel over the metric-history ring.

The regression gates (scripts/perf_ledger.py and friends) only fire at
CI time; a live incident — step-time creep, queue growth, an embed-hit
collapse, a disk going slow — used to be invisible until a crash wrote a
postmortem. This module watches the history ring (utils/timeseries.py)
ONLINE and makes a live incident leave the same evidence a crash does:

- **watch list** (:data:`WATCHLIST`): step-time p95, lane wait, queue
  depth, SLO burn rate, embed/compile cache hit rates, HBM watermark,
  heartbeat staleness, per-role stage p95s, journal/ledger disk-append
  p95 — every signal read off the ring's windowed readers, never off a
  hot step path.
- **robust online detectors**: :class:`BandDetector` keeps an EWMA
  baseline and an EWMA absolute deviation (the online MAD proxy) and
  fires on a banded z-score (|z| > z_max, direction-aware, baseline
  FROZEN while firing so the anomaly can't teach the detector that
  broken is normal); :class:`TrendDetector` fires on monotone growth
  (queue depth — a queue that only ever grows is saturation long before
  any absolute bound trips). Both are pure functions of the sample
  series: same seed + same series = same firings, so chaos runs
  (scripts/chaos.py) assert EXACT attribution instead of flaky noise.
- **a firing emits everything at once**: the
  ``pa_anomaly_active{signal=,host=}`` gauge,
  ``pa_anomaly_events_total{signal=}`` (and ``_unattributed_total`` when
  nothing declared explains it), an ``anomaly``-category instant span, a
  ``kind="anomaly"`` perf-ledger record naming
  signal/baseline/observed/window, and — rate-limited per signal
  (``PA_ANOMALY_POSTMORTEM_S``) — a ``write_postmortem`` forensics
  bundle carrying the history window.
- **attribution**: a firing inside a declared load phase
  (``HistoryRing.mark_phase``) or overlapping a fired fault site
  (``pa_fault_injected_total{site=}`` window delta) is ATTRIBUTED —
  fault-injection phases become labeled anomalies, not pages;
  scripts/anomaly_report.py ``--check`` gates on zero unattributed
  firings.

Flag discipline: ``PA_ANOMALY=0`` disables observation, emission and
gauges entirely (the tracer's null-path rule — a tier-1-tested no-op;
the disabled path is one env read). Import discipline: module level is
stdlib-only and free of package-relative imports (the standalone
contract) — metrics/tracing/telemetry emission is lazy best-effort, so
scripts/anomaly_report.py and tests load this file without jax.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time

ANOMALY_SCHEMA = "pa-anomaly/v1"


def enabled(env=os.environ) -> bool:
    """The PA_ANOMALY flag (default on — observation is a handful of ring
    reads per sampler tick, never on a step path)."""
    return env.get("PA_ANOMALY", "") not in ("0", "false")


def postmortem_interval_s(env=os.environ) -> float:
    """Min seconds between auto-forensics bundles PER SIGNAL
    (``PA_ANOMALY_POSTMORTEM_S``; 0 disables capture, not detection)."""
    raw = env.get("PA_ANOMALY_POSTMORTEM_S")
    try:
        return float(raw) if raw not in (None, "") else 300.0
    except ValueError:
        return 300.0


@dataclasses.dataclass(frozen=True)
class Watch:
    """One watched signal: how to read it off the ring and how to judge it.

    ``kind``: ``gauge`` (latest value, ``agg`` across label sets),
    ``rate``/``delta`` (reset-aware counter readers), ``quantile``
    (windowed histogram quantile ``q``), ``ratio`` (windowed
    hit/(hit+miss) of two cumulative series — cache hit rates).
    ``detector``: ``band`` (EWMA + MAD z-score, ``direction``-aware) or
    ``trend`` (monotone growth over ``trend_k`` points ≥ ``min_rise``).
    ``min_sigma`` floors the deviation scale so μs-level jitter on a
    quiet host can never manufacture a huge z."""

    name: str
    metric: str
    kind: str = "gauge"
    labels: tuple = ()            # (("k","v"),...) — hashable dict twin
    agg: str = "sum"
    q: float = 95.0
    miss_metric: str | None = None
    window_s: float | None = 600.0
    detector: str = "band"
    direction: str = "high"
    z_max: float = 8.0
    warmup: int = 5
    min_sigma: float = 0.01
    trend_k: int = 4
    min_rise: float = 4.0


WATCHLIST: tuple[Watch, ...] = (
    Watch("step_time_p95", "pa_serving_step_seconds", kind="quantile",
          min_sigma=0.005),
    Watch("lane_wait_p95", "pa_slo_stage_seconds", kind="quantile",
          labels=(("stage", "lane_wait"),), min_sigma=0.01),
    Watch("queue_depth", "pa_server_queue_pending", kind="gauge",
          detector="trend", trend_k=4, min_rise=6.0),
    Watch("burn_rate", "pa_slo_burn_rate", kind="gauge", agg="max",
          min_sigma=0.25),
    Watch("embed_hit_rate", "pa_embed_cache_hits", kind="ratio",
          miss_metric="pa_embed_cache_misses", direction="low",
          min_sigma=0.15, z_max=6.0),
    Watch("compile_hit_rate", "pa_compile_cache_hits_total", kind="ratio",
          miss_metric="pa_compile_cache_misses_total", direction="low",
          min_sigma=0.15, z_max=6.0),
    Watch("hbm_watermark", "pa_hbm_utilization", kind="gauge", agg="max",
          min_sigma=0.05, z_max=6.0),
    Watch("heartbeat_staleness", "pa_fleet_host_health_age_s", kind="gauge",
          agg="max", min_sigma=2.0),
    Watch("stage_p95_encode", "pa_role_stage_seconds", kind="quantile",
          labels=(("role", "encode"),), min_sigma=0.01),
    Watch("stage_p95_denoise", "pa_role_stage_seconds", kind="quantile",
          labels=(("role", "denoise"),), min_sigma=0.01),
    Watch("stage_p95_decode", "pa_role_stage_seconds", kind="quantile",
          labels=(("role", "decode"),), min_sigma=0.01),
    Watch("disk_append_p95", "pa_disk_append_seconds", kind="quantile",
          min_sigma=0.005),
)


class BandDetector:
    """EWMA baseline + EWMA absolute deviation (online MAD proxy), banded
    z-score. Deterministic: state is a pure fold over the value series.
    The baseline FREEZES while firing (anomalous samples must not teach
    the detector that broken is normal); ``clear_k`` consecutive in-band
    samples clear the firing and resume adaptation."""

    MAD_TO_SIGMA = 1.4826  # normal-consistency constant

    def __init__(self, z_max: float = 8.0, warmup: int = 5,
                 alpha: float = 0.3, min_sigma: float = 0.01,
                 direction: str = "high", clear_k: int = 2):
        self.z_max = float(z_max)
        self.warmup = int(warmup)
        self.alpha = float(alpha)
        self.min_sigma = float(min_sigma)
        self.direction = direction
        self.clear_k = int(clear_k)
        self.mean: float | None = None
        self.dev = 0.0
        self.n = 0
        self.firing = False
        self.z = 0.0
        self._calm = 0

    def update(self, x: float) -> bool:
        """Feed one sample; returns the post-sample firing state."""
        x = float(x)
        if self.mean is None:
            self.mean, self.n = x, 1
            return False
        sigma = max(self.MAD_TO_SIGMA * self.dev, self.min_sigma)
        z = (x - self.mean) / sigma
        self.z = z
        out_of_band = (
            z > self.z_max if self.direction == "high"
            else z < -self.z_max if self.direction == "low"
            else abs(z) > self.z_max
        )
        if self.n < self.warmup:
            out_of_band = False
        if out_of_band:
            self.firing = True
            self._calm = 0
            return True  # baseline frozen while firing
        if self.firing:
            self._calm += 1
            if self._calm >= self.clear_k:
                self.firing = False
        self.n += 1
        self.mean += self.alpha * (x - self.mean)
        self.dev += self.alpha * (abs(x - self.mean) - self.dev)
        return self.firing

    def baseline(self) -> float | None:
        return self.mean


class TrendDetector:
    """Monotone-growth detector (queue depth): fires when the last
    ``k`` inter-sample deltas are all positive and the total rise is at
    least ``min_rise`` — saturation shows as a queue that only grows,
    long before any absolute threshold trips. Clears on the first
    non-increasing sample."""

    def __init__(self, k: int = 4, min_rise: float = 4.0):
        self.k = int(k)
        self.min_rise = float(min_rise)
        self.window: list[float] = []
        self.firing = False
        self.z = 0.0

    def update(self, x: float) -> bool:
        self.window.append(float(x))
        del self.window[:-(self.k + 1)]
        if len(self.window) < self.k + 1:
            self.firing = False
            return False
        deltas = [b - a for a, b in zip(self.window, self.window[1:])]
        rise = self.window[-1] - self.window[0]
        self.firing = all(d > 0 for d in deltas) and rise >= self.min_rise
        self.z = rise / max(self.min_rise, 1e-9)
        return self.firing

    def baseline(self) -> float | None:
        return self.window[0] if self.window else None


def _make_detector(w: Watch):
    if w.detector == "trend":
        return TrendDetector(k=w.trend_k, min_rise=w.min_rise)
    return BandDetector(z_max=w.z_max, warmup=w.warmup,
                        min_sigma=w.min_sigma, direction=w.direction)


def _read(ring, w: Watch) -> float | None:
    """One watched value off the ring's reset-aware readers."""
    labels = dict(w.labels) or None
    if w.kind == "quantile":
        return ring.quantile_at(w.metric, w.q, window_s=w.window_s,
                                labels=labels)
    if w.kind == "rate":
        return ring.rate(w.metric, window_s=w.window_s, labels=labels)
    if w.kind == "delta":
        return ring.delta(w.metric, window_s=w.window_s, labels=labels)
    if w.kind == "ratio":
        hits = ring.delta(w.metric, window_s=w.window_s, labels=labels)
        misses = ring.delta(w.miss_metric, window_s=w.window_s,
                            labels=labels)
        if hits is None and misses is None:
            return None
        hits, misses = hits or 0.0, misses or 0.0
        total = hits + misses
        return None if total <= 0 else hits / total
    return ring.latest(w.metric, labels=labels, agg=w.agg)


class AnomalySentinel:
    """Watch-list evaluation + the ``pa_anomaly_*`` emission points.

    Driven by the history sampler's tick (utils/timeseries.HistorySampler)
    — one :meth:`observe` per snapshot, entirely off the step path.
    Thread-safe: ticks and /metrics publishes interleave."""

    def __init__(self, watchlist: tuple[Watch, ...] | None = None,
                 seed: int = 0):
        self.watchlist = tuple(watchlist if watchlist is not None
                               else WATCHLIST)
        self.seed = int(seed)
        self._lock = threading.Lock()
        self._detectors = {}          # name → detector — guarded-by: _lock
        self._active: dict[str, dict] = {}   # guarded-by: _lock
        self._events = 0                     # guarded-by: _lock
        self._unattributed = 0               # guarded-by: _lock
        self._last_event: dict | None = None  # guarded-by: _lock
        self._last_pm: dict[str, float] = {}  # guarded-by: _lock
        self._host = ""                      # guarded-by: _lock

    def reset(self, watchlist: tuple[Watch, ...] | None = None,
              seed: int | None = None) -> None:
        with self._lock:
            if watchlist is not None:
                self.watchlist = tuple(watchlist)
            if seed is not None:
                self.seed = int(seed)
            self._detectors.clear()
            self._active.clear()
            self._events = 0
            self._unattributed = 0
            self._last_event = None
            self._last_pm.clear()

    # -- observation ---------------------------------------------------------

    def observe(self, ring, host: str | None = None,
                ts: float | None = None) -> list[dict]:
        """Evaluate every watched signal against the ring; returns the
        NEW firings (empty on a quiet tick). Disabled path: one env read
        in the module-level hook."""
        if ts is None:
            # palint: allow[observability] anomaly-event STAMP — ledger
            # records and phase marks share the wall clock
            ts = time.time()
        fired: list[dict] = []
        with self._lock:
            if host:
                self._host = str(host)
            host = self._host
        for w in self.watchlist:
            value = _read(ring, w)
            if value is None:
                continue
            with self._lock:
                det = self._detectors.get(w.name)
                if det is None:
                    det = self._detectors[w.name] = _make_detector(w)
                was = det.firing
                firing = det.update(value)
                newly = firing and not was
                cleared = was and not firing
                if newly:
                    event = {
                        "signal": w.name,
                        "metric": w.metric,
                        "host": host,
                        "observed": round(float(value), 6),
                        "baseline": (None if det.baseline() is None
                                     else round(det.baseline(), 6)),
                        "z": round(getattr(det, "z", 0.0), 3),
                        "window_s": w.window_s,
                        "detector": w.detector,
                        "seed": self.seed,
                        "ts": ts,
                    }
                    self._active[w.name] = event
                elif firing:
                    self._active.get(w.name, {}).update(
                        observed=round(float(value), 6))
                elif cleared:
                    self._active.pop(w.name, None)
            if cleared:
                self._set_active_gauge(w.name, host, 0.0)
            if not newly:
                continue
            event["attributed_to"] = self._attribute(ring, w)
            event["attributed"] = bool(event["attributed_to"]["faults"]
                                       or event["attributed_to"]["phase"])
            with self._lock:
                self._events += 1
                if not event["attributed"]:
                    self._unattributed += 1
                self._last_event = event
            fired.append(event)
            self._emit(event, ring)
        return fired

    def _attribute(self, ring, w: Watch) -> dict:
        """What declared cause overlaps this firing: fault sites whose
        injection counter moved inside the signal's window, and the
        innermost open declared load phase."""
        sites = []
        try:
            for site in ring.label_values("pa_fault_injected_total", "site"):
                d = ring.delta("pa_fault_injected_total",
                               window_s=w.window_s,
                               labels={"site": site})
                if d is not None and d > 0:
                    sites.append(site)
        except Exception:
            pass
        phase = None
        try:
            phase = ring.phase_at()
        except Exception:
            pass
        return {"faults": sites, "phase": phase}

    # -- emission (lazy, best-effort — the standalone contract) --------------

    def _set_active_gauge(self, signal: str, host: str, v: float) -> None:
        try:
            from .metrics import registry

            registry.gauge("pa_anomaly_active", v,
                           labels={"signal": signal, "host": host},
                           help="1 while the sentinel's detector for this "
                                "signal is firing")
        except Exception:
            pass

    def _emit(self, event: dict, ring) -> None:
        signal, host = event["signal"], event["host"]
        self._set_active_gauge(signal, host, 1.0)
        try:
            from .metrics import registry

            registry.counter("pa_anomaly_events_total",
                             labels={"signal": signal},
                             help="anomaly firings (utils/anomaly.py)")
            if not event["attributed"]:
                registry.counter(
                    "pa_anomaly_unattributed_total",
                    labels={"signal": signal},
                    help="firings with no declared fault/phase cause — "
                         "scripts/anomaly_report.py gates on zero",
                )
        except Exception:
            pass
        try:
            from . import tracing

            if tracing.on():
                tracing.record(
                    "anomaly", tracing.now_us(), 0.0, cat="anomaly",
                    signal=signal, observed=event["observed"],
                    baseline=event["baseline"], z=event["z"],
                    attributed=event["attributed"],
                )
        except Exception:
            pass
        try:
            from . import telemetry

            telemetry.append_ledger_record(dict(event), kind="anomaly")
        except Exception:
            pass
        self._maybe_postmortem(event, ring)
        try:
            from .logging import get_logger

            get_logger().warning(
                "anomaly fired [%s] observed=%s baseline=%s z=%s "
                "attributed=%s",
                signal, event["observed"], event["baseline"], event["z"],
                event["attributed_to"],
            )
        except Exception:
            pass

    def _maybe_postmortem(self, event: dict, ring) -> None:
        """Auto-forensics, rate-limited per signal: the bundle carries the
        history window (and, when tracing is live, write_postmortem's
        trace.json already holds every in-flight prompt's spans — the
        worst one is whichever the stitched view shows still open)."""
        interval = postmortem_interval_s()
        if interval <= 0:
            return
        now = time.monotonic()
        with self._lock:
            last = self._last_pm.get(event["signal"])
            if last is not None and now - last < interval:
                return
            self._last_pm[event["signal"]] = now
        try:
            from . import telemetry

            path = telemetry.write_postmortem(
                f"anomaly-{event['signal']}",
                extra={"anomaly": event, "history": ring.window()},
            )
            if path:
                event["postmortem"] = path
        except Exception:
            pass

    # -- surfaces ------------------------------------------------------------

    def publish_gauges(self) -> None:
        """Scrape-time gauges: explicit zeros for every quiet watched
        signal (absent series read as 'never watched', not 'healthy')."""
        if not enabled():
            return
        with self._lock:
            active = set(self._active)
            host = self._host
            names = [w.name for w in self.watchlist]
        for name in names:
            self._set_active_gauge(name, host,
                                   1.0 if name in active else 0.0)

    def snapshot(self) -> dict:
        """The ``GET /health`` anomaly section."""
        with self._lock:
            out = {
                "schema": ANOMALY_SCHEMA,
                "enabled": enabled(),
                "watchlist": [w.name for w in self.watchlist],
                "active": {k: dict(v) for k, v in self._active.items()},
                "events_total": self._events,
                "unattributed_total": self._unattributed,
                "last_event": (dict(self._last_event)
                               if self._last_event else None),
            }
        try:
            from . import timeseries

            out["ring"] = timeseries.ring.stats()
        except Exception:
            out["ring"] = None
        return out


# The process-wide sentinel the history sampler ticks and /metrics
# publishes. Tests may reset() it.
sentinel = AnomalySentinel()


def observe(ring=None, host: str | None = None) -> list[dict]:
    """Module-level hook (the sampler tick): disabled path is one env
    read; ``ring`` defaults to the process-wide history ring."""
    if not enabled():
        return []
    if ring is None:
        try:
            from . import timeseries

            ring = timeseries.ring
        except Exception:
            return []
    return sentinel.observe(ring, host=host)
