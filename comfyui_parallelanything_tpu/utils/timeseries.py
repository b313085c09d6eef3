"""Bounded in-process metric history: the continuous-telemetry ring.

Every observability surface before round 22 is point-in-time (``GET
/metrics``, ``/health``, ``/fleet/slo``) or per-run (the perf ledger):
nobody can answer "what was this host doing over the last ten minutes"
— the reference can't either, its ``[ParallelAnything]`` prints scroll
away and ``any_device_parallel.py`` retains nothing. This module keeps a
byte-bounded ring of periodic snapshots of every ``pa_*`` family
(counters/gauges as values, histograms as their raw cumulative bucket
accumulators) so trajectories — step-time creep, queue growth,
cache-hit collapse — are readable while they happen:

- :class:`HistoryRing` — per-family point series with monotone
  timestamps, bounded in bytes (``PA_HISTORY_BYTES``; ``0`` disables the
  whole layer, a tier-1-tested no-op). On byte pressure the FATTEST
  family downsamples (every second interior point dropped, first/last
  kept) so the window SPAN survives at lower resolution instead of the
  oldest history falling off a cliff.
- **counter-reset-aware readers**: :meth:`HistoryRing.delta` /
  :meth:`HistoryRing.rate` sum only non-negative inter-point deltas (a
  restarted process's counter restarting from 0 contributes its new
  value, not a huge negative step); :meth:`HistoryRing.quantile_at`
  reads a quantile off histogram BUCKET DELTAS across the window — the
  windowed twin of ``MetricsRegistry.quantile``'s lifetime view.
- **phase marks**: :meth:`HistoryRing.mark_phase` stamps declared load
  phases (scripts/loadgen.py open-loop rung boundaries, chaos phases)
  into the window so the anomaly sentinel (utils/anomaly.py) can
  attribute a rate ramp to a declared phase instead of paging on it.
- ``pa-history/v1`` export (:meth:`HistoryRing.window`) — the
  ``GET /metrics/history?window=&family=`` body server.py serves and the
  router's ``GET /fleet/history`` merges host-labeled.
- :class:`HistorySampler` — the seeded-cadence daemon thread
  (``PA_HISTORY_INTERVAL_S``): its first tick is offset by a stable hash
  of the host id so a fleet's samplers de-synchronize, and every tick
  runs OFF the hot step path (the MemoryMonitor discipline — palint's
  host-sync pass never sees it).

Flag discipline: ``PA_HISTORY_BYTES=0`` disables snapshots, readers and
the sampler entirely (the tracer/sentinel null-path rule — the disabled
path is one env read). Import discipline: module level is stdlib-only
and free of package-relative imports (the utils/roofline.py standalone
contract) so scripts/console.py and tests load this file without jax;
the metrics read is a lazy best-effort import.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time

HISTORY_SCHEMA = "pa-history/v1"

# Default ring budget: ~2 MiB holds hours of 5 s-cadence snapshots for a
# serving host's typical family count; small enough to be invisible next
# to one compiled program.
DEFAULT_BYTES = 2 << 20
DEFAULT_INTERVAL_S = 5.0
MAX_PHASES = 256


def max_bytes(env=os.environ) -> int:
    """The ``PA_HISTORY_BYTES`` ring budget (0 disables the layer)."""
    raw = env.get("PA_HISTORY_BYTES")
    if raw in (None, ""):
        return DEFAULT_BYTES
    try:
        return max(0, int(raw))
    except ValueError:
        return DEFAULT_BYTES


def enabled(env=os.environ) -> bool:
    return max_bytes(env) > 0


def interval_s(env=os.environ) -> float:
    raw = env.get("PA_HISTORY_INTERVAL_S")
    try:
        return max(0.1, float(raw)) if raw else DEFAULT_INTERVAL_S
    except ValueError:
        return DEFAULT_INTERVAL_S


def cadence_offset_s(key: str, interval: float) -> float:
    """Deterministic per-host phase offset in ``[0, interval)`` — the
    seeded cadence: a fleet's samplers (and two runs of one host id)
    tick at stable, de-synchronized instants."""
    u = int.from_bytes(hashlib.md5(str(key).encode()).digest()[:8], "big")
    return (u % 10_000) / 10_000.0 * float(interval)


def _point_bytes(values: dict) -> int:
    """Deterministic byte estimate for one sample point: timestamp + per
    series key + payload floats (8 B each, JSON-ish overhead folded into
    the constants). An estimate, not an accounting — the bound only needs
    to hold within a small constant factor, identically on every host."""
    n = 24
    for lbl, v in values.items():
        n += len(lbl) + 16
        n += 8 * (len(v) if isinstance(v, list) else 1)
    return n


def _match(lbl: str, labels: dict | None) -> bool:
    if not labels:
        return True
    return all(f'{k}="{v}"' in lbl for k, v in labels.items())


class HistoryRing:
    """Byte-bounded per-family time series over the metrics registry.

    Thread-safe: the sampler thread snapshots, HTTP handler threads read
    windows, loadgen stamps phases over HTTP. Timestamps are wall-clock
    (the one clock a fleet's windows can align on) and forced strictly
    monotone per ring — a stepped NTP clock never produces an
    out-of-order window."""

    def __init__(self, budget: int | None = None):
        self._budget = budget  # None → read PA_HISTORY_BYTES per snapshot
        self._lock = threading.Lock()
        # name → {"type", "bounds", "points": [(ts, {label: v})], "bytes"}
        self._families: dict[str, dict] = {}  # guarded-by: _lock
        self._phases: list[dict] = []         # guarded-by: _lock
        self._bytes = 0                       # guarded-by: _lock
        self._snapshots = 0                   # guarded-by: _lock
        self._downsampled = 0                 # guarded-by: _lock
        self._last_ts = 0.0                   # guarded-by: _lock
        self._first_ts = 0.0                  # guarded-by: _lock

    def budget(self) -> int:
        return self._budget if self._budget is not None else max_bytes()

    # -- write side ----------------------------------------------------------

    def record(self, sample: dict, ts: float | None = None) -> int:
        """Append one snapshot (``MetricsRegistry.dump()`` shape). Returns
        the families recorded (0 when the layer is disabled)."""
        budget = self.budget()
        if budget <= 0 or not sample:
            return 0
        if ts is None:
            # palint: allow[observability] history STAMP — the wall clock is
            # the one clock fleet windows align on (monotonic is per-process)
            ts = time.time()
        n = 0
        with self._lock:
            # Strictly monotone per ring, even under a stepped wall clock.
            ts = max(float(ts), self._last_ts + 1e-6)
            self._last_ts = ts
            if not self._first_ts:
                self._first_ts = ts
            self._snapshots += 1
            for name, m in sample.items():
                values = m.get("values") or {}
                if not values:
                    continue
                fam = self._families.get(name)
                if fam is None:
                    fam = self._families[name] = {
                        "type": m.get("type"),
                        "bounds": m.get("bounds"),
                        "points": [],
                        "bytes": 0,
                    }
                pb = _point_bytes(values)
                fam["points"].append((ts, values))
                fam["bytes"] += pb
                self._bytes += pb
                n += 1
            self._downsample_locked(budget)
        return n

    def snapshot(self, ts: float | None = None) -> int:
        """Sample the process-wide metrics registry into the ring and
        publish the ring's own occupancy gauges. Best-effort: absent
        metrics (standalone load) is a clean no-op."""
        if self.budget() <= 0:
            return 0
        try:
            from .metrics import registry as _metrics

            sample = _metrics.dump(prefix="pa_")
        except Exception:
            return 0
        n = self.record(sample, ts=ts)
        st = self.stats()
        try:
            _metrics.gauge("pa_history_bytes", st["bytes"],
                           help="metric-history ring occupancy (bytes)")
            _metrics.gauge("pa_history_points", st["points"],
                           help="metric-history ring sample points")
            _metrics.gauge("pa_history_span_seconds", st["span_s"],
                           help="metric-history window span (seconds)")
        except Exception:
            pass
        return n

    def _downsample_locked(self, budget: int) -> None:  # palint: holds _lock
        """While over budget, thin the fattest family: drop every second
        INTERIOR point (first and last kept) so the window span survives
        at halved resolution — per-family, so one chatty family never
        evicts a quiet family's history."""
        guard = 64
        while self._bytes > budget and guard > 0:
            guard -= 1
            fat = None
            for name, fam in self._families.items():
                if len(fam["points"]) > 2 and (
                        fat is None
                        or fam["bytes"] > self._families[fat]["bytes"]):
                    fat = name
            if fat is None:
                # Nothing left to thin: drop whole 2-point families oldest-
                # first rather than busy-loop (a budget smaller than two
                # snapshots of every family).
                for name, fam in list(self._families.items()):
                    if self._bytes <= budget:
                        break
                    self._bytes -= fam["bytes"]
                    del self._families[name]
                return
            fam = self._families[fat]
            pts = fam["points"]
            kept = [pts[0]] + pts[1:-1][1::2] + [pts[-1]]
            freed = sum(_point_bytes(v) for _, v in pts) - sum(
                _point_bytes(v) for _, v in kept)
            fam["points"] = kept
            fam["bytes"] -= freed
            self._bytes -= freed
            self._downsampled += 1

    def mark_phase(self, label: str, state: str = "begin",
                   ts: float | None = None, detail: str | None = None) -> None:
        """Stamp a declared load-phase boundary (state ``begin``/``end``)
        into the window — loadgen's open-loop rungs and chaos phases
        declare themselves here so the sentinel attributes, not pages."""
        if self.budget() <= 0:
            return
        if ts is None:
            # palint: allow[observability] phase STAMP, same clock as points
            ts = time.time()
        mark = {"ts": float(ts), "label": str(label), "state": str(state)}
        if detail:
            mark["detail"] = str(detail)
        with self._lock:
            self._phases.append(mark)
            del self._phases[:-MAX_PHASES]

    def phase_at(self, ts: float | None = None) -> str | None:
        """The innermost declared phase open at ``ts`` (default: now), or
        None — replayed from the begin/end marks."""
        with self._lock:
            marks = list(self._phases)
            if ts is None:
                ts = self._last_ts or float("inf")
        open_phases: list[str] = []
        for m in marks:
            if m["ts"] > ts:
                break
            if m["state"] == "begin":
                open_phases.append(m["label"])
            elif m["label"] in open_phases:
                open_phases.remove(m["label"])
        return open_phases[-1] if open_phases else None

    def reset(self) -> None:
        with self._lock:
            self._families.clear()
            self._phases.clear()
            self._bytes = 0
            self._snapshots = 0
            self._downsampled = 0
            self._last_ts = 0.0
            self._first_ts = 0.0

    # -- read side -----------------------------------------------------------

    def _points(self, name: str, window_s: float | None,
                labels: dict | None, fill_empty: bool = False):
        """Matching series values per point inside the window (a list of
        payloads per point — one entry per matching label set).
        ``fill_empty`` keeps points where the family was sampled but no
        label matched, as empty lists — the counter-delta read needs them
        so a label set BORN mid-window contributes its first value (born
        at 0, not born invisible)."""
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                return [], None, None
            pts = list(fam["points"])
            ftype, bounds = fam["type"], fam["bounds"]
        if window_s is not None and pts:
            cutoff = pts[-1][0] - float(window_s)
            pts = [p for p in pts if p[0] >= cutoff]
        out = []
        for ts, values in pts:
            vs = [v for lbl, v in values.items() if _match(lbl, labels)]
            if vs or fill_empty:
                out.append((ts, vs))
        return out, ftype, bounds

    def latest(self, name: str, labels: dict | None = None,
               agg: str = "sum") -> float | None:
        """Last sampled scalar value, aggregated (``sum``/``max``/``mean``)
        across matching label sets — the gauge read."""
        pts, _, _ = self._points(name, None, labels)
        if not pts:
            return None
        vs = [float(v) for v in pts[-1][1] if not isinstance(v, list)]
        if not vs:
            return None
        if agg == "max":
            return max(vs)
        if agg == "mean":
            return sum(vs) / len(vs)
        return sum(vs)

    def label_values(self, name: str, key: str) -> list[str]:
        """Distinct values of one label key across the family's latest
        point — how the sentinel enumerates fault sites / hosts without
        knowing them a priori."""
        pts, _, _ = self._points(name, None, None)
        if not pts:
            return []
        out: set[str] = set()
        with self._lock:
            fam = self._families.get(name)
            if fam is None or not fam["points"]:
                return []
            values = fam["points"][-1][1]
        needle = f'{key}="'
        for lbl in values:
            i = lbl.find(needle)
            if i >= 0:
                j = lbl.index('"', i + len(needle))
                out.add(lbl[i + len(needle):j])
        return sorted(out)

    def delta(self, name: str, window_s: float | None = None,
              labels: dict | None = None) -> float | None:
        """Counter increase over the window, reset-aware: only non-negative
        inter-point deltas count, and a reset (value dropping) contributes
        the post-reset value — a restarted backend never reads as a giant
        negative rate."""
        pts, _, _ = self._points(name, window_s, labels, fill_empty=True)
        if not pts:
            return None
        with self._lock:
            first_ring = self._first_ts
            fam = self._families.get(name)
            first_fam = (fam["points"][0][0]
                         if fam and fam["points"] else None)
        totals = [sum(float(v) for v in vs if not isinstance(v, list))
                  for _, vs in pts]
        d = 0.0
        # Birth credit: a family first sampled AFTER the ring started (and
        # whose birth point is inside this window) counted from 0 — its
        # first value IS growth, not pre-existing history.
        if (first_ring and first_fam is not None
                and first_fam > first_ring + 1e-9
                and pts[0][0] <= first_fam + 1e-9):
            d += totals[0]
        elif len(pts) < 2:
            return None
        for prev, cur in zip(totals, totals[1:]):
            step = cur - prev
            d += step if step >= 0 else cur
        return d

    def rate(self, name: str, window_s: float | None = None,
             labels: dict | None = None) -> float | None:
        """Reset-aware counter rate (per second) over the window."""
        pts, _, _ = self._points(name, window_s, labels)
        if len(pts) < 2:
            return None
        span = pts[-1][0] - pts[0][0]
        if span <= 0:
            return None
        d = self.delta(name, window_s, labels)
        return None if d is None else d / span

    def quantile_at(self, name: str, q: float,
                    window_s: float | None = None,
                    labels: dict | None = None) -> float | None:
        """Histogram quantile (0-100) over the WINDOW's observations:
        bucket-count deltas between the window's first and last points
        (reset-aware — a shrunken cumulative count reads as post-reset),
        interpolated exactly like ``MetricsRegistry.quantile``."""
        pts, ftype, bounds = self._points(name, window_s, labels)
        if ftype != "histogram" or not bounds or len(pts) < 2:
            return None
        nb = len(bounds)

        def bucket_sum(vs):
            counts = [0.0] * (nb + 1)
            for v in vs:
                if isinstance(v, list) and len(v) >= nb + 3:
                    for i in range(nb + 1):
                        counts[i] += v[i]
            return counts

        first, last = bucket_sum(pts[0][1]), bucket_sum(pts[-1][1])
        counts = []
        for f, l in zip(first, last):
            d = l - f
            counts.append(d if d >= 0 else l)
        total = sum(counts)
        if total <= 0:
            return None
        target = q / 100.0 * total
        cum, lo = 0.0, 0.0
        for i, c in enumerate(counts):
            hi = bounds[i] if i < nb else bounds[-1]
            if cum + c >= target and c > 0:
                frac = (target - cum) / c
                return lo + (hi - lo) * min(1.0, max(0.0, frac))
            cum += c
            lo = hi
        return lo

    # -- surfaces ------------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            points = sum(len(f["points"]) for f in self._families.values())
            span = 0.0
            for f in self._families.values():
                if len(f["points"]) >= 2:
                    span = max(span,
                               f["points"][-1][0] - f["points"][0][0])
            return {
                "bytes": self._bytes,
                "max_bytes": self.budget(),
                "families": len(self._families),
                "points": points,
                "span_s": round(span, 3),
                "snapshots": self._snapshots,
                "downsampled": self._downsampled,
            }

    def window(self, window_s: float | None = None,
               families=None) -> dict:
        """The ``pa-history/v1`` document (``GET /metrics/history``).
        ``families`` filters by name prefix (string or iterable)."""
        if isinstance(families, str):
            families = [f for f in families.split(",") if f]
        prefixes = list(families) if families else None
        with self._lock:
            fams = {}
            for name, fam in self._families.items():
                if prefixes is not None and not any(
                        name.startswith(p) for p in prefixes):
                    continue
                pts = fam["points"]
                if window_s is not None and pts:
                    cutoff = pts[-1][0] - float(window_s)
                    pts = [p for p in pts if p[0] >= cutoff]
                fams[name] = {
                    "type": fam["type"],
                    "bounds": fam["bounds"],
                    "points": [
                        {"ts": round(ts, 6), "values": values}
                        for ts, values in pts
                    ],
                }
            phases = list(self._phases)
        if window_s is not None and phases:
            last = self._last_ts
            phases = [p for p in phases if p["ts"] >= last - float(window_s)]
        return {
            "schema": HISTORY_SCHEMA,
            "enabled": self.budget() > 0,
            "interval_hint_s": interval_s(),
            "families": fams,
            "phases": phases,
            # Nested, NOT merged: stats() reuses the "families"/"points"
            # keys as counts and would clobber the series dict above.
            "stats": self.stats(),
        }


# The process-wide ring server.py samples into and GET /metrics/history
# serves. Tests may reset() it.
ring = HistoryRing()


class HistorySampler:
    """Seeded-cadence snapshot thread (the MemoryMonitor shape): every
    ``PA_HISTORY_INTERVAL_S`` it samples the registry into :data:`ring`
    and feeds the anomaly sentinel — a daemon thread entirely off the
    hot step path. The first tick is phase-offset by a stable hash of
    the host id so fleet samplers de-synchronize deterministically."""

    def __init__(self, host: str = "", interval: float | None = None,
                 target: HistoryRing | None = None):
        self.host = str(host)
        self.interval = float(interval) if interval else interval_s()
        self.ring = target or ring
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="pa-history-sampler", daemon=True
        )

    def start(self) -> "HistorySampler":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def tick(self) -> int:
        """One snapshot + sentinel pass (the loop body, callable directly
        by tests and chaos phases for deterministic cadence)."""
        n = self.ring.snapshot()
        try:
            from . import anomaly

            anomaly.observe(self.ring, host=self.host)
        except Exception:
            pass
        return n

    def _loop(self) -> None:
        if self._stop.wait(cadence_offset_s(self.host, self.interval)):
            return
        while True:
            try:
                self.tick()
            except Exception:
                pass
            if self._stop.wait(self.interval):
                return
