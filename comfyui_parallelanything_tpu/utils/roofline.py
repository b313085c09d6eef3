"""Roofline attribution: calibrated predicted-vs-actual accounting for every
compiled program.

How far a measured step sits from its analytic roofline — and where the rest
of the step goes — has not been measured on the chip this repo now runs on.
Rounds 8-11 collected every raw input — per-program HLO
``cost_analysis`` FLOPs/bytes (utils/telemetry.py), span timings
(utils/tracing.py), step/HBM history (ledger/perf_ledger.jsonl), mesh
topology (parallel/mesh.py) — and this module is the join:

- **Analytic cost model** (:func:`predict_time_s`): compute time from FLOPs
  vs platform peak, memory time from bytes vs HBM bandwidth, collective time
  from an ICI/DCN link model over the mesh width, combined as
  ``max(compute, memory) + comms`` — the same roofline scripts/mfu_budget.py
  projects per op class, here per *program* and per *step*.
- **Per-program predictions** (:data:`programs`): ``instrument_jit``
  (utils/telemetry.py) feeds every named program's first-compile cost
  analysis through :func:`observe_program`, so the registry carries
  ``predicted_s`` alongside the compile registry's FLOPs/bytes for the loop
  programs (``loop:k:euler``), stage programs (``stream-stage[0:3)``,
  ``pipeline-stage[..)``), ``parallel-apply`` and ``model-apply:*`` — the
  cost table the ROADMAP's auto-parallel planner scores candidate plans
  with. Surfaced as ``pa_roofline_predicted_s`` gauges, the ``roofline``
  section of ``GET /health``, and per-program rows in the perf ledger.
- **Measured-side attribution** (:func:`attribution_from_trace`): each
  traced window decomposes into compute / exposed-transfer / host-gap /
  comms buckets from the existing span vocabulary — streaming's
  ``stream-prefetch-wait`` discipline generalized. Exactly one bucket per
  window is the residual (whatever the host-side spans cannot directly
  measure): streamed windows measure compute (``stream-stage-compute`` is
  device-accurate — the backpressure blocks) and leave host-gap residual;
  async dispatch windows (bench's chained loop — ``step`` spans are
  dispatch windows, nothing blocks per step) measure the host gaps
  (inter-step gaps net of comms) and leave compute residual — the opaque
  readback the host waits in IS the device working. Buckets are
  non-negative and sum to the wall by construction.
- **Calibration store** (``ledger/roofline_calib.json``): per
  (program, platform, shape-bucket) scale factors fitted from ledger
  history — ``scale = median(actual / predicted_raw)`` —
  so predictions self-correct as evidence banks
  (``scripts/roofline_report.py --bank``), the same stdlib-only
  bank-and-gate handshake as scripts/numerics_audit.py.

Flag discipline: ``PA_ROOFLINE=0`` disables observation and gauge
publication entirely (the tracer/sentinel pattern — a tier-1-tested no-op).
Import discipline: module level is stdlib-only and free of package-relative
imports, so ``scripts/roofline_report.py`` loads this file standalone (no
jax); jax/metrics/tracing load lazily inside
functions and every side channel is best-effort.

Reference parity note: the reference places work by a *static* VRAM
heuristic — ``get_free_vram`` scoring plus a fixed 0.7/0.3 memory blend
(any_device_parallel.py:724-766, 1317-1322). This layer replaces that with
a measured-history-calibrated cost model: placement consumers (the fleet
ring's capacity weights, the planned auto-parallel search) read speed the
hardware actually demonstrated, not a capacity proxy.
"""

from __future__ import annotations

import json
import math
import os
import statistics
import threading

CALIB_SCHEMA = "pa-roofline-calib/v1"
CALIB_FILENAME = "roofline_calib.json"

# Platform roofline specs, keyed by a lowercase substring of the device's
# ``device_kind`` (a v5e chip reports "TPU v5 lite"): peak dense bf16 FLOP/s
# per chip, HBM bytes/s, and the per-chip ICI bandwidth — source: Google
# Cloud TPU documentation, the "TPU v5e" / "v5p" / "v6e" / "v4" / "v3"
# system-architecture pages. ``dcn_bw`` is a conservative per-host
# 100 Gb/s; ``h2d_bw`` — the host→HBM DMA rate the weight-streaming cost
# model (parallel/planner.py stream candidates) divides weight bytes by — is
# PCIe-class and deliberately conservative, not measured: calibration
# corrects upward, a too-fast guess would make the planner pick stream over
# placements that actually win. Matched in order, first substring hit wins.
# An accelerator that is not listed is an error (:func:`platform_spec`).
PLATFORM_SPECS: tuple[tuple[str, dict], ...] = (
    ("v6", {"peak_flops": 918e12, "hbm_bw": 1640e9, "ici_bw": 448e9,
            "dcn_bw": 12.5e9, "h2d_bw": 32e9}),
    ("v5p", {"peak_flops": 459e12, "hbm_bw": 2765e9, "ici_bw": 600e9,
             "dcn_bw": 12.5e9, "h2d_bw": 32e9}),
    ("v5e", {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 200e9,
             "dcn_bw": 12.5e9, "h2d_bw": 16e9}),
    ("v5 lite", {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 200e9,
                 "dcn_bw": 12.5e9, "h2d_bw": 16e9}),
    ("v4", {"peak_flops": 275e12, "hbm_bw": 1228e9, "ici_bw": 300e9,
            "dcn_bw": 12.5e9, "h2d_bw": 16e9}),
    ("v3", {"peak_flops": 123e12, "hbm_bw": 900e9, "ici_bw": 200e9,
            "dcn_bw": 12.5e9, "h2d_bw": 8e9}),
)

# Deterministic pseudo-spec for ``platform == "cpu"`` only — the same
# off-hardware philosophy as devices/memory.py's fallback accounting: the
# numbers are optimistic (XLA CPU never hits them), so uncalibrated
# predictions land well *under* measured time and roofline_ratio stays in
# its sane (0, 1.2] band until the calibration store learns the host.
CPU_SPEC = {"peak_flops": 2e12, "hbm_bw": 50e9, "ici_bw": 10e9,
            "dcn_bw": 1e9, "h2d_bw": 10e9, "generation": "cpu-pseudo"}


def enabled() -> bool:
    """The PA_ROOFLINE flag (default on; the observation itself is one dict
    write per program per process — the heavy lowering is telemetry's and
    already happened)."""
    return os.environ.get("PA_ROOFLINE", "") not in ("0", "false")


def platform_spec(device_kind: str = "", platform: str = "cpu") -> dict:
    """Roofline spec for a chip: the CPU pseudo-spec for ``platform ==
    "cpu"``, else the ``device_kind`` substring match over
    :data:`PLATFORM_SPECS`. An accelerator the table does not list raises —
    a peak assumed for an unknown device makes every MFU and roofline share
    computed from it wrong in silence."""
    if platform == "cpu":
        return {**CPU_SPEC, "platform": platform}
    kind = str(device_kind or "").lower()
    for key, spec in PLATFORM_SPECS:
        if key in kind:
            return {**spec, "generation": key, "platform": platform}
    raise ValueError(
        f"no roofline spec for {platform!r} device_kind {device_kind!r}; "
        f"add its published peaks (with their source) to "
        f"utils/roofline.PLATFORM_SPECS"
    )


# The speed-blend's reference workload (ROADMAP "speed-aware hybrid
# blending"): roughly one sd15 batch-16 1024² denoise step — the absolute
# numbers cancel in the share normalization, but the flops:bytes ratio
# decides which wall (compute vs memory) each platform's nominal time sits
# against, so it is pinned here rather than left to callers.
NOMINAL_STEP_FLOPS = 2e12
NOMINAL_STEP_BYTES = 4e10


def nominal_step_time_s(device_kind: str = "", platform: str = "cpu",
                        flops: float = NOMINAL_STEP_FLOPS,
                        bytes_accessed: float = NOMINAL_STEP_BYTES) -> float:
    """Per-platform nominal step time from the roofline spec alone — the
    SPEED signal ``parallel/split.blend_speed_weights`` blends into
    heterogeneous-chain workload weights the way free memory is blended
    today (the banked hybrid_sd15 showed a VRAM-only split makes a tpu+cpu
    chain a de-optimization: the CPU's share must reflect that it is ~40x
    slower, not that it has spare RAM)."""
    spec = platform_spec(device_kind, platform)
    return max(flops / spec["peak_flops"], bytes_accessed / spec["hbm_bw"])


# ---------------------------------------------------------------------------
# the analytic cost model
# ---------------------------------------------------------------------------


def collective_time_s(nbytes: float, n_devices: int, spec: dict,
                      link: str = "ici") -> float:
    """Ring all-gather/all-reduce time for ``nbytes`` over ``n_devices``:
    each chip moves ``(n-1)/n`` of the payload over its link
    (the standard alpha-free ring model; alpha is folded into calibration).
    Zero on a single device — no collective runs at all."""
    n = max(1, int(n_devices))
    if n <= 1 or not nbytes:
        return 0.0
    bw = spec.get(f"{link}_bw") or spec.get("ici_bw") or 1.0
    return (n - 1) / n * float(nbytes) / bw


def predict_time_s(flops: float | None, bytes_accessed: float | None,
                   spec: dict, n_devices: int = 1,
                   collective_bytes: float = 0.0,
                   link: str = "ici") -> dict:
    """One program/step roofline: SPMD divides FLOPs and bytes over the mesh
    width, compute and memory overlap (``max``), collectives serialize on
    top (``+``) — the shape the MPMD/auto-parallel papers' cost models share
    (PAPERS.md arxiv 2606.17566, 2412.14374). Returns the full decomposition
    so consumers can see *which* wall the prediction sits against."""
    n = max(1, int(n_devices))
    f = float(flops or 0.0) / n
    b = float(bytes_accessed or 0.0) / n
    compute_s = f / spec["peak_flops"]
    memory_s = b / spec["hbm_bw"]
    comms_s = collective_time_s(collective_bytes, n, spec, link=link)
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "comms_s": comms_s,
        "predicted_s": max(compute_s, memory_s) + comms_s,
        "bound": ("comms" if comms_s > max(compute_s, memory_s)
                  else "memory" if memory_s > compute_s else "compute"),
    }


def shape_bucket(flops: float | None) -> str:
    """Coarse work-size bucket for the calibration key: the power-of-two
    exponent of the FLOP count (programs within 2x of each other share a
    scale factor; a lane-width or depth change moves buckets)."""
    f = float(flops or 0.0)
    if f <= 0:
        return "2^0"
    return f"2^{int(math.log2(f))}"


# ---------------------------------------------------------------------------
# calibration store (ledger/roofline_calib.json)
# ---------------------------------------------------------------------------


def _ledger_dir() -> str:
    """Mirror of utils/telemetry.ledger_dir — duplicated because this module
    must stay loadable standalone (no package-relative imports) for the
    stdlib-only scripts."""
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


def calib_path(ledger_dir: str | None = None) -> str:
    return os.path.join(ledger_dir or _ledger_dir(), CALIB_FILENAME)


# (path → (mtime, scales)) memo: the planner prices candidates on every
# parallelize call, and an uncached open+parse per wrap is avoidable I/O —
# a changed mtime (re-bank, test write) invalidates naturally.
_calib_cache: dict = {}
_calib_cache_lock = threading.Lock()


def load_calibration(path: str | None = None) -> dict:
    """The banked scale factors, ``{}`` when nothing is banked yet (fresh
    checkouts predict uncalibrated — scale 1.0 everywhere). Memoized by
    file mtime (one stat per call, parse only on change)."""
    p = path or calib_path()
    try:
        mtime = os.path.getmtime(p)
    except OSError:
        return {}
    with _calib_cache_lock:
        cached = _calib_cache.get(p)
        if cached is not None and cached[0] == mtime:
            return cached[1]
    try:
        with open(p) as f:
            data = json.load(f)
        scales = data.get("scales") if isinstance(data, dict) else None
        scales = scales if isinstance(scales, dict) else {}
    except (OSError, json.JSONDecodeError):
        return {}
    with _calib_cache_lock:
        _calib_cache[p] = (mtime, scales)
    return scales


def save_calibration(scales: dict, path: str | None = None) -> str | None:
    """Persist the fitted scales (best-effort — a read-only checkout must
    not fail the run that fitted them). Returns the path or None."""
    import time

    p = path or calib_path()
    try:
        os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
        with open(p, "w") as f:
            # palint: allow[observability] calibration-bank epoch STAMP
            json.dump({"schema": CALIB_SCHEMA, "ts": time.time(),
                       "scales": scales}, f, indent=1, sort_keys=True)
        return p
    except OSError:
        return None


def calib_key(program: str, platform: str, bucket: str) -> str:
    return f"{program}|{platform}|{bucket}"


def calibration_scale(calib: dict, program: str, platform: str,
                      bucket: str) -> float:
    """Most-specific banked scale: exact (program, platform, bucket) →
    (program, platform, any bucket) → (platform-wide) → 1.0 (uncalibrated).
    The hierarchy means one banked rung already improves every same-platform
    prediction — a new program starts from the platform's learned optimism
    instead of from spec-sheet peaks."""
    for key in (calib_key(program, platform, bucket),
                calib_key(program, platform, "*"),
                calib_key("*", platform, "*")):
        entry = calib.get(key)
        if isinstance(entry, dict) and entry.get("scale"):
            return float(entry["scale"])
    return 1.0


def _quantile(vals: list[float], q: float) -> float:
    """Nearest-rank quantile (the scripts/loadgen.py percentile
    convention)."""
    s = sorted(vals)
    k = max(0, min(len(s) - 1, round(q * (len(s) - 1))))
    return s[k]


# Calibration fits the 25th-percentile measured/predicted ratio, not the
# median: the gate's sane band is (0, 1.2] — fixed — so a median-centered
# scale would red-flag any run >20% faster than banked history (ordinary
# host-load variance, or an honest optimization). The conservative quantile
# keeps calibrated predictions below typical measurements; a deliberate
# perf change still re-banks, exactly like the perf/numerics baselines.
_FIT_QUANTILE = 0.25


def fit_calibration(records: list[dict]) -> dict:
    """Fit per-(program, platform, shape-bucket) scales from ledger history.

    Input: perf-ledger records. Two row sources, both always fitted against
    the RAW (uncalibrated) prediction so repeated re-banking converges
    instead of compounding:

    - rung-level: bench records carrying ``predicted_step_raw_s`` +
      ``value`` (measured s/it), keyed ``rung:<rung>``;
    - program-level: any record whose ``roofline_programs`` rows carry a
      ``measured_s`` alongside ``predicted_raw_s`` (bench attaches the DP
      step program's per-dispatch wall);
    - plan-level: ``kind="plan"`` decisions (parallel/planner.py, appended
      by bench/dryrun with the measured step) carrying
      ``plan_predicted_raw_s`` + ``plan_actual_s``, keyed
      ``plan:<rung>`` — the feedback loop that sharpens the planner's
      candidate scores per platform as its decisions get measured.

    The fitted scale is the conservative :data:`_FIT_QUANTILE` of the
    measured/raw ratios (see above). Each key additionally rolls up into
    the ``(program, platform, *)`` and platform-wide ``(*, platform, *)``
    fallbacks. Stale re-emits, ``kind=dryrun``/``dryrun``-marked, and error
    records are never fitted (the perf-gate comparability discipline —
    virtual-mesh CPU timings must not calibrate real predictions)."""
    by_key: dict[str, list[float]] = {}

    def feed(program: str, platform: str, bucket: str,
             predicted: float, actual: float) -> None:
        if predicted <= 0 or actual <= 0:
            return
        ratio = actual / predicted
        for key in (calib_key(program, platform, bucket),
                    calib_key(program, platform, "*"),
                    calib_key("*", platform, "*")):
            by_key.setdefault(key, []).append(ratio)

    for rec in records:
        if rec.get("stale") or rec.get("dryrun") or rec.get("invalid"):
            continue
        if rec.get("kind") not in ("bench", "loadgen", "plan"):
            continue  # error records and virtual-mesh dryruns never fit
        platform = rec.get("platform") or "?"
        if rec.get("kind") == "plan":
            pred = rec.get("plan_predicted_raw_s")
            act = rec.get("plan_actual_s")
            if isinstance(pred, (int, float)) and isinstance(act, (int, float)):
                feed(f"plan:{rec.get('rung') or '?'}", platform,
                     shape_bucket(rec.get("plan_flops")),
                     float(pred), float(act))
            continue
        pred_raw = rec.get("predicted_step_raw_s")
        value = rec.get("value")
        if (rec.get("kind") == "bench"
                and isinstance(pred_raw, (int, float))
                and isinstance(value, (int, float))):
            feed(f"rung:{rec.get('rung') or '?'}", platform,
                 shape_bucket(rec.get("model_flops_per_step")),
                 float(pred_raw), float(value))
        progs = rec.get("roofline_programs")
        if isinstance(progs, dict):
            for name, row in progs.items():
                if not isinstance(row, dict):
                    continue
                p = row.get("predicted_raw_s")
                m = row.get("measured_s")
                if isinstance(p, (int, float)) and isinstance(m, (int, float)):
                    feed(name, row.get("platform") or platform,
                         shape_bucket(row.get("flops")), float(p), float(m))
    return {
        key: {"scale": round(_quantile(ratios, _FIT_QUANTILE), 6),
              "n": len(ratios)}
        for key, ratios in by_key.items()
    }


def load_jsonl(path: str) -> list[dict]:
    out: list[dict] = []
    if not os.path.exists(path):
        return out
    try:
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        pass
    return out


def ledger_records(path: str | None = None) -> list[dict]:
    return load_jsonl(path or os.path.join(_ledger_dir(),
                                           "perf_ledger.jsonl"))


# ---------------------------------------------------------------------------
# per-program prediction registry (fed by utils/telemetry.instrument_jit)
# ---------------------------------------------------------------------------


class ProgramRegistry:
    """Per-program roofline rows: one entry per instrumented program name,
    written once at the program's first compile (when telemetry's cost
    analysis runs) and re-priced lazily when the calibration store is
    reloaded. Thread-safe; read by ``GET /health``, the ledger writers, and
    the dryrun's assertions."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._rows: dict[str, dict] = {}  # guarded-by: _lock
        self._calib: dict | None = None

    def _calibration(self) -> dict:
        if self._calib is None:
            self._calib = load_calibration()
        return self._calib

    def refresh_calibration(self) -> None:
        """Drop the cached store (next record/reprice reloads from disk) —
        called after ``roofline_report.py --bank`` rewrites the file."""
        with self._lock:
            self._calib = None
            for row in self._rows.values():
                self._price(row)

    def _price(self, row: dict) -> None:
        spec = platform_spec(row.get("device_kind") or "",
                             row.get("platform") or "cpu")
        pred = predict_time_s(
            row.get("flops"), row.get("bytes_accessed"), spec,
            n_devices=row.get("n_devices") or 1,
            collective_bytes=row.get("collective_bytes") or 0.0,
        )
        bucket = shape_bucket(row.get("flops"))
        scale = calibration_scale(
            self._calibration(), row["program"],
            row.get("platform") or "cpu", bucket,
        )
        row.update(
            predicted_raw_s=pred["predicted_s"],
            predicted_s=pred["predicted_s"] * scale,
            compute_s=pred["compute_s"],
            memory_s=pred["memory_s"],
            comms_s=pred["comms_s"],
            bound=pred["bound"],
            shape_bucket=bucket,
            calib_scale=scale,
        )

    def record(self, program: str, *, flops=None, bytes_accessed=None,
               n_devices: int = 1, platform: str = "cpu",
               device_kind: str = "", collective_bytes: float = 0.0) -> dict:
        row = {
            "program": program,
            "flops": float(flops) if flops else None,
            "bytes_accessed": float(bytes_accessed) if bytes_accessed
            else None,
            "n_devices": max(1, int(n_devices)),
            "platform": platform,
            "device_kind": device_kind,
            "collective_bytes": float(collective_bytes or 0.0),
        }
        with self._lock:
            self._price(row)
            self._rows[program] = row
        _publish_predicted(program, row["predicted_s"])
        return row

    def rows(self) -> dict[str, dict]:
        with self._lock:
            return {n: dict(r) for n, r in sorted(self._rows.items())}

    def snapshot(self) -> dict:
        """The ``roofline`` section of ``GET /health``."""
        rows = self.rows()
        return {
            "enabled": enabled(),
            "programs": rows,
            "calibrated": sum(
                1 for r in rows.values() if r.get("calib_scale") != 1.0
            ),
        }

    def reset(self) -> None:
        with self._lock:
            self._rows.clear()
            self._calib = None


programs = ProgramRegistry()


def _publish_predicted(program: str, value: float) -> None:
    """The one ``pa_roofline_predicted_s`` emission point (record-time and
    scrape-time both go through here). No-op standalone / when metrics is
    absent."""
    try:
        from .metrics import registry as _metrics

        _metrics.gauge(
            "pa_roofline_predicted_s", value,
            labels={"program": program},
            help="calibrated analytic roofline prediction per compiled "
                 "program (utils/roofline.py)",
        )
    except Exception:
        pass


def observe_program(program: str, *, flops=None, bytes_accessed=None,
                    args=None) -> None:
    """telemetry._InstrumentedJit's hook: turn a program's first-compile
    cost analysis into a roofline row. ``args`` are the CONCRETE call
    arguments — mesh width and platform are read off their shardings (an
    SPMD program's per-device work is total/N), and the collective term is
    fed the total bytes of every NON-replicated argument leaf: on a
    multi-device mesh those are the values XLA must gather/scatter at use
    sites (FSDP/TP weight all-gathers dominate; batch-sharded activations
    that need no gather are small against them — a first-order link-model
    estimate, refined per platform by the calibration store). Best-effort
    by contract: accounting must never break the program it accounts."""
    if not enabled():
        return
    n_devices = 1
    platform = "cpu"
    device_kind = ""
    sharded_bytes = 0
    try:
        import jax

        dev = None
        for leaf in jax.tree.leaves(args):
            sharding = getattr(leaf, "sharding", None)
            if sharding is None:
                continue
            try:
                dset = sharding.device_set
                if len(dset) > n_devices:
                    n_devices = len(dset)
                if dev is None:
                    dev = next(iter(dset))
                if len(dset) > 1 and not sharding.is_fully_replicated:
                    sharded_bytes += int(getattr(leaf, "nbytes", 0))
            except Exception:
                pass
        if dev is None:
            dev = jax.devices()[0]
        platform = dev.platform
        device_kind = getattr(dev, "device_kind", "") or ""
    except Exception:
        pass
    try:
        programs.record(
            program, flops=flops, bytes_accessed=bytes_accessed,
            n_devices=n_devices, platform=platform, device_kind=device_kind,
            collective_bytes=sharded_bytes if n_devices > 1 else 0.0,
        )
    except Exception:
        pass


def program_rows_for_ledger() -> dict[str, dict] | None:
    """Compact per-program rows for a perf-ledger record (the fields
    fit_calibration reads back, minus the registry's internals)."""
    rows = programs.rows()
    if not rows:
        return None
    out = {}
    for name, r in rows.items():
        out[name] = {
            "predicted_s": round(r["predicted_s"], 6),
            "predicted_raw_s": round(r["predicted_raw_s"], 6),
            "flops": r["flops"],
            "bytes_accessed": r["bytes_accessed"],
            "n_devices": r["n_devices"],
            "platform": r["platform"],
            "bound": r["bound"],
        }
    return out


# ---------------------------------------------------------------------------
# measured-side attribution (trace spans → compute/transfer/host-gap/comms)
# ---------------------------------------------------------------------------


def _x_events(events) -> list[dict]:
    if isinstance(events, dict):
        events = events.get("traceEvents", [])
    return [e for e in events if e.get("ph") == "X"]


def attribution_from_trace(events, wall_s: float | None = None,
                           last_steps: int | None = None) -> dict | None:
    """Decompose a traced window into the four dispatch buckets —
    ``compute_s`` / ``exposed_transfer_s`` / ``comms_s`` / ``host_gap_s``,
    non-negative and summing to the wall. One bucket per window is the
    RESIDUAL (whatever the host-side spans cannot directly measure); which
    one depends on the window's sync discipline:

    - **streamed window** (``stream-stage-compute`` spans present — the
      backpressure blocks make them device-accurate): compute is the
      measured Σ stage-compute, exposed transfer the measured
      Σ ``stream-prefetch-wait`` (what double-buffering failed to hide),
      comms the Σ ``fleet-hop``/comms-cat spans, and HOST-GAP is the
      residual — scheduling/dispatch time the device cannot see.
    - **dispatch window** (only ``step`` spans — async dispatch, nothing
      blocks per step; bench's chained loop, eager runs): the directly
      measurable part is the HOST side — per-thread gaps *between*
      consecutive step spans (the ``host_gap_ms`` discipline) net of any
      comms spans filling them — and COMPUTE is the residual: dispatch +
      device execution + the blocking readback the host observed as one
      opaque wait. Booking that wait as "host gap" would claim the device
      was idle while it was doing all the work.

    ``wall_s`` pins the wall to an externally measured clock (bench's
    ``sec_it * iters`` — which extends past the last dispatch to the final
    readback); default is the window spanned by the selected spans.
    ``last_steps`` restricts to the last N ``step`` spans — how bench drops
    its warmup steps. None when the trace holds nothing attributable."""
    xs = _x_events(events)
    steps = sorted((e for e in xs if e["name"] == "step"),
                   key=lambda e: e["ts"])
    if last_steps:
        steps = steps[-int(last_steps):]
    if steps:
        w0 = steps[0]["ts"]
        w1 = max(e["ts"] + e.get("dur", 0.0) for e in steps)
    else:
        runs = [e for e in xs if e["name"] == "stream-run"]
        if not runs:
            return None
        w0 = min(e["ts"] for e in runs)
        w1 = max(e["ts"] + e.get("dur", 0.0) for e in runs)
    window_s = max(0.0, (w1 - w0) / 1e6)
    wall = float(wall_s) if wall_s else window_s
    if wall <= 0:
        return None

    def total(pred) -> float:
        return sum(
            e.get("dur", 0.0) for e in xs
            if pred(e) and e["ts"] >= w0 - 1.0
            and e["ts"] + e.get("dur", 0.0) <= w1 + 1.0
        ) / 1e6

    stream_compute = total(lambda e: e["name"] == "stream-stage-compute")
    transfer = total(lambda e: e["name"] == "stream-prefetch-wait")
    comms = total(lambda e: e["name"] == "fleet-hop"
                  or e.get("cat") == "comms")
    if stream_compute > 0:
        # Sync-disciplined window: compute/transfer measured, host-gap
        # residual. Clamp in measurement-priority order — concurrent
        # threads can overlap spans past the wall clock.
        compute = min(stream_compute, wall)
        transfer = min(transfer, max(0.0, wall - compute))
        comms = min(comms, max(0.0, wall - compute - transfer))
        host_gap = max(0.0, wall - compute - transfer - comms)
    else:
        # Dispatch window: host gaps measured (per-thread inter-step gaps,
        # net of comms spans that fill them), compute residual.
        by_tid: dict = {}
        for e in steps:
            by_tid.setdefault(e.get("tid"), []).append(e)
        gaps = 0.0
        for evs in by_tid.values():
            for a, b in zip(evs, evs[1:]):
                gaps += max(
                    0.0, b["ts"] - (a["ts"] + a.get("dur", 0.0))
                ) / 1e6
        comms = min(comms, wall)
        host_gap = min(max(0.0, gaps - comms), max(0.0, wall - comms))
        transfer = min(transfer, max(0.0, wall - comms - host_gap))
        compute = max(0.0, wall - transfer - comms - host_gap)
    return {
        "compute_s": round(compute, 6),
        "exposed_transfer_s": round(transfer, 6),
        "comms_s": round(comms, 6),
        "host_gap_s": round(host_gap, 6),
        "wall_s": round(wall, 6),
    }


def attribution_fractions(attr: dict | None) -> dict | None:
    """The bucket fractions of wall time (what trace_summary/loadgen print);
    None in, None out."""
    if not attr or not attr.get("wall_s"):
        return None
    w = attr["wall_s"]
    return {
        "compute_fraction": round(attr["compute_s"] / w, 4),
        "exposed_transfer_fraction": round(attr["exposed_transfer_s"] / w, 4),
        "comms_fraction": round(attr["comms_s"] / w, 4),
        "host_gap_fraction": round(attr["host_gap_s"] / w, 4),
    }


def publish_gauges() -> None:
    """Scrape-time refresh (the server's ``GET /metrics``): per-program
    predictions plus — when tracing is live — the attribution fractions of
    the current trace window as ``pa_roofline_*_fraction`` gauges. No-op
    standalone or with PA_ROOFLINE=0."""
    if not enabled():
        return
    try:
        from .metrics import registry as _metrics
    except Exception:
        return
    for name, row in programs.rows().items():
        _publish_predicted(name, row["predicted_s"])
    try:
        from . import tracing

        if not tracing.on():
            return
        fracs = attribution_fractions(
            attribution_from_trace(tracing.export())
        )
        if not fracs:
            return
        for key, val in fracs.items():
            _metrics.gauge(
                f"pa_roofline_{key}", val,
                help="measured-side roofline attribution over the live "
                     "trace window (utils/roofline.py buckets)",
            )
    except Exception:
        pass


# ---------------------------------------------------------------------------
# unified step-FLOPs accessor (satellite: mfu_budget vs telemetry sources)
# ---------------------------------------------------------------------------
#
# The jaxpr walk below is the exact per-equation count scripts/mfu_budget.py
# buckets per op class; it lives here so bench.py, mfu_budget, and the
# roofline all read ONE implementation — MFU and roofline_ratio can no
# longer silently disagree about what a step costs.


def _aval_nbytes(aval) -> int:
    return (math.prod(aval.shape) * aval.dtype.itemsize if aval.shape
            else aval.dtype.itemsize)


def _dot_flops(eqn):
    """Exact dot_general FLOPs (2·M·N·K over batch dims) + the lane-padded
    variant (contraction and output dims rounded up to the 128-lane MXU
    granularity)."""
    lane = 128
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    k = math.prod(lhs.shape[d] for d in lc)
    b = math.prod(lhs.shape[d] for d in lb)
    m = math.prod(
        lhs.shape[d] for d in range(len(lhs.shape)) if d not in (*lc, *lb)
    )
    n = math.prod(
        rhs.shape[d] for d in range(len(rhs.shape)) if d not in (*rc, *rb)
    )
    pad = lambda v: -(-v // lane) * lane  # noqa: E731
    return 2 * b * m * n * k, 2 * b * pad(m) * pad(n) * pad(k), (m, n, k, b)


def _conv_flops(eqn):
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval  # kernel (spatial..., in/feature, out) per dnums
    # 2 · out_elements · (kernel elements per output) — feature_group_count
    # divides the per-output kernel work.
    groups = eqn.params.get("feature_group_count", 1)
    kernel_per_out = math.prod(rhs.shape[:-1]) // max(groups, 1)
    flops = 2 * math.prod(out.shape) * kernel_per_out
    return flops, flops  # convs lower through MXU-shaped patches; no pad model


def _subjaxprs(eqn):
    """Inner jaxprs of one equation (pjit/scan/cond/custom-call params)."""
    from jax.extend import core as jex_core

    closed = getattr(jex_core, "ClosedJaxpr", None)
    bare = getattr(jex_core, "Jaxpr", None)
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if closed is not None and isinstance(x, closed):
                yield x.jaxpr
            elif bare is not None and isinstance(x, bare):
                yield x


def walk_jaxpr(jaxpr, acc, seq_lens) -> None:
    """Bucket every equation's FLOPs/bytes by op class into ``acc`` —
    scripts/mfu_budget.py's per-class walk (conv / matmul / attention /
    elementwise), shared verbatim so the budget and the roofline count the
    same ops."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        for sub in _subjaxprs(eqn):  # recurse into pjit/scan/cond
            walk_jaxpr(sub, acc, seq_lens)
        if name == "dot_general":
            f, fpad, (m, n, k, b) = _dot_flops(eqn)
            cls = "matmul"
            # Attention score/value products: QK^T contracts the head dim
            # (k ≤ 256) against a full sequence (m or n ∈ seq_lens — the
            # chunked path keeps full length only on the K side); PV
            # contracts the sequence itself (k ∈ seq_lens).
            if (k in seq_lens) or (
                (m in seq_lens or n in seq_lens) and k <= 256
            ):
                cls = "attention"
            acc[cls]["flops"] += f
            acc[cls]["flops_padded"] += fpad
            acc[cls]["bytes"] += sum(
                _aval_nbytes(v.aval) for v in eqn.invars
            )
            acc[cls]["bytes"] += sum(
                _aval_nbytes(v.aval) for v in eqn.outvars
            )
            acc[cls]["count"] += 1
        elif name == "conv_general_dilated":
            f, fpad = _conv_flops(eqn)
            acc["conv"]["flops"] += f
            acc["conv"]["flops_padded"] += fpad
            acc["conv"]["bytes"] += sum(
                _aval_nbytes(v.aval) for v in eqn.invars
            )
            acc["conv"]["bytes"] += sum(
                _aval_nbytes(v.aval) for v in eqn.outvars
            )
            acc["conv"]["count"] += 1
        elif not eqn.primitive.multiple_results or name in ("scan", "while"):
            byts = sum(
                _aval_nbytes(v.aval) for v in eqn.invars
                if hasattr(v, "aval")
            )
            byts += sum(_aval_nbytes(v.aval) for v in eqn.outvars)
            acc["elementwise"]["flops"] += math.prod(
                eqn.outvars[0].aval.shape
            ) if eqn.outvars and eqn.outvars[0].aval.shape else 0
            acc["elementwise"]["bytes"] += byts
            acc["elementwise"]["count"] += 1
            acc.setdefault("_by_prim", {}).setdefault(name, [0, 0])
            acc["_by_prim"][name][0] += 1
            acc["_by_prim"][name][1] += byts


def empty_acc() -> dict:
    return {
        c: {"flops": 0, "flops_padded": 0, "bytes": 0, "count": 0}
        for c in ("conv", "matmul", "attention", "elementwise")
    }


def analytic_flops(apply, params, x, t, ctx, kwargs=None):
    """Total model FLOPs of ONE forward step from the exact jaxpr walk —
    the fallback when XLA HLO cost analysis returns nothing. Pure tracing —
    nothing executes, CPU-safe."""
    import jax as _jax

    kw = dict(kwargs or {})
    jaxpr = _jax.make_jaxpr(
        lambda p, x_, t_, c_: apply(p, x_, t_, c_, **kw)
    )(params, x, t, ctx)
    acc = empty_acc()
    walk_jaxpr(jaxpr.jaxpr, acc, set())
    acc.pop("_by_prim", None)
    total = float(sum(c["flops"] for c in acc.values()))
    return total if total > 0 else None


def step_cost(apply, params, x, t, ctx, kwargs=None) -> dict:
    """THE shared step-FLOPs accessor (one source for MFU and for the
    roofline): XLA HLO ``cost_analysis`` of a CPU lowering (FLOPs AND bytes
    accessed — dot/conv counts are backend-independent) with the jaxpr walk
    as fallback and cross-check. Returns::

        {flops, bytes_accessed, flops_hlo, flops_jaxpr,
         flops_source: "hlo"|"jaxpr"|None, flops_discrepancy_ratio}

    ``flops_discrepancy_ratio`` (hlo/jaxpr, when both resolved) is logged
    and recorded so the two counters can never silently disagree — a ratio
    far from 1 means one of them stopped counting something real."""
    flops_hlo = bytes_hlo = None
    try:
        import jax

        abstract = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
            (params, x, t, ctx, dict(kwargs or {})),
        )
        with jax.default_device(jax.devices("cpu")[0]):
            cost = jax.jit(apply).lower(
                abstract[0], abstract[1], abstract[2], abstract[3],
                **abstract[4],
            ).cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        cost = cost or {}
        f = cost.get("flops")
        b = cost.get("bytes accessed")
        flops_hlo = float(f) if f and f > 0 else None
        bytes_hlo = float(b) if b and b > 0 else None
    except Exception:
        pass
    flops_jaxpr = None
    try:
        flops_jaxpr = analytic_flops(apply, params, x, t, ctx, kwargs)
    except Exception:
        pass
    flops = flops_hlo or flops_jaxpr
    source = ("hlo" if flops_hlo else "jaxpr" if flops_jaxpr else None)
    discrepancy = (
        round(flops_hlo / flops_jaxpr, 4)
        if flops_hlo and flops_jaxpr else None
    )
    if discrepancy is not None and not 0.5 <= discrepancy <= 2.0:
        try:
            from .logging import get_logger

            get_logger().warning(
                "step-FLOPs sources disagree %.2fx (hlo %.3g vs jaxpr "
                "%.3g) — one counter stopped counting something real",
                discrepancy, flops_hlo, flops_jaxpr,
            )
        except Exception:
            pass
    return {
        "flops": flops,
        "bytes_accessed": bytes_hlo,
        "flops_hlo": flops_hlo,
        "flops_jaxpr": flops_jaxpr,
        "flops_source": source,
        "flops_discrepancy_ratio": discrepancy,
    }


# ---------------------------------------------------------------------------
# ledger-history capacity weights (the fleet ring's consumer)
# ---------------------------------------------------------------------------


def host_step_weights(records: list[dict],
                      clamp: tuple[float, float] = (0.25, 4.0)) -> dict:
    """Per-host capacity weights from banked step-time history: weight ∝
    1 / median(step seconds), normalized to mean 1.0 and clamped (a single
    wild record must not hand one host the whole ring).

    Sources are TIERED, never mixed — a 1/median comparison is only
    meaningful over one metric measured on one workload shape, so only the
    fleet's OWN measurements qualify (a loadgen run drives every host with
    the same prompt mix in the same window; bench s/it is rung-dependent
    and would compare a host that benched ``smoke`` against one that
    benched ``flux_16`` as if 80x apart):

    1. loadgen per-host ``server_step_p50_s`` (per-dispatch step seconds,
       same workload across hosts by construction) — used when ANY host
       has them;
    2. loadgen per-host client latency p50 — only when NO host has
       server-side step history (older loadgen records).

    ``{}`` when no usable history — the ring then weights every host
    equally, exactly as before calibration existed."""
    step_times: dict[str, list[float]] = {}
    lat_times: dict[str, list[float]] = {}

    def feed(into, host, t) -> None:
        if host and isinstance(t, (int, float)) and t > 0:
            into.setdefault(str(host), []).append(float(t))

    for rec in records:
        if rec.get("stale") or rec.get("invalid") or rec.get("kind") == "error":
            continue
        # loadgen AND openloop records qualify: both drive every host with
        # the same prompt mix in the same window (the same-workload rule).
        if (rec.get("kind") in ("loadgen", "openloop")
                and isinstance(rec.get("hosts"), dict)):
            for hid, row in rec["hosts"].items():
                if isinstance(row, dict):
                    feed(step_times, hid, row.get("server_step_p50_s"))
                    feed(lat_times, hid, row.get("latency_p50_s"))
    times = step_times or lat_times
    if not times:
        return {}
    speeds = {h: 1.0 / statistics.median(ts) for h, ts in times.items()}
    mean = sum(speeds.values()) / len(speeds)
    lo, hi = clamp
    return {
        h: round(min(hi, max(lo, s / mean)), 4) for h, s in speeds.items()
    }
