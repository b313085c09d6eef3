"""Step-time / throughput metrics and profiler hooks.

The reference's observability is ~40 ``[ParallelAnything]`` print sites and the advice
to read s/it off the ComfyUI progress bar (SURVEY §5.1, §5.5). The BASELINE metric
("sec/it at batch=16 1024²; images/sec scaling 1→8 cores") must instead be emitted by
the framework itself:

- ``StepTimer`` — honest per-step wall timing (`block_until_ready` on the step output
  before the clock stops, because XLA dispatch is async), accumulating ``StepStats``
  (images/sec + sec/it) with warmup-step exclusion (first steps include compilation);
- ``trace`` — context manager around ``jax.profiler.trace`` for Perfetto/XProf dumps;
- ``MetricsRegistry`` — process-wide labeled counters/gauges/summaries with a
  Prometheus-text renderer (round 7): the serving subsystem's per-bucket
  occupancy, lane-wait, step-time, and dispatch-count instruments, exposed by
  the HTTP server's ``GET /metrics``.

Instrument families registered against this registry (create-on-first-touch
— no registration step): ``pa_serving_*`` (serving/), ``pa_compile_*`` /
``pa_hbm_*`` (utils/telemetry.py, devices/memory.py), ``pa_trace_dropped_*``
(utils/tracing.py), and ``pa_numerics_*`` (utils/numerics.py —
``pa_numerics_nonfinite_total{where=}`` / ``pa_numerics_quarantined_total``
counters at the event sites, plus the ``pa_numerics_sentinel_enabled`` /
``pa_numerics_nonfinite_events`` / ``pa_numerics_quarantined_lanes`` gauges
the server publishes at scrape time so healthy zeros are visible), and
``pa_fleet_*`` (fleet/ — router-side placement/failover accounting:
``pa_fleet_dispatch_total{host=}`` / ``pa_fleet_spill_total{host=}`` /
``pa_fleet_failover_total{host=}`` / ``pa_fleet_completed_total`` counters,
the CI-gated ``pa_fleet_prompts_lost_total``, and the scoreboard gauges
``pa_fleet_hosts`` / ``pa_fleet_hosts_healthy`` /
``pa_fleet_host_inflight{host=}`` / ``pa_fleet_host_accepting{host=}`` /
``pa_fleet_inflight`` / ``pa_fleet_queued`` published at scrape time).

Later rounds' families (this map is the OWNING REGISTRY: palint's
registry-consistency pass fails CI on any ``pa_*`` emission site whose
family is missing here): ``pa_server_*`` (server.py — queue depth /
running / rejected), ``pa_stream_overlap_efficiency`` (parallel/streaming
— stage-compute fraction of streamed-run wall), ``pa_slo_*`` (utils/slo.py
— burn rate / budget / objective verdicts / threshold-aligned request and
stage histograms), ``pa_roofline_*`` (utils/roofline.py + fleet/twin.py —
per-program predicted seconds, twin capacity source), ``pa_fault_injected_total{site=}``
(utils/faults.py — chaos attribution), and ``pa_degradation_total{rung=}``
(utils/degrade.py — ladder rungs taken).

Cross-request compute reuse (round 17): ``pa_embed_cache_*``
(models/embed_cache.py — content-addressed encoder-output cache hit/miss/
byte/eviction gauges, published at /metrics scrape), ``pa_encoder_*``
(the ``pa_encoder_invocations_total`` counter — real encoder program runs,
the loadgen ``encoder_invocations`` delta), and ``pa_decode_*``
(serving/decode.py — batched tail decode: dispatch/request counters,
queue-depth and batched-fraction gauges, wait/step histograms).

Auto-parallel planner (round 18): ``pa_planner_*`` (parallel/planner.py —
``pa_planner_decisions_total`` / ``pa_planner_divergence_total`` counters
per plan decision, and the ``pa_planner_predicted_s{mode=}`` /
``pa_planner_hand_predicted_s`` / ``pa_planner_candidates`` gauges carrying
the last decision's chosen-vs-shadow-hand score).

Universal lane batching (round 19, within the ``pa_serving_*`` family):
``pa_serving_lane_capability_total{kind=}`` (serving/bucket.py — lanes
seated by capability carried: ``img2img_mask`` / ``multi_cond`` /
``controlnet`` / ``lora``, plain lanes as ``txt2img``; a multi-capability
lane counts once per capability — the loadgen mixed-workload per-kind
deltas), ``pa_serving_inline_fallback_total{reason=,sampler=}``
(sampling/runner.py — runs bounced to the inline path, the
mixed-workload smoke's must-stay-zero gate for eligible shapes), and
``pa_serving_ctrl_conflict_total{bucket=}`` (serving/bucket.py — lanes
bounced because the bucket epoch already carries a different control
trunk).

Disaggregated role pools (round 20): ``pa_role_*`` (fleet/roles.py +
fleet/router.py + server.py — ``pa_role_pool_size{role=}`` gauges,
``pa_role_dispatch_total{role=,host=}`` /
``pa_role_stage_resolved_total{role=}`` /
``pa_role_handle_hits`` / ``pa_role_handle_misses`` counters, the
``pa_role_stage_seconds{role=}`` histogram, and the stage-store
``pa_role_stage_store_bytes`` / ``pa_role_stage_store_entries`` gauges),
plus ``pa_embed_cache_remote_hits`` / ``pa_embed_cache_remote_misses``
inside the existing ``pa_embed_cache_*`` family (models/embed_cache.py —
the cross-host second tier: a denoise host fetching conds from an encode
host's ``GET /embed/{key}``).

Request forensics (round 21): ``pa_trace_dropped_total{reason=}``
(utils/tracing.py — spans evicted from the tracer's bounded retention
tiers: ``retired-ring`` for dead-thread buffers pushed off the retired
ring, ``prompt-retention`` for completed-prompt snapshots LRU-evicted
past the budget; nonzero warns that a stitched ``GET /fleet/trace``
timeline may be incomplete).

Continuous telemetry (round 22): ``pa_history_*`` (utils/timeseries.py —
the bounded metric-history ring's occupancy gauges: ``pa_history_bytes``
/ ``pa_history_points`` / ``pa_history_span_seconds``, published at
snapshot time so the ring's coverage is itself observable),
``pa_anomaly_*`` (utils/anomaly.py — the online sentinel:
``pa_anomaly_active{signal=,host=}`` gauges,
``pa_anomaly_events_total{signal=}`` /
``pa_anomaly_unattributed_total{signal=}`` counters — the loadgen
``anomalies_fired`` / ``anomalies_unattributed`` deltas and the
scripts/anomaly_report.py attribution gate), and
``pa_disk_append_seconds{target=}`` (fleet/journal.py + this package's
utils/telemetry.py — journal/ledger append wall time, the slow-disk
chaos site's watched latency signal; ``target`` is ``journal`` or
``ledger``), plus ``pa_fleet_host_health_age_s{host=}`` inside the
existing ``pa_fleet_*`` family (fleet/scoreboard.py — seconds since each
backend's last successful health poll, the sentinel's
heartbeat-staleness signal).

Denoiser forwards (PR 24): ``pa_denoiser_calls_total{program=}``
(models/api.py ``denoise_span`` — one per model forward the eager sampler
loops dispatch, through ``DiffusionModel`` (``program`` =
``model-apply:<name>``; a ControlNet composition is ONE program,
``<base>+control``, and counts once) or ``ParallelModel``
(``parallel-apply``); always on. Operator's use: its rate is forwards per
second per program, and its increase over a prompt against the graph's
``steps`` (2n−1 for heun) shows a skipped or repeated forward on a server
running without the span tracer; the ``denoise`` span is its traced twin).
``pa_trace_dropped_total`` gains ``reason="abandoned"``: spans the traced
code left open, closed by their parent and not recorded.

Sampler loop form (PR 27): ``pa_sampler_loop_total{path=,sampler=}``
(sampling/runner.py — once an inline k-sampler run, beside
``pa_serving_inline_fallback_total``; always on): ``path="planned"`` is the
step as two compiled programs around the denoiser's own (the sampler has a
plan in ``sampling/lane_specs.py`` and the denoiser is one model call an
eval), ``path="eager"`` the denoiser called whole with host scalars
(multi-cond conditioning; ``lms`` / ``uni_pc*``, which have no plan).

Attention routing (PR 25): ``pa_attention_route_total{backend=}``
(ops/attention.py — one count per ``attention_local`` resolution, made
while a program is traced and not per forward: ``pallas`` moving while a
UNet's step program compiles is the evidence that its long self-attention
took the fused flash kernel; ``resolved_backends()`` is the same fact as a
set). ``pa_attention_padded_total{backend=}`` (PR 26) counts, the same way,
the calls among them whose sequence length was not a multiple of 128 and was
padded and masked to reach the kernel (SD3's joint text + image tokens).
``pa_attention_key_blocks_total{rule=,keys=}`` (PR 33) counts, the same way,
each call the fused kernel serves by the row of ``tuning.route`` that named
its blocks and by whether its row of keys is ``one`` key block or
``streamed``: ``lane-aligned`` / ``one`` moves by 9 while FLUX.1-schnell's
step program at 3 + 6 blocks compiles.

The q/k prologue (PR 35): ``pa_qk_prologue_total{path=,rope=}``
(ops/attention.py ``qk_prologue`` — counted like
``pa_attention_route_total``, once a TRACE: the per-head RMS norm of q and k,
and the rotary where the model has one, by the path it took — ``fused`` the
one-pass Pallas kernel (ops/pallas/qk_prologue.py), ``xla`` the jnp
functions — and by ``rope`` ``interleaved`` / ``none``. While the cells' step
programs compile: SD3.5-medium 37 ``fused`` / ``none`` (the image stream of
24 joint and 13 dual attentions) and 24 ``xla`` (77 text tokens); Z-Image at
8 main layers 10 ``fused`` / ``interleaved`` and 2 ``xla`` (the context
refiner's 32 tokens); FLUX.1-schnell at 3 + 6 blocks 12 ``fused``).

Upsample + convolution pairs (PR 38): ``pa_upsample_conv_total{form=}``
(ops/basic.py ``upsample2x_conv3x3`` — counted like
``pa_attention_route_total``, once a TRACE: each nearest ×2 upsample + 3×3
convolution of a program by the form it took — ``phase`` the four output
phases' folded 2×2 taps from the low-resolution input, as one convolution of
the zero-stuffed input; no call takes another form today. While the cells'
programs compile: 3 a decode program (the autoencoder's three stages), 3 a
step program of SD1.5's UNet, 2 of SDXL's).

Caption buckets (PR 34): ``pa_caption_bucket_total{tokens=}``
(models/zimage.py — counted like ``pa_attention_route_total``, once a TRACE
of the single-stream denoiser, with the padded caption length the program
was compiled at: the text node pads a chat-templated prompt to a multiple
of 32 tokens and hands the valid count beside it, so one step program
serves every caption of a bucket. One value after a run is the evidence
that it ran ONE step program; a second value moving is a compile).

PNG encoder (PR 29): ``pa_png_images_total`` / ``pa_png_strips_total``
(utils/png_encode.py ``write_pngs`` — once a save node's call, always on:
the files written and the row strips deflated for them on the pool's
threads; their ratio is strips a file, 1 where images are too small or too
many for strips to engage).

Text towers and what a loader keeps resident (PR 32):
``pa_text_encode_total{tower=,cache=}`` (nodes.py ``TPUTextEncode.encode`` —
once a tower a call, always on: the embed cache's outcome, ``hit`` or
``miss``, beside the ``text-encode`` span) and
``pa_params_resident_bytes{model=,dtype=}`` (models/loader.py
``record_resident`` — a gauge set once where a loader hands its pytree
over: the bytes it keeps resident by stored type, bfloat16 kernels for the
FLUX and T5 load paths, float32 for the others; 0 while the loader's
residency rule holds the model off the chip).

Model residency (PR 39): ``pa_model_residency_total{model=,event=}``
(models/loader.py ``Residency._move`` — one count a move of a model between
the chip and off it, ``evict`` or ``restore``, beside the ``model-residency``
span). Video decodes (PR 39): ``pa_video_decode_total{frames=,form=}``
(models/video_vae.py ``VideoVAE._decode_program`` — once a trace, by the
clip's pixel frames and ``scan`` / ``frame``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any

import jax

from .logging import get_logger


class MetricsRegistry:
    """Thread-safe labeled metrics with Prometheus text exposition.

    Four instrument kinds, created on first touch (no registration step —
    instrumentation sites must never crash a serving path over bookkeeping):
    ``counter`` (monotonic), ``gauge`` (set to the latest value), ``summary``
    (accumulates ``_sum``/``_count`` — enough for rate/mean queries without
    carrying quantile sketches), and ``histogram`` (log-spaced buckets by
    default, with Prometheus ``_bucket``/``_sum``/``_count`` exposition — the
    server-side quantile source, so a load generator can read p50/p95 off
    ``GET /metrics`` instead of only computing them client-side). Labels are
    a plain dict, canonicalized to a sorted tuple key.

    A histogram may declare EXPLICIT bucket bounds at first touch
    (``histogram(..., bounds=...)``) — the SLO plane aligns
    ``pa_slo_request_seconds`` edges to the declared latency thresholds so
    an objective verdict is a bucket read, never an interpolation. Bounds
    are per-metric and first-touch-wins (all label sets of one metric share
    one ladder, so exposition always merges across hosts that declared the
    same objectives)."""

    # Log-spaced duration buckets, 1 ms … 100 s (~2.5x steps): wide enough
    # for lane waits under load AND sub-5ms compiled step dispatches; the
    # shared default so two servers' exposition always merges.
    HIST_BOUNDS = (
        0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
        1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
    )

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # name -> {"type": kind, "help": str, "values": {label_key: float|[sum, count]}}
        self._metrics: dict[str, dict] = {}  # guarded-by: _lock

    @staticmethod
    def _label_key(labels: dict | None) -> tuple:
        return tuple(sorted((str(k), str(v)) for k, v in (labels or {}).items()))

    def _slot(self, name: str, kind: str, help_: str) -> dict:  # palint: holds _lock
        m = self._metrics.get(name)
        if m is None:
            m = self._metrics[name] = {"type": kind, "help": help_, "values": {}}
        return m

    def counter(self, name: str, inc: float = 1.0, labels: dict | None = None,
                help: str = "") -> None:
        with self._lock:
            vals = self._slot(name, "counter", help)["values"]
            k = self._label_key(labels)
            vals[k] = vals.get(k, 0.0) + inc

    def gauge(self, name: str, value: float, labels: dict | None = None,
              help: str = "") -> None:
        with self._lock:
            self._slot(name, "gauge", help)["values"][self._label_key(labels)] = (
                float(value)
            )

    def observe(self, name: str, value: float, labels: dict | None = None,
                help: str = "") -> None:
        with self._lock:
            vals = self._slot(name, "summary", help)["values"]
            k = self._label_key(labels)
            acc = vals.get(k)
            if acc is None:
                acc = vals[k] = [0.0, 0.0]
            acc[0] += float(value)
            acc[1] += 1.0

    def histogram(self, name: str, value: float, labels: dict | None = None,
                  help: str = "", bounds=None) -> None:
        """Observe ``value`` (seconds) into the metric's buckets. ``bounds``
        (an ascending tuple of upper edges) fixes the ladder at the metric's
        FIRST touch — omitted, the log-spaced default applies; on later
        touches it is ignored (first wins: one ladder per metric, so every
        label set and every host's exposition stays mergeable)."""
        v = float(value)
        with self._lock:
            m = self._slot(name, "histogram", help)
            hb = m.get("bounds")
            if hb is None:
                hb = m["bounds"] = (
                    tuple(float(b) for b in bounds)
                    if bounds else self.HIST_BOUNDS
                )
            vals = m["values"]
            k = self._label_key(labels)
            acc = vals.get(k)
            if acc is None:
                # [per-bound counts..., +Inf count, sum, count]
                acc = vals[k] = [0.0] * (len(hb) + 1) + [0.0, 0.0]
            for i, bound in enumerate(hb):
                if v <= bound:
                    acc[i] += 1.0
                    break
            else:
                acc[len(hb)] += 1.0
            acc[-2] += v
            acc[-1] += 1.0

    def get(self, name: str, labels: dict | None = None):
        """Current value (float for counter/gauge, (sum, count) for summary
        AND histogram), or None — the test/introspection read side."""
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                return None
            v = m["values"].get(self._label_key(labels))
            if isinstance(v, list):
                return (v[-2], v[-1]) if m["type"] == "histogram" else tuple(v)
            return v

    def quantile(self, name: str, q: float, labels: dict | None = None):
        """Histogram quantile (0-100) by linear interpolation within the
        bucket holding the target rank, or None. Merges across all label sets
        when ``labels`` is None — the read side loadgen's server-side p50/p95
        comes from (scraped over HTTP there; this is the in-process twin)."""
        with self._lock:
            m = self._metrics.get(name)
            if m is None or m["type"] != "histogram":
                return None
            if labels is None:
                accs = list(m["values"].values())
            else:
                acc = m["values"].get(self._label_key(labels))
                accs = [acc] if acc is not None else []
            if not accs:
                return None
            hb = m.get("bounds") or self.HIST_BOUNDS
            n = len(hb)
            counts = [sum(a[i] for a in accs) for i in range(n + 1)]
        total = sum(counts)
        if total <= 0:
            return None
        target = q / 100.0 * total
        cum = 0.0
        lo = 0.0
        for i, c in enumerate(counts):
            if i < n:
                hi = hb[i]
            else:
                hi = hb[-1]  # +Inf bucket clamps to last bound
            if cum + c >= target and c > 0:
                frac = (target - cum) / c
                return lo + (hi - lo) * min(1.0, max(0.0, frac))
            cum += c
            lo = hi
        return lo

    def dump(self, prefix: str | None = None) -> dict:
        """Structured point-in-time copy of every metric (optionally name-
        prefix filtered): ``{name: {"type", "bounds", "values":
        {label_str: float | list}}}`` where ``label_str`` is the sorted
        ``k="v"`` comma join (empty for the unlabeled series) and histogram
        lists are the raw ``[per-bound counts..., +Inf, sum, count]``
        accumulator. The history ring's (utils/timeseries.py) snapshot
        source — one lock hold, values copied out."""
        out: dict = {}
        with self._lock:
            for name, m in self._metrics.items():
                if prefix is not None and not name.startswith(prefix):
                    continue
                values = {}
                for key, v in m["values"].items():
                    lbl = ",".join(f'{k}="{val}"' for k, val in key)
                    values[lbl] = list(v) if isinstance(v, list) else v
                out[name] = {
                    "type": m["type"],
                    "bounds": list(m["bounds"]) if m.get("bounds") else None,
                    "values": values,
                }
        return out

    def reset(self) -> None:
        with self._lock:
            self._metrics.clear()

    def render(self) -> str:
        """Prometheus text format 0.0.4 (the GET /metrics body)."""

        def esc(v: str) -> str:
            return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")

        lines: list[str] = []
        with self._lock:
            for name in sorted(self._metrics):
                m = self._metrics[name]
                if m["help"]:
                    lines.append(f"# HELP {name} {m['help']}")
                lines.append(f"# TYPE {name} {m['type']}")
                for key, v in sorted(m["values"].items()):
                    lbl = (
                        "{" + ",".join(f'{k}="{esc(val)}"' for k, val in key) + "}"
                        if key else ""
                    )
                    if m["type"] == "summary":
                        lines.append(f"{name}_sum{lbl} {v[0]:.9g}")
                        lines.append(f"{name}_count{lbl} {v[1]:.9g}")
                    elif m["type"] == "histogram":
                        def le_lbl(le: str) -> str:
                            pairs = list(key) + [("le", le)]
                            return "{" + ",".join(
                                f'{k}="{esc(val)}"' for k, val in pairs
                            ) + "}"

                        hb = m.get("bounds") or self.HIST_BOUNDS
                        cum = 0.0
                        for i, bound in enumerate(hb):
                            cum += v[i]
                            lines.append(
                                f"{name}_bucket{le_lbl(f'{bound:.9g}')} "
                                f"{cum:.9g}"
                            )
                        cum += v[len(hb)]
                        lines.append(f"{name}_bucket{le_lbl('+Inf')} {cum:.9g}")
                        lines.append(f"{name}_sum{lbl} {v[-2]:.9g}")
                        lines.append(f"{name}_count{lbl} {v[-1]:.9g}")
                    else:
                        lines.append(f"{name}{lbl} {v:.9g}")
        return "\n".join(lines) + "\n"


# The process-wide registry every instrumentation site writes to (serving/,
# server.py) and GET /metrics renders. Tests may reset() it.
registry = MetricsRegistry()


@dataclasses.dataclass
class StepStats:
    steps: int = 0
    total_s: float = 0.0
    last_s: float = 0.0
    images: int = 0

    @property
    def sec_per_it(self) -> float:
        return self.total_s / self.steps if self.steps else 0.0

    @property
    def images_per_sec(self) -> float:
        return self.images / self.total_s if self.total_s > 0 else 0.0


class StepTimer:
    """Times sampler steps honestly: blocks on the step's output before stopping the
    clock. Warmup steps (default 1 — the compile step) are recorded separately and
    excluded from the throughput stats."""

    def __init__(self, warmup_steps: int = 1):
        self.warmup_steps = warmup_steps
        self.warmup = StepStats()
        self.stats = StepStats()

    @contextlib.contextmanager
    def step(self, batch_size: int = 1):
        t0 = time.perf_counter()
        out_box: list[Any] = []
        yield out_box
        if out_box:
            jax.block_until_ready(out_box[0])
        dt = time.perf_counter() - t0
        target = (
            self.warmup
            if self.warmup.steps < self.warmup_steps
            else self.stats
        )
        target.steps += 1
        target.total_s += dt
        target.last_s = dt
        target.images += batch_size

    def time_step(self, fn, *args, batch_size: int = 1, **kwargs):
        """Run ``fn`` as one timed step and return its result."""
        with self.step(batch_size=batch_size) as box:
            out = fn(*args, **kwargs)
            box.append(out)
        return out

    def log_summary(self, label: str = "sampler") -> None:
        s = self.stats
        get_logger().info(
            "%s: %d steps, %.4f s/it, %.2f images/s (warmup %d steps, %.2fs)",
            label,
            s.steps,
            s.sec_per_it,
            s.images_per_sec,
            self.warmup.steps,
            self.warmup.total_s,
        )


def force_ready(v) -> float:
    """Force execution of ``v``'s whole dependency chain via a 4-byte
    device->host readback of a reduced scalar: possessing the bytes on the
    host proves the computation actually finished, whatever the backend's
    ``block_until_ready`` does."""
    import jax.numpy as jnp
    import numpy as np

    return float(np.asarray(jnp.sum(v.astype(jnp.float32))))


def chained_time(step, x0, iters: int, warmup: int = 2):
    """Mean seconds per ``step`` call, closed by a host readback.

    ``step`` must map an array to a like-shaped array (denoise models and
    attention both do). Each iteration feeds its output back as the next
    input, making the timed region one serial dependency chain — no runtime
    can skip, dedupe, or overlap it — and it closes with a ``force_ready``
    readback. ``warmup`` calls (>= 2 — both the original and the chained
    dtype signatures must compile outside the timed region) run first; the
    count is explicit so bench.py can pin and record the protocol.

    Returns ``(sec_per_iter, last_output)``."""
    out = step(x0)
    for _ in range(max(2, warmup) - 1):
        out = step(out)
    force_ready(out)
    run = out
    t0 = time.perf_counter()
    for _ in range(iters):
        run = step(run)
    force_ready(run)
    return (time.perf_counter() - t0) / iters, run


@contextlib.contextmanager
def trace(log_dir: str = "/tmp/parallelanything-trace"):
    """Profile a region → Perfetto/XProf trace in ``log_dir`` (SURVEY §5.1 plan)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()
        get_logger().info("profiler trace written to %s", log_dir)
