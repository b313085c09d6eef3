"""Numerics sentinel: non-finite quarantine, latent fingerprints, drift audit.

PR 5 bought a strong correctness contract — every sampler's output is a pure,
bitwise-stable function of (request, step) — and PRs 3-4 made time and memory
attributable. Nothing yet *watched* that contract or the numeric health of the
latents themselves: the reference's only numeric-failure story is coarse OOM
degradation (any_device_parallel.py:1114-1128, 1435-1448), and a NaN'd latent
there surfaces as a black image N seconds later with nothing to name the
block, step, or σ that produced it. This module is the audit surface every
next step (wider lane eligibility, multi-host failover mid-denoise, a Pallas
attention kernel behind an equivalence gate) needs before it can land safely:

- **On-device reductions** (:func:`array_stats` / :func:`lane_stats`): a tiny
  ``[nonfinite_count, max|x|, mean, rms]`` vector computed *inside* the
  compiled programs as an auxiliary output — no host sync on the hot path;
  the host reads it at boundaries that already block (the serving bucket's
  post-dispatch block, the streaming runner's backpressure block).
- **Latent fingerprints** (:func:`digest` / :func:`lane_digest` /
  :func:`latent_fingerprint`): a deterministic bf16-quantized digest of a
  latent. The digest is a wrapping-uint32 sum of position-weighted bf16 bit
  patterns — modular integer addition is exactly associative and commutative,
  so the value is invariant to XLA reduction order and therefore to dp
  sharding; per-lane digests use lane-local element indices, so a lane's
  digest is invariant to occupancy and bucket width by construction (the
  fold_in RNG contract makes the *values* bitwise-stable; the digest makes
  that checkable in four bytes). ``scripts/numerics_audit.py --check`` banks
  golden fingerprints per rung and fails on drift, like the perf gate.
- **The sentinel** (:data:`sentinel`): process-wide event/quarantine/
  fingerprint bookkeeping behind a single ``enabled`` flag. Disabled is one
  flag check and nothing else — the tracer's null-singleton discipline
  (utils/tracing.py), tier-1-tested as a no-op.
- **Per-lane quarantine support**: :func:`bisect_nonfinite` re-runs one
  failing model eval through the model's ``PipelineSpec`` stages
  (prepare → per-block segments → finalize) to name the FIRST block whose
  output goes non-finite — the forensic detail the serving bucket writes into
  its ``write_postmortem`` bundle when it retires a poisoned lane.
- **Failure injection**: ``PA_FAIL_INJECT=nan:<lane>`` (guarded by
  ``PA_LEDGER_DIR``/``PA_EVIDENCE_DIR``, like bench.py's injection) poisons
  one seated lane's next eval input once, so the quarantine path is
  rehearsed off-hardware — the round-3 lesson applied to the sentinel itself.

Import discipline: stdlib-only at module level (jax loads lazily inside the
device helpers), mirroring utils/telemetry.py, so schema-reading callers
never initialise a backend.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque

__all__ = [
    "NonFiniteLatent",
    "NumericsSentinel",
    "array_stats",
    "bisect_nonfinite",
    "digest",
    "disable",
    "enable",
    "fail_inject_lane",
    "gate_status",
    "lane_digest",
    "lane_stats",
    "latent_fingerprint",
    "on",
    "sentinel",
    "stats_to_dict",
    "take_injection",
    "tree_nonfinite",
]

GATE_FILENAME = "numerics_gate.json"

# Stat-vector layout shared by every emitter and reader (the aux output of
# the compiled programs, the host dicts, the postmortem extras).
STAT_FIELDS = ("nonfinite", "max_abs", "mean", "rms")

# Digest constants: Knuth multiplicative hash step over lane-local element
# positions. Everything is mod 2^32, so summation order cannot matter.
_DIGEST_MULT = 2654435761
_DIGEST_SALT = 0x9E3779B9


class NonFiniteLatent(RuntimeError):
    """A lane's (or run's) latent state went NaN/Inf — raised to the
    submitter whose lane was quarantined (serving/bucket.py)."""


# ---------------------------------------------------------------------------
# on-device reductions (in-jit safe: jnp ops only, tiny outputs)
# ---------------------------------------------------------------------------


def array_stats(x):
    """``[nonfinite_count, max|x|, mean, rms]`` float32 vector for one array,
    with non-finite entries masked out of the max/mean/rms so the magnitudes
    stay readable even on a poisoned latent. In-jit safe (pure jnp)."""
    import jax.numpy as jnp

    xf = jnp.asarray(x, jnp.float32)
    finite = jnp.isfinite(xf)
    nf = jnp.sum(~finite).astype(jnp.float32)
    safe = jnp.where(finite, xf, 0.0)
    return jnp.stack([
        nf,
        jnp.max(jnp.abs(safe)),
        jnp.mean(safe),
        jnp.sqrt(jnp.mean(safe * safe)),
    ])


def lane_stats(x, extra=None):
    """Per-lane stats ``[W, 4]`` over a ``[W, ...]`` state stack. ``extra``
    (same leading dim) contributes its non-finite count only — the serving
    bucket passes the next eval input ``xe`` so a NaN parked mid-step by a
    two-eval sampler is caught at the dispatch that produced it, one eval
    before it would reach the latent."""
    import jax.numpy as jnp

    axes = tuple(range(1, jnp.ndim(x)))
    xf = jnp.asarray(x, jnp.float32)
    finite = jnp.isfinite(xf)
    nf = jnp.sum(~finite, axis=axes).astype(jnp.float32)
    if extra is not None:
        ef = jnp.asarray(extra, jnp.float32)
        nf = nf + jnp.sum(
            ~jnp.isfinite(ef), axis=tuple(range(1, jnp.ndim(ef)))
        ).astype(jnp.float32)
    safe = jnp.where(finite, xf, 0.0)
    return jnp.stack([
        nf,
        jnp.max(jnp.abs(safe), axis=axes),
        jnp.mean(safe, axis=axes),
        jnp.sqrt(jnp.mean(safe * safe, axis=axes)),
    ], axis=1)


def _bits_u32(x):
    """bf16-quantized bit patterns of ``x`` as uint32 (the digest's input)."""
    import jax
    import jax.numpy as jnp

    b16 = jnp.asarray(x, jnp.bfloat16)
    return jax.lax.bitcast_convert_type(b16, jnp.uint16).astype(jnp.uint32)


def digest(x):
    """Deterministic uint32 digest of one latent (in-jit safe).

    ``Σ (bits_i + 1) · (i · 2654435761 + salt)  (mod 2^32)`` over the
    flattened bf16 bit patterns: modular addition is order-independent, so
    the same values digest identically under any sharding/reduction order —
    the property that makes the fingerprint dp-sharding-invariant."""
    import jax.numpy as jnp

    bits = _bits_u32(x).reshape(-1)
    idx = jnp.arange(bits.shape[0], dtype=jnp.uint32)
    w = idx * jnp.uint32(_DIGEST_MULT) + jnp.uint32(_DIGEST_SALT)
    return jnp.sum((bits + jnp.uint32(1)) * w, dtype=jnp.uint32)


def lane_digest(x):
    """Per-lane digests ``[W]`` over a ``[W, ...]`` stack, each computed over
    LANE-LOCAL element positions — so ``lane_digest(stack)[i]`` equals
    ``digest(stack[i])`` regardless of where the lane sits or how wide the
    bucket is (occupancy/width invariance by construction)."""
    import jax.numpy as jnp

    w_lanes = x.shape[0]
    bits = _bits_u32(x).reshape(w_lanes, -1)
    idx = jnp.arange(bits.shape[1], dtype=jnp.uint32)
    w = idx * jnp.uint32(_DIGEST_MULT) + jnp.uint32(_DIGEST_SALT)
    return jnp.sum((bits + jnp.uint32(1)) * w[None, :], axis=1,
                   dtype=jnp.uint32)


def latent_fingerprint(x) -> str:
    """Host-side fingerprint string ``bf16:<shape>:<%08x>`` of a latent —
    what bench.py records per rung and the audit gate diffs. Pure function of
    the values: independent of the sentinel flag."""
    import numpy as np

    shape = "x".join(str(d) for d in getattr(x, "shape", ()))
    d = int(np.asarray(digest(x)))
    return f"bf16:{shape}:{d:08x}"


def stats_to_dict(vec) -> dict:
    """A host stats vector as the named dict the postmortems/events carry."""
    import numpy as np

    v = np.asarray(vec, np.float64).reshape(-1)
    out = {k: float(v[i]) for i, k in enumerate(STAT_FIELDS)}
    out["nonfinite"] = int(out["nonfinite"])
    return out


def tree_nonfinite(tree) -> int:
    """Total non-finite elements over all floating array leaves of a pytree
    (host-side; the streaming runner's per-stage check at sync boundaries)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            total += int(np.asarray(jnp.sum(~jnp.isfinite(
                jnp.asarray(leaf, jnp.float32)
            ))))
    return total


# ---------------------------------------------------------------------------
# the sentinel
# ---------------------------------------------------------------------------


class NumericsSentinel:
    """Process-wide numerics bookkeeping behind one ``enabled`` flag.

    Disabled costs instrumentation sites a single attribute read (the
    tracer's null-path discipline); enabled, it accumulates non-finite
    events, quarantine records, and bounded per-request fingerprint stacks,
    and mirrors them into ``pa_numerics_*`` metrics and ``numerics``-cat
    trace spans (both best-effort — a metrics hiccup must never break the
    path it observes)."""

    def __init__(self) -> None:
        self.enabled = False
        self._lock = threading.Lock()
        self._events = 0
        self._quarantined = 0
        self.last_event: dict | None = None
        self.last_quarantine: dict | None = None
        # Per-request fingerprint records: {"rid", "sampler", "bucket",
        # "steps", "digests": [uint32 per eval]} — bounded; the invariance
        # tests and dryrun §15 read these back.
        self._fingerprints: deque = deque(maxlen=64)  # guarded-by: _lock
        self._inject_done = False

    # -- lifecycle ----------------------------------------------------------

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        """Test/bench hygiene: zero the counters and records (flag
        untouched); re-arms the one-shot failure injection."""
        with self._lock:
            self._events = 0
            self._quarantined = 0
            self.last_event = None
            self.last_quarantine = None
            self._fingerprints.clear()
            self._inject_done = False

    # -- recording ----------------------------------------------------------

    def record_event(self, where: str, **info) -> dict:
        """One non-finite observation (NOT necessarily a quarantine: the
        streaming runner records stage events, bench records a poisoned
        final output). Feeds the counter, the last-event slot, and — when
        the tracer is on — an instant ``numerics`` span."""
        # palint: allow[observability] forensic-record epoch STAMP
        event = {"where": where, "ts": time.time(), **info}
        with self._lock:
            self._events += 1
            self.last_event = event
        try:
            from .metrics import registry

            registry.counter(
                "pa_numerics_nonfinite_total", labels={"where": where},
                help="non-finite latent/state observations by site",
            )
        except Exception:
            pass
        try:
            from . import tracing

            if tracing.on():
                tracing.record("nonfinite-event", tracing.now_us(), 0.0,
                               cat="numerics", **{k: v for k, v in info.items()
                                                  if isinstance(v, (str, int,
                                                                    float))},
                               where=where)
        except Exception:
            pass
        return event

    def record_quarantine(self, **info) -> dict:
        """One lane quarantine (serving/bucket.py): the full forensic record
        — bucket/lane/rid/sampler, the first non-finite step/σ/block, and the
        postmortem bundle path."""
        # palint: allow[observability] forensic-record epoch STAMP
        rec = {"ts": time.time(), **info}
        with self._lock:
            self._quarantined += 1
            self.last_quarantine = rec
        try:
            from .metrics import registry

            registry.counter(
                "pa_numerics_quarantined_total",
                labels={"bucket": str(info.get("bucket", "?"))},
                help="serving lanes retired by the non-finite quarantine",
            )
        except Exception:
            pass
        try:
            from . import tracing

            if tracing.on():
                tracing.record(
                    "quarantine", tracing.now_us(), 0.0, cat="numerics",
                    bucket=str(info.get("bucket")), lane=info.get("lane"),
                    step=info.get("step"), rid=info.get("rid"),
                )
        except Exception:
            pass
        return rec

    def record_fingerprints(self, **rec) -> None:
        with self._lock:
            self._fingerprints.append(rec)

    def recent_fingerprints(self) -> list[dict]:
        with self._lock:
            return list(self._fingerprints)

    # -- read side ----------------------------------------------------------

    @property
    def event_count(self) -> int:
        return self._events

    @property
    def quarantined_count(self) -> int:
        return self._quarantined

    def snapshot(self) -> dict:
        """The ``numerics`` section of ``GET /health``: flag state, event and
        quarantine totals, the last of each, and the fingerprint gate's last
        verdict (``scripts/numerics_audit.py --check`` writes it beside the
        ledger; None when the gate has never run)."""
        with self._lock:
            out = {
                "enabled": self.enabled,
                "nonfinite_events": self._events,
                "quarantined_lanes": self._quarantined,
                "last_event": dict(self.last_event) if self.last_event else None,
                "last_quarantine": (
                    dict(self.last_quarantine) if self.last_quarantine else None
                ),
            }
        out["fingerprint_gate"] = gate_status()
        return out

    def publish_gauges(self) -> None:
        """Mirror the totals into gauges so a /metrics scrape sees them even
        before the first event touches the counters."""
        try:
            from .metrics import registry

            registry.gauge("pa_numerics_sentinel_enabled",
                           1.0 if self.enabled else 0.0,
                           help="numerics sentinel flag (utils/numerics.py)")
            registry.gauge("pa_numerics_nonfinite_events", self._events,
                           help="non-finite observations this process")
            registry.gauge("pa_numerics_quarantined_lanes", self._quarantined,
                           help="lanes quarantined this process")
        except Exception:
            pass


sentinel = NumericsSentinel()


def on() -> bool:
    """The hot-path enabled check — guard stats computation with this."""
    return sentinel.enabled


def enable() -> None:
    sentinel.enable()


def disable() -> None:
    sentinel.disable()


def gate_status() -> dict | None:
    """Last fingerprint-gate verdict (``<ledger>/numerics_gate.json``,
    written by scripts/numerics_audit.py), or None."""
    try:
        from .telemetry import ledger_dir

        with open(os.path.join(ledger_dir(), GATE_FILENAME)) as f:
            return json.load(f)
    except Exception:
        return None


# ---------------------------------------------------------------------------
# failure injection (PA_FAIL_INJECT=nan:<lane>)
# ---------------------------------------------------------------------------


def fail_inject_lane() -> int | None:
    """The lane index to poison, or None. Round 14: parsed by the unified
    fault registry (utils/faults.py ``lane-nan`` site) — one syntax
    (``PA_FAULT_PLAN`` or the legacy ``PA_FAIL_INJECT=nan:<lane>`` alias)
    and ONE arming rule (explicit ``PA_LEDGER_DIR``/``PA_EVIDENCE_DIR``
    redirect, so an injected NaN's postmortem bundle can never land in the
    repo's real ledger). ``refresh()`` honors env set after import (tests,
    the dryrun's §15 re-arm)."""
    from . import faults

    return faults.refresh().lane_nan_target()


def take_injection(active_lanes) -> int | None:
    """One-shot: the armed lane index if it is currently seated, consuming
    the injection; else None (stays armed until the lane exists). The
    serving bucket calls this per dispatch when the sentinel is on; tests
    and the dryrun re-arm via ``sentinel.reset()``. A consumed injection is
    reported to the fault registry (``faults``-cat span +
    ``pa_fault_injected_total{site="lane-nan"}``), so chaos postmortems
    prove the NaN was injected, not organic."""
    lane = fail_inject_lane()
    if lane is None or lane not in active_lanes:
        return None
    with sentinel._lock:
        if sentinel._inject_done:
            return None
        sentinel._inject_done = True
    from . import faults

    faults.registry.record_external("lane-nan", key=str(lane), mode="nan")
    return lane


# ---------------------------------------------------------------------------
# per-block bisection (the quarantine postmortem's "which block did it")
# ---------------------------------------------------------------------------


def _finite(tree) -> bool:
    return tree_nonfinite(tree) == 0


def _subset(params, keys):
    try:
        return {k: params[k] for k in keys}
    except (KeyError, TypeError):
        return params


def eval_input(xe, sigma_eval: float, prediction: str, log_sigmas):
    """Replicate the lane program's per-eval model-input prep for ONE
    request: ``(x_in, t_vec)`` from the eval-input latent and σ — the
    EpsDenoiser formulas (k_samplers.py:390-400) with the σ→timestep
    log-interp for eps/v and flow time passed through for flow."""
    import jax.numpy as jnp

    batch = xe.shape[0]
    s = jnp.float32(sigma_eval)
    if prediction == "flow":
        return xe, jnp.full((batch,), s, jnp.float32)
    scale = 1.0 / jnp.sqrt(s**2 + 1.0)
    t = jnp.interp(
        jnp.log(s), log_sigmas,
        jnp.arange(log_sigmas.shape[0], dtype=jnp.float32),
    )
    return xe * scale, jnp.full((batch,), t, jnp.float32)


def bisect_nonfinite(model, xe, sigma_eval: float, prediction: str,
                     log_sigmas, context, kwargs: dict | None = None) -> dict:
    """Re-run ONE model eval stage-by-stage to name the first non-finite
    block. Returns ``{"block": <label or None>, "sigma": σ, ...}``:

    - ``"lane-input"`` — the eval input itself was already poisoned (the
      injection rehearsal's shape, or an upstream sampler-update blowup);
    - a ``PipelineSpec`` stage label (``prepare`` / the segment's own label /
      ``finalize``) when the model declares staged structure — the per-block
      bisection through the same prepare→segments→finalize decomposition the
      pipeline/streaming executors run;
    - ``"model-output"`` — spec-less model whose whole forward emits the
      non-finite value;
    - ``None`` — nothing non-finite reproduced (a transient the re-run could
      not reproduce; the step/σ naming in the bundle still stands).

    Runs the cond branch only (CFG mixing is elementwise after the forward,
    so a block-level NaN shows up on either branch). Best-effort by
    contract: callers wrap it in try/except — forensics must never raise
    over the quarantine it documents."""
    out: dict = {"sigma": float(sigma_eval), "prediction": prediction}
    if not _finite(xe):
        out["block"] = "lane-input"
        return out
    x_in, t_vec = eval_input(xe, sigma_eval, prediction, log_sigmas)
    kwargs = dict(kwargs or {})
    spec = getattr(model, "pipeline_spec", None)
    params = getattr(model, "params", None)
    if spec is not None and params is not None and spec.segments:
        carry = spec.prepare(
            _subset(params, spec.prepare_keys), x_in, t_vec, context, **kwargs
        )
        if not _finite(carry):
            out["block"] = "prepare"
            return out
        for i, seg in enumerate(spec.segments):
            carry = seg.fn(_subset(params, seg.param_keys), carry)
            if not _finite(carry):
                out["block"] = seg.label or f"segment[{i}]"
                out["segment_index"] = i
                return out
        final = spec.finalize(
            _subset(params, spec.finalize_keys), carry, tuple(x_in.shape)
        )
        out["block"] = "finalize" if not _finite(final) else None
        return out
    try:
        y = model(x_in, t_vec, context, **kwargs)
        out["block"] = "model-output" if not _finite(y) else None
    except Exception as e:  # noqa: BLE001 — forensics, not control flow
        out["block"] = None
        out["rerun_error"] = f"{type(e).__name__}: {e}"
    return out
