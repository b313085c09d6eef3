"""Continuous-batching scheduler: step-boundary batched scheduling of
concurrent sampler runs.

The seam: every model eval is an identical compiled dispatch, so sampler
runs that agree on (model, latent shape, cfg-mode) — running ANY sampler in
the LaneStepSpec registry — can share ONE step program: a request joins the
shared batch at the next step boundary, runs its own schedule (and its own
per-lane sampler state machine) in its own lane, and retires when its own
eval count completes (serving/bucket.py). This module is the glue between
the callers (sampling/runner.py routes eligible ``run_sampler`` work here
when a scheduler is installed; server.py installs one when it runs multiple
prompt workers) and the buckets:

- **shape-bucketed admission**: incoming work keyed by (model id, latent
  shape/dtype, prediction, cfg-mode, static/traced kwarg shapes) — NOT the
  sampler, which rides per-lane (round 10) — and
  routed to the matching bucket, created on first sight with a width the
  model itself bounds (``ParallelModel.serving_bucket_width`` — stream-mode
  chains stay width-1, mesh chains round to the data-axis width); within a
  bucket, requests aliasing ONE cond object (same-prompt siblings via the
  embed cache) seat against a shared broadcast cond tensor (round 17,
  serving/bucket.py shared-cond mode; ``reuse_stats()`` surfaces the
  per-bucket mode on /health);
- **policy**: FIFO-within-priority admission with bounded depth
  (serving/policy.py), per-request deadline, cancel — wired to the per-thread
  cooperative interrupt scope (utils/progress.py), so a prompt's Cancel frees
  its lane at the next boundary without touching its neighbors;
- **dispatcher**: one thread owns every compiled dispatch (one accelerator —
  lockstep is the schedule), round-robining buckets; ``auto=False`` exposes
  the same loop as a manual ``pump()`` for deterministic tests.

Ineligible work (unknown sampler, odd kwarg shapes, full queue) is never
queued: ``maybe_submit`` returns None and the caller runs inline exactly as
before — the scheduler can only ever ADD batching, not change results.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time

import numpy as np

from ..utils.metrics import registry
from ..utils.progress import (
    Interrupted,
    clear_interrupt,
    current_progress_hook,
    current_scope,
    interrupt_requested,
)
from .bucket import ServeRequest, StepBucket
from .policy import ServingRejected

# Samplers the stateful-lane program family implements (round 10): every
# registered LaneStepSpec (sampling/lane_specs.py) — history-carrying,
# two-eval, and stochastic families included. Stochastic lanes are
# occupancy-deterministic because the per-step noise key is fold_in(rng, i)
# on every path; tests/test_serving.py's registry-driven equivalence matrix
# gates additions (a wired-but-unverified sampler fails the build).
from ..sampling.lane_specs import LANE_SPECS

BATCHABLE_SAMPLERS = frozenset(LANE_SPECS)

_installed: "ContinuousBatchingScheduler | None" = None
_install_lock = threading.Lock()
_hints = threading.local()


def get_scheduler() -> "ContinuousBatchingScheduler | None":
    """The process-wide scheduler run_sampler consults, or None (inline)."""
    return _installed


@contextlib.contextmanager
def serving_hints(priority: int = 0, deadline_s: float | None = None):
    """Per-thread policy hints for sampler work submitted inside the block
    (the server worker sets these from POST /prompt extra_data)."""
    prev = getattr(_hints, "value", None)
    _hints.value = {
        "priority": int(priority),
        "deadline": (
            None if deadline_s is None else time.monotonic() + float(deadline_s)
        ),
    }
    try:
        yield
    finally:
        _hints.value = prev


def _current_hints() -> dict:
    return getattr(_hints, "value", None) or {"priority": 0, "deadline": None}


def _kwarg_sig(tree: dict, batch: int):
    """Hashable (name, shape, dtype) signature of a traced-kwargs dict, or
    None if any leaf lacks the per-request batch dim (ineligible — lanes
    stack kwargs along a new axis, so every leaf must be per-request)."""
    sig = []
    for k in sorted(tree):
        v = tree[k]
        if getattr(v, "ndim", 0) < 1 or v.shape[0] != batch:
            return None
        sig.append((k, tuple(v.shape), str(v.dtype)))
    return tuple(sig)


class ContinuousBatchingScheduler:
    """Owns the buckets, the admission policy, and the dispatcher thread."""

    def __init__(self, max_width: int | None = None, max_waiting: int = 64,
                 samplers=BATCHABLE_SAMPLERS, auto: bool = True):
        self.max_width = int(
            max_width if max_width is not None
            else os.environ.get("PA_SERVING_WIDTH", "4")
        )
        self.max_waiting = max_waiting
        self.samplers = frozenset(samplers)
        self.buckets: dict[tuple, StepBucket] = {}  # guarded-by: _lock
        # Degradation-ladder width caps (utils/degrade.py "lane-width-halve"):
        # bucket-key-prefix (the key minus its width component) → the widest
        # lane count the ladder still allows after a dispatch OOM. Applied to
        # every later submission for the same shape, so the shed width stays
        # shed until the process restarts (an OOM is a property of the shape
        # on this device, not of one request).
        self._width_caps: dict[tuple, int] = {}  # guarded-by: _lock
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._pump_lock = threading.Lock()
        self._stop = False
        self._thread = None
        if auto:
            self._thread = threading.Thread(
                target=self._loop, name="pa-serving-dispatcher", daemon=True
            )
            self._thread.start()

    # -- lifecycle ----------------------------------------------------------

    def install(self) -> "ContinuousBatchingScheduler":
        global _installed
        with _install_lock:
            _installed = self
        return self

    def uninstall(self) -> None:
        global _installed
        with _install_lock:
            if _installed is self:
                _installed = None

    def shutdown(self, timeout: float = 10.0) -> None:
        """Stop the dispatcher and resolve every outstanding request with
        Interrupted — no submitter may be left blocked on a dead scheduler."""
        self.uninstall()
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
        with self._lock:
            buckets = list(self.buckets.values())
            self.buckets.clear()
        for b in buckets:
            while True:
                req = b.queue.pop()
                if req is None:
                    break
                req.resolve(error=Interrupted("scheduler shutdown"))
            for i in b.active_lanes():
                b.lanes[i].req.resolve(error=Interrupted("scheduler shutdown"))
                b.lanes[i] = None

    # -- submission ---------------------------------------------------------

    def maybe_submit(
        self, *, model, x, sigmas, context, sampler, cfg_scale,
        uncond_context, uncond_kwargs, alphas_cumprod, prediction,
        cfg_rescale, model_kwargs, rng=None,
        latent_mask=None, mask_init=None, mask_noise=None,
        extra_conds=(), cond_area=None, cond_area_pct=None, cond_mask=None,
        cond_strength=1.0, cond_mask_strength=1.0, lora=None,
    ) -> ServeRequest | None:
        """Admit one sampler run, or return None when it cannot share a step
        program (caller runs inline). Called from run_sampler with the fully
        prepared (noised x, schedule, conditioning) — the serving layer never
        re-derives sampler semantics; per-step sampler math comes from the
        sampler's LaneStepSpec. ``rng`` is the stochastic base key (the same
        one the eager loop would fold per step).

        Capability state (round 16) rides the request as per-lane data, NOT
        the bucket key — a denoise mask, extra conds, a delegated ControlNet,
        or LoRA factors never fragment buckets, so mixed traffic shares one
        dispatch stream. Eligibility here only checks what the lane program
        cannot absorb (shape/(L,D)/pooled-y mismatches → inline)."""
        if self._stop or sampler not in self.samplers:
            return None
        spec_entry = LANE_SPECS.get(sampler)
        if spec_entry is None:
            return None
        if prediction == "flow" and not spec_entry.flow_ok:
            return None
        if spec_entry.needs_rng and rng is None:
            return None
        from ..utils.progress import current_preview_hook

        if current_preview_hook() is not None:
            # Latent previews are emitted by the inline loops' report_progress
            # (the only preview call site); a lane has no preview channel, so
            # a preview-enabled prompt must keep the inline path.
            return None
        from ..parallel.split import partition_kwargs, static_kwargs_key
        from ..sampling.compiled import trace_spec_of

        b = int(x.shape[0])
        traced, static = partition_kwargs(model_kwargs or {})
        t_sig = _kwarg_sig(traced, b)
        if t_sig is None:
            return None
        use_cfg = uncond_context is not None and cfg_scale != 1.0
        u_traced: dict = {}
        u_sig: tuple = ()
        if use_cfg:
            if getattr(uncond_context, "shape", None) != tuple(context.shape):
                return None
            u_traced, _ = partition_kwargs(uncond_kwargs or {})
            u_sig = _kwarg_sig(u_traced, b)
            if u_sig is None:
                return None
        if context is not None and (
            getattr(context, "ndim", 0) < 1 or context.shape[0] != b
        ):
            return None
        # -- capability eligibility (round 16) --------------------------------
        # ControlNet delegation: an apply_control composition buckets on the
        # BASE model (so control lanes co-batch with plain txt2img of the same
        # UNet) and the control trunk rides the request. Chained compositions
        # publish no delegate (models/controlnet.py) and stay opaque.
        eager_model = None
        control = None
        delegate = getattr(model, "control_delegate", None)
        if delegate is not None and getattr(x, "ndim", 0) == 4:
            base = delegate["base"]
            if trace_spec_of(base) is not None:
                hint = delegate["hint"]
                hb = 1 if getattr(hint, "ndim", 3) == 3 else int(hint.shape[0])
                if hb not in (1, b):
                    # apply_control rejects per-sample hint batches in-graph;
                    # inline surfaces that same ValueError to the caller.
                    return None
                control = {
                    "apply": delegate["ctrl_apply"],
                    "params": delegate["ctrl_params"],
                    "hint": hint,
                    "strength": delegate["strength"],
                    "start": delegate["start"],
                    "end": delegate["end"],
                }
                eager_model = model  # width-1 eager twin keeps the merged net
                model = base
        # Denoise-mask lanes need both blend references (the runner's inline
        # loop derives them; a bare mask cannot reconstruct the keep region).
        if latent_mask is not None:
            if mask_init is None or mask_noise is None:
                return None
            try:
                for ref in (latent_mask, mask_init, mask_noise):
                    if np.broadcast_shapes(
                        tuple(getattr(ref, "shape", ())), tuple(x.shape)
                    ) != tuple(x.shape):
                        return None
            except ValueError:
                return None
        # Multi-cond extras must pin to the primary cond's (L, D) — the lane
        # program stacks every role row in one eval; a different sequence
        # length cannot share the block. Pooled extras need ``y`` in the
        # traced kwargs (the bucket key already carries its shape via t_sig).
        extra_conds = tuple(extra_conds or ())
        if extra_conds:
            if context is None or getattr(context, "ndim", 0) != 3:
                return None
            for e in extra_conds:
                ec = e.get("context")
                if ec is None or getattr(ec, "ndim", 0) != 3:
                    return None
                if tuple(ec.shape[1:]) != tuple(context.shape[1:]):
                    return None
                if int(ec.shape[0]) not in (1, b):
                    return None
                pooled = e.get("pooled")
                if pooled is not None:
                    y = traced.get("y")
                    if (
                        y is None
                        or getattr(pooled, "ndim", 0) != 2
                        or int(pooled.shape[-1]) != int(y.shape[-1])
                        or int(pooled.shape[0]) not in (1, b)
                    ):
                        return None
        spec = trace_spec_of(model)
        # Per-lane LoRA: factors must address the param tree the lane program
        # evals (models/lora.py signature check — None means a path/shape
        # mismatch). Width-1 eager lanes gain nothing over the inline merge.
        lora_factors = None
        if lora:
            if spec is None:
                return None
            from ..models.lora import lora_signature

            sig = lora_signature(lora, spec.params)
            if sig is None:
                return None
            if sig:
                lora_factors = dict(lora)
        width = self.max_width
        bound = getattr(model, "serving_bucket_width", None)
        if callable(bound):
            width = bound(width)
        elif spec is None:
            width = 1
        if spec is not None and spec.mesh is not None:
            n = spec.mesh.shape[spec.data_axis]
            width = max(n, (width // n) * n)
        acp = alphas_cumprod
        if acp is None:
            acp_fp = None
        else:
            # Fingerprint interior samples too, not just the endpoints: two
            # custom schedules agreeing on length and range must not share a
            # bucket (the bucket's log-sigma table comes from the FIRST
            # request's schedule).
            a = np.asarray(acp, np.float64)
            stride = max(1, a.shape[0] // 7)
            acp_fp = (a.shape[0],) + tuple(
                float(v) for v in a[::stride]
            ) + (float(a[-1]),)
        # The sampler is NOT part of the key (round 10): per-lane sampler
        # state/updates ride the lane axis, so lanes running different
        # samplers share one bucket — and one compiled dispatch stream.
        key_prefix = (
            id(model), prediction, use_cfg, float(cfg_rescale),
            tuple(x.shape), str(x.dtype),
            None if context is None
            else (tuple(context.shape), str(context.dtype)),
            static_kwargs_key(static), t_sig, u_sig, acp_fp,
        )
        cap = self._width_caps.get(key_prefix)
        if cap is not None:
            width = min(width, cap)
        key = key_prefix + (width,)
        from ..utils import tracing

        req = ServeRequest(
            x=x, sigmas=np.asarray(sigmas, np.float32), context=context,
            sampler=sampler, rng=rng,
            uncond_context=uncond_context if use_cfg else None,
            traced_kwargs=traced, static_kwargs=static, u_traced=u_traced,
            uncond_kwargs=uncond_kwargs if use_cfg else None,
            cfg_scale=float(cfg_scale), cfg_rescale=float(cfg_rescale),
            prediction=prediction, acp=acp,
            latent_mask=latent_mask, mask_init=mask_init,
            mask_noise=mask_noise, extra_conds=extra_conds,
            cond_area=cond_area, cond_area_pct=cond_area_pct,
            cond_mask=cond_mask, cond_strength=float(cond_strength),
            cond_mask_strength=float(cond_mask_strength),
            control=control, lora=lora_factors, eager_model=eager_model,
            progress_hook=current_progress_hook(),
            interrupt_event=(
                current_scope().interrupt_event
                if current_scope() is not None else None
            ),
            # Trace correlation captured on the SUBMITTING thread: its
            # prompt, its tid (the dispatcher records this request's
            # lane-wait/step/lane spans onto that timeline), its submit time
            # on the trace clock.
            prompt_id=tracing.current_prompt_id() if tracing.on() else None,
            trace_tid=threading.get_ident() if tracing.on() else None,
            trace_submit_us=tracing.now_us() if tracing.on() else None,
            trace_id=tracing.current_trace_id() if tracing.on() else None,
            trace_parent=tracing.current_span_id() if tracing.on() else None,
            **_current_hints(),
        )
        with self._lock:
            bucket = self.buckets.get(key)
            if bucket is None:
                name = getattr(model, "name", None) or type(model).__name__
                # No sampler in the label either — a bucket serves the whole
                # k-sampler family in one dispatch stream.
                label = (
                    f"{name}:{prediction}:"
                    f"{'x'.join(str(d) for d in x.shape)}"
                )
                bucket = StepBucket(
                    key, label, width=width, model=model, spec=spec,
                    max_waiting=self.max_waiting,
                )
                self.buckets[key] = bucket
            try:
                bucket.queue.push(req)
            except ServingRejected:
                registry.counter("pa_serving_rejected_total",
                                 labels={"bucket": bucket.label},
                                 help="admissions refused (queue depth bound)")
                return None
            self._cond.notify_all()
        return req

    def cancel(self, rid: str) -> bool:
        """Cancel one request by id — queued entries resolve at the next
        admission sweep, a seated lane frees its slot at the next boundary."""
        with self._lock:
            buckets = list(self.buckets.values())
        for b in buckets:
            req = b.queue.remove(rid)
            if req is not None:
                req.cancel_event.set()
                req.resolve(error=Interrupted("cancelled while queued"))
                return True
            for i in b.active_lanes():
                if b.lanes[i].req.rid == rid:
                    b.lanes[i].req.cancel_event.set()
                    self.kick()
                    return True
        return False

    def kick(self) -> None:
        """Wake the dispatcher (a cancel/interrupt should take effect at the
        next boundary, not the next poll)."""
        with self._cond:
            self._cond.notify_all()

    # -- dispatch -----------------------------------------------------------

    def total_dispatches(self) -> int:
        with self._lock:
            return sum(b.dispatch_count for b in self.buckets.values())

    def reuse_stats(self) -> dict:
        """Sibling-seed cond sharing view (round 17) — the /health
        ``reuse.serving`` section: how many occupied buckets currently run
        the shared-cond broadcast program vs stacked per-lane rows (the
        seat/dispatch totals live on the labeled
        ``pa_serving_{shared_cond_seats,cond_broadcast}_total`` counters)."""
        with self._lock:
            buckets = list(self.buckets.values())
        modes = [b._cond_mode for b in buckets if b.active_lanes()]
        return {
            "buckets_shared_cond": sum(1 for m in modes if m == "shared"),
            "buckets_stacked_cond": sum(1 for m in modes if m == "stacked"),
        }

    def _has_work(self) -> bool:
        return any(not b.idle() for b in self.buckets.values())

    def pump(self) -> bool:
        """One scheduling round: sweep cancels, admit at the boundary, and
        run ONE lockstep dispatch per non-empty bucket. Returns whether any
        bucket dispatched. The dispatcher thread calls this in a loop;
        ``auto=False`` tests call it directly for step-deterministic control."""
        did = False
        with self._pump_lock:
            with self._lock:
                buckets = list(self.buckets.values())
            if interrupt_requested() and any(
                b.active_lanes() or len(b.queue) for b in buckets
            ):
                # Process-wide Cancel (POST /interrupt semantics): every lane
                # and queued request stops at this boundary; the flag is
                # consumed exactly as the inline loops' check_interrupt would.
                clear_interrupt()
                for b in buckets:
                    while True:
                        req = b.queue.pop()
                        if req is None:
                            break
                        req.resolve(error=Interrupted("interrupted while queued"))
                    for i in b.active_lanes():
                        b.lanes[i].req.cancel_event.set()
            for b in buckets:
                b.sweep_cancelled()
                b.admit()
            for b in buckets:
                try:
                    did = b.dispatch() or did
                except Exception as e:  # noqa: BLE001 — no waiter may hang
                    if self._degrade_bucket(b, e):
                        continue  # ladder absorbed it (requests re-seated
                        #           or shed to the inline path)
                    # Resolve EVERY request the dying bucket holds — seated
                    # lanes AND the waiting line — before dropping it, or
                    # their submitters block forever in ticket.result().
                    # (Pop-then-drain, same ordering discipline as the
                    # ladder: no new submission can land in the doomed
                    # bucket after the pop.)
                    with self._lock:
                        self.buckets.pop(b.key, None)
                    for req in self._drain_bucket(b):
                        req.resolve(error=e)
            # Drained buckets release their stacked device arrays (lane
            # state rebuilds from the next admitted request) so an idle
            # serving layer holds no latents/contexts in device memory
            # between bursts.
            for b in buckets:
                if b.idle():
                    b.release_state()
            self._trim_buckets()
        return did

    # -- degradation ladder (utils/degrade.py) -------------------------------

    def _drain_bucket(self, b: StepBucket) -> list:
        """Every request the bucket holds (seated lanes first, then the
        waiting line), with the bucket emptied. Seated requests restart from
        step 0 when re-seated — exactly the fleet-failover replay discipline,
        bitwise-safe by the fold_in RNG contract."""
        reqs = []
        for i in b.active_lanes():
            reqs.append(b.lanes[i].req)
            b.lanes[i] = None
        while True:
            req = b.queue.pop()
            if req is None:
                break
            reqs.append(req)
        return reqs

    def _reseat(self, reqs, model, spec, label: str, key_prefix: tuple,
                width: int) -> None:
        """Park drained requests in a (new) bucket at ``width``; anything the
        admission bound refuses is shed to the inline path rather than lost."""
        from ..utils.degrade import DegradedToInline

        key = key_prefix + (width,)
        with self._lock:
            bucket = self.buckets.get(key)
            if bucket is None:
                bucket = StepBucket(key, label, width=width, model=model,
                                    spec=spec, max_waiting=self.max_waiting)
                self.buckets[key] = bucket
            for req in reqs:
                try:
                    bucket.queue.push(req)
                except ServingRejected as e:
                    req.resolve(error=DegradedToInline(
                        f"re-seat after degradation refused: {e}"
                    ))
            self._cond.notify_all()

    def _degrade_bucket(self, b: StepBucket, e: BaseException) -> bool:
        """The serving OOM/compile ladder: width halve → attn-chunk shrink →
        inline fallback (OOM), or straight to inline on a compile failure.
        Returns True when the ladder absorbed the error (every request the
        bucket held is re-seated or shed — none resolves with ``e``); False
        hands the error back to the caller's resolve-everything path."""
        from ..utils.degrade import (
            DegradedToInline,
            is_compile_failure,
            record_rung,
        )
        from ..utils.telemetry import looks_like_oom

        oom = looks_like_oom(e)
        if not oom and not is_compile_failure(e):
            return False
        # Pop BEFORE draining, under the submit lock: maybe_submit resolves
        # the bucket and pushes inside one lock hold, so after this pop no
        # new request can land in the doomed bucket's queue (a push that
        # raced in earlier is drained below).
        with self._lock:
            self.buckets.pop(b.key, None)
        reqs = self._drain_bucket(b)
        key_prefix = b.key[:-1]
        if not oom:
            # Compile failure on the lane program: the eager inline loop is
            # the fallback program — DegradedToInline routes each submitter
            # there (run_sampler records the compile-eager rung's sibling,
            # inline-fallback, when it lands).
            record_rung("compile-eager",
                        f"bucket {b.label}: lane program compile failed "
                        f"({type(e).__name__}) — requests shed to inline",
                        bucket=b.label)
            for req in reqs:
                req.resolve(error=DegradedToInline(
                    f"lane program compile failure in bucket {b.label}: {e}"
                ))
            return True
        min_width = 1
        if b.spec is not None and b.spec.mesh is not None:
            min_width = b.spec.mesh.shape[b.spec.data_axis]
        new_width = max(min_width, b.width // 2)
        if new_width < b.width:
            record_rung("lane-width-halve",
                        f"bucket {b.label}: {type(e).__name__} at width "
                        f"{b.width} → {new_width}; requests re-seated",
                        bucket=b.label, width_before=b.width,
                        width_after=new_width)
            with self._lock:
                self._width_caps[key_prefix] = new_width
            self._reseat(reqs, b.model, b.spec, b.label, key_prefix, new_width)
            return True
        from ..ops.attention import shrink_chunk_threshold
        from ..sampling.compiled import clear_compiled_loops

        new_chunk = shrink_chunk_threshold()
        if new_chunk is not None:
            # Smaller attention blocks only help once the cached lane
            # programs (traced at the old threshold) are rebuilt.
            clear_compiled_loops()
            record_rung("attn-chunk-shrink",
                        f"bucket {b.label}: width already {b.width}; "
                        f"attention chunk → {new_chunk} elems, programs "
                        f"rebuilt",
                        bucket=b.label, chunk_elems=new_chunk)
            self._reseat(reqs, b.model, b.spec, b.label, key_prefix, b.width)
            return True
        # Ladder spent: shed to the inline path (graceful — the prompts
        # still complete; run_sampler records the inline-fallback rung).
        for req in reqs:
            req.resolve(error=DegradedToInline(
                f"serving OOM ladder exhausted for bucket {b.label}: {e}"
            ))
        return True

    def drain(self, timeout: float = 120.0) -> None:
        """Pump until every bucket is idle (manual mode helper)."""
        t0 = time.monotonic()
        while self._has_work():
            self.pump()
            if time.monotonic() - t0 > timeout:
                raise TimeoutError("serving drain timed out")

    def _trim_buckets(self, keep: int = 32) -> None:
        with self._lock:
            if len(self.buckets) <= keep:
                return
            for key in [k for k, b in self.buckets.items() if b.idle()]:
                if len(self.buckets) <= keep:
                    break
                self.buckets.pop(key)

    def _loop(self) -> None:
        while True:
            with self._cond:
                if self._stop:
                    return
                if not self._has_work():
                    self._cond.wait(timeout=0.2)
                    continue
            try:
                self.pump()
            except Exception:  # noqa: BLE001 — the dispatcher must survive
                time.sleep(0.05)
