"""Double-buffered weight-streaming execution: run models whose weights exceed HBM.

The flagship workload this repo is benchmarked on (FLUX-dev, bf16 ~24 GiB /
int8 ~12 GiB) does not fit one 16 GB chip's usable HBM (the usable share
itself: not measured), so neither the reference's replicate-everything placement
(README.md:167) nor this repo's resident pipeline placement can ever run it
single-chip. The ZeRO-Inference / DeepSpeed-Inference answer is to keep the
weights HOST-side and stream them through the chip layer by layer, overlapping
the next layer's transfer with the current layer's compute (PAPERS.md:
ZeRO-Offload lineage; GPipe-style stage overlap).

This module is that scheduler, built on the staging the models already
declare: a ``PipelineSpec`` (models/api.py) partitions the forward into
prepare → per-block segments → finalize, and ``models/loader.carve_stages``
groups contiguous segments into byte-bounded *stages*. Execution on ONE
device:

- params live host-side (``loader.pin_params_host`` — ``pinned_host`` memory
  kind where supported, plain numpy otherwise); prepare/finalize params (the
  small non-block remainder) are placed resident once at build time;
- a double-buffered prefetch ring streams stage *k+1*'s sub-pytree into HBM
  (async ``jax.device_put``) while stage *k*'s jitted program computes;
- stage *k−1*'s buffers are donated back on retirement: once its compute has
  provably finished (the backpressure block below), its device arrays are
  explicitly deleted, so peak HBM ≈ 2 stages of weights + activations;
- backpressure: before dispatching the NEXT prefetch the host blocks on the
  previous stage's output. Without it the async dispatch queue would let the
  host race every transfer into flight at once — exactly the concurrent-
  staging OOM ``mesh.streamed_tree_put`` exists to prevent (round-3
  evidence: flux_16_int8 OOM'd during placement);
- ``overlap=False`` is the debug mode: every transfer and compute is blocked
  to completion in program order, so a failure points at one stage instead of
  an async queue.

Residency is accounted through ``devices.memory.ResidencyTracker`` — tests
assert the 2-stage bound off-hardware (tests/test_streaming.py): no code
path should execute first on the chip.

The orchestrator routes here when weights don't fit the HBM budget
(orchestrator.parallelize: weights-don't-fit → stream), and re-carves with
smaller stages on a streaming OOM — the stream-mode analogue of the step-OOM
demotion (any_device_parallel.py:1435-1448; there is nothing below streaming
to demote TO, so the degradation axis is stage size, not device count).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

from ..devices.memory import ResidencyTracker
from ..models.api import PipelineSpec
from ..models.loader import carve_stages, params_nbytes, pin_params_host
from ..utils import faults, numerics, tracing
from ..utils.logging import get_logger, log_placement
from ..utils.telemetry import instrument_jit, watermark
from .split import partition_kwargs, static_kwargs_key


@dataclasses.dataclass
class _Stage:
    keys: tuple[str, ...]          # top-level param keys this stage streams
    fn: Callable[[Any, dict], dict]  # jitted: all of the stage's segments
    nbytes: int
    labels: tuple[str, ...]


def _delete_buffers(tree) -> None:
    """Donate retired stage buffers back to the allocator immediately.

    Called only after the consuming compute has completed (the backpressure
    block), so ``delete()`` never invalidates an in-flight argument; errors
    are swallowed because deletion is an optimization over refcount-freeing,
    not a correctness requirement."""
    for leaf in jax.tree.leaves(tree):
        try:
            leaf.delete()
        except Exception:
            pass


class StreamingRunner:
    """Callable ``(x, timesteps, context=None, **kwargs) -> output`` executing
    the staged forward on ONE device with double-buffered weight streaming.

    Built once per (spec, params, device, carve); every call re-streams the
    stage weights from host — that is the point: the model's full pytree
    never resides in HBM, only ~2 stages of it at any moment.
    """

    def __init__(
        self,
        spec: PipelineSpec,
        params: Any,
        device: jax.Device,
        *,
        max_stage_bytes: int | None = None,
        n_stages: int | None = None,
        overlap: bool = True,
        host_params_pinned: bool = False,
    ):
        self.device = device
        self.overlap = overlap
        self.tracker = ResidencyTracker()
        self._spec = spec
        self._max_stage_bytes = max_stage_bytes

        def subset(keys):
            missing = [k for k in keys if k not in params]
            if missing:
                raise KeyError(
                    f"pipeline spec references param keys not in the pytree: "
                    f"{missing}"
                )
            return {k: params[k] for k in keys}

        # Host-resident master copy (pinned where supported). The caller may
        # pass an already-pinned pytree (recarve path) to skip the re-pin.
        self._host_params = (
            params if host_params_pinned else pin_params_host(params, device)
        )
        # prepare/finalize params are the small non-block remainder — resident
        # on the device for the runner's lifetime, like the reference's
        # non-block layers that never leave the lead device (SURVEY §3.4).
        # Explicit ``device`` memory kind: a put to a bare device keeps a
        # pinned-host leaf's memory kind, and the stage programs then refuse
        # it (the recarve path hands this constructor pinned params).
        self._hbm = jax.sharding.SingleDeviceSharding(
            device, memory_kind="device"
        )
        self._prepare_params = jax.device_put(
            subset(spec.prepare_keys), self._hbm
        )
        self._finalize_params = jax.device_put(
            subset(spec.finalize_keys), self._hbm
        )
        self.tracker.add_resident(
            params_nbytes(self._prepare_params)
            + params_nbytes(self._finalize_params)
        )
        self._prepare_jits: dict[tuple, Any] = {}
        self._finalize_jits: dict[tuple, Any] = {}

        ranges = carve_stages(
            spec, self._host_params, max_stage_bytes=max_stage_bytes,
            n_stages=n_stages,
        )
        self.stages: list[_Stage] = []
        for s, e in ranges:
            keys: list[str] = []
            for i in range(s, e):
                for k in spec.segments[i].param_keys:
                    if k not in keys:
                        keys.append(k)
            seg_fns = tuple(spec.segments[i].fn for i in range(s, e))

            def stage_fn(stage_params, carry, _fns=seg_fns):
                for f in _fns:
                    carry = f(stage_params, carry)
                return carry

            self.stages.append(
                _Stage(
                    keys=tuple(keys),
                    # palint: allow[recompile-hazard] the byte-carve range IS
                    # program identity (a re-carve is a new program), bounded
                    # by the carve count
                    fn=instrument_jit(stage_fn, f"stream-stage[{s}:{e})"),
                    nbytes=params_nbytes(
                        {k: self._host_params[k] for k in keys}
                    ),
                    labels=tuple(
                        spec.segments[i].label for i in range(s, e)
                    ),
                )
            )
        log_placement(
            str(device),
            f"weight streaming: {len(self.stages)} stages over "
            f"{len(spec.segments)} segments, max stage "
            f"{max(st.nbytes for st in self.stages) / 2**20:.1f} MiB, "
            f"double-buffered ({'overlap' if overlap else 'no-overlap debug'})",
        )

    # -- introspection -----------------------------------------------------

    @property
    def n_stages(self) -> int:
        return len(self.stages)

    @property
    def max_stage_nbytes(self) -> int:
        return max(st.nbytes for st in self.stages)

    @property
    def streamed_nbytes(self) -> int:
        return sum(st.nbytes for st in self.stages)

    def recarved(self) -> "StreamingRunner | None":
        """A runner over the SAME host-pinned params with stage granularity
        halved — the streaming OOM demotion. None when no STRICTLY finer
        carve exists: at one segment per stage, or when the byte cap is
        pinned by a lone oversized segment (halving the cap then reproduces
        the identical carve — without this progress check the _stream_call
        retry loop would respin a deterministic OOM forever)."""
        if len(self.stages) >= len(self._spec.segments):
            return None
        cap = max(1, self.max_stage_nbytes // 2)
        ranges = carve_stages(
            self._spec, self._host_params, max_stage_bytes=cap
        )
        if len(ranges) <= len(self.stages):
            return None
        return StreamingRunner(
            self._spec, self._host_params, self.device,
            max_stage_bytes=cap, overlap=self.overlap,
            host_params_pinned=True,
        )

    # -- per-static jit caches (the PipelineRunner discipline) -------------

    def _prepare_for(self, static: dict):
        key = static_kwargs_key(static)
        fn = self._prepare_jits.get(key)
        if fn is None:
            prepare = self._spec.prepare
            bound = dict(static)

            def wrapped(params, x, t, context, traced):
                return prepare(params, x, t, context, **traced, **bound)

            fn = instrument_jit(wrapped, "stream-prepare")
            self._prepare_jits[key] = fn
        return fn

    def _finalize_for(self, out_shape: tuple[int, ...]):
        fn = self._finalize_jits.get(out_shape)
        if fn is None:
            finalize = self._spec.finalize

            def wrapped(params, carry):
                return finalize(params, carry, out_shape)

            fn = instrument_jit(wrapped, "stream-finalize")
            self._finalize_jits[out_shape] = fn
        return fn

    # -- the double-buffered schedule --------------------------------------

    def _publish_residency(self) -> None:
        """The pa_hbm_stream_* gauge view of the tracker (utils/metrics.py);
        refreshed at every placement/retirement so /metrics always shows the
        live streamed-weight footprint against its 2-stage bound."""
        try:
            from ..devices.memory import _device_label

            # Same platform:id label vocabulary as the pa_hbm_bytes_* device
            # gauges, so residency joins against capacity on the device label.
            self.tracker.publish_gauges(
                _device_label(self.device),
                bound_bytes=2 * self.max_stage_nbytes,
            )
        except Exception:
            pass

    def _check_stage(self, idx: int, value, where: str = "stream-stage") -> None:
        """Numerics sentinel (utils/numerics.py): per-stage output stats so a
        bad stage is NAMED — called only at boundaries the schedule already
        synchronizes (the backpressure block / the caller's own sync), so the
        sentinel adds no sync of its own to the double-buffered schedule."""
        try:
            nf = numerics.tree_nonfinite(value)
        except Exception:  # noqa: BLE001 — observation must never kill the run
            return
        if nf:
            stage = self.stages[idx] if 0 <= idx < len(self.stages) else None
            numerics.sentinel.record_event(
                where, stage=idx, device=str(self.device), nonfinite=int(nf),
                blocks=",".join(stage.labels) if stage is not None else "",
            )

    def _place_stage(self, idx: int):
        stage = self.stages[idx]
        # Fault site (utils/faults.py): an injected prefetch OOM raises the
        # same RESOURCE_EXHAUSTED shape a real allocator failure would, so
        # the orchestrator's re-carve ladder is rehearsed end to end
        # (chaos runs gate on the prompt still completing).
        act = faults.check("stream-prefetch-oom", key=str(idx))
        if act is not None:
            raise faults.oom_error(act)
        placed = jax.device_put(
            {k: self._host_params[k] for k in stage.keys}, self._hbm
        )
        self.tracker.place(idx, stage.nbytes)
        self._publish_residency()
        if not self.overlap:
            jax.block_until_ready(placed)
        return placed

    def _retire_stage(self, idx: int, ring: dict) -> None:
        """Drop stage ``idx``'s device buffers — only ever called after its
        compute has completed, so the explicit delete is safe."""
        placed = ring.pop(idx, None)
        if placed is None:
            return
        _delete_buffers(placed)
        self.tracker.retire(idx)
        self._publish_residency()

    def __call__(self, x, timesteps, context=None, **kwargs):
        from ..ops.attention import sequence_ctx_key

        if sequence_ctx_key() is not None:
            raise ValueError(
                "weight streaming does not compose with an active "
                "sequence_parallel context (stage programs are pinned to one "
                "device); exit the context or run a resident placement"
            )
        traced, static = partition_kwargs(kwargs)
        dev = self.device
        trace_on = tracing.on()
        # Span vocabulary (utils/tracing.py): one ``stream-run`` per call;
        # ``stream-stage-prefetch`` per device_put (async issue under
        # overlap, blocking in debug mode); ``stream-prefetch-wait`` for the
        # pre-dispatch block on the CURRENT stage's placed weights — the
        # EXPOSED transfer time double-buffering failed to hide (~0 when
        # overlap works; the ISSUE's blocked-on-prefetch wait). Traced runs
        # only: the compute is data-dependent on the transfer and the host's
        # next action is this dispatch, so the block shifts no work — but an
        # untraced run keeps the original sync-free schedule. ``stream-wait``
        # is the backpressure block on stage k-1's output;
        # ``stream-stage-compute`` runs from dispatch (weights already
        # on-device, so transfer stalls are excluded) to the moment the
        # output is KNOWN done, observed at the next backpressure block.
        # ``trace_aggregates`` turns these into stream_overlap_efficiency.
        t_run0 = tracing.now_us() if trace_on else 0.0
        comp_us = [0.0]  # Σ stage-compute span time → the overlap-eff gauge

        def record_compute(stage_idx: int, ts: float, **attrs) -> None:
            dur = tracing.now_us() - ts
            comp_us[0] += dur
            tracing.record(
                "stream-stage-compute", ts, dur, cat="stream",
                stage=stage_idx, nbytes=self.stages[stage_idx].nbytes,
                **attrs,
            )
        with tracing.span("stream-run", cat="stream", stages=len(self.stages),
                          device=str(dev), overlap=self.overlap):
            with tracing.span("stream-prepare", cat="stream"):
                carry = self._prepare_for(static)(
                    self._prepare_params,
                    jax.device_put(x, self._hbm),
                    jax.device_put(timesteps, self._hbm),
                    (jax.device_put(context, self._hbm)
                     if context is not None else None),
                    {k: jax.device_put(v, self._hbm)
                     for k, v in traced.items()},
                )
            with tracing.span("stream-stage-prefetch", cat="stream", stage=0,
                              nbytes=self.stages[0].nbytes,
                              blocking=not self.overlap):
                ring: dict[int, Any] = {0: self._place_stage(0)}
            prev_out = None  # output of stage k-1 — the backpressure handle
            pending = None   # (stage idx, dispatch ts) of the open compute span
            try:
                for k, stage in enumerate(self.stages):
                    if prev_out is not None:
                        # Wait for stage k-1's compute: its weights are provably
                        # consumed (retire donates them) and at most TWO stages
                        # are ever in HBM — without this block the async queue
                        # would admit every remaining prefetch at once.
                        with tracing.span("stream-wait", cat="stream",
                                          stage=k - 1, blocked_on="compute"):
                            # palint: allow[host-sync] the 2-stage HBM
                            # backpressure block — booked as stream-wait,
                            # never compute (the bound's load-bearing sync)
                            jax.block_until_ready(prev_out)
                        if numerics.on():
                            # The output is provably ready (the block above),
                            # so this reduction is pure post-hoc accounting.
                            self._check_stage(k - 1, prev_out)
                        if pending is not None:
                            record_compute(pending[0], pending[1])
                            pending = None
                        self._retire_stage(k - 1, ring)
                        if trace_on:
                            # Per-phase HBM watermark (traced runs only: the
                            # untraced schedule stays probe-free). This is
                            # the boundary where residency is at its 2-stage
                            # peak — the honest sample point.
                            watermark.sample([self.device])
                    if k + 1 < len(self.stages):
                        with tracing.span(
                            "stream-stage-prefetch", cat="stream", stage=k + 1,
                            nbytes=self.stages[k + 1].nbytes,
                            blocking=not self.overlap,
                        ):
                            ring[k + 1] = self._place_stage(k + 1)
                    if trace_on:
                        # EXPOSED transfer: how long stage k's own weights
                        # keep the (otherwise idle) device waiting past this
                        # point. ~0 when double-buffering hid the transfer;
                        # the whole point of the overlap-efficiency number is
                        # that this wait must NOT be booked as compute. The
                        # block is trace-mode-only and shifts no work: the
                        # compute below is data-dependent on these very
                        # buffers, and dispatching it is the host's next act.
                        with tracing.span("stream-prefetch-wait", cat="stream",
                                          stage=k, blocked_on="prefetch"):
                            # palint: allow[host-sync] trace-mode-only block
                            # booking EXPOSED transfer as wait, not compute
                            # (the PR 3 discipline's defining site)
                            jax.block_until_ready(ring[k])
                    t_dispatch = tracing.now_us() if trace_on else 0.0
                    carry = stage.fn(ring[k], carry)
                    if not self.overlap:
                        # palint: allow[host-sync] overlap-off DEBUG mode
                        # serializes by contract (round 6)
                        jax.block_until_ready(carry)
                        if trace_on:
                            record_compute(k, t_dispatch)
                    elif trace_on:
                        pending = (k, t_dispatch)
                    prev_out = carry
                with tracing.span("stream-finalize", cat="stream"):
                    out = self._finalize_for(tuple(x.shape))(
                        self._finalize_params, carry
                    )
                if pending is not None:
                    # The last stage's completion is never awaited here (it
                    # retires by refcount); close its span at finalize
                    # dispatch, marked as an async tail.
                    record_compute(pending[0], pending[1], async_tail=True)
                    pending = None
                if trace_on:
                    # The /metrics twin of the trace-derived aggregate:
                    # fraction of this streamed run spent in stage compute.
                    from ..utils.metrics import registry

                    run_us = tracing.now_us() - t_run0
                    if run_us > 0:
                        registry.gauge(
                            "pa_stream_overlap_efficiency",
                            min(1.0, comp_us[0] / run_us),
                            labels={"device": str(dev)},
                            help="stage-compute fraction of streamed-run wall "
                                 "time (1.0 = transfers fully hidden)",
                        )
                # The last stage retires by refcount once its compute
                # completes — deleting here would need a blocking sync on the
                # output instead.
                last = len(self.stages) - 1
                if last in ring:
                    ring.pop(last)
                    self.tracker.retire(last)
                    self._publish_residency()
                if numerics.on():
                    # Tail check (last stage + finalize — neither is awaited
                    # by the backpressure loop): the sentinel's pull doubles
                    # as the sync the caller was about to perform anyway.
                    self._check_stage(last, out, where="stream-output")
                return out
            finally:
                # Failure path (OOM mid-schedule): release whatever the ring
                # still holds so the recarved retry starts from a clean
                # allocator.
                for idx in list(ring):
                    self._retire_stage(idx, ring)


def build_streaming_runner(
    spec: PipelineSpec | None,
    params: Any,
    device: jax.Device,
    *,
    hbm_budget_bytes: int | None = None,
    n_stages: int | None = None,
    overlap: bool = True,
) -> StreamingRunner | None:
    """Build the weight-streaming runner, or None when the model declares no
    pipeline spec (nothing to carve — the router must then fail placement the
    ordinary way). ``hbm_budget_bytes`` sizes the stages: two buffers plus
    activation headroom must fit, so each stage is capped at 2/5 of the
    budget (2 × 2/5 weights + 1/5 activations/temps). An explicit
    ``n_stages`` (the planner's chosen carve, parallel/planner.py) wins
    over the byte cap only when its byte-balanced carve still fits the
    cap — a planned carve must never widen the double-buffer bound."""
    if spec is None or not spec.segments:
        return None
    max_stage_bytes = None
    if hbm_budget_bytes:
        max_stage_bytes = max(1, int(hbm_budget_bytes) * 2 // 5)
    if n_stages and max_stage_bytes:
        from ..models.loader import carve_ranges, segment_nbytes

        sizes = segment_nbytes(spec, params)
        ranges = carve_ranges(sizes, n_stages=int(n_stages))
        if max(sum(sizes[s:e]) for s, e in ranges) <= max_stage_bytes:
            max_stage_bytes = None  # the planned carve honors the cap
        else:
            n_stages = None  # planned carve would blow the budget; cap rules
    runner = StreamingRunner(
        spec, params, device,
        max_stage_bytes=max_stage_bytes, n_stages=n_stages, overlap=overlap,
    )
    get_logger().info(
        "weight streaming enabled: %.2f GiB streamed + %.2f MiB resident "
        "through %d stages on %s",
        runner.streamed_nbytes / 2**30,
        runner.tracker.resident_bytes / 2**20,
        runner.n_stages, device,
    )
    return runner
