"""The orchestrator: wrap a diffusion model once, run every step parallel.

This is the TPU-native counterpart of ParallelAnything.setup_parallel + the injected
``parallel_forward`` closure (any_device_parallel.py:917-1471). The reference clones the
torch module to every device and monkey-patches ``model.forward`` with a thread-fan-out
scheduler; here the model is a pure apply function + parameter pytree, "replication" is
a `NamedSharding` placement, and the per-step scheduler is a routing table in front of
jit-compiled SPMD programs.

Routing parity (parallel_forward, 1287-1315):

- ``batch == 1`` and ``workload_split``  → pipeline block-placement mode (1295-1305)
- ``batch < active devices`` or ``not workload_split`` → single-device (1307-1315)
- otherwise → data parallel (1317-1433)
- OOM at a step → aggressive cleanup, then whole-batch single-device retry (1435-1448)

Setup parity (setup_parallel):

- weight normalization with sum<=0 abort → model returned unchanged (1019-1027)
- memory-aware weight blending 0.7/0.3 (737-766) — measured ONCE at setup, because on
  TPU every new split shape is a recompile (SURVEY §7 hard part 3); the reference
  re-reads VRAM every step at zero cost, which XLA's compilation model forbids.
- placement OOM → drop a device, renormalize survivors, retry (1114-1128). The SPMD
  analogue drops the *last* chain device (an SPMD placement fails as a whole, so the
  specific failing device is unobservable — documented divergence); surviving weights
  renormalize and the model's reported chain reflects only survivors.
- teardown/lifecycle (211-282, 1459) → ``ParallelModel.cleanup()`` + GC.

Documented divergences from the reference (deliberate):

- Step-OOM demotes the model to single-device execution *permanently* (until
  ``reactivate()``), freeing the replicated params first. The reference retries the
  parallel path every step (1435-1448) — cheap on CUDA, but on TPU an OOM for a given
  shape is deterministic, so retrying re-OOMs every sampler step.
- When ``1 < batch < n_devices`` the reference drops to a single device (1307-1315);
  default here pads the batch up to the mesh size instead (``pad_small_batches=True``)
  so e.g. batch=4 on 8 cores still runs 4-way faster than one core. Set it False for
  strict parity.
- Non-array kwargs (strings, bools, python objects) are treated as *static*: baked
  into the compiled program, one compile per distinct combination. The reference
  forwards them dynamically into torch (1348-1356) — meaningless under XLA tracing.

Weighted splits on homogeneous meshes degenerate to even SPMD sharding (uneven splits
only exist to serve devices of unequal speed/memory; TPU cores are identical). Weighted
splits survive for heterogeneous chains (e.g. tpu+cpu), executed as one SPMD program
per platform group with a host-side weighted scatter/concat — the one place the
reference's fan-out shape survives (SURVEY §7 hard part 1).
"""

from __future__ import annotations

import dataclasses
import weakref
from collections.abc import Callable, Mapping, Sequence
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..devices.discovery import device_platform
from ..devices.memory import free_memory_bytes
from ..models.api import denoise_span
from ..utils.cleanup import aggressive_cleanup
from ..utils.logging import (
    get_logger,
    log_degradation,
    log_placement,
    log_setup_summary,
)
from .chain import DeviceChain, DeviceLink
from .mesh import (
    AXIS_DATA,
    build_mesh,
    mesh_context,
    place_params,
    place_params_fsdp,
)
from .split import (
    batch_size_of,
    pad_leaf as _pad_leaf,
    slice_padded as _slice_padded,
    blend_memory_weights,
    blend_speed_weights,
    largest_remainder_split,
    normalize_weights,
    partition_kwargs,
    split_kwargs,
    split_tree,
    static_kwargs_key,
    concat_results,
)


def _is_resource_exhausted(err: BaseException) -> bool:
    msg = str(err)
    return "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg or "OOM" in msg


def _gc_teardown(purge_cache: bool, purge_models: bool) -> None:
    """Finalizer body — the reference's cleanup_parallel_model fired from
    weakref.finalize (any_device_parallel.py:1459, 211-282). Runs only when a
    ParallelModel is garbage-collected without an explicit cleanup(). Must be
    shutdown-safe: finalizers also fire at interpreter exit, when log streams
    may already be closed and module state torn down."""
    import sys

    if sys.is_finalizing():
        return  # process exit frees everything anyway
    try:
        logger = get_logger()
        # A test harness (or daemonized host) may have closed the stream a
        # handler holds before GC runs; logging would print an internal error
        # rather than raise, so check explicitly.
        streams_ok = all(
            not getattr(getattr(h, "stream", None), "closed", False)
            for h in logger.handlers
        )
        if streams_ok:
            logger.info("parallel model garbage-collected; teardown per purge flags")
        if purge_cache:
            aggressive_cleanup(clear_compile_cache=purge_models)
    except Exception:
        pass


def _is_arraylike(v) -> bool:
    return isinstance(v, (jax.Array, np.ndarray))


def _pad_tree(tree, batch, padded):
    """Repeat-pad every batch-dim array leaf of a tree from ``batch`` to
    ``padded`` rows (non-arrays and non-batch leaves pass through)."""
    if padded == batch:
        return tree
    return jax.tree.map(
        lambda l: _pad_leaf(l, padded - batch)
        if _is_arraylike(l) and l.ndim > 0 and l.shape[0] == batch
        else l,
        tree,
    )


def _device_step_times(devices) -> list[float]:
    """Per-device nominal step time from the roofline platform specs
    (utils/roofline.nominal_step_time_s) — the speed signal
    ``blend_speed_weights`` folds into heterogeneous-chain splits. Reads
    only static spec tables: no device work, no measurement, so it is safe
    at setup time (the reference re-reads VRAM per step; specs don't move)."""
    from ..utils import roofline

    return [
        roofline.nominal_step_time_s(
            getattr(d, "device_kind", "") or "",
            getattr(d, "platform", "cpu") or "cpu",
        )
        for d in devices
    ]


def _split_inputs(batch, sizes, x, timesteps, context, kwargs):
    """Per-chunk (x, timesteps, context, kwargs) under the shared
    split-or-broadcast contract: a value splits on dim0 iff it carries the
    batch, else it broadcasts to every chunk (parity 1252-1267). One
    implementation for the hybrid scatter and microbatched pipeline paths."""
    xs = split_tree(x, sizes)
    ts = (
        split_tree(timesteps, sizes)
        if batch_size_of(timesteps) == batch
        else [timesteps] * len(sizes)
    )
    cs = (
        split_tree(context, sizes)
        if context is not None and batch_size_of(context) == batch
        else [context] * len(sizes)
    )
    kws = split_kwargs(kwargs, batch, sizes)
    return list(zip(xs, ts, cs, kws))


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """The orchestrator's knobs — exactly the reference's widget surface (SURVEY §5.6).

    ``workload_split``     — enable batch splitting / pipeline mode (893-896, default True)
    ``auto_memory_balance`` — blend user weights with free device memory (897-900;
        widget default True wins over the python-signature default False, SURVEY §5.6)
    ``purge_cache`` / ``purge_models`` — cleanup aggressiveness at teardown (901-908)
    ``pad_small_batches``  — see "documented divergences" in the module docstring
    ``weight_sharding``    — "replicate" (reference parity: full model per device,
        README.md:167), "fsdp" (shard each weight over the data axis; required
        when the model doesn't fit one chip — e.g. FLUX-dev bf16 on v5e), or
        "stream" (weights stay host-pinned and stream through the lead device
        double-buffered — parallel/streaming.py; the single-chip answer when
        even 1/N of the sharded model, or a chip to shard over, is missing)
    ``tensor_parallel``    — size of the ``model`` mesh axis; >1 builds a 2-D
        (data × model) mesh per group and shards weights over ``model`` so XLA
        partitions the matmuls themselves (GSPMD TP). Must divide each group's
        device count; composes with batch sharding, not with fsdp.
    """

    workload_split: bool = True
    auto_memory_balance: bool = True
    # Blend per-platform nominal step time (utils/roofline.py platform
    # specs) into heterogeneous-chain weights the way free memory is
    # blended above (round 17, ROADMAP "speed-aware hybrid blending"): a
    # tpu+cpu chain's split must reflect that the CPU is ~40x SLOWER, not
    # that it has spare RAM. Homogeneous chains are a structural no-op
    # (equal specs → equal speed shares → user weights unchanged).
    auto_speed_balance: bool = True
    purge_cache: bool = True
    purge_models: bool = False
    data_axis: str = AXIS_DATA
    pad_small_batches: bool = True
    weight_sharding: str = "replicate"
    tensor_parallel: int = 1
    # After a step-OOM demotion, automatically attempt reactivate() once this
    # many single-device steps have run (None = permanent demotion until manual
    # reactivate()/rebalance(), the documented default — an XLA OOM for a given
    # shape is deterministic, so eager per-step retry like the reference's
    # 1435-1448 would re-OOM every step; a counted backoff lets a TRANSIENT
    # host-side RESOURCE_EXHAUSTED — e.g. during a hybrid-chain host concat —
    # stop permanently serializing a long run). On a failed attempt the counter
    # restarts, giving exponential-free periodic retry.
    reactivate_after: int | None = None
    # Weight-streaming knobs (weight_sharding="stream", or the automatic
    # weights-don't-fit routing in parallelize):
    # ``hbm_budget_bytes`` — device HBM budget the placement decision and the
    #   stage carve use; None reads devices.memory.usable_hbm_bytes (the
    #   PA_HBM_BUDGET_BYTES override, else 90% of reported capacity). On
    #   backends reporting no memory (CPU tests) pass it explicitly.
    # ``stream_overlap`` — False serializes every transfer/compute (the
    #   streaming debug mode; parallel/streaming.py module docstring).
    hbm_budget_bytes: int | None = None
    stream_overlap: bool = True
    # Planner-chosen stream stage COUNT (parallel/planner.py stream-carve
    # axis): when set, the streaming runner carves byte-balanced into this
    # many stages instead of the budget-derived byte cap — but only if the
    # resulting largest stage still fits the 2-buffer budget
    # (build_streaming_runner falls back to the cap otherwise). None (the
    # default, and the PA_PLANNER=0 behavior) keeps the hand carve.
    stream_stages: int | None = None
    # >1 enables GPipe-style THROUGHPUT pipelining for batch>1 (beyond the
    # reference, whose pipeline mode is batch==1 layer placement only, SURVEY
    # §2e): the batch splits into this many microbatches streamed through the
    # per-device stage programs without host blocking — XLA's per-device
    # execution queues overlap microbatch j's later stages with j+1's earlier
    # ones. Useful when weights are stage-placed because a full replica does
    # not fit (the FSDP alternative without per-step all-gather traffic).
    pipeline_microbatches: int = 0


@dataclasses.dataclass
class _PlatformGroup:
    """One homogeneous sub-program: a mesh over same-platform devices + placed params.

    ``device_strs``/``device_weights`` stay index-aligned with ``devices`` so that
    dropping a device on placement OOM also drops its workload share (the reference's
    renormalize-survivors, 1114-1128).
    """

    platform: str
    devices: list[jax.Device]
    device_strs: list[str]
    device_weights: list[float]
    # Pre-blend user weights, kept so rebalance() can re-blend against *fresh*
    # memory readings instead of compounding blend-on-blend drift.
    user_weights: list[float] = dataclasses.field(default_factory=list)
    mesh: Any = None
    params: Any = None  # pytree placed replicated on this group's mesh

    @property
    def weight(self) -> float:
        return float(sum(self.device_weights))

    def drop_last_device(self) -> str:
        self.mesh = None
        self.params = None
        self.devices.pop()
        self.device_weights.pop()
        if self.user_weights:
            self.user_weights.pop()
        return self.device_strs.pop()


def _group_mesh(devices, config: "ParallelConfig"):
    """1-D data mesh, or 2-D (data x model) when tensor_parallel > 1."""
    n = len(devices)
    tp = max(1, int(config.tensor_parallel))
    if tp == 1:
        return build_mesh(devices, {config.data_axis: n})
    if config.weight_sharding == "fsdp":
        raise ValueError("tensor_parallel does not compose with weight_sharding='fsdp'")
    if n % tp:
        raise ValueError(
            f"tensor_parallel={tp} does not divide the group's {n} device(s)"
        )
    from .mesh import AXIS_MODEL

    return build_mesh(devices, {config.data_axis: n // tp, AXIS_MODEL: tp})


def _place_for(config: "ParallelConfig", params, mesh):
    """Single placement policy for setup, _place and reactivate: returns
    (placed_pytree, description)."""
    if config.weight_sharding == "fsdp":
        return (
            place_params_fsdp(params, mesh, config.data_axis),
            "fsdp-sharded parameter pytree",
        )
    if config.tensor_parallel > 1:
        from .mesh import place_params_tp

        return (
            place_params_tp(params, mesh),
            f"tensor-parallel parameter pytree (model axis ×{config.tensor_parallel})",
        )
    return place_params(params, mesh), "replicated parameter pytree"


class ParallelModel:
    """The wrapped model: call it like the model's forward, it routes and runs SPMD.

    Callable as ``model(x, timesteps, context=None, **kwargs)`` — the diffusion forward
    convention the reference's injected forward assumes (1287), batch dim is dim0.
    """

    def __init__(
        self,
        apply_fn: Callable[..., Any],
        params: Any,
        chain: DeviceChain,
        config: ParallelConfig,
        groups: list[_PlatformGroup],
        weights: tuple[float, ...],
        pipeline_spec: Any = None,
        model_config: Any = None,
        sampler_prefs: dict | None = None,
        streaming: bool = False,
        plan: dict | None = None,
    ):
        self._apply = apply_fn
        self._host_params = params
        self.chain = chain
        self.config = config
        # Weight-streaming mode (weights-don't-fit routing or an explicit
        # weight_sharding="stream"): groups hold NO placed params; every call
        # routes through the double-buffered StreamingRunner on the lead
        # device (parallel/streaming.py) and the full pytree never exists in
        # HBM — so neither the lead-copy fallback nor whole-loop compilation
        # may ever materialize it.
        self._stream = bool(streaming)
        self._stream_runner: Any = None
        # The wrapped model's own config (FluxConfig/UNetConfig/...), distinct from
        # the ParallelConfig above — pipelines read patch_size etc. through this.
        self.model_config = model_config
        # Model-level sampling preferences carried through from the wrapped
        # model (api.DiffusionModel.sampler_prefs) — samplers read them here.
        self.sampler_prefs = sampler_prefs
        self._groups = groups
        self.weights = weights
        # The planner decision this wrap routed through (parallel/planner.py)
        # — None when PA_PLANNER=0, the chain was ineligible (hybrid
        # multi-group, pinned fsdp/tp), or the planner predates this model.
        # bench.py reads it onto the JSON line; /health's ``plan`` section
        # shows the process-wide last decision.
        self.plan = plan
        self._pipeline_spec = pipeline_spec
        self._pipeline_runner: Any = None  # built lazily on first pipeline-path use
        self._jits: dict[tuple, Callable] = {}
        self._lead_params = None  # lazy single-device placement (fallback path)
        self.active = True
        self._steps_demoted = 0  # single-device steps since a step-OOM demotion
        self._demoted = False    # active=False via step-OOM (reactivatable)
        self._cleaned = False    # active=False via cleanup() (terminal)
        # GC-teardown parity (any_device_parallel.py:1459 registers
        # weakref.finalize(model, cleanup_parallel_model, ...)): a host graph
        # that simply DROPS the wrapped MODEL — exactly the ComfyUI pattern the
        # reference defends against — still honors the purge flags. The placed
        # arrays themselves free by refcount with the instance; the finalizer's
        # job is the cache purges + the teardown log event. It must not hold
        # ``self`` (that would keep the model alive forever), so it captures
        # only the two flags.
        self._finalizer = weakref.finalize(
            self, _gc_teardown, config.purge_cache, config.purge_models
        )

    # -- introspection (parity with the reference's tag attrs, 1452-1457) ----------

    @property
    def devices(self) -> tuple[str, ...]:
        return tuple(s for g in self._groups for s in g.device_strs)

    @property
    def lead_device(self) -> jax.Device:
        return self._groups[0].devices[0]

    @property
    def n_devices(self) -> int:
        return sum(len(g.devices) for g in self._groups)

    @property
    def is_streaming(self) -> bool:
        """True when this model executes via the weight-streaming runner
        (weights host-pinned, double-buffered through the lead device)."""
        return self._stream

    # -- compiled-apply cache ------------------------------------------------------

    def _jit_for(self, static: Mapping[str, Any]) -> Callable:
        # The ambient sequence_parallel context is read at trace time inside
        # ops.attention, so it must be part of the compile-cache key — otherwise
        # whichever context was active at first trace would be silently baked in.
        from ..ops.attention import sequence_ctx_key

        key = (sequence_ctx_key(), static_kwargs_key(static))
        fn = self._jits.get(key)
        if fn is None:
            apply = self._apply
            bound = dict(static)

            def wrapped(params, x, t, context, traced_kwargs):
                return apply(params, x, t, context, **traced_kwargs, **bound)

            from ..utils.telemetry import instrument_jit

            fn = instrument_jit(wrapped, "parallel-apply")
            self._jits[key] = fn
        return fn

    # -- execution -----------------------------------------------------------------

    def _data_width(self) -> int:
        """Total size of the data axis across groups — the unit batch routing
        compares against (== device count for 1-D meshes; smaller under TP)."""
        return sum(
            g.mesh.shape[self.config.data_axis] if g.mesh is not None else len(g.devices)
            for g in self._groups
        )

    def __call__(self, x, timesteps, context=None, **kwargs):
        with denoise_span("parallel-apply", x):
            return self._route(x, timesteps, context, kwargs)

    def _route(self, x, timesteps, context, kwargs):
        from ..ops.attention import sequence_ctx_key

        if self._stream:
            # Weight streaming is the ONLY placement that fits — every batch
            # size, every path (the demote/single fallbacks below would
            # re-materialize the full pytree on one chip, the thing that
            # cannot exist).
            return self._stream_call(x, timesteps, context, kwargs)
        if not self.active:
            ra = self.config.reactivate_after
            if (
                self._demoted
                and not self._cleaned
                and ra is not None
                and self._steps_demoted >= ra
            ):
                # N single-device steps have RUN since the demotion; this call
                # attempts the parallel path again. Gated on _demoted so an
                # explicitly cleaned-up model is never resurrected behind the
                # user's back.
                ran = self._steps_demoted
                try:
                    self.reactivate()
                    log_degradation(
                        "reactivate",
                        f"parallel execution resumed after {ran} "
                        "single-device step(s)",
                    )
                except Exception as e:  # noqa: BLE001
                    if not _is_resource_exhausted(e):
                        raise
                    # Still too tight — stay demoted, retry in another N steps.
                    self._steps_demoted = 0
            if not self.active:
                self._steps_demoted += 1
                return self.single(x, timesteps, context, **kwargs)
        batch = batch_size_of(x)
        n = self._data_width()
        try:
            if self.config.tensor_parallel > 1 and self.config.workload_split:
                # TP premise: weights only fit sharded — pipeline stage placement
                # and lead-device fallbacks would re-materialize full weights.
                # Every batch (incl. batch==1, where the data axis may be 1) runs
                # the sharded program.
                return self._data_parallel(batch, x, timesteps, context, kwargs)
            mb = self.config.pipeline_microbatches
            if mb > 1 and self.config.workload_split and batch >= mb and n > 1:
                # Opt-in GPipe-style throughput pipelining (see ParallelConfig):
                # microbatches stream through the stage chain; async dispatch
                # overlaps them across stage devices. Falls through to normal
                # routing when the model declares no pipeline spec or a
                # sequence_parallel context pins the attention mesh.
                if sequence_ctx_key() is None:
                    runner = self._get_pipeline_runner()
                    if runner is not None:
                        return self._pipeline_microbatch(
                            runner, mb, batch, x, timesteps, context, kwargs
                        )
            if batch == 1 and self.config.workload_split and n > 1:
                # Pipeline block-placement mode (reference 1295-1305); a model that
                # declares no stages runs single-device (1156-1166) — padded DP on a
                # 1-sample batch would just compute the same sample on every device.
                # Under an active sequence_parallel context the pipeline is skipped
                # entirely: stage programs are pinned to single devices and cannot
                # host a seq-mesh shard_map — the single-device path (whose jit
                # cache IS ctx-keyed) lets the requested context parallelism run.
                if sequence_ctx_key() is None:
                    runner = self._get_pipeline_runner()
                    if runner is not None:
                        return runner(x, timesteps, context, **kwargs)
                return self.single(x, timesteps, context, **kwargs)
            if not self.config.workload_split or n <= 1:
                return self.single(x, timesteps, context, **kwargs)
            if batch < n and not self.config.pad_small_batches:
                # Strict parity: batch < devices → single device (1307-1315).
                return self.single(x, timesteps, context, **kwargs)
            return self._data_parallel(batch, x, timesteps, context, kwargs)
        except Exception as e:  # noqa: BLE001 — OOM fallback, parity 1435-1448
            if not _is_resource_exhausted(e):
                raise
            log_degradation(
                "step-oom",
                f"{type(e).__name__}; freeing replicas, demoting to single-device",
            )
            self._demote()
            return self.single(x, timesteps, context, **kwargs)

    def _get_streaming_runner(self):
        """Build the weight-streaming runner on first use (placing the
        resident prepare/finalize params costs device memory, same laziness
        argument as _get_pipeline_runner)."""
        if self._stream_runner is None:
            from ..devices.memory import usable_hbm_bytes
            from .streaming import build_streaming_runner

            budget = self.config.hbm_budget_bytes
            if not budget:
                budget = usable_hbm_bytes(self.lead_device) or None
            self._stream_runner = build_streaming_runner(
                self._pipeline_spec, self._host_params, self.lead_device,
                hbm_budget_bytes=budget, overlap=self.config.stream_overlap,
                n_stages=self.config.stream_stages,
            )
            if self._stream_runner is None:
                raise ValueError(
                    "weight streaming requires a model with a PipelineSpec "
                    "(the staged decomposition the stream is carved from); "
                    "this model declares none"
                )
        return self._stream_runner

    def _stream_call(self, x, timesteps, context, kwargs):
        """Streamed execution with the stream-mode OOM demotion: a
        RESOURCE_EXHAUSTED re-carves the schedule at half the stage size and
        retries (deterministic for a given shape, like every XLA OOM — see
        the module docstring's demotion note), until stages bottom out at
        one segment each."""
        while True:
            runner = self._get_streaming_runner()
            try:
                return runner(x, timesteps, context, **kwargs)
            except Exception as e:  # noqa: BLE001 — OOM demotion, stream form
                if not _is_resource_exhausted(e):
                    raise
                deeper = runner.recarved()
                if deeper is None:
                    # Ladder exhausted (one segment per stage already):
                    # bounded degradation ends in a clean, attributable
                    # failure — postmortem bundle + the original error.
                    from ..utils import degrade

                    degrade.ladder_exhausted(
                        "stream-recarve", e,
                        detail=f"{runner.n_stages} stages, no finer carve",
                    )
                    raise
                from ..utils import degrade

                degrade.record_rung(
                    "stream-recarve",
                    f"{type(e).__name__}; re-carving weight stream "
                    f"{runner.n_stages} → {deeper.n_stages} stages",
                    stages_before=runner.n_stages,
                    stages_after=deeper.n_stages,
                )
                aggressive_cleanup(clear_compile_cache=False)
                self._stream_runner = deeper

    def _pipeline_microbatch(self, runner, mb, batch, x, timesteps, context, kwargs):
        """GPipe-style throughput pipelining over the stage chain.

        Every microbatch is dispatched through the per-device stage programs
        WITHOUT host blocking: each stage is an async program pinned to its own
        device, so XLA's per-device execution queues run microbatch j's later
        stages concurrently with j+1's earlier ones — the host only blocks on
        the final concat's consumers. The reference has no analogue (its
        pipeline mode is batch==1 only; SURVEY §2e calls it layer placement,
        not throughput pipelining)."""
        # Uniform chunk shapes: pad the batch up to mb * ceil(batch/mb) so
        # every microbatch compiles ONE set of stage/prepare/finalize programs
        # (uneven largest-remainder sizes would double every XLA compile).
        per = -(-batch // mb)
        padded = per * mb
        if padded != batch:
            x, timesteps, context, kwargs = (
                _pad_tree(v, batch, padded)
                for v in (x, timesteps, context, kwargs)
            )
        chunks = _split_inputs(padded, [per] * mb, x, timesteps, context, kwargs)
        outs = [runner(xi, ti, ci, **ki) for xi, ti, ci, ki in chunks]
        return _slice_padded(concat_results(outs), batch, padded)

    def _get_pipeline_runner(self):
        """Build the stage-placement runner on first use — placing per-stage param
        sub-pytrees costs device memory, so it only happens once a pipeline-path
        call (batch==1, or batch>1 with pipeline_microbatches) actually arrives
        (the reference pre-wraps at setup, 1152-1198)."""
        if self._pipeline_runner is None and self._pipeline_spec is not None:
            from .pipeline import build_pipeline_runner

            devices = [d for g in self._groups for d in g.devices]
            # Planner-chosen byte-balanced stage carve (parallel/planner.py
            # pipeline axis) — only when the decision was ENACTED (mode
            # "on", never shadow) AND the carve cleared the planner's
            # hysteresis ("enact"), and only on uniform-weight chains:
            # explicit uneven user weights (or a rebalance that shifted
            # them) keep the weight-proportional hand carve, which is what
            # those weights mean.
            ranges = None
            pipe_plan = (self.plan or {}).get("pipeline") \
                if isinstance(self.plan, dict) else None
            w = list(self.weights)
            if (
                pipe_plan and pipe_plan.get("enact") and w
                and (self.plan or {}).get("mode_flag") == "on"
                and max(w) - min(w) < 1e-9
                and len(pipe_plan.get("ranges") or []) <= len(devices)
            ):
                ranges = [tuple(r) for r in pipe_plan["ranges"]]
                # The carve REALLY applies now — stamp the decision (the
                # /health and ledger views read the shared dict) and count
                # it, so observability reflects enacted routing changes,
                # never mere intent (planner._pipeline_plan docstring).
                pipe_plan["enacted"] = True
                try:
                    from ..utils.metrics import registry as _metrics

                    _metrics.counter(
                        "pa_planner_pipeline_carve_total",
                        help="batch==1 pipeline runners built with the "
                             "planner's byte-balanced stage carve instead "
                             "of the weight-proportional hand carve",
                    )
                except Exception:
                    pass
            self._pipeline_runner = build_pipeline_runner(
                self._pipeline_spec, self._host_params, devices,
                list(self.weights), ranges=ranges,
            )
            if self._pipeline_runner is None:
                self._pipeline_spec = None  # unpipelineable; don't retry every step
        return self._pipeline_runner

    # The reference keeps ``_original_forward`` callable on the lead device
    # (1380-1383); ``single`` is that escape hatch.
    def single(self, x, timesteps, context=None, **kwargs):
        # Streaming premise: the full pytree does not fit ANY single chip —
        # the escape hatch is the streamed schedule itself, never a lead copy.
        if self._stream:
            return self._stream_call(x, timesteps, context, kwargs)
        # FSDP/TP premise: the full pytree does NOT fit one chip, so the fallback
        # cannot be a lead-device copy. Run over the group mesh with inputs
        # replicated instead — params stay 1/N per chip, XLA gathers per-use.
        g = self._groups[0]
        sharded_weights = (
            self.config.weight_sharding == "fsdp" or self.config.tensor_parallel > 1
        )
        if sharded_weights and g.params is not None:
            traced, static = partition_kwargs(kwargs)
            repl = NamedSharding(g.mesh, P())

            def put_repl(v):
                return jax.tree.map(
                    lambda l: jax.device_put(l, repl) if _is_arraylike(l) else l, v
                )

            fn = self._jit_for(static)
            with mesh_context(g.mesh):
                return fn(
                    g.params, put_repl(x), put_repl(timesteps),
                    put_repl(context), put_repl(traced),
                )
        traced, static = partition_kwargs(kwargs)

        def put(v):
            return jax.tree.map(
                lambda l: jax.device_put(l, self.lead_device) if _is_arraylike(l) else l,
                v,
            )

        fn = self._jit_for(static)
        return fn(self._lead(), put(x), put(timesteps), put(context), put(traced))

    def _lead(self):
        """Lazy full-pytree copy on the lead device — the shared placement for
        the eager single() fallback and traceable()'s single-device spec."""
        if self._lead_params is None:
            from .mesh import streamed_tree_put

            self._lead_params = streamed_tree_put(
                self._host_params, lambda _: self.lead_device
            )
        return self._lead_params

    def _data_parallel(self, batch, x, timesteps, context, kwargs):
        if len(self._groups) == 1:
            return self._dp_on_group(self._groups[0], batch, x, timesteps, context, kwargs)
        # Heterogeneous chain: weighted host-side scatter over platform groups, one
        # async SPMD program each, concat on host order (SURVEY §7 hard part 1).
        gweights = normalize_weights([g.weight for g in self._groups])
        assert gweights is not None
        sizes = largest_remainder_split(batch, gweights)
        chunks = _split_inputs(batch, sizes, x, timesteps, context, kwargs)
        outs = []
        for g, size, (xg, tg, cg, kg) in zip(self._groups, sizes, chunks):
            if size == 0:
                continue  # inactive group this batch (active-device list, 1324-1337)
            outs.append(self._dp_on_group(g, size, xg, tg, cg, kg))
        # Every group's program was dispatched asynchronously above; now gather each
        # output to the lead device (the reference's move-to-lead, 1408) and concat.
        outs = [
            jax.tree.map(
                lambda l: jax.device_put(l, self.lead_device) if _is_arraylike(l) else l,
                o,
            )
            for o in outs
        ]
        return concat_results(outs)

    def _dp_on_group(self, group: _PlatformGroup, batch, x, timesteps, context, kwargs):
        n = group.mesh.shape[self.config.data_axis]
        padded = batch + ((-batch) % n)
        sharded = NamedSharding(group.mesh, P(self.config.data_axis))
        repl = NamedSharding(group.mesh, P())

        def place(v):
            """Batch-dim leaves pad+shard; other array leaves replicate; the rest
            pass through (they become jit statics via kwargs partitioning or are
            non-batch pytree leaves)."""

            def leaf(l):
                if not _is_arraylike(l):
                    return l
                if l.ndim > 0 and l.shape[0] == batch:
                    return jax.device_put(_pad_leaf(l, padded - batch), sharded)
                return jax.device_put(l, repl)

            return jax.tree.map(leaf, v)

        traced, static = partition_kwargs(kwargs)
        fn = self._jit_for(static)
        with mesh_context(group.mesh):
            out = fn(group.params, place(x), place(timesteps), place(context),
                     place(traced))
        return _slice_padded(out, batch, padded)

    # -- whole-loop compilation handle (sampling/compiled.py) ----------------------

    def traceable(self):
        """A ``TraceSpec`` letting a sampler compile its ENTIRE denoise loop as
        one XLA program over this chain, or None when that cannot be a single
        program (heterogeneous multi-group chains need host-side scatter; an
        ambient sequence_parallel context pins shard_map meshes this path does
        not carry). Trades away per-step elasticity (step-OOM demotion,
        1435-1448) for zero per-step dispatch — the opt-in documented on
        ``run_sampler(compile_loop=True)``."""
        from ..ops.attention import sequence_ctx_key
        from ..sampling.compiled import TraceSpec

        if self._stream:
            # One XLA program would close over the FULL weight pytree — the
            # exact allocation streaming exists to avoid. The sampler loop
            # stays eager and drives the per-stage programs each step
            # (sampling/runner.py logs the fallback).
            return None
        if sequence_ctx_key() is not None:
            return None
        if len(self._groups) != 1:
            return None
        g = self._groups[0]
        sharded = (
            self.config.weight_sharding == "fsdp" or self.config.tensor_parallel > 1
        )
        if g.params is not None:
            if self.active and self.config.workload_split and self._data_width() > 1:
                return TraceSpec(
                    apply=self._apply, params=g.params, mesh=g.mesh,
                    data_axis=self.config.data_axis,
                )
            if sharded:
                # Sharded weights are the ONLY placement that fits — run the
                # loop over the group mesh with replicated inputs (the single()
                # premise), whether active or step-OOM-demoted; a lead-device
                # copy would re-materialize the full pytree on one chip.
                return TraceSpec(apply=self._apply, params=g.params)
        return TraceSpec(apply=self._apply, params=self._lead())

    def serving_bucket_width(self, requested: int) -> int:
        """How many concurrent serving lanes one step dispatch may co-batch
        for this chain (serving/scheduler.py consults this at admission).

        Stream-mode chains stay width-1: every step already re-streams the
        full weight pytree under a carved HBM budget, and co-batched lanes
        would multiply the activation peak that budget was carved against —
        they keep step-boundary scheduling (cancel, metrics, ragged retire)
        without co-batching. Hybrid multi-group chains and active
        sequence-parallel contexts are width-1 for the same reason they are
        not whole-loop traceable: no single step program exists to widen.
        Single-group chains take the requested width; the scheduler rounds it
        to the data-axis width so padded lanes shard evenly over the mesh."""
        if self._stream or self.traceable() is None:
            return 1
        return max(1, int(requested))

    # -- degradation (parity 1435-1448, divergence documented above) ---------------

    def _demote(self) -> None:
        self.active = False
        self._demoted = True
        self._steps_demoted = 0
        keep = (
            self.config.weight_sharding == "fsdp" or self.config.tensor_parallel > 1
        )
        for g in self._groups:
            if not keep:
                # Replicate mode frees the per-device replicas (the lead copy
                # takes over). FSDP/TP keep the sharded pytree: it is the ONLY
                # placement that fits, and single() runs on it with replicated
                # inputs.
                g.params = None
        self._pipeline_runner = None
        aggressive_cleanup(clear_compile_cache=True)
        self._jits.clear()

    def _place(self, params, mesh):
        placed, _ = _place_for(self.config, params, mesh)
        return placed

    def reactivate(self) -> None:
        """Re-place replicas and resume parallel execution after a demotion.
        Called manually, from rebalance(), or automatically after
        ``config.reactivate_after`` single-device steps. All-or-nothing: a
        placement failure on a later group rolls back the groups placed in
        THIS attempt, so a failed retry never leaves extra replicas pinned
        through the (memory-pressured) demoted period."""
        if self._stream:
            # Stream mode never demotes (OOM re-carves the schedule instead)
            # and a group placement would materialize the full pytree — the
            # allocation that cannot exist. No-op.
            return
        self._steps_demoted = 0
        placed_now: list = []
        try:
            for g in self._groups:
                if g.params is None:
                    g.mesh = _group_mesh(g.devices, self.config)
                    g.params = self._place(self._host_params, g.mesh)
                    placed_now.append(g)
        except Exception:
            for g in placed_now:
                g.params = None
                g.mesh = None
            raise
        self.active = True
        self._demoted = False

    # -- periodic re-balance (parity: per-step VRAM re-read, 737-766/1317-1322) ----

    def rebalance(self) -> tuple[float, ...]:
        """Re-read free device memory and re-blend workload weights.

        The reference re-reads VRAM *every step* (any_device_parallel.py:737-766,
        blended at 1317-1322) — free on CUDA, but on TPU a changed split shape is
        a recompile, so the deferred analogue runs on demand between sampler runs.
        Re-blends the *original* user weights (kept per group) against a fresh
        memory reading — not the already-blended values, which would compound —
        and resets the lazy pipeline runner so batch==1 stage placement also
        re-balances on next use. Returns the new normalized weights. No-op on
        chains where no device reports memory (blend falls back to user weights),
        and when ``auto_memory_balance`` is off — the reference gates the
        per-step VRAM re-blend on ``auto_balance_ref`` the same way
        (any_device_parallel.py:1317-1322), so explicit user weights are never
        silently overridden by memory stats.
        """
        if self._demoted and not self._cleaned:
            # An explicit rebalance signals intent to resume parallel execution
            # after a step-OOM demotion; failure to re-place keeps the single-device path.
            # Never resurrects an explicitly cleaned-up model.
            try:
                self.reactivate()
            except Exception as e:  # noqa: BLE001
                if not _is_resource_exhausted(e):
                    raise
        if not self.config.auto_memory_balance \
                and not self.config.auto_speed_balance:
            return self.weights
        user = [w for g in self._groups for w in g.user_weights]
        base = normalize_weights(user)
        if base is None:
            return self.weights
        devs = [d for g in self._groups for d in g.devices]
        new = base
        if self.config.auto_memory_balance:
            free = [free_memory_bytes(d) for d in devs]
            new = blend_memory_weights(new, free)
        if self.config.auto_speed_balance:
            # The SPEED half of the re-blend (round 17): same discipline as
            # memory — re-blended from the ORIGINAL user weights, platform
            # specs read fresh.
            new = blend_speed_weights(new, _device_step_times(devs))
        i = 0
        for g in self._groups:
            for j in range(len(g.device_weights)):
                g.device_weights[j] = new[i]
                i += 1
        self.weights = tuple(new)
        # Stage ranges are weight-proportional; rebuild lazily on next batch==1.
        self._pipeline_runner = None
        return self.weights

    # -- lifecycle (parity: cleanup_parallel_model, 211-282) -----------------------

    def cleanup(self) -> None:
        """Teardown: drop placed replicas and compile caches per the purge
        flags. Idempotent; also runs fully on a step-OOM-demoted model (it may
        still hold sharded params, a lead copy, and compile caches)."""
        # Explicit teardown supersedes the GC finalizer (don't purge twice).
        fin = getattr(self, "_finalizer", None)
        if fin is not None:
            fin.detach()
        if self._cleaned:
            return
        self._cleaned = True
        self.active = False
        for g in self._groups:
            g.params = None
        self._lead_params = None
        self._pipeline_runner = None
        self._stream_runner = None
        self._jits.clear()
        if self.config.purge_cache:
            aggressive_cleanup(clear_compile_cache=self.config.purge_models)
        get_logger().info("parallel teardown complete")


# --------------------------------------------------------------------------------------
# setup_parallel analogue
# --------------------------------------------------------------------------------------


def model_config_of(model) -> Any:
    """The underlying model's own config (FluxConfig/UNetConfig/WanConfig/...),
    whether ``model`` is bare or a ParallelModel — whose ``.config`` is the
    ParallelConfig, with the wrapped config kept on ``.model_config``."""
    cfg = getattr(model, "model_config", None)
    if cfg is None:
        cfg = getattr(model, "config", None)
    return cfg


def _unwrap_model(model) -> tuple[Callable[..., Any], Any]:
    """Accept ``(apply_fn, params)`` or any object with ``.apply`` + ``.params`` —
    the duck-typed analogue of the ModelPatcher unwrap (921-930)."""
    if isinstance(model, tuple) and len(model) == 2 and callable(model[0]):
        return model
    apply_fn = getattr(model, "apply", None)
    params = getattr(model, "params", None)
    if callable(apply_fn) and params is not None:
        from ..models.loader import residency

        residency.ensure(params)  # placed from its tensors: back on the chip first
        return apply_fn, params
    raise TypeError(
        "model must be (apply_fn, params) or expose .apply/.params; "
        f"got {type(model).__name__}"
    )


def _plan_inputs(params, pipeline_spec, devices, config: "ParallelConfig",
                 hints) -> "Any":
    """Assemble the planner's pure inputs from the wrap's facts (byte
    profile, budget, device identity) plus the caller's optional hints
    (bench passes the rung's measured FLOPs/bytes and batch; model wraps
    without hints plan from the weight bytes alone)."""
    from ..devices.memory import usable_hbm_bytes
    from ..models.loader import params_nbytes, segment_nbytes
    from .planner import PlanInputs

    hints = dict(hints or {})
    budget = config.hbm_budget_bytes or usable_hbm_bytes(devices[0]) or None
    seg: tuple = ()
    if pipeline_spec is not None and getattr(pipeline_spec, "segments", None):
        try:
            seg = tuple(segment_nbytes(pipeline_spec, params))
        except Exception:  # non-dict param containers: plan without the axis
            seg = ()
    lead = devices[0]
    return PlanInputs(
        n_devices=len(devices),
        platform=getattr(lead, "platform", "cpu") or "cpu",
        device_kind=getattr(lead, "device_kind", "") or "",
        weights_bytes=params_nbytes(params),
        budget_bytes=int(budget) if budget else None,
        segment_bytes=seg,
        flops=hints.get("flops"),
        bytes_accessed=hints.get("bytes_accessed"),
        batch=hints.get("batch"),
        seq_len=hints.get("seq_len"),
        head_dim=hints.get("head_dim"),
        heads=hints.get("heads"),
        rung=str(hints.get("rung") or ""),
    )


def parallelize(
    model,
    chain: DeviceChain | Sequence[tuple[str, float]],
    config: ParallelConfig | None = None,
    *,
    pipeline_spec: Any = None,
    plan_hints: Mapping[str, Any] | None = None,
) -> ParallelModel | Any:
    """Wrap ``model`` for parallel execution over ``chain``.

    Returns a ``ParallelModel``; on an unusable chain (empty, or total percentage <= 0)
    returns ``model`` unchanged, exactly like the reference's abort paths
    (1019-1027, 1037-1042).

    Strategy selection (round 18, parallel/planner.py): with ``PA_PLANNER``
    on (the default) and an open decision — single-platform chain,
    ``weight_sharding="replicate"``, no explicit tensor_parallel — the
    roofline-scored planner enumerates (mesh dp×tp × weight mode ×
    stage-carve × attention) candidates, prunes HBM-infeasible ones against
    the residency budget, and routes through the best predicted plan; an
    explicit ``weight_sharding="stream"`` pins the mode but still searches
    the stage carve. ``plan_hints`` feeds the cost model measured facts
    (``flops``/``bytes_accessed``/``batch``/``seq_len``/``head_dim``/
    ``rung`` — bench.py passes its rung's step cost). ``PA_PLANNER=0``
    restores the hand routing ladder below bitwise; ``PA_PLANNER=shadow``
    records the decision but enacts the hand plan.

    Re-entrant: passing an existing ``ParallelModel`` tears down its placements and
    rebuilds from the retained host params with the new chain/config — the
    reference's cleanup-then-rebuild on repeated setup_parallel calls (1006-1013,
    which runs *before* the weight-normalization abort at 1019-1027, so an unusable
    chain still leaves the previous setup torn down; the returned model keeps
    executing via its single-device path).
    """
    config = config or ParallelConfig()
    if not isinstance(chain, DeviceChain):
        chain = DeviceChain.from_pairs(chain)
    # An explicit ``pipeline_spec`` is the segments hint for models that cannot
    # carry one as an attribute — (apply, params) tuples wrapping third-party
    # code (the wrap-anything parity of the reference's name-based block
    # discovery, any_device_parallel.py:1156; see models/generic.py for the
    # flax auto-derivation).
    if isinstance(model, ParallelModel):
        apply_fn, params = model._apply, model._host_params
        if pipeline_spec is None:
            pipeline_spec = model._pipeline_spec
        wrapped_config = model.model_config
        sampler_prefs = getattr(model, "sampler_prefs", None)
        model.cleanup()
    else:
        apply_fn, params = _unwrap_model(model)
        if pipeline_spec is None:
            pipeline_spec = getattr(model, "pipeline_spec", None)
        wrapped_config = getattr(model, "config", None)
        # Model-level sampling preferences (RescaleCFG and friends) survive
        # wrapping — the stock ordering is patch -> ParallelAnything ->
        # KSampler, and samplers read prefs off whatever MODEL they get.
        sampler_prefs = getattr(model, "sampler_prefs", None)

    chain = chain.validated().deduplicated()
    weights = chain.normalized_weights()
    if not chain or weights is None:
        get_logger().warning("unusable device chain; returning model unchanged")
        return model

    devices = chain.jax_devices()

    user_weights = weights
    if config.auto_memory_balance:
        free = [free_memory_bytes(d) for d in devices]
        weights = blend_memory_weights(weights, free)
    if config.auto_speed_balance:
        weights = blend_speed_weights(weights, _device_step_times(devices))

    # Group consecutive-platform links into homogeneous SPMD sub-programs.
    groups: list[_PlatformGroup] = []
    for dev_str, dev, w, uw in zip(chain.devices, devices, weights, user_weights):
        plat = device_platform(dev_str)
        if groups and groups[-1].platform == plat:
            groups[-1].devices.append(dev)
            groups[-1].device_strs.append(dev_str)
            groups[-1].device_weights.append(w)
            groups[-1].user_weights.append(uw)
        else:
            groups.append(
                _PlatformGroup(
                    platform=plat,
                    devices=[dev],
                    device_strs=[dev_str],
                    device_weights=[w],
                    user_weights=[uw],
                )
            )

    # Weights-don't-fit routing rung: a replicate-mode
    # model whose pytree exceeds the lead device's HBM budget cannot place —
    # on hardware the loop below would OOM deterministically, burn the
    # degradation ladder chip by chip, and still fail on the last one. When
    # the model declares the PipelineSpec staging, route to the
    # weight-streaming executor instead: params stay host-pinned and stream
    # double-buffered through the lead device (parallel/streaming.py).
    stream_mode = config.weight_sharding == "stream"
    if stream_mode and pipeline_spec is None:
        raise ValueError(
            "weight_sharding='stream' requires a model with a PipelineSpec "
            "(the staged decomposition the stream is carved from)"
        )
    if stream_mode and config.tensor_parallel > 1:
        raise ValueError("weight_sharding='stream' does not compose with "
                         "tensor_parallel")

    # Auto-parallel planner (parallel/planner.py): search the plan space
    # where the decision is open. Hybrid multi-group chains keep the hand
    # weighted-scatter rules (one SPMD program per platform is the only
    # shape that exists there), explicit fsdp/tp configs are the user's
    # pinned decision, and PA_PLANNER=0 skips this block entirely — the
    # ladder below then routes bitwise-identically to the pre-planner code.
    plan_decision = None
    plan_enacted = False
    from . import planner as _planner

    if (
        _planner.enabled()
        and len(groups) == 1
        and config.pipeline_microbatches == 0
        and (stream_mode or (config.weight_sharding == "replicate"
                             and config.tensor_parallel <= 1))
    ):
        try:
            plan_decision = _planner.plan(
                _plan_inputs(params, pipeline_spec, devices, config,
                             plan_hints),
                pinned_mode="stream" if stream_mode else None,
            )
        except Exception:  # noqa: BLE001 — planning must never kill a wrap
            get_logger().warning(
                "auto-parallel planner failed; falling back to hand rules",
                exc_info=True,
            )
            plan_decision = None
        if plan_decision is not None and _planner.mode() == "on":
            chosen = plan_decision["chosen"]
            if chosen["mode"] == "stream" and pipeline_spec is not None:
                if not stream_mode and plan_decision["hand"]["mode"] != "stream":
                    log_degradation(
                        "plan-stream",
                        f"planner routed to weight streaming "
                        f"({chosen.get('n_stages')} stage(s), predicted "
                        f"{chosen['predicted_s']:.4g}s vs hand "
                        f"{plan_decision['hand']['predicted_s']:.4g}s)",
                    )
                stream_mode = True
                # A divergent carve enacts its stage count; a hand-equal
                # decision keeps the budget-cap carve byte-for-byte.
                if plan_decision["divergent"] and chosen.get("n_stages"):
                    config = dataclasses.replace(
                        config, stream_stages=int(chosen["n_stages"])
                    )
                plan_enacted = True
            elif chosen["mode"] == "fsdp":
                config = dataclasses.replace(config, weight_sharding="fsdp")
                plan_enacted = True
            elif chosen["mode"] == "tp" and chosen["tp"] > 1:
                config = dataclasses.replace(
                    config, tensor_parallel=int(chosen["tp"])
                )
                plan_enacted = True
            elif chosen["mode"] == "replicate":
                plan_enacted = True

    if (
        not plan_enacted
        and not stream_mode
        and config.weight_sharding == "replicate"
        and config.tensor_parallel <= 1
        and pipeline_spec is not None
    ):
        from ..devices.memory import usable_hbm_bytes
        from ..models.loader import params_nbytes

        budget = config.hbm_budget_bytes or usable_hbm_bytes(devices[0])
        total = params_nbytes(params)
        if budget and total > budget:
            log_degradation(
                "weights-dont-fit",
                f"{total / 2**30:.2f} GiB of weights vs {budget / 2**30:.2f} "
                "GiB HBM budget; routing to the weight-streaming executor",
            )
            stream_mode = True

    # Place params on each group's mesh, degrading on OOM: drop the last chain device
    # and retry (reference drops the failing device and renormalizes, 1114-1128).
    # Stream mode skips placement entirely — groups carry no params and the
    # lazily-built StreamingRunner owns all device residency.
    while not stream_mode:
        try:
            for g in groups:
                if g.params is None:
                    g.mesh = _group_mesh(g.devices, config)
                    g.params, desc = _place_for(config, params, g.mesh)
                    log_placement(f"{g.platform}×{len(g.devices)}", desc)
            break
        except Exception as e:  # noqa: BLE001
            if not _is_resource_exhausted(e):
                raise
            g = groups[-1]
            tp = max(1, config.tensor_parallel)
            if len(g.devices) > tp:
                # Drop enough trailing devices that the survivor count still
                # divides the tensor_parallel degree (always exactly 1 for tp=1).
                dropped = [g.drop_last_device()]
                while len(g.devices) % tp:
                    dropped.append(g.drop_last_device())
                log_degradation("setup-oom", f"dropped {dropped}, retrying")
            elif len(groups) > 1:
                groups.pop()
                log_degradation("setup-oom", f"dropped platform group {g.platform}")
            else:
                raise
            aggressive_cleanup(clear_compile_cache=True)

    # Rebuild the chain/weights views from the survivors so introspection and split
    # arithmetic agree with what was actually placed (renormalize-survivors parity).
    surviving = [(s, w) for g in groups for s, w in zip(g.device_strs, g.device_weights)]
    final_weights = normalize_weights([w for _, w in surviving])
    assert final_weights is not None
    chain = DeviceChain(
        tuple(DeviceLink(s, w * 100.0) for (s, _), w in zip(surviving, final_weights))
    )

    if stream_mode:
        mode = "stream"
    elif len(groups) == 1:
        mode = "spmd"
    else:
        mode = "hybrid"
    log_setup_summary(chain.devices, final_weights, mode)

    return ParallelModel(
        apply_fn=apply_fn,
        params=params,
        chain=chain,
        config=config,
        groups=groups,
        weights=final_weights,
        pipeline_spec=pipeline_spec,
        model_config=wrapped_config,
        sampler_prefs=sampler_prefs,
        streaming=stream_mode,
        plan=plan_decision,
    )
