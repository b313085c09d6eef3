"""Shape-bucketed step batches: fixed-width stateful lanes in lockstep.

One ``StepBucket`` owns everything needed to run ONE compiled step program
over a fixed-width batch of lanes (padded, masked), where each lane is one
request at its own position in its own sigma schedule, running its OWN
sampler (round 10 — the dispatch unit is one batched model eval, not one
sampler's step):

- stacked device state ``(x, xe, h1, h2)[W, b, ...]`` — latent, next eval
  input, and two history slots (the lane form of the fused-loop carries,
  e.g. dpmpp_2m's ``old_x0``) — plus per-lane host bookkeeping (the
  sampler's eval-ordered ``StepPlan`` list from sampling/lane_specs.py, a
  plan counter, precomputed per-step noise-key table, request handle);
- step-boundary join/leave: a request enters by ``x.at[lane].set(...)`` at a
  boundary (history slots zeroed — the lane state-pytree init) and retires
  (its slice extracted, its waiter resolved) the moment its own EVAL count
  completes, while other lanes keep running — ragged schedules, mixed
  sampler families, lockstep dispatches;
- masking: retired/empty lanes ride along with ``sigma`` pinned to 1,
  identity update coefficients, and the ``jnp.where`` select, so occupancy
  can never perturb a live lane's values (the model is per-sample
  independent; the select guarantees even a NaN in a pad lane stays there);
- stochastic lanes: the step-``i`` key is ``fold_in(request rng, i)`` —
  keys are precomputed per request at seat time, so noise is a pure
  function of (request, step) and output is bit-identical alone vs
  co-batched (the occupancy-determinism contract);
- sibling-seed cond sharing (round 17): a fresh cond epoch runs SHARED —
  every lane references ONE cond tensor broadcast on the lane axis inside
  the program (``lane_step_program(broadcast_cond=True)``) instead of
  stacked per lane, so an N-seed fanout of one prompt (whose requests
  alias one cond object via the embed cache) costs one cond in HBM and
  ceil(N/width) dispatches per eval; the first foreign cond demotes to
  stacked rows (a mode change, never a value change — siblings' rows
  refill from the shared ref), and an idle release resets the epoch;
- numerics quarantine (round 11, utils/numerics.py): with the sentinel on,
  every dispatch also emits per-lane non-finite counts and bf16 latent
  digests as on-device aux outputs; a lane whose state goes NaN/Inf is
  retired at that boundary through the SAME select-mask discipline (its
  submitter gets :class:`~..utils.numerics.NonFiniteLatent`, survivors are
  untouched by construction), with a ``write_postmortem`` bundle naming the
  first offending block (PipelineSpec bisection re-run), step, and σ. The
  reference's only numeric-failure story is whole-run OOM degradation
  (any_device_parallel.py:1114-1128, 1435-1448) — here one poisoned lane
  costs one lane.

Two execution modes share the bookkeeping: a compiled per-lane step program
(sampling/compiled.py ``lane_step_program`` — single-program models, width N)
and a width-1 eager mode for models that can never be one XLA program
(weight-streaming / hybrid chains, parallel/orchestrator.py) — those walk
the SAME StepPlans against their own denoiser, gaining step-boundary
scheduling, the full sampler family, cancel, and metrics, just not
co-batching.

Bitwise discipline: the update math here is the same plan walk as the solo
loop's (``k_samplers.sample_planned``), the schedule-derived scalars
host-lifted per lane;
``tests/test_serving.py`` pins the full registry's lane-vs-solo equivalence
at bf16 tolerances on CPU and the 8-device mesh.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
import uuid
from typing import Any, Callable, Optional

import numpy as np

from ..sampling.lane_specs import LANE_SPECS, StepPlan, plan_schedule
from ..utils import numerics, slo, tracing
from ..utils.metrics import registry
from ..utils.progress import Interrupted
from .policy import AdmissionQueue, DeadlineExceeded

# Identity update for padded/retired lanes: x'=x, xe'=xe, h1'=h1, h2'=h2 —
# the host-side twin of the program's active-mask select.
_IDENTITY_COEF = np.zeros((4, 6), np.float32)
for _j, _k in ((0, 0), (1, 1), (2, 3), (3, 4)):
    _IDENTITY_COEF[_j, _k] = 1.0
del _j, _k

# Process-wide shared-dispatch accounting: lane-steps served in dispatches
# with occupancy > 1, over all lane-steps — the pa_serving_batched_fraction
# gauge (ISSUE 5 satellite; surfaced in GET /health and loadgen output).
_batch_stats = {"total": 0, "shared": 0}
_batch_lock = threading.Lock()


def record_dispatch_occupancy(occupancy: int) -> None:
    """Account one dispatch's lane-steps and refresh the fraction gauge."""
    with _batch_lock:
        _batch_stats["total"] += occupancy
        if occupancy > 1:
            _batch_stats["shared"] += occupancy
        frac = _batch_stats["shared"] / max(1, _batch_stats["total"])
    registry.gauge(
        "pa_serving_batched_fraction", frac,
        help="lane-steps served via shared dispatch / total lane-steps",
    )


def batched_fraction() -> float:
    """Lane-steps served via shared (occupancy>1) dispatch / total."""
    with _batch_lock:
        return _batch_stats["shared"] / max(1, _batch_stats["total"])


@dataclasses.dataclass
class ServeRequest:
    """One sampler run handed to the scheduler — the (x, sigmas, conditioning)
    triple run_sampler would otherwise have fed its own eager Euler loop,
    plus the policy/bookkeeping the serving layer adds."""

    x: Any                      # noised start latent [b, ...]
    sigmas: np.ndarray          # (n_steps+1,) descending, host-side
    context: Any
    uncond_context: Any
    traced_kwargs: dict
    static_kwargs: dict
    u_traced: dict
    uncond_kwargs: dict | None
    cfg_scale: float
    cfg_rescale: float
    prediction: str
    acp: Any                    # alphas_cumprod or None (default schedule)
    sampler: str = "euler"      # LaneStepSpec registry name
    rng: Any = None             # stochastic base key (None → deterministic)
    # Capability state (round 16, universal lane batching) — everything a
    # feature-carrying request needs rides the request itself, so a
    # degradation-ladder re-seat (_drain_bucket → _reseat) reconstructs the
    # full per-lane state from step 0, not just (x, xe, h1, h2).
    latent_mask: Any = None     # denoise mask (img2img/inpaint), 1 = denoise
    mask_init: Any = None       # keep-region init latent reference
    mask_noise: Any = None      # keep-region unit-noise reference
    extra_conds: tuple = ()     # multi-cond CFG extras (EpsDenoiser schema)
    cond_area: Any = None       # primary-cond scoping (SetArea family)
    cond_area_pct: Any = None
    cond_mask: Any = None
    cond_strength: float = 1.0
    cond_mask_strength: float = 1.0
    control: dict | None = None  # {"apply", "params", "hint", "strength",
                                 #  "start", "end"} from model.control_delegate
    lora: dict | None = None    # {param_path: (a, b)} — W_eff = W + b @ a
    eager_model: Any = None     # width-1 eager twin (merged control/LoRA)
    priority: int = 0
    deadline: float | None = None          # time.monotonic() deadline
    progress_hook: Optional[Callable[[int, int], None]] = None
    interrupt_event: Optional[threading.Event] = None
    # Trace correlation (utils/tracing.py), captured at submit: the prompt the
    # request serves, the submitting thread's tid (the request's spans land on
    # ITS timeline — it is blocked in result() for exactly that interval), and
    # the submit timestamp on the trace clock (lane-wait span start).
    prompt_id: Optional[str] = None
    trace_tid: Optional[int] = None
    trace_submit_us: Optional[float] = None
    # Distributed trace identity captured on the submitting thread (the
    # fleet traceparent's trace_id) — the dispatcher stamps it onto this
    # request's lane-wait/step/lane spans, same rule as trace_tid.
    trace_id: Optional[str] = None
    # The span open on the submitting thread at submit (its ``sampler-run``):
    # the parent of every span the dispatcher records for this request.
    trace_parent: Optional[int] = None
    rid: str = dataclasses.field(default_factory=lambda: uuid.uuid4().hex)
    submit_ts: float = dataclasses.field(default_factory=time.monotonic)

    def __post_init__(self):
        self.cancel_event = threading.Event()
        self._done = threading.Event()
        self._result: Any = None
        self._error: BaseException | None = None

    @property
    def n_steps(self) -> int:
        return len(self.sigmas) - 1

    def cancelled(self) -> bool:
        return self.cancel_event.is_set() or (
            self.interrupt_event is not None and self.interrupt_event.is_set()
        )

    def resolve(self, result=None, error: BaseException | None = None) -> None:
        self._result, self._error = result, error
        self._done.set()

    def result(self, timeout: float | None = None):
        """Block the submitting thread until its lane retires; re-raises the
        lane's error (Interrupted propagates exactly as the inline sampler's
        cooperative check would have raised it)."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"serving request {self.rid} still in flight")
        if self._error is not None:
            raise self._error
        return self._result


@dataclasses.dataclass
class _Lane:
    req: ServeRequest
    idx: int = 0   # σ-intervals completed (progress unit)
    pc: int = 0    # next StepPlan to run (the eval unit — 2/interval for
                   # second-order samplers)
    plans: list = dataclasses.field(default_factory=list)
    keys: Any = None  # [n_steps, 2, key_width] uint32 noise-key table or None
    # Width-1 eager mode only: the lane's own state pytree + denoiser
    # (program mode keeps lane state stacked in the bucket's device arrays).
    x_eager: Any = None
    xe_eager: Any = None
    h1_eager: Any = None
    h2_eager: Any = None
    denoiser: Any = None
    seat_us: float = 0.0  # trace-clock admission time (the lane span start)
    # Numerics sentinel (utils/numerics.py): per-eval bf16 digests of this
    # lane's latent — the (request, step) fingerprint stack, recorded into
    # the sentinel's ring at retirement. Empty when the sentinel is off.
    digests: list = dataclasses.field(default_factory=list)

    def plan(self) -> StepPlan:
        return self.plans[self.pc]

    def done(self) -> bool:
        return self.pc >= len(self.plans)


def _lane_key_table(rng, n_steps: int, split: bool):
    """[n_steps, 2, key_width] uint32 per-step key data under the fold_in
    discipline; columns are the ``split(fold_in(rng, i))`` halves when
    ``split`` (dpmpp_sde's mid/end draws), else both the per-step key. One
    tiny vmapped dispatch per admission — the whole table is then host-side
    numpy, indexed per dispatch with zero device work."""
    import jax
    import jax.numpy as jnp

    if rng is None or n_steps <= 0:
        return None
    base = rng
    if not jnp.issubdtype(jnp.asarray(base).dtype, jax.dtypes.prng_key):
        base = jax.random.wrap_key_data(jnp.asarray(base, jnp.uint32))
    ks = jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(n_steps))
    if split:
        data = jax.random.key_data(jax.vmap(jax.random.split)(ks))
    else:
        d = jax.random.key_data(ks)
        data = jnp.stack([d, d], axis=1)
    return np.asarray(data)


def _noise_key_row(lane: "_Lane", plan: StepPlan):
    """The lane's key data for this plan's draw, or None when no draw."""
    if plan.noise is None or lane.keys is None:
        return None
    col = 1 if plan.noise == "sde_end" else 0
    return lane.keys[plan.step, col]


class StepBucket:
    """Fixed-width lockstep batch for one (model, shape, sampler-config) key."""

    def __init__(self, key, label: str, *, width: int, model, spec,
                 max_waiting: int = 64):
        import jax.numpy as jnp

        from ..sampling.k_samplers import model_sigmas
        from ..sampling.schedules import scaled_linear_schedule

        self.key, self.label = key, label
        self.width = max(1, int(width))
        self.model, self.spec = model, spec
        self.queue = AdmissionQueue(max_waiting=max_waiting)
        self.lanes: list[_Lane | None] = [None] * self.width
        self.dispatch_count = 0
        self._program = None
        self._prog_kw = None
        # Sentinel state captured at program build (the stats/digest aux
        # outputs are part of the compiled signature); width-1 eager mode
        # reads numerics.on() live instead.
        self._emit_stats = False
        self._log_sigmas = None
        self._acp_default = None
        # Stacked device state, built from the first admitted request's
        # shapes: latent, eval input, and the two per-lane history slots.
        self._x = None
        self._xe = None
        self._h1 = None
        self._h2 = None
        self._ctx = None
        self._uctx = None
        self._kw = None
        self._ukw = None
        # Sibling-seed cond sharing (round 17): a fresh cond epoch starts
        # in "shared" mode — every lane references ONE cond tensor,
        # broadcast over the lane axis inside the program
        # (sampling/compiled.py broadcast_cond) instead of stacked per
        # lane, so an N-seed fanout of one prompt costs one cond in HBM.
        # The first seat whose cond is a DIFFERENT object demotes the
        # bucket to "stacked" (per-lane rows) until the state releases.
        # Identity is the sharing signal: the embed cache returns one
        # object per (model, text), so same-prompt requests alias by
        # construction.
        self._cond_mode = None        # "shared" | "stacked"
        self._ctx_ref = None          # identity refs (original objects)
        self._uctx_ref = None
        self._ctx_dev = None          # placed shared copies (mesh: replicated)
        self._uctx_dev = None
        # Traced-kwargs sharing (PR 12 remainder): the SAME state machine
        # for the traced kwarg trees — pooled ``y`` vectors, ``guidance``,
        # and the negative-prompt/uncond extras (``u_traced``) — which a
        # sibling-seed fanout also aliases by object identity. Tracked
        # independently of the cond mode: siblings that share the prompt
        # cond but carry per-request kwargs still ride the broadcast-cond
        # program with stacked kwargs, and vice versa.
        self._kw_mode = None          # "shared" | "stacked"
        self._kw_ref = None           # identity refs (original trees)
        self._ukw_ref = None
        self._kw_dev = None           # placed shared copies (mesh: replicated)
        self._ukw_dev = None
        # Capability overlays (round 16, universal lane batching). The
        # denoise-mask axis is ALWAYS-ON — zero stacks built with the state,
        # no program variant, so any txt2img/img2img mix shares ONE program
        # bitwise. Multi-cond / ControlNet / LoRA overlays materialize
        # lazily the first time a carrying request seats: each
        # materialization swaps the program variant once per bucket epoch
        # (the PR 12 shared→stacked demotion precedent), after which any
        # traffic mix rides the variant without recompiling. Every overlay
        # keeps zero rows structurally inert (zero mask gate / zero weight
        # map / zero residual gain / zero factors), so non-carrying lanes
        # pass through bitwise.
        self._mask = None             # [W, b, ...] f32 denoise masks
        self._mask_init = None        # [W, b, ...] keep-region init latents
        self._mask_noise = None       # [W, b, ...] keep-region unit noise
        self._mask_has = np.zeros(self.width, bool)   # host gate source
        self._mc_k = None             # None → overlay off; else bucket max K
        self._mc_has_y = False
        self._mc_w0 = None            # [W, b, ..., 1] primary weight maps
        self._mc_ctx = None           # [W, K, b, L, D] extra cond rows
        self._mc_w = None             # [W, K, b, ..., 1] extra weight maps
        self._mc_y = None             # [W, K, b, Y] pooled rows (has_y only)
        self._mc_win = None           # host [W, K, 2] progress windows
        self._ctrl = None             # {"apply", "params", "params_ref"}
        self._ctrl_hint = None        # [W, b, H8, W8, C] hint stack
        self._ctrl_strength = np.zeros(self.width, np.float32)
        self._ctrl_win = np.tile(
            np.asarray([0.0, 1.0], np.float32), (self.width, 1)
        )
        self._lora_sig = ()           # ordered ((path, m, k), ...)
        self._lora_rmax = 0
        self._lora_ab = []            # per path: (a[W,r,k], b[W,m,r]) stacks
        self._jnp = jnp
        self._model_sigmas = model_sigmas
        self._default_schedule = scaled_linear_schedule
        self._labels = {"bucket": label}

    # -- occupancy ----------------------------------------------------------

    def active_lanes(self) -> list[int]:
        return [i for i, l in enumerate(self.lanes) if l is not None]

    def idle(self) -> bool:
        return not self.active_lanes() and len(self.queue) == 0

    def release_state(self) -> None:
        """Drop the stacked device arrays while idle — an idle serving layer
        must not pin width×batch latents/contexts in device memory between
        bursts. Rebuilt by ``_ensure_state`` on the next admission (the
        compiled step program itself stays in the bounded loop-jit cache).
        Also resets the cond mode: the next burst re-enters shared-cond
        from scratch."""
        self._x = self._xe = self._h1 = self._h2 = None
        self._ctx = self._uctx = self._kw = self._ukw = None
        self._cond_mode = None
        self._ctx_ref = self._uctx_ref = None
        self._ctx_dev = self._uctx_dev = None
        self._kw_mode = None
        self._kw_ref = self._ukw_ref = None
        self._kw_dev = self._ukw_dev = None
        # Capability overlays drop with the state: the next burst re-enters
        # the overlay-free (cheapest) program variant from scratch.
        self._mask = self._mask_init = self._mask_noise = None
        self._mask_has = np.zeros(self.width, bool)
        self._mc_k = None
        self._mc_has_y = False
        self._mc_w0 = self._mc_ctx = self._mc_w = self._mc_y = None
        self._mc_win = None
        self._ctrl = None
        self._ctrl_hint = None
        self._ctrl_strength = np.zeros(self.width, np.float32)
        self._ctrl_win = np.tile(
            np.asarray([0.0, 1.0], np.float32), (self.width, 1)
        )
        self._lora_sig = ()
        self._lora_rmax = 0
        self._lora_ab = []
        self._program = None

    def _gauges(self) -> None:
        registry.gauge("pa_serving_occupancy", len(self.active_lanes()),
                       labels=self._labels,
                       help="live lanes in the bucket's step batch")
        registry.gauge("pa_serving_queue_depth", len(self.queue),
                       labels=self._labels,
                       help="requests waiting for a lane")

    # -- state assembly -----------------------------------------------------

    def _zeros_stack(self, template):
        """[W, *template.shape] zeros matching the template's dtype, lane-axis
        sharded when the bucket runs over a mesh (composes with the chain's
        data sharding: the lane axis IS the batch axis the orchestrator
        shards)."""
        import jax

        jnp = self._jnp

        def leaf(l):
            z = jnp.zeros((self.width,) + tuple(l.shape), l.dtype)
            if self.spec is not None and self.spec.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                z = jax.device_put(
                    z, NamedSharding(self.spec.mesh, P(self.spec.data_axis))
                )
            return z

        return jax.tree.map(leaf, template)

    def _ensure_state(self, req: ServeRequest) -> None:
        if self.spec is None or self._x is not None:
            return
        self._x = self._zeros_stack(req.x)
        self._xe = self._zeros_stack(req.x)
        self._h1 = self._zeros_stack(req.x)
        self._h2 = self._zeros_stack(req.x)
        # Denoise-mask stacks are always-on (the mask axis has no program
        # variant): zero rows + a zero host gate make maskless lanes a
        # structural where-pass-through inside the program.
        self._mask = self._zeros_stack(
            self._jnp.zeros(req.x.shape, self._jnp.float32)
        )
        self._mask_init = self._zeros_stack(req.x)
        self._mask_noise = self._zeros_stack(req.x)
        # Traced-kwargs stacks build lazily: a fresh epoch enters SHARED
        # kwargs mode (_seat_kwargs), so the [W, ...] stacks only exist
        # after a foreign-kwargs demotion.
        if req.prediction != "flow":
            acp = req.acp if req.acp is not None else self._default_schedule()
            self._log_sigmas = self._jnp.log(self._model_sigmas(acp))
        # Program meta (bucket-key constants) banked once; the program
        # itself builds lazily per cond mode (_ensure_program) — a
        # shared→stacked demotion swaps the broadcast_cond variant, and
        # both live in the bounded loop-jit cache.
        self._emit_stats = numerics.on()
        self._prog_kw = dict(
            prediction=req.prediction,
            use_cfg=req.uncond_context is not None and req.cfg_scale != 1.0,
            cfg_rescale=req.cfg_rescale,
            static_kwargs=req.static_kwargs,
        )

    def _ensure_program(self) -> None:
        if self._program is not None or self.spec is None:
            return
        from ..sampling.compiled import lane_step_program

        self._program = lane_step_program(
            self.spec,
            emit_stats=self._emit_stats,
            broadcast_cond=self._cond_mode == "shared",
            broadcast_kwargs=self._kw_mode == "shared",
            n_extra=self._mc_k,
            mc_has_y=self._mc_has_y,
            control_apply=None if self._ctrl is None else self._ctrl["apply"],
            lora_sig=self._lora_sig,
            **self._prog_kw,
        )

    def _place_shared(self, arr):
        """The shared cond tensor as the program input: replicated over the
        mesh when the bucket runs on one (the lane-axis sharding belongs to
        the state stacks; the broadcast happens inside the program)."""
        if arr is None:
            return None
        if self.spec is not None and self.spec.mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            return jax.device_put(arr, NamedSharding(self.spec.mesh, P()))
        return arr

    def _seat_cond(self, i: int, req: ServeRequest) -> None:
        """Seat lane ``i``'s conditioning. Fresh epochs (no other live lane)
        enter SHARED mode: the request's cond objects become the bucket's
        refs and every sibling whose cond is the SAME object (the embed
        cache's same-prompt aliasing) rides the broadcast program. The
        first foreign cond demotes to STACKED per-lane rows — re-filling
        the seated siblings' rows from the shared refs, so demotion is a
        mode change, never a value change."""
        others = [j for j in self.active_lanes() if j != i]
        if not others:
            self._cond_mode = "shared"
            self._ctx_ref = req.context
            self._uctx_ref = req.uncond_context
            self._ctx_dev = self._place_shared(req.context)
            self._uctx_dev = self._place_shared(req.uncond_context)
            self._ctx = self._uctx = None
            self._program = None
            return
        if self._cond_mode == "shared":
            if req.context is self._ctx_ref \
                    and req.uncond_context is self._uctx_ref:
                registry.counter(
                    "pa_serving_shared_cond_seats_total",
                    labels=self._labels,
                    help="lanes seated against an already-shared cond "
                         "tensor (sibling-seed reuse)",
                )
                return
            self._cond_mode = "stacked"
            self._ctx = (
                None if self._ctx_ref is None
                else self._zeros_stack(self._ctx_ref)
            )
            self._uctx = (
                None if self._uctx_ref is None
                else self._zeros_stack(self._uctx_ref)
            )
            for j in others:
                if self._ctx is not None:
                    self._ctx = self._ctx.at[j].set(self.lanes[j].req.context)
                if self._uctx is not None:
                    self._uctx = self._uctx.at[j].set(
                        self.lanes[j].req.uncond_context
                    )
            self._ctx_ref = self._uctx_ref = None
            self._ctx_dev = self._uctx_dev = None
            self._program = None
        if self._ctx is not None:
            self._ctx = self._ctx.at[i].set(req.context)
        if self._uctx is not None:
            self._uctx = self._uctx.at[i].set(req.uncond_context)

    def _place_shared_tree(self, tree):
        if not tree:
            return None
        import jax

        return jax.tree.map(self._place_shared, tree)

    @staticmethod
    def _same_tree(a, b) -> bool:
        """Leaf-for-leaf OBJECT identity — the sharing signal (the embed
        cache / node layer hands siblings the same arrays)."""
        if a is b:
            return True
        if a is None or b is None:
            return False
        import jax

        la, ta = jax.tree.flatten(a)
        lb, tb = jax.tree.flatten(b)
        return ta == tb and all(x is y for x, y in zip(la, lb))

    def _seat_kwargs(self, i: int, req: ServeRequest) -> None:
        """Seat lane ``i``'s traced kwargs under the same shared/stacked
        state machine as ``_seat_cond`` (PR 12 remainder): fresh epochs
        share the request's kwarg trees — ``traced_kwargs`` AND the
        negative-prompt/uncond ``u_traced`` — as ONE broadcast program
        input; the first seat whose trees are not the same objects
        leaf-for-leaf demotes to stacked per-lane rows, refilled from the
        seated siblings' own requests (a mode change, never a value
        change)."""
        import jax

        kw = req.traced_kwargs or None
        ukw = req.u_traced or None
        others = [j for j in self.active_lanes() if j != i]
        if not others:
            self._kw_mode = "shared"
            self._kw_ref = kw
            self._ukw_ref = ukw
            self._kw_dev = self._place_shared_tree(kw)
            self._ukw_dev = self._place_shared_tree(ukw)
            self._kw = self._ukw = None
            self._program = None
            return
        if self._kw_mode == "shared":
            if self._same_tree(kw, self._kw_ref) \
                    and self._same_tree(ukw, self._ukw_ref):
                registry.counter(
                    "pa_serving_shared_kwargs_seats_total",
                    labels=self._labels,
                    help="lanes seated against already-shared traced "
                         "kwargs (sibling-seed reuse, uncond included)",
                )
                return
            self._kw_mode = "stacked"
            self._kw = (
                None if self._kw_ref is None
                else self._zeros_stack(self._kw_ref)
            )
            self._ukw = (
                None if self._ukw_ref is None
                else self._zeros_stack(self._ukw_ref)
            )
            for j in others:
                jr = self.lanes[j].req
                if self._kw is not None:
                    self._kw = jax.tree.map(
                        lambda stack, v, _j=j: stack.at[_j].set(v),
                        self._kw, jr.traced_kwargs,
                    )
                if self._ukw is not None:
                    self._ukw = jax.tree.map(
                        lambda stack, v, _j=j: stack.at[_j].set(v),
                        self._ukw, jr.u_traced,
                    )
            self._kw_ref = self._ukw_ref = None
            self._kw_dev = self._ukw_dev = None
            self._program = None
        if self._kw is not None:
            self._kw = jax.tree.map(
                lambda stack, v: stack.at[i].set(v),
                self._kw, req.traced_kwargs,
            )
        if self._ukw is not None:
            self._ukw = jax.tree.map(
                lambda stack, v: stack.at[i].set(v), self._ukw, req.u_traced
            )

    # -- capability overlays (round 16) -------------------------------------

    def _mc_map(self, req: ServeRequest, w):
        """One cond's weight (scalar / [1,H,W,1] / [b,H,W,1] from
        ``area_weight``) materialized to the bucket's FIXED full per-sample
        map shape — [b, *spatial, 1] for 4-D latents, [b, 1, ...] otherwise —
        so scalar-weight and masked lanes share one stack."""
        jnp = self._jnp
        b = req.x.shape[0]
        if req.x.ndim == 4:
            tgt = (b,) + tuple(req.x.shape[1:-1]) + (1,)
        else:
            tgt = (b,) + (1,) * (req.x.ndim - 1)
        return jnp.broadcast_to(jnp.asarray(w, jnp.float32), tgt)

    def _ensure_mc(self, req: ServeRequest) -> None:
        """Materialize / grow the multi-cond overlay (bucket-key discipline:
        the extra count K only grows within an epoch — pad-to-max — and the
        pooled-y leg switches on at most once; either change swaps the
        program variant and refills every seated lane's rows from its own
        request, a mode change never a value change)."""
        k_req = len(req.extra_conds or ())
        if not k_req and self._mc_k is None:
            return
        need_y = self._mc_has_y or any(
            e.get("pooled") is not None for e in (req.extra_conds or ())
        )
        if self._mc_k is not None and k_req <= self._mc_k \
                and need_y == self._mc_has_y:
            return
        jnp = self._jnp
        k_new = max(k_req, self._mc_k or 0)
        map_t = self._mc_map(req, jnp.float32(0.0))
        self._mc_w0 = self._zeros_stack(map_t)
        self._mc_w = self._zeros_stack(
            jnp.zeros((k_new,) + tuple(map_t.shape), jnp.float32)
        )
        self._mc_ctx = self._zeros_stack(
            jnp.zeros((k_new,) + tuple(req.context.shape), req.context.dtype)
        )
        self._mc_y = None
        if need_y:
            y = req.traced_kwargs["y"]
            self._mc_y = self._zeros_stack(
                jnp.zeros((k_new,) + tuple(y.shape), y.dtype)
            )
        self._mc_win = np.zeros((self.width, k_new, 2), np.float32)
        self._mc_win[:, :, 1] = 1.0
        self._mc_k, self._mc_has_y = k_new, need_y
        self._program = None
        for j in self.active_lanes():
            self._write_mc_row(j, self.lanes[j].req)

    def _write_mc_row(self, i: int, req: ServeRequest) -> None:
        """Lane ``i``'s multi-cond rows: primary weight map + per-extra
        (cond rows, weight map, pooled row, progress window), zero rows /
        identity windows for non-carrying lanes AND for pad slots beyond the
        lane's own extra count — a reused slot never inherits its
        predecessor's maps."""
        if self._mc_k is None:
            return
        jnp = self._jnp
        self._mc_w0 = self._mc_w0.at[i].set(0.0)
        self._mc_w = self._mc_w.at[i].set(0.0)
        self._mc_ctx = self._mc_ctx.at[i].set(0.0)
        if self._mc_y is not None:
            self._mc_y = self._mc_y.at[i].set(0.0)
        self._mc_win[i, :, 0] = 0.0
        self._mc_win[i, :, 1] = 1.0
        extras = req.extra_conds or ()
        if not extras:
            return
        from ..sampling.k_samplers import area_weight, broadcast_cond_batch

        b = req.x.shape[0]
        self._mc_w0 = self._mc_w0.at[i].set(self._mc_map(req, area_weight(
            req.cond_area, req.cond_strength, req.x.shape,
            mask=req.cond_mask, mask_strength=req.cond_mask_strength,
            area_pct=req.cond_area_pct,
        )))
        y_fill = (req.traced_kwargs or {}).get("y")
        for k, e in enumerate(extras):
            self._mc_ctx = self._mc_ctx.at[i, k].set(
                broadcast_cond_batch(e["context"], b)
            )
            self._mc_w = self._mc_w.at[i, k].set(self._mc_map(
                req, area_weight(
                    e.get("area"), float(e.get("strength", 1.0)), req.x.shape,
                    mask=e.get("mask"),
                    mask_strength=float(e.get("mask_strength", 1.0)),
                    area_pct=e.get("area_pct"),
                )
            ))
            tr = e.get("timestep_range")
            if tr is not None:
                self._mc_win[i, k] = (float(tr[0]), float(tr[1]))
            if self._mc_y is not None:
                pooled = e.get("pooled")
                y_row = y_fill if pooled is None else broadcast_cond_batch(
                    pooled, b
                )
                if y_row is not None:
                    self._mc_y = self._mc_y.at[i, k].set(
                        jnp.broadcast_to(
                            jnp.asarray(y_row), self._mc_y.shape[2:]
                        )
                    )

    def _ctrl_hint_norm(self, req: ServeRequest):
        """apply_control's hint normalization, host-side at seat: rank-4,
        repeated to the request batch, bilinear-resized to 8× the latent
        grid (models/controlnet.py apply does the same ops in-graph; the
        scheduler's eligibility check already rejected per-sample hint
        batches, mirroring apply_control's guard)."""
        import jax

        jnp = self._jnp
        hint = jnp.asarray(req.control["hint"], jnp.float32)
        if hint.ndim == 3:
            hint = hint[None]
        b = req.x.shape[0]
        if hint.shape[0] != b:
            hint = jnp.repeat(hint[:1], b, axis=0)
        want = (req.x.shape[1] * 8, req.x.shape[2] * 8)
        if hint.shape[1:3] != want:
            hint = jax.image.resize(
                hint, (b, *want, hint.shape[-1]), method="bilinear"
            )
        return hint

    def _ensure_ctrl(self, req: ServeRequest) -> None:
        """Materialize the ControlNet overlay on the first carrying seat:
        ONE control-trunk identity per bucket epoch (conflicting nets are
        bounced to inline at admission, before any state mutates)."""
        if req.control is None or self._ctrl is not None:
            return
        params = req.control["params"]
        placed = self._place_shared_tree(params)
        self._ctrl = {
            "apply": req.control["apply"],
            "params_ref": params,
            "params": params if placed is None else placed,
        }
        self._ctrl_hint = self._zeros_stack(self._ctrl_hint_norm(req))
        self._ctrl_strength = np.zeros(self.width, np.float32)
        self._ctrl_win = np.tile(
            np.asarray([0.0, 1.0], np.float32), (self.width, 1)
        )
        self._program = None

    def _ctrl_conflict(self, req: ServeRequest) -> bool:
        """True when the request carries a DIFFERENT control trunk than the
        one this bucket epoch already runs (identity on apply + params)."""
        return (
            self.spec is not None
            and req.control is not None
            and self._ctrl is not None
            and (req.control["apply"] is not self._ctrl["apply"]
                 or req.control["params"] is not self._ctrl["params_ref"])
        )

    def _ensure_lora(self, req: ServeRequest) -> None:
        """Materialize / grow the LoRA overlay: the target-path union and
        rank max only grow within an epoch; a growth rebuilds the factor
        stacks (zero-padded) and refills every seated lane's rows — rank
        padding is structural (zero slots give a bitwise-zero delta)."""
        if not req.lora:
            return
        from ..models.lora import get_path

        jnp = self._jnp
        paths = sorted(set(req.lora) | {p for (p, _, _) in self._lora_sig})
        r_req = max(int(a.shape[0]) for (a, _b) in req.lora.values())
        r_new = max(r_req, self._lora_rmax)
        if tuple(p for (p, _, _) in self._lora_sig) == tuple(paths) \
                and r_new == self._lora_rmax:
            return
        sig = []
        for p in paths:
            w = get_path(self.spec.params, p)
            # nd targets (head-split attention kernels, conv): the factor
            # pair addresses the (shape[0], prod(rest)) flattening and the
            # merge reshapes the delta back (models/lora.py contract).
            sig.append((p, int(w.shape[0]),
                        int(math.prod(w.shape[1:]))))
        self._lora_sig = tuple(sig)
        self._lora_rmax = r_new
        self._lora_ab = [
            (self._zeros_stack(jnp.zeros((r_new, k), jnp.float32)),
             self._zeros_stack(jnp.zeros((m, r_new), jnp.float32)))
            for (_p, m, k) in sig
        ]
        self._program = None
        for j in self.active_lanes():
            self._write_lora_row(j, self.lanes[j].req)

    def _write_lora_row(self, i: int, req: ServeRequest) -> None:
        if not self._lora_sig:
            return
        from ..models.lora import pad_rank

        factors = req.lora or {}
        for idx, (path, _m, _k) in enumerate(self._lora_sig):
            a_s, b_s = self._lora_ab[idx]
            pair = factors.get(path)
            if pair is None:
                a_s, b_s = a_s.at[i].set(0.0), b_s.at[i].set(0.0)
            else:
                a_, b_ = pad_rank(
                    self._jnp.asarray(pair[0], a_s.dtype),
                    self._jnp.asarray(pair[1], b_s.dtype),
                    self._lora_rmax,
                )
                a_s, b_s = a_s.at[i].set(a_), b_s.at[i].set(b_)
            self._lora_ab[idx] = (a_s, b_s)

    def _seat_caps(self, i: int, req: ServeRequest) -> None:
        """Seat lane ``i``'s capability state. The mask axis is always-on
        (row writes + a host gate flag); the other overlays materialize on
        the first carrying seat. A reused slot ALWAYS rewrites its rows in
        every active overlay, so a lane can never inherit its predecessor's
        factors/hints/maps."""
        jnp = self._jnp
        kinds = []
        if req.latent_mask is not None:
            self._mask = self._mask.at[i].set(jnp.broadcast_to(
                jnp.asarray(req.latent_mask, jnp.float32), req.x.shape
            ))
            self._mask_init = self._mask_init.at[i].set(
                jnp.broadcast_to(jnp.asarray(req.mask_init), req.x.shape)
                .astype(self._mask_init.dtype)
            )
            self._mask_noise = self._mask_noise.at[i].set(
                jnp.broadcast_to(jnp.asarray(req.mask_noise), req.x.shape)
                .astype(self._mask_noise.dtype)
            )
            self._mask_has[i] = True
            kinds.append("img2img_mask")
        else:
            # Gate off suffices: the program's where-select never reads a
            # zero-gated lane's mask rows, so no device clear is needed.
            self._mask_has[i] = False
        if req.extra_conds:
            kinds.append("multi_cond")
        self._ensure_mc(req)
        self._write_mc_row(i, req)
        if req.control is not None:
            self._ensure_ctrl(req)
            kinds.append("controlnet")
        if self._ctrl is not None:
            if req.control is not None:
                self._ctrl_hint = self._ctrl_hint.at[i].set(
                    self._ctrl_hint_norm(req)
                )
                self._ctrl_strength[i] = float(req.control["strength"])
                self._ctrl_win[i] = (
                    float(req.control["start"]), float(req.control["end"])
                )
            else:
                # Zero gain → exact zero residual trees (additive no-op);
                # a stale hint row only ever feeds the zeroed trunk output.
                self._ctrl_strength[i] = 0.0
                self._ctrl_win[i] = (0.0, 1.0)
        if req.lora:
            self._ensure_lora(req)
            kinds.append("lora")
        self._write_lora_row(i, req)
        for kind in (kinds or ["txt2img"]):
            registry.counter(
                "pa_serving_lane_capability_total",
                labels={**self._labels, "kind": kind},
                help="lanes seated, by capability carried (a multi-"
                     "capability lane counts once per capability; plain "
                     "lanes count as txt2img)",
            )

    def _set_lane(self, i: int, req: ServeRequest) -> bool:
        import jax

        if self._ctrl_conflict(req):
            # One control trunk per bucket epoch: a different net cannot
            # join this program — bounce to the inline path (the runner
            # catches DegradedToInline and falls back) BEFORE any stacked
            # state mutates.
            from ..utils.degrade import DegradedToInline

            req.resolve(error=DegradedToInline(
                f"bucket {self.label} already carries a different "
                "ControlNet this epoch; re-submit inline"
            ))
            registry.counter(
                "pa_serving_ctrl_conflict_total", labels=self._labels,
                help="seats bounced to inline: a second ControlNet identity "
                     "arrived within one bucket epoch",
            )
            return False
        self._ensure_state(req)
        lane = _Lane(req)
        # The lane's whole schedule compiles to an eval-ordered plan list at
        # seat time (host float64 — one pass per request, not per dispatch);
        # stochastic lanes also bank their fold_in key table here.
        lane.plans = plan_schedule(req.sampler, req.sigmas, req.prediction)
        spec_entry = LANE_SPECS[req.sampler]
        if spec_entry.needs_rng:
            lane.keys = _lane_key_table(
                req.rng, req.n_steps, spec_entry.split_keys
            )
        if self.spec is not None:
            # State-pytree init: latent and eval input seed from the request,
            # history slots zero — a reused lane must never see its
            # predecessor's carries.
            self._x = self._x.at[i].set(req.x)
            self._xe = self._xe.at[i].set(req.x)
            self._h1 = self._h1.at[i].set(0.0)
            self._h2 = self._h2.at[i].set(0.0)
            self._seat_cond(i, req)
            self._seat_kwargs(i, req)
            self._seat_caps(i, req)
        else:
            from ..sampling.k_samplers import EpsDenoiser

            jnp = self._jnp
            lane.x_eager = req.x
            lane.xe_eager = req.x
            lane.h1_eager = jnp.zeros_like(req.x)
            lane.h2_eager = jnp.zeros_like(req.x)
            # Width-1 eager capability twin: multi-cond rides the denoiser's
            # own _combine_conds; ControlNet/LoRA ride the pre-merged
            # ``eager_model``; the denoise mask is a post-completion blend
            # in dispatch() (the masked_callback formula).
            model_lane = (
                req.eager_model if req.eager_model is not None else self.model
            )
            lane.denoiser = EpsDenoiser(
                model_lane, req.context, cfg_scale=req.cfg_scale,
                uncond_context=req.uncond_context,
                uncond_kwargs=req.uncond_kwargs,
                alphas_cumprod=req.acp, prediction=req.prediction,
                cfg_rescale=req.cfg_rescale,
                extra_conds=req.extra_conds or None,
                cond_area=req.cond_area, cond_area_pct=req.cond_area_pct,
                cond_mask=req.cond_mask, cond_strength=req.cond_strength,
                cond_mask_strength=req.cond_mask_strength,
                **req.traced_kwargs, **req.static_kwargs,
            )
        self.lanes[i] = lane
        return True

    # -- scheduling ---------------------------------------------------------

    def admit(self, now: float | None = None) -> int:
        """Fill free lanes from the waiting line (policy order), resolving
        expired/cancelled entries instead of seating them. Returns how many
        joined — always at a step boundary (the dispatcher calls this between
        dispatches, never mid-step)."""
        now = time.monotonic() if now is None else now
        for req in self.queue.expired(now):
            req.resolve(error=DeadlineExceeded(
                f"deadline passed after {now - req.submit_ts:.3f}s waiting"
            ))
            registry.counter("pa_serving_expired_total", labels=self._labels)
        joined = 0
        for i in range(self.width):
            if self.lanes[i] is not None:
                continue
            req = self.queue.pop()
            if req is None:
                break
            if req.cancelled():
                req.resolve(error=Interrupted("cancelled while queued"))
                registry.counter("pa_serving_cancelled_total", labels=self._labels)
                continue
            if req.deadline is not None and now >= req.deadline:
                # Deadline-vs-admission race: a deadline that lapses between
                # the expired() sweep above and this pop (or was pushed
                # already-expired) must reject with the deadline error, not
                # seat for step 0 — seating would spend a dispatch on work
                # whose client has already given up.
                req.resolve(error=DeadlineExceeded(
                    f"deadline passed after {now - req.submit_ts:.3f}s "
                    "waiting (caught at admission)"
                ))
                registry.counter("pa_serving_expired_total",
                                 labels=self._labels)
                continue
            if not self._set_lane(i, req):
                # Bounced (capability conflict) — the request resolved with
                # DegradedToInline; the slot refills on the next sweep.
                continue
            joined += 1
            registry.histogram(
                "pa_serving_lane_wait_seconds", now - req.submit_ts,
                labels=self._labels,
                help="submit-to-lane admission wait",
            )
            # SLO lane_wait stage: the same clock, bucket-label-free — the
            # decomposition view of the per-bucket histogram above.
            slo.observe_stage("lane_wait", now - req.submit_ts)
            if tracing.on():
                # admission→lane-assign on the submitter's timeline: one
                # completed span from submit to seat (both trace-clock).
                self.lanes[i].seat_us = tracing.now_us()
                if req.trace_submit_us is not None:
                    tracing.record(
                        "lane-wait", req.trace_submit_us,
                        self.lanes[i].seat_us - req.trace_submit_us,
                        cat="serving", tid=req.trace_tid,
                        parent_span_id=req.trace_parent,
                        prompt_id=req.prompt_id, bucket=self.label, lane=i,
                        rid=req.rid, queue_depth=len(self.queue),
                        **({"trace_id": req.trace_id}
                           if req.trace_id else {}),
                    )
        if joined:
            self._gauges()
        return joined

    def _retire(self, i: int, result=None, error=None) -> None:
        lane = self.lanes[i]
        self.lanes[i] = None
        if lane.digests:
            # The lane's per-eval fingerprint stack (numerics sentinel):
            # invariant to occupancy/width/sharding by the digest's
            # construction, so any drift here IS a numerics change.
            numerics.sentinel.record_fingerprints(
                rid=lane.req.rid, sampler=lane.req.sampler, bucket=self.label,
                steps=lane.idx, digests=list(lane.digests),
            )
        if tracing.on() and lane.seat_us:
            # lane-assign→retire on the submitter's timeline; the per-step
            # spans recorded by dispatch() nest inside this interval.
            tracing.record(
                "lane", lane.seat_us, tracing.now_us() - lane.seat_us,
                cat="serving", tid=lane.req.trace_tid,
                parent_span_id=lane.req.trace_parent,
                prompt_id=lane.req.prompt_id, bucket=self.label, lane=i,
                rid=lane.req.rid, steps_run=lane.idx,
                outcome="error" if error is not None else "completed",
                **({"trace_id": lane.req.trace_id}
                   if lane.req.trace_id else {}),
            )
        lane.req.resolve(result=result, error=error)
        registry.counter(
            "pa_serving_cancelled_total" if error is not None
            else "pa_serving_completed_total",
            labels=self._labels,
        )

    def _quarantine(self, i: int, plan: StepPlan, stats_vec, xe_lane,
                    occupancy: int = 0) -> None:
        """Non-finite quarantine (numerics sentinel): retire lane ``i`` via
        the existing select-mask discipline — the stacked state is NOT
        touched, so co-batched neighbors are bit-identical to their solo
        runs by construction — and dump a ``write_postmortem`` bundle whose
        extras name the first non-finite block/step/σ. The block comes from
        :func:`utils.numerics.bisect_nonfinite`: a re-run of the failing
        eval input through the model's PipelineSpec stages (prepare →
        per-block segments → finalize); the step/σ come from the lane's own
        StepPlan — this dispatch IS the first non-finite one, because every
        emitting dispatch is checked."""
        lane = self.lanes[i]
        req = lane.req
        err = numerics.NonFiniteLatent(
            f"lane {i} ({req.sampler}) went non-finite at step {plan.step} "
            f"(σ_eval={plan.sigma_eval:.6g}) in bucket {self.label}; lane "
            f"quarantined, postmortem bundle written"
        )
        forensics = {
            "bucket": self.label, "lane": i, "rid": req.rid,
            "sampler": req.sampler, "step": int(plan.step),
            "sigma": float(plan.sigma_eval), "pc": lane.pc,
            "occupancy": occupancy, "prompt_id": req.prompt_id,
            "stats": numerics.stats_to_dict(stats_vec),
        }
        log_sig = self._log_sigmas
        if log_sig is None and lane.denoiser is not None:
            log_sig = getattr(lane.denoiser, "log_sigmas", None)
        try:
            bisect = numerics.bisect_nonfinite(
                self.model, xe_lane, plan.sigma_eval, req.prediction,
                log_sig, req.context,
                {**req.traced_kwargs, **req.static_kwargs},
            )
        except Exception as e:  # noqa: BLE001 — forensics never blocks retire
            bisect = {"block": None, "bisect_error": f"{type(e).__name__}: {e}"}
        forensics["first_nonfinite"] = {
            "step": int(plan.step), "sigma": float(plan.sigma_eval), **bisect,
        }
        bundle = None
        try:
            from ..utils.telemetry import write_postmortem

            bundle = write_postmortem(
                f"numerics-{self.label}-lane{i}", error=err, extra=forensics
            )
        except Exception:  # noqa: BLE001
            pass
        numerics.sentinel.record_event(
            "serving-lane", bucket=self.label, lane=i, step=int(plan.step),
            sampler=req.sampler,
        )
        numerics.sentinel.record_quarantine(**forensics, bundle=bundle)
        self._retire(i, error=err)

    def sweep_cancelled(self) -> int:
        """Retire lanes whose request was cancelled (client cancel, per-prompt
        interrupt, deadline) — frees the slot at the boundary WITHOUT touching
        the stacked state: the lane goes inactive-masked, so neighbors are
        untouched by construction."""
        now = time.monotonic()
        swept = 0
        for i in self.active_lanes():
            req = self.lanes[i].req
            if req.cancelled():
                self._retire(i, error=Interrupted(
                    f"cancelled mid-batch at step {self.lanes[i].idx}"
                ))
                swept += 1
            elif req.deadline is not None and now >= req.deadline:
                self._retire(i, error=DeadlineExceeded(
                    f"deadline passed at step {self.lanes[i].idx}"
                ))
                swept += 1
        if swept:
            self._gauges()
        return swept

    def dispatch(self) -> bool:
        """Run ONE lockstep model eval for every active lane (one compiled
        dispatch in program mode), apply each lane's own sampler update,
        advance per-lane plan counters, fire per-lane progress hooks at
        σ-interval boundaries, retire finished lanes. Returns False when
        there was nothing to run."""
        active = self.active_lanes()
        if not active:
            return False
        import jax

        jnp = self._jnp
        t0_us = tracing.now_us() if tracing.on() else 0.0
        t0 = time.perf_counter()
        plans = {i: self.lanes[i].plan() for i in active}
        # Numerics sentinel (utils/numerics.py): (stats, digests, xe-of-lane)
        # when this dispatch emitted them — read below, AFTER the block the
        # dispatch already performs AND after the step clock stops, so the
        # sentinel adds no sync of its own and its (tiny) device→host stats
        # readback never lands in pa_serving_step_seconds (the host-sync
        # discipline palint enforces: this window is timed).
        quarantine_src = None
        stats_dev = None      # program mode: deferred (st, dg, xe_of) refs
        eager_stats = None    # eager mode: deferred xe-inputs map
        if self.spec is not None:
            self._ensure_program()
            sig = np.ones((self.width,), np.float32)
            act = np.zeros((self.width,), np.float32)
            cfg = np.ones((self.width,), np.float32)
            coef = np.broadcast_to(
                _IDENTITY_COEF, (self.width, 4, 6)
            ).copy()
            key_width = next(
                (self.lanes[i].keys.shape[-1] for i in active
                 if self.lanes[i].keys is not None), 2,
            )
            keys = np.zeros((self.width, key_width), np.uint32)
            # Denoise-mask mix (always-on capability axis): per dispatch,
            # per lane, (gate, keep_a, keep_b) — gate only on σ-interval
            # completion of a masked lane; the keep coefficients are the
            # masked_callback formula per prediction family at the lane's
            # own σ_next (eps/v: init + σ'·noise; flow: (1−σ')·init +
            # σ'·noise). All-zero rows make the blend a structural no-op.
            mask_mix = np.zeros((self.width, 3), np.float32)
            for i in active:
                lane, plan = self.lanes[i], plans[i]
                sig[i] = plan.sigma_eval
                act[i] = 1.0
                cfg[i] = lane.req.cfg_scale
                coef[i] = plan.coef
                row = _noise_key_row(lane, plan)
                if row is not None:
                    keys[i] = row
                if self._mask_has[i] and plan.completes:
                    # palint: allow[host-sync] req.sigmas is host-side
                    # np.ndarray by ServeRequest contract — no device sync
                    s_next = float(lane.req.sigmas[plan.step + 1])
                    if lane.req.prediction == "flow":
                        mask_mix[i] = (1.0, 1.0 - s_next, s_next)
                    else:
                        mask_mix[i] = (1.0, 1.0, s_next)
            xe_prev = None
            if self._emit_stats:
                inj = numerics.take_injection(active)
                if inj is not None:
                    # PA_FAIL_INJECT=nan:<lane> rehearsal: poison ONE element
                    # of the seated lane's next eval input, once — the
                    # quarantine path below must catch it at this dispatch.
                    idx = (inj,) + (0,) * (self._xe.ndim - 1)
                    self._xe = self._xe.at[idx].set(jnp.nan)
                # emit mode keeps xe UNdonated (lane_step_program) so the
                # failing eval input survives for the per-block bisection.
                xe_prev = self._xe
            shared = self._cond_mode == "shared"
            ctx_arg = self._ctx_dev if shared else self._ctx
            uctx_arg = self._uctx_dev if shared else self._uctx
            if shared:
                registry.counter(
                    "pa_serving_cond_broadcast_total", labels=self._labels,
                    help="dispatches whose cond rode the lane axis as ONE "
                         "broadcast tensor (sibling-seed sharing)",
                )
            kw_shared = self._kw_mode == "shared"
            kw_arg = self._kw_dev if kw_shared else self._kw
            ukw_arg = self._ukw_dev if kw_shared else self._ukw
            if kw_shared and (self._kw_ref is not None
                              or self._ukw_ref is not None):
                registry.counter(
                    "pa_serving_kwargs_broadcast_total", labels=self._labels,
                    help="dispatches whose traced kwargs (uncond extras "
                         "included) rode the lane axis as ONE broadcast "
                         "tree (sibling-seed sharing)",
                )
            # Capability overlay inputs (only the materialized ones — the
            # program variant was built with the matching signature).
            cap_kw = {}
            if self._mc_k is not None:
                cap_kw.update(
                    mc_w0=self._mc_w0, mc_ctx=self._mc_ctx, mc_w=self._mc_w,
                    mc_win=jnp.asarray(self._mc_win), mc_y=self._mc_y,
                )
            if self._ctrl is not None:
                cap_kw.update(
                    ctrl_params=self._ctrl["params"],
                    ctrl_hint=self._ctrl_hint,
                    ctrl_strength=jnp.asarray(self._ctrl_strength),
                    ctrl_win=jnp.asarray(self._ctrl_win),
                )
            if self._lora_sig:
                cap_kw["lora_ab"] = tuple(
                    (a_s, b_s) for (a_s, b_s) in self._lora_ab
                )
            outs = self._program(
                self.spec.params, self._x, self._xe, self._h1, self._h2,
                jnp.asarray(sig), jnp.asarray(act), jnp.asarray(cfg),
                jnp.asarray(coef), jnp.asarray(keys),
                ctx_arg, uctx_arg, kw_arg, ukw_arg, self._log_sigmas,
                self._mask, self._mask_init, self._mask_noise,
                jnp.asarray(mask_mix), **cap_kw,
            )
            if self._emit_stats:
                (self._x, self._xe, self._h1, self._h2, st_dev, dg_dev) = outs
            else:
                self._x, self._xe, self._h1, self._h2 = outs
            # palint: allow[host-sync] the completion boundary: the step
            # histogram must include device time (the StepTimer discipline)
            jax.block_until_ready(self._x)
            if self._emit_stats:
                stats_dev = (st_dev, dg_dev, lambda i, _xe=xe_prev: _xe[i])
        else:
            # Width-1 eager mode (streaming/hybrid models): the SAME StepPlan
            # walk against the lane's own denoiser — full sampler family,
            # one model call per eval.
            emit_eager = numerics.on()
            xe_inputs: dict[int, Any] = {}
            if emit_eager:
                inj = numerics.take_injection(active)
                if inj is not None:
                    lane0 = self.lanes[inj]
                    idx = (0,) * lane0.xe_eager.ndim
                    lane0.xe_eager = lane0.xe_eager.at[idx].set(jnp.nan)
            for i in active:
                lane, plan = self.lanes[i], plans[i]
                if emit_eager:
                    xe_inputs[i] = lane.xe_eager
                x0e = lane.denoiser(
                    lane.xe_eager, jnp.float32(plan.sigma_eval)
                )
                row = _noise_key_row(lane, plan)
                noise = None
                if row is not None:
                    noise = jax.random.normal(
                        jax.random.wrap_key_data(jnp.asarray(row)),
                        lane.x_eager.shape, lane.x_eager.dtype,
                    )
                basis = (lane.x_eager, lane.xe_eager, x0e,
                         lane.h1_eager, lane.h2_eager, noise)

                def _combine(row_c, like):
                    acc = None
                    for c, term in zip(row_c, basis):
                        if float(c) == 0.0 or term is None:
                            continue
                        part = float(c) * term
                        acc = part if acc is None else acc + part
                    if acc is None:
                        return jnp.zeros_like(like)
                    return acc.astype(like.dtype)

                lane.x_eager, lane.xe_eager, lane.h1_eager, lane.h2_eager = (
                    _combine(plan.coef[0], lane.x_eager),
                    _combine(plan.coef[1], lane.xe_eager),
                    _combine(plan.coef[2], lane.h1_eager),
                    _combine(plan.coef[3], lane.h2_eager),
                )
                if plan.completes and lane.req.latent_mask is not None:
                    # Eager twin of the program's mask_mix blend: re-pin the
                    # keep region on σ-interval completion (histories stay
                    # untouched, as inline's post-step callback never sees
                    # sampler history either).
                    rq = lane.req
                    # palint: allow[host-sync] rq.sigmas is host-side
                    # np.ndarray by ServeRequest contract — no device sync
                    s_next = float(rq.sigmas[plan.step + 1])
                    if rq.prediction == "flow":
                        keep = (
                            (1.0 - s_next) * rq.mask_init
                            + s_next * rq.mask_noise
                        )
                    else:
                        keep = rq.mask_init + s_next * rq.mask_noise
                    mk = jnp.asarray(rq.latent_mask, jnp.float32)
                    lane.x_eager = (
                        lane.x_eager * mk + keep * (1.0 - mk)
                    ).astype(lane.x_eager.dtype)
                    lane.xe_eager = (
                        lane.xe_eager * mk + keep * (1.0 - mk)
                    ).astype(lane.xe_eager.dtype)
            # palint: allow[host-sync] the completion boundary: the step
            # histogram must include device time (the StepTimer discipline)
            jax.block_until_ready([self.lanes[i].x_eager for i in active])
            if emit_eager:
                eager_stats = xe_inputs
        dt = time.perf_counter() - t0
        # Sentinel readback AFTER the clock stopped (the outputs are ready —
        # the blocks above — so these transfers cost microseconds and, now,
        # zero booked step time).
        if stats_dev is not None:
            st_dev, dg_dev, xe_of = stats_dev
            # palint: allow[host-sync] stats readback at the boundary —
            # post-block, post-clock; the sentinel adds no sync of its own
            quarantine_src = (np.asarray(st_dev), np.asarray(dg_dev), xe_of)
        elif eager_stats is not None:
            st_rows, dg_rows = {}, {}
            for i in active:
                lane = self.lanes[i]
                # palint: allow[host-sync] stats readback at the boundary —
                # post-block, post-clock; the sentinel adds no sync of its own
                st_rows[i] = np.asarray(numerics.lane_stats(
                    lane.x_eager[None], extra=lane.xe_eager[None]
                ))[0]
                # palint: allow[host-sync] digest readback, same boundary
                dg_rows[i] = int(np.asarray(numerics.digest(lane.x_eager)))
            quarantine_src = (
                st_rows, dg_rows, lambda i, _xs=eager_stats: _xs[i]
            )
        self.dispatch_count += 1
        registry.counter("pa_serving_dispatch_total", labels=self._labels,
                         help="compiled lockstep step dispatches")
        registry.counter("pa_serving_lane_steps_total", inc=len(active),
                         labels=self._labels,
                         help="lane-steps served (occupancy summed over "
                              "dispatches) — amortization numerator")
        record_dispatch_occupancy(len(active))
        registry.histogram("pa_serving_step_seconds", dt, labels=self._labels,
                           help="wall time of one lockstep dispatch")
        if tracing.on() and t0_us:
            # (t0_us guards the enable-raced-mid-dispatch case: never emit a
            # span whose start predates the trace.)
            dur_us = tracing.now_us() - t0_us
            # One dispatcher-side span (per-dispatch occupancy + masked-lane
            # count) ...
            tracing.record(
                "serving-dispatch", t0_us, dur_us, cat="serving",
                bucket=self.label, occupancy=len(active),
                masked_lanes=self.width - len(active), width=self.width,
            )
            # ... and one step span per live lane on its OWN prompt's
            # timeline (the submitter is blocked in result() for exactly this
            # interval, so per-tid nesting holds). The dispatch already
            # blocked on the step output above — the duration is honest, and
            # tracing added no sync of its own.
            for i in active:
                lane = self.lanes[i]
                tracing.record(
                    "step", t0_us, dur_us, cat="serving",
                    tid=lane.req.trace_tid, prompt_id=lane.req.prompt_id,
                    parent_span_id=lane.req.trace_parent,
                    bucket=self.label, lane=i, step=lane.idx + 1,
                    of=lane.req.n_steps, occupancy=len(active),
                    **({"trace_id": lane.req.trace_id}
                       if lane.req.trace_id else {}),
                )
        if quarantine_src is not None:
            # Sentinel boundary: the per-lane stats/digests this dispatch
            # emitted (surfaced at the same boundary the progress hooks
            # fire). A non-finite lane is quarantined BEFORE its plan
            # counter advances — its slot goes inactive-masked (the select
            # discipline), so survivors stay bit-identical to solo runs.
            st, dg, xe_of = quarantine_src
            for i in active:
                lane = self.lanes[i]
                lane.digests.append(int(dg[i]))
                # palint: allow[host-sync] st is host-side numpy here
                # (converted once at the post-clock boundary above)
                if float(st[i][0]) > 0:
                    self._quarantine(i, plans[i], st[i], xe_of(i),
                                     occupancy=len(active))
        for i in active:
            lane, plan = self.lanes[i], plans[i]
            if lane is None:
                continue  # quarantined at this boundary — already retired
            lane.pc += 1
            if plan.completes:
                # The σ-interval finished (second-order lanes take two evals
                # to get here) — the progress unit the hooks report.
                lane.idx += 1
                hook = lane.req.progress_hook
                if hook is not None:
                    try:
                        hook(lane.idx, lane.req.n_steps)
                    except Exception:  # noqa: BLE001 — a UI hook must not kill lanes
                        pass
            if lane.done():
                result = (
                    self._x[i] if self.spec is not None else lane.x_eager
                )
                self._retire(i, result=result)
        self._gauges()
        return True
