"""Single sampler dispatch shared by pipelines.py and the TPUKSampler node.

One table, one CFG plumbing, one noise-scaling convention — so a sampler added
here is immediately available to both the Python pipeline API and the node graph
(and they cannot drift apart)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import tracing
from ..utils.degrade import DegradedToInline
from ..utils.metrics import registry

from .ddim import ddim_sample
from .flow import flow_euler_sample, flow_timesteps
from .k_samplers import (
    FLOW_REJECT,
    FLOW_VARIANTS,
    RNG_SAMPLERS,
    SAMPLERS as K_SAMPLERS,
    EpsDenoiser,
    flow_sigma_table,
    host_sigmas,
    make_sigmas,
    takes_fused_step,
)
from .lane_specs import LANE_SPECS

SAMPLER_NAMES = ("ddim", *K_SAMPLERS, "flow_euler")


def _compile_eager_rung(e: BaseException, sampler: str) -> None:
    """Compile-failure ladder (utils/degrade.py): a compile-side error on the
    whole-loop program falls back to the eager per-step loop — the rung is
    recorded and the caller's code FALLS THROUGH to the eager path. Runtime
    errors (incl. OOM, which has its own ladder) re-raise unchanged."""
    from ..utils.degrade import is_compile_failure, record_rung

    if not is_compile_failure(e):
        raise e
    record_rung("compile-eager",
                f"{sampler}: {type(e).__name__}: {e} — eager loop fallback",
                sampler=sampler)


def _compiled_spec(model, callback):
    """TraceSpec for the whole-loop compiled path, or None with a logged reason
    (the caller falls back to the eager per-step loops)."""
    from ..utils import get_logger
    from .compiled import trace_spec_of

    if callback is not None:
        get_logger().info(
            "compile_loop: user callback cannot trace into the loop; eager path"
        )
        return None
    if getattr(model, "is_streaming", False):
        # Weight-streaming models can never be one XLA program (the program
        # would close over the full weight pytree — the allocation streaming
        # exists to avoid). The eager loop is not a degradation here: each
        # denoise step drives the double-buffered per-stage programs
        # (parallel/streaming.py), so streaming survives the full sampler.
        get_logger().info(
            "compile_loop: weight-streaming model — per-stage programs run "
            "inside the eager denoise loop instead"
        )
        return None
    spec = trace_spec_of(model)
    if spec is None:
        get_logger().info(
            "compile_loop: model is not single-program traceable (hybrid chain "
            "or active sequence-parallel context); eager path"
        )
    return spec


def _merge_lora(model, factors):
    """Eager factor merge for inline legs. A ControlNet composition nests its
    base params under "base" while the factor paths address the BASE pytree,
    so recompose around the merged base via the serving delegate instead of
    patching the merged tree."""
    from ..models.lora import lora_model

    delegate = getattr(model, "control_delegate", None)
    if delegate is None:
        return lora_model(model, factors)
    from ..models.api import DiffusionModel
    from ..models.controlnet import apply_control

    return apply_control(
        lora_model(delegate["base"], factors),
        DiffusionModel(apply=delegate["ctrl_apply"],
                       params=delegate["ctrl_params"], name="ctrl"),
        delegate["hint"], delegate["strength"],
        delegate["start"], delegate["end"],
    )


def _traced_sampler_run(fn):
    """Wrap the whole dispatch in a ``sampler-run`` span (utils/tracing.py) —
    the per-prompt timeline node every step/lane-wait span nests under.
    Disabled tracing costs one flag check; ``sampler``/``steps`` are
    keyword-only on run_sampler, so the wrapper reads them from kwargs."""

    @functools.wraps(fn)
    def wrapped(model, noise, context=None, **kwargs):
        if not tracing.on():
            return fn(model, noise, context, **kwargs)
        with tracing.span(
            "sampler-run", cat="sampling",
            sampler=kwargs.get("sampler"), steps=kwargs.get("steps"),
            batch=int(noise.shape[0]) if hasattr(noise, "shape") else None,
        ):
            return fn(model, noise, context, **kwargs)

    return wrapped


@jax.jit
def sampler_mask_blend(x, mask, keep):
    """Inpainting's re-pin of the keep region after a step, as one program."""
    return x * mask + keep * (1.0 - mask)


@_traced_sampler_run
def run_sampler(
    model,
    noise: jnp.ndarray,
    context,
    *,
    sampler: str,
    steps: int,
    cfg_scale: float = 1.0,
    uncond_context=None,
    uncond_kwargs: dict | None = None,
    rng=None,
    karras: bool = True,
    scheduler: str | None = None,
    shift: float = 1.0,
    guidance: float | None = None,
    callback=None,
    init_latent: jnp.ndarray | None = None,
    denoise: float = 1.0,
    latent_mask: jnp.ndarray | None = None,
    prediction: str = "eps",
    cfg_rescale: float = 0.0,
    compile_loop: bool = False,
    sigmas: jnp.ndarray | None = None,
    extra_conds=None,
    cond_area=None,
    cond_area_pct=None,
    cond_mask=None,
    cond_strength: float = 1.0,
    cond_mask_strength: float = 1.0,
    lora: dict | None = None,
    **model_kwargs,
) -> jnp.ndarray:
    """Drive ``model`` from ``noise`` to a clean latent with the named sampler.

    ``noise`` is unit-variance N(0,1); eps-family samplers scale it to sigma_max
    internally. ``shift``/``guidance`` apply to the flow paths — ``flow_euler``
    AND any k-sampler running with ``prediction="flow"`` (shift warps the flow
    sigma table the scheduler menu ranges over; guidance feeds the FLUX-dev
    distilled-guidance kwarg).

    img2img: with ``init_latent`` + ``denoise < 1``, the schedule for
    ``steps/denoise`` total steps is truncated to its last ``steps`` entries and
    ``init_latent`` is noised to the truncated schedule's start (ComfyUI's
    KSampler denoise semantics: ``steps`` forwards always run — except when a
    scheduler realizes fewer than ``steps`` sigmas, where the truncation is
    rescaled to the realized length to preserve the requested strength).

    Inpainting: ``latent_mask`` (broadcastable to the latent; 1 = denoise this
    region, 0 = keep ``init_latent``) re-pins the keep region to the init noised
    to each step's level after every sampler step — the ComfyUI latent-noise-
    mask mechanism. Works at any ``denoise`` (requires ``init_latent``).

    ``compile_loop=True`` compiles the ENTIRE denoise loop into one XLA program
    (sampling/compiled.py): zero per-step dispatch, latent donated, inpaint mask
    traced in. Opt-in because it covers single-program models only (bare models
    and single-platform-group parallel chains) and trades away per-step OOM
    demotion; hybrid chains or a user ``callback`` silently fall back to the
    eager loops (logged).

    ``sigmas`` supplies an explicit descending schedule (the host's
    SamplerCustom/BasicScheduler split): schedule construction, ``scheduler``/
    ``steps``-based truncation, and the ``denoise`` math are all skipped, and
    noising follows the host's ``noise_scaling`` with ``init_latent`` as the
    base (``init + σ₀·noise`` eps; ``σ₀·noise + (1−σ₀)·init`` flow) — a
    truncated sigma ladder therefore gives img2img exactly as the host's
    custom-sampling graphs do. flow_euler treats it as its ``ts`` ladder; ddim
    (timestep-indexed, not sigma-driven) rejects it."""
    use_cfg = cfg_scale != 1.0 and uncond_context is not None
    eff_cfg = cfg_scale if use_cfg else 1.0
    # The loader's residency rule: once a run, never a step — a model that
    # was sent off the chip comes back before its first forward.
    from ..models.loader import residency

    residency.ensure(getattr(model, "params", None))
    # Per-request LoRA (round 16): ``lora`` maps param paths to low-rank
    # (a, b) factor pairs (models/lora.py extract_lora_factors). The inline
    # paths run the eagerly merged model; the serving path submits the BASE
    # model + factors so LoRA lanes co-batch with plain traffic (the lane
    # program applies W + b@a per lane). The merge is deferred past the
    # serving seam — a served request must never pay it.
    lora_factors = None
    if lora:
        lora_factors = dict(lora)
        if sampler in ("ddim", "flow_euler"):
            # TPU-native extras: not in the lane registry, always inline.
            model = _merge_lora(model, lora_factors)
            lora_factors = None
    # Model-level sampler preferences (patch nodes, e.g. RescaleCFG): defaults
    # only — an explicit caller value wins.
    prefs = getattr(model, "sampler_prefs", None) or {}
    if cfg_rescale == 0.0:
        cfg_rescale = float(prefs.get("cfg_rescale", 0.0))
    multi_cond = (bool(extra_conds) or cond_area is not None
                  or cond_area_pct is not None or cond_mask is not None)
    if multi_cond and sampler in ("ddim", "flow_euler"):
        # Multi-cond lives in EpsDenoiser (the k-sampler family — every stock
        # KSampler menu name). ddim/flow_euler are TPU-native extras with
        # their own model-call sites; combined/area conditioning there is out
        # of scope, and silence would mean silently dropping a prompt.
        raise ValueError(
            "combined/area conditioning (ConditioningCombine/SetArea) is "
            "supported on the k-sampler family only, not "
            f"{sampler!r} — pick any stock sampler name"
        )
    if multi_cond and compile_loop:
        from ..utils import get_logger

        get_logger().info(
            "compile_loop: multi-cond (Combine/SetArea) runs the eager path"
        )
        compile_loop = False
    if not 0.0 < denoise <= 1.0:
        raise ValueError(f"denoise must be in (0, 1], got {denoise}")
    if latent_mask is not None and init_latent is None:
        raise ValueError("latent_mask requires init_latent (the kept content)")
    if prediction == "v" and sampler == "flow_euler":
        raise ValueError("flow_euler is velocity-parameterized already; "
                         "prediction='v' applies to the eps-family samplers")
    if prediction == "flow" and sampler == "ddim":
        raise ValueError("ddim runs in alpha-bar space and has no flow form; "
                         "use flow_euler or any k-sampler for flow models")
    if sigmas is not None and sampler == "ddim":
        raise ValueError("ddim is timestep-indexed, not sigma-driven; explicit "
                         "sigmas apply to flow_euler and the k-samplers")
    img2img = init_latent is not None and denoise < 1.0
    total = max(steps, int(round(steps / denoise))) if img2img else steps
    # Shared by every compiled-loop dispatch below: the traced inpaint-mask
    # blend needs the init/noise references only when a mask is present.
    compiled_mask_kw = dict(
        mask=latent_mask,
        mask_init=init_latent if latent_mask is not None else None,
        mask_noise=noise if latent_mask is not None else None,
    )

    def masked_callback(keep_at):
        """Blend the keep-region back after each step; the user callback (which
        may itself replace x) runs on the blended latent."""
        if latent_mask is None:
            return callback
        m = latent_mask
        user = callback

        def cb(i, x):
            x = sampler_mask_blend(x, m, keep_at(i))
            if user is not None:
                out = user(i, x)
                x = x if out is None else out
            return x

        return cb

    def with_progress(cb, n_steps):
        """Per-step progress + cooperative interrupt on the eager loops (the
        ComfyUI protocol's ``progress`` event source; utils/progress.py). The
        compiled path is one XLA program — no step boundaries to report or
        stop at, which run_sampler's docstring lists among its trade-offs.

        Tracing: each boundary-to-boundary interval is a live ``step`` span —
        opened at one boundary, closed at the next, on this thread — so the
        ``denoise`` span of the step's forward nests under it and its
        profiler annotation carries the step number. It is the host-side
        dispatch window of one denoise step (the eager loops do not sync per
        step, and tracing must not add a sync; the serving bucket's step
        spans, which do block, carry the device-inclusive durations). One
        span is recorded per callback, as before; a step that never reaches
        its boundary (an interrupt, a raising model, a sampler that skips an
        iteration) is dropped when ``sampler-run`` closes over it.

        Run-ahead: before step *i*'s event the loop waits for the LATENT of
        step *i − 1*. Step *i*'s programs are queued by then, so the device
        never idles under the wait, the event fires while the device runs
        step *i*, and an interrupt costs at most one step of device work."""
        from ..utils.progress import report_progress

        def open_step(k):
            sp = tracing.span("step", cat="sampling", step=k, of=n_steps)
            sp.__enter__()
            return sp

        live = [open_step(1)] if tracing.on() else None
        behind = [None]

        def cb2(i, x):
            if behind[0] is not None:
                jax.block_until_ready(behind[0])
            behind[0] = x
            if live is not None:
                live[0].__exit__(None, None, None)
            # Raises Interrupted if requested; x feeds the WS latent-preview
            # hook (utils/progress.set_preview_hook) when one is installed.
            report_progress(i + 1, n_steps, latent=x)
            out = cb(i, x) if cb is not None else None
            if live is not None and i + 2 <= n_steps:
                live[0] = open_step(i + 2)
            return out

        return cb2

    if sampler == "flow_euler":
        if sigmas is not None:
            ts = jnp.asarray(sigmas, jnp.float32)
            x = ts[0] * noise
            if init_latent is not None:
                x = x + (1.0 - ts[0]) * init_latent
        else:
            ts = flow_timesteps(total, shift)
            x = noise
            if img2img:
                # x_t = t·noise + (1-t)·x0 under the v = noise - x0 flow.
                ts = ts[-(steps + 1) :]
                x = ts[0] * noise + (1.0 - ts[0]) * init_latent
        if compile_loop:
            spec = _compiled_spec(model, callback)
            if spec is not None:
                from .compiled import compiled_flow_sample

                if x is noise:
                    # The loop donates its latent; never donate the CALLER's
                    # noise array (plain txt2img passes it through unchanged).
                    x = jnp.copy(x)
                try:
                    return compiled_flow_sample(
                        spec, x, ts, context, cfg_scale=eff_cfg,
                        uncond_context=uncond_context,
                        uncond_kwargs=uncond_kwargs,
                        guidance=guidance, cfg_rescale=cfg_rescale,
                        **compiled_mask_kw, model_kwargs=model_kwargs,
                    )
                except Exception as e:  # noqa: BLE001 — classified below
                    _compile_eager_rung(e, "flow_euler")
        cb = with_progress(masked_callback(
            lambda i: (1.0 - ts[i + 1]) * init_latent + ts[i + 1] * noise
        ), len(ts) - 1)
        return flow_euler_sample(
            model, x, context, steps=steps, shift=shift, guidance=guidance,
            cfg_scale=eff_cfg, uncond_context=uncond_context,
            uncond_kwargs=uncond_kwargs, callback=cb, ts=ts,
            cfg_rescale=cfg_rescale, **model_kwargs,
        )
    if sampler == "ddim":
        # A caller-supplied schedule must drive BOTH the truncation/noising here
        # and the sampler itself, or the init is noised to a different level
        # than the sampler assumes.
        acp = model_kwargs.pop("alphas_cumprod", None)
        if acp is None:
            from .schedules import scaled_linear_schedule

            acp = scaled_linear_schedule()
        from .schedules import ddim_timesteps

        x = noise
        if img2img:
            # Exact-strength truncation: `steps` timesteps evenly spaced over
            # [0, denoise·T) descending (ddim_timesteps' integer stride can't
            # express this — 1000//n is 0 for n>1000 and quantizes badly above
            # 500).
            t_start = max(1, round(denoise * (acp.shape[0] - 1)))
            ts = jnp.linspace(t_start, 0, steps).round().astype(jnp.int32)
            a0 = acp[ts[0]]
            x = jnp.sqrt(a0) * init_latent + jnp.sqrt(1.0 - a0) * noise
        else:
            ts = ddim_timesteps(steps, acp.shape[0])

        if compile_loop:
            spec = _compiled_spec(model, callback)
            if spec is not None:
                from .compiled import compiled_ddim_sample

                if x is noise:
                    # See the flow branch: the donated latent must not be the
                    # caller's noise array.
                    x = jnp.copy(x)
                try:
                    return compiled_ddim_sample(
                        spec, x, ts, acp, context, cfg_scale=eff_cfg,
                        uncond_context=uncond_context,
                        uncond_kwargs=uncond_kwargs,
                        prediction=prediction, cfg_rescale=cfg_rescale,
                        **compiled_mask_kw, model_kwargs=model_kwargs,
                    )
                except Exception as e:  # noqa: BLE001 — classified below
                    _compile_eager_rung(e, "ddim")

        def ddim_keep(i):
            a = acp[ts[i + 1]] if i + 1 < len(ts) else jnp.float32(1.0)
            return jnp.sqrt(a) * init_latent + jnp.sqrt(1.0 - a) * noise

        return ddim_sample(
            model, x, context, steps=steps, cfg_scale=eff_cfg,
            uncond_context=uncond_context, uncond_kwargs=uncond_kwargs,
            callback=with_progress(masked_callback(ddim_keep), len(ts)),
            ts=ts, alphas_cumprod=acp,
            prediction=prediction, cfg_rescale=cfg_rescale, **model_kwargs,
        )
    step_fn = K_SAMPLERS.get(sampler)
    if step_fn is None:
        raise ValueError(
            f"unknown sampler {sampler!r} (have {', '.join(SAMPLER_NAMES)})"
        )
    is_flow = prediction == "flow"
    acp = model_kwargs.pop("alphas_cumprod", None)
    explicit_sigmas = sigmas is not None
    if is_flow:
        if acp is not None:
            # The coherence rule (one schedule drives sigmas, truncation, AND
            # the denoiser) makes silently ignoring this worse than rejecting:
            # flow schedules come from flow_sigma_table(shift), not alpha-bars.
            raise ValueError(
                "alphas_cumprod is an eps-schedule input with no flow meaning; "
                "flow schedules derive from the shift-warped flow sigma table"
            )
        if sampler in FLOW_REJECT:
            raise ValueError(
                f"{sampler} is an eps-schedule construction (alpha-bar "
                "posterior) with no rectified-flow form; pick any other "
                "k-sampler for flow models"
            )
        # Flow models sample over flow time (σ ≡ t): the scheduler menu
        # ranges over the CONST sigma table exactly like the host's
        # calculate_sigmas — "normal" is the shifted ladder; karras/beta/…
        # re-space it. FLUX-dev's distilled guidance rides a model kwarg as
        # in the flow_euler branch.
        if not explicit_sigmas:
            sched_name = scheduler if scheduler is not None else "normal"
            sigmas = make_sigmas(
                sched_name, total, sigma_table=flow_sigma_table(shift)
            )
        if guidance is not None:
            model_kwargs["guidance"] = jnp.full(
                (noise.shape[0],), guidance, jnp.float32
            )
    elif not explicit_sigmas:
        # Same coherence rule as the ddim branch: a caller-supplied schedule
        # must drive the sampling sigmas (and img2img truncation), not just
        # the denoiser's sigma→timestep table. ``scheduler`` names the full
        # KSampler menu (make_sigmas); the older ``karras`` boolean remains
        # as a fallback when no name is given.
        sched_name = (
            scheduler if scheduler is not None else ("karras" if karras else "normal")
        )
        sigmas = make_sigmas(sched_name, total, acp)
    if explicit_sigmas:
        # A supplied ladder IS the schedule: no construction, no denoise-based
        # truncation (the host's BasicScheduler already applied it).
        sigmas = jnp.asarray(sigmas, jnp.float32)
    if img2img and not explicit_sigmas:
        # The realized schedule can be shorter than requested (ddim_uniform's
        # integer stride; beta's duplicate-timestep dedup in make_sigmas).
        # While the fixed ComfyUI slice still truncates (realized > steps) use
        # it verbatim — ``steps`` forwards run, reference-faithful even when
        # the realized count is slightly off the request. Only when the fixed
        # slice would degenerate (realized <= steps keeps the WHOLE schedule,
        # i.e. effective denoise 1.0 regardless of the request — beta at high
        # step counts) rescale the truncation to the realized length so the
        # requested strength survives; documented divergence from the host
        # KSampler, which has no guard for this case.
        realized = len(sigmas) - 1
        if realized > steps:
            sigmas = sigmas[-(steps + 1) :]
        else:
            keep = min(realized, max(1, round(steps * realized / total)))
            sigmas = sigmas[-(keep + 1) :]
    # Noising: host noise_scaling semantics. With an explicit ladder any
    # supplied init is the base (the custom-sampling graphs' behavior — a
    # zero EmptyLatent base degenerates to pure noise); otherwise only
    # img2img mixes the init.
    mix_init = img2img or (explicit_sigmas and init_latent is not None)
    # The run's one read of the schedule; the loops below take every scalar
    # from this copy (k_samplers.host_sigmas).
    sig = host_sigmas(sigmas).astype(np.float32)
    if is_flow:
        # Flow forward process: x_t = t·noise + (1−t)·x0.
        x = sig[0] * noise
        if mix_init:
            x = x + (1.0 - sig[0]) * init_latent
    else:
        x = noise * sig[0]
        if mix_init:
            x = init_latent + x
    if sampler in RNG_SAMPLERS and rng is None:
        rng = jax.random.key(0)
    # Continuous-batching seam (round 7, widened rounds 10 and 16, serving/):
    # when a scheduler is installed, route eligible work — any registered
    # LaneStepSpec sampler (stateful and stochastic included), no user
    # callback — into a shared step-boundary batch with whatever other
    # requests are in flight. Denoise-masked img2img/inpaint, multi-cond CFG
    # extras, delegated ControlNet compositions, and per-request LoRA all
    # ride the lane as per-lane state (round 16) instead of forcing inline.
    # Stochastic lanes are occupancy-deterministic because the per-step noise
    # key is fold_in(base, i) on BOTH paths (same base as the eager call
    # below). Ineligible or refused work falls through to the inline paths
    # unchanged; compile_loop callers asked for the whole-loop program and
    # are never hijacked.
    if not compile_loop and callback is None:
        from ..serving.scheduler import get_scheduler

        _sched = get_scheduler()
        if _sched is not None:
            ticket = _sched.maybe_submit(
                model=model,  # still the LoRA base — the merge is deferred
                x=x, sigmas=sigmas, context=context,
                sampler=sampler, cfg_scale=eff_cfg,
                uncond_context=uncond_context, uncond_kwargs=uncond_kwargs,
                alphas_cumprod=acp, prediction=prediction,
                cfg_rescale=cfg_rescale, model_kwargs=model_kwargs,
                rng=(
                    jax.random.fold_in(rng, 1)
                    if sampler in RNG_SAMPLERS else None
                ),
                latent_mask=latent_mask,
                mask_init=init_latent if latent_mask is not None else None,
                mask_noise=noise if latent_mask is not None else None,
                extra_conds=extra_conds, cond_area=cond_area,
                cond_area_pct=cond_area_pct, cond_mask=cond_mask,
                cond_strength=cond_strength,
                cond_mask_strength=cond_mask_strength,
                lora=lora_factors,
            )
            if ticket is not None:
                try:
                    return ticket.result()
                except DegradedToInline as e:
                    # The serving layer shed this request (its OOM ladder ran
                    # out of width/chunk to give): the inline eager path below
                    # is the final rung — the prompt still completes.
                    from ..utils.degrade import record_rung

                    record_rung("inline-fallback",
                                f"{sampler}: {e}", sampler=sampler)
                    registry.counter(
                        "pa_serving_inline_fallback_total",
                        labels={"reason": "degraded", "sampler": sampler},
                        help="sampler runs that fell back to the inline "
                             "eager loop with a scheduler installed",
                    )
            else:
                # A scheduler was installed but could not take this request
                # (capability/shape/queue ineligibility): it runs inline.
                # Round 16's loadgen mixed-workload summary watches this
                # counter — eligible mixed traffic must NOT tick it.
                registry.counter(
                    "pa_serving_inline_fallback_total",
                    labels={"reason": "ineligible", "sampler": sampler},
                    help="sampler runs that fell back to the inline eager "
                         "loop with a scheduler installed",
                )
    if lora_factors:
        # Inline (or shed-from-serving) leg: merge the factors eagerly. A
        # ControlNet composition nests its base params under "base" (the
        # factor paths address the BASE pytree), so recompose around the
        # merged base via the delegate instead of patching the merged tree.
        from ..models.lora import lora_model

        delegate = getattr(model, "control_delegate", None)
        if delegate is not None:
            from ..models.api import DiffusionModel
            from ..models.controlnet import apply_control

            model = apply_control(
                lora_model(delegate["base"], lora_factors),
                DiffusionModel(apply=delegate["ctrl_apply"],
                               params=delegate["ctrl_params"],
                               name="ctrl"),
                delegate["hint"], delegate["strength"],
                delegate["start"], delegate["end"],
            )
        else:
            model = lora_model(model, lora_factors)
    if compile_loop:
        spec = _compiled_spec(model, callback)
        if spec is not None:
            from .compiled import compiled_k_sample

            try:
                return compiled_k_sample(
                    spec, sampler, x, sigmas, context, cfg_scale=eff_cfg,
                    uncond_context=uncond_context, uncond_kwargs=uncond_kwargs,
                    acp=acp, prediction=prediction, cfg_rescale=cfg_rescale,
                    rng=rng, **compiled_mask_kw, model_kwargs=model_kwargs,
                )
            except Exception as e:  # noqa: BLE001 — classified below
                _compile_eager_rung(e, sampler)
    denoiser = EpsDenoiser(
        model, context, cfg_scale=eff_cfg, uncond_context=uncond_context,
        uncond_kwargs=uncond_kwargs, alphas_cumprod=acp, prediction=prediction,
        cfg_rescale=cfg_rescale, extra_conds=extra_conds, cond_area=cond_area,
        cond_area_pct=cond_area_pct,
        cond_mask=cond_mask, cond_strength=cond_strength,
        cond_mask_strength=cond_mask_strength, **model_kwargs,
    )
    if is_flow:
        # Host CONST-dispatch parity: samplers with an RF renoise form swap in.
        step_fn = FLOW_VARIANTS.get(sampler, step_fn)
        cb = masked_callback(
            lambda i: (1.0 - sig[i + 1]) * init_latent + sig[i + 1] * noise
        )
    else:
        cb = masked_callback(lambda i: init_latent + noise * sig[i + 1])
    cb = with_progress(cb, len(sig) - 1)
    registry.counter(
        "pa_sampler_loop_total",
        labels={
            "path": ("planned" if sampler in LANE_SPECS
                     and takes_fused_step(denoiser) else "eager"),
            "sampler": sampler,
        },
        help="inline k-sampler runs by step form: planned = two compiled "
             "programs around the denoiser, eager = the denoiser called "
             "whole with host scalars",
    )
    if sampler in RNG_SAMPLERS:
        return step_fn(denoiser, x, sig, jax.random.fold_in(rng, 1), callback=cb)
    return step_fn(denoiser, x, sig, callback=cb)
