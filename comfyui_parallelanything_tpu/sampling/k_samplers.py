"""k-diffusion-family samplers: Euler, Euler-ancestral, Heun, DPM++ 2M.

The reference is driven by its host's KSampler — every sampler in that menu calls the
(monkey-patched) ``diffusion_model.forward`` once or twice per step
(any_device_parallel.py:1287). To stand alone, this framework carries the standard
sigma-space sampler set itself. Host-side step loops like ddim.py/flow.py: each model
call routes through the (possibly parallelized) forward, so the DP/pipeline scheduler
sees exactly the per-step batched calls it is designed for. The loops keep the
schedule and every scalar derived from it on the host and never read the device
between steps; the samplers that have a plan in lane_specs.py are walked from it
(``sample_planned``), ``lms`` and ``uni_pc*`` keep a loop of their own.

Conventions (eps-prediction SD family, k-diffusion/EDM parameterization):
``sigma_t = sqrt((1-ᾱ_t)/ᾱ_t)``; model input is ``x/sqrt(sigma²+1)`` at the discrete
timestep nearest in log-sigma; denoised prediction ``x0 = x - sigma·eps``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .cfg import apply_callback, double_kwargs, rescale_guidance
from .lane_specs import LANE_SPECS
from .schedules import scaled_linear_schedule


def broadcast_cond_batch(arr, batch: int):
    """ComfyUI conditioning-batch semantics: one encoded prompt (or any even
    divisor) tiles to the latent batch; a non-divisor batch is a user error
    surfaced here rather than as a downstream XLA shape mismatch. Shared by
    the node boundary (nodes._prepare_sampling_inputs) and the denoiser's
    extra-cond path so direct ``run_sampler(extra_conds=...)`` callers get the
    same contract."""
    if arr is not None and arr.shape[0] != batch:
        if batch % arr.shape[0]:
            raise ValueError(
                f"conditioning batch {arr.shape[0]} does not divide "
                f"latent batch {batch}"
            )
        arr = jnp.repeat(arr, batch // arr.shape[0], axis=0)
    return arr


def model_sigmas(alphas_cumprod: jnp.ndarray) -> jnp.ndarray:
    """Per-trained-timestep sigma table, ascending with t."""
    return jnp.sqrt((1.0 - alphas_cumprod) / alphas_cumprod)


def sampling_sigmas(
    n_steps: int,
    alphas_cumprod: jnp.ndarray | None = None,
    sigma_table: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """(n_steps+1,) descending sigmas over the model's range, ending at 0."""
    table = _sigma_table(alphas_cumprod, sigma_table)
    idx = jnp.linspace(len(table) - 1, 0, n_steps, dtype=jnp.float32)
    sig = jnp.interp(idx, jnp.arange(len(table), dtype=jnp.float32), table)
    return jnp.concatenate([sig, jnp.zeros((1,), jnp.float32)])


def karras_sigmas(
    n_steps: int,
    sigma_min: float = 0.0292,
    sigma_max: float = 14.6146,
    rho: float = 7.0,
) -> jnp.ndarray:
    """Karras et al. (2022) spacing — denser near sigma_min; (n_steps+1,), ends at 0."""
    ramp = jnp.linspace(0.0, 1.0, n_steps, dtype=jnp.float32)
    min_inv, max_inv = sigma_min ** (1 / rho), sigma_max ** (1 / rho)
    sig = (max_inv + ramp * (min_inv - max_inv)) ** rho
    return jnp.concatenate([sig, jnp.zeros((1,), jnp.float32)])


def exponential_sigmas(
    n_steps: int, sigma_min: float = 0.0292, sigma_max: float = 14.6146
) -> jnp.ndarray:
    """Log-uniform spacing (k-diffusion ``get_sigmas_exponential``); ends at 0."""
    sig = jnp.exp(
        jnp.linspace(
            jnp.log(jnp.float32(sigma_max)), jnp.log(jnp.float32(sigma_min)), n_steps
        )
    )
    return jnp.concatenate([sig, jnp.zeros((1,), jnp.float32)])


def _sigma_table(
    alphas_cumprod: jnp.ndarray | None, sigma_table: jnp.ndarray | None = None
) -> jnp.ndarray:
    if sigma_table is not None:
        return sigma_table
    if alphas_cumprod is None:
        alphas_cumprod = scaled_linear_schedule()
    return model_sigmas(alphas_cumprod)


def flow_sigma_table(shift: float = 1.0, n: int = 1000) -> jnp.ndarray:
    """The CONST (rectified-flow) model sigma table: sigma(t) = t with the
    resolution shift applied, ascending over n trained timesteps — the host's
    ModelSamplingDiscreteFlow table, which its scheduler menu samples for flow
    models. sigma_max = 1, sigma_min = shifted(1/n) (~1e-3)."""
    from .flow import apply_flow_shift

    return apply_flow_shift(
        jnp.linspace(1.0 / n, 1.0, n, dtype=jnp.float32), shift
    )


def sgm_uniform_sigmas(
    n_steps: int,
    alphas_cumprod: jnp.ndarray | None = None,
    sigma_table: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """SGM/EDM "trailing" uniform-timestep spacing (ComfyUI ``sgm_uniform``):
    n+1 uniform timesteps, last dropped, so the final nonzero sigma sits one
    uniform stride above 0 instead of at sigma_min."""
    table = _sigma_table(alphas_cumprod, sigma_table)
    # The dropped end point is the host's ``timestep(sigma_min)``: index 0 of
    # an eps table; for a CONST (flow) table, whose timestep is sigma·n, the
    # index sigma_min·n − 1 (2 at SD3's shift 3, not 0).
    last = 0.0 if sigma_table is None else float(table[0]) * len(table) - 1.0
    idx = jnp.linspace(len(table) - 1, last, n_steps + 1, dtype=jnp.float32)[:-1]
    sig = jnp.interp(idx, jnp.arange(len(table), dtype=jnp.float32), table)
    return jnp.concatenate([sig, jnp.zeros((1,), jnp.float32)])


def simple_sigmas(
    n_steps: int,
    alphas_cumprod: jnp.ndarray | None = None,
    sigma_table: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """ComfyUI ``simple``: raw table entries at equal index strides (no interp)."""
    table = _sigma_table(alphas_cumprod, sigma_table)
    stride = len(table) / n_steps
    idx = [len(table) - 1 - int(i * stride) for i in range(n_steps)]
    sig = table[jnp.asarray(idx, jnp.int32)]
    return jnp.concatenate([sig, jnp.zeros((1,), jnp.float32)])


def _beta_ppf(q: np.ndarray, a: float, b: float, grid_points: int = 65537) -> np.ndarray:
    """Beta quantile function by numeric CDF inversion (jax betainc + interp) —
    keeps the beta scheduler dependency-free (scipy is not a package dep)."""
    from jax.scipy.special import betainc

    grid = np.linspace(0.0, 1.0, grid_points, dtype=np.float64)
    cdf = np.asarray(betainc(a, b, jnp.asarray(grid)), np.float64)
    return np.interp(q, cdf, grid)


def beta_sigmas(
    n_steps: int,
    alphas_cumprod: jnp.ndarray | None = None,
    alpha: float = 0.6,
    beta: float = 0.6,
    sigma_table: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """ComfyUI ``beta`` (arXiv:2407.12173): timesteps at Beta(0.6, 0.6) quantiles —
    denser at both schedule ends. Duplicate timesteps (quantiles collide after
    rounding at high step counts) are skipped like the reference implementation,
    so the result may be shorter than ``n_steps + 1`` — a repeated sigma would
    divide-by-zero the multistep samplers (lms, dpm++ sde)."""
    table = _sigma_table(alphas_cumprod, sigma_table)
    # endpoint=False matches the reference scheduler: quantiles stop one stride
    # above q=0, so the last nonzero sigma sits above sigma_min.
    ts = 1.0 - np.linspace(0.0, 1.0, n_steps, endpoint=False, dtype=np.float64)
    idx = np.rint(_beta_ppf(ts, alpha, beta) * (len(table) - 1)).astype(np.int64)
    keep = np.concatenate([[True], np.diff(idx) != 0])
    sig = table[jnp.asarray(idx[keep], jnp.int32)]
    return jnp.concatenate([sig, jnp.zeros((1,), jnp.float32)])


def ddim_uniform_sigmas(
    n_steps: int,
    alphas_cumprod: jnp.ndarray | None = None,
    sigma_table: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """ComfyUI ``ddim_uniform``: the DDIM stride — table entries at indices
    ``1, 1+T//n, 1+2·T//n, … (< T)`` (integer stride, so the realized step count
    can differ slightly from ``n_steps``), descending."""
    table = _sigma_table(alphas_cumprod, sigma_table)
    T = len(table)
    stride = T // n_steps
    if stride <= 1:
        # Stride 1 would enumerate (nearly) the whole table regardless of the
        # request. Uniform trailing spacing is the exact limit of the stride
        # scheme as stride→1, and it honors the requested count — so the
        # degenerate regime hands off to sgm_uniform.
        return sgm_uniform_sigmas(n_steps, alphas_cumprod, sigma_table)
    idx = list(range(1, T, stride))
    sig = table[jnp.asarray(list(reversed(idx)), jnp.int32)]
    return jnp.concatenate([sig, jnp.zeros((1,), jnp.float32)])


def kl_optimal_sigmas(
    n_steps: int,
    alphas_cumprod: jnp.ndarray | None = None,
    sigma_table: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """"Align Your Steps" KL-optimal spacing (arXiv:2404.14507):
    σᵢ = tan((1−i/(n−1))·atan(σ_max) + (i/(n−1))·atan(σ_min)) — inclusive
    interpolation, so the last nonzero sigma is exactly σ_min."""
    table = _sigma_table(alphas_cumprod, sigma_table)
    sigma_min, sigma_max = jnp.float32(table[0]), jnp.float32(table[-1])
    frac = jnp.linspace(0.0, 1.0, n_steps, dtype=jnp.float32)
    sig = jnp.tan((1.0 - frac) * jnp.arctan(sigma_max) + frac * jnp.arctan(sigma_min))
    return jnp.concatenate([sig, jnp.zeros((1,), jnp.float32)])


SCHEDULER_NAMES = (
    "karras", "normal", "exponential", "sgm_uniform", "simple", "ddim_uniform",
    "beta", "kl_optimal",
)


def make_sigmas(
    scheduler: str,
    n_steps: int,
    alphas_cumprod: jnp.ndarray | None = None,
    sigma_table: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """The KSampler scheduler menu: named spacing → (n_steps+1,) descending sigmas
    ending at 0, ranged over the model's sigma table when one is supplied.
    ``sigma_table`` overrides the eps alpha-bar derivation — flow models pass
    ``flow_sigma_table(shift)`` so every scheduler ranges over flow time,
    exactly as the host menu does for CONST model sampling."""
    if scheduler in ("karras", "exponential"):
        fn = karras_sigmas if scheduler == "karras" else exponential_sigmas
        if alphas_cumprod is None and sigma_table is None:
            return fn(n_steps)
        table = _sigma_table(alphas_cumprod, sigma_table)
        return fn(n_steps, sigma_min=float(table[0]), sigma_max=float(table[-1]))
    if scheduler == "normal":
        return sampling_sigmas(n_steps, alphas_cumprod, sigma_table)
    if scheduler == "sgm_uniform":
        return sgm_uniform_sigmas(n_steps, alphas_cumprod, sigma_table)
    if scheduler == "simple":
        return simple_sigmas(n_steps, alphas_cumprod, sigma_table)
    if scheduler == "ddim_uniform":
        return ddim_uniform_sigmas(n_steps, alphas_cumprod, sigma_table)
    if scheduler == "beta":
        return beta_sigmas(n_steps, alphas_cumprod, sigma_table=sigma_table)
    if scheduler == "kl_optimal":
        return kl_optimal_sigmas(n_steps, alphas_cumprod, sigma_table)
    raise ValueError(
        f"unknown scheduler {scheduler!r} (have {', '.join(SCHEDULER_NAMES)})"
    )


def area_weight(area, strength: float, shape, mask=None,
                mask_strength: float = 1.0, area_pct=None):
    """Per-pixel weight for one cond: ``strength`` everywhere (no
    scoping), strength inside the (h, w, y, x) latent-unit box (SetArea),
    or a pixel-space MASK resized to the latent grid (SetMask — stock's
    mask conditioning; "mask bounds" and "default" produce the same
    weights, the bounds only being stock's compute-crop optimization).
    Non-2D latents (video) use the full frame — stock scoping is 2D.

    Module-level (round 16) so the serving bucket composes the SAME weight
    maps host-side at seat time for the lane program's per-lane ``mc_w0`` /
    ``mc_w`` stacks; EpsDenoiser._area_mask delegates here."""
    weight = jnp.float32(strength)
    if area_pct is not None and area is None and len(shape) == 4:
        # Fractional box (ConditioningSetAreaPercentage): resolve against
        # the LATENT frame at weight time, when its shape is known.
        fh, fw, fy, fx = (float(v) for v in area_pct)
        area = (max(1, round(fh * shape[1])), max(1, round(fw * shape[2])),
                round(fy * shape[1]), round(fx * shape[2]))
    if area is not None and len(shape) == 4:
        h, w, y, x0 = (int(v) for v in area)
        box = jnp.zeros((1, shape[1], shape[2], 1), jnp.float32)
        weight = weight * box.at[:, y:y + h, x0:x0 + w, :].set(1.0)
    if mask is not None and len(shape) == 4:
        from ..models.vae import normalize_mask

        m = normalize_mask(mask, (shape[1], shape[2]))
        if m.shape[0] not in (1, shape[0]):
            m = m[:1]
        # Both present (SetMask then SetArea): stock composes — the area
        # crop times the mask weight inside it (get_area_and_mult), with
        # the mask's OWN strength multiplier kept separate from the
        # area's (stock's strength × mask_strength).
        weight = weight * m * jnp.float32(mask_strength)
    return weight


class EpsDenoiser:
    """Wraps a model forward into ``denoise(x, sigma) -> x0`` with batched CFG
    (cond ‖ uncond in one call — what feeds the DP path its batch, ddim.py).

    ``prediction`` selects the parameterization:

    - ``"eps"`` — noise prediction (SD1.5/SDXL family); x0 = x − σ·eps with
      the 1/√(σ²+1) input scaling and log-interp σ→timestep table.
    - ``"v"``   — SD2.x-768 v-param (x0 = c_skip·x + c_out·v with
      c_skip = 1/(σ²+1), c_out = −σ/√(σ²+1)).
    - ``"flow"`` — rectified-flow velocity (FLUX/WAN family). Here σ IS the
      flow time t ∈ (0, 1]: the model takes x unscaled and t directly, and
      x0 = x − σ·v. This is exact, not an approximation: under the flow
      forward x_t = (1−t)·x0 + t·n, the k-diffusion ODE d = (x − x0)/σ equals
      the velocity n − x0, so the whole sigma-space sampler family integrates
      the same probability-flow ODE ``flow_euler`` does — any k-sampler works
      on a flow model given a flow-time schedule (the host KSampler's CONST
      model-sampling wrapper, reproduced TPU-side)."""

    def __init__(
        self,
        model,
        context=None,
        *,
        cfg_scale: float = 1.0,
        uncond_context=None,
        uncond_kwargs: dict | None = None,
        alphas_cumprod: jnp.ndarray | None = None,
        prediction: str = "eps",
        cfg_rescale: float = 0.0,
        extra_conds: tuple | list | None = None,
        cond_area: tuple | None = None,
        cond_area_pct: tuple | None = None,
        cond_mask=None,
        cond_strength: float = 1.0,
        cond_mask_strength: float = 1.0,
        **model_kwargs,
    ):
        if alphas_cumprod is None:
            alphas_cumprod = scaled_linear_schedule()
        if prediction not in ("eps", "v", "flow"):
            raise ValueError(
                f"prediction must be 'eps', 'v' or 'flow', got {prediction!r}"
            )
        self.prediction = prediction
        self.model = model
        self.context = context
        self.cfg_scale = cfg_scale
        self.cfg_rescale = cfg_rescale
        self.uncond_context = uncond_context
        self.uncond_kwargs = uncond_kwargs
        # Multi-cond (stock ConditioningCombine/SetArea): extra positive conds,
        # each {"context", "pooled"?, "strength"?, "area"? (h, w, y, x) in
        # latent units}. Predictions are area-weight-normalized per pixel —
        # ComfyUI's calc_cond_batch combination rule, minus its crop-run
        # optimization (each cond here sees the full latent; documented
        # divergence). ``cond_area``/``cond_strength`` scope the PRIMARY cond
        # the same way when SetArea was applied to it directly.
        self.extra_conds = tuple(extra_conds or ())
        self.cond_area = cond_area
        self.cond_area_pct = cond_area_pct  # fractional SetAreaPercentage box
        self.cond_mask = cond_mask  # pixel-space MASK (ConditioningSetMask)
        self.cond_strength = cond_strength
        self.cond_mask_strength = cond_mask_strength
        self.kwargs = model_kwargs
        self.alphas_cumprod = alphas_cumprod
        self.sigma_table = model_sigmas(alphas_cumprod)
        self.log_sigmas = jnp.log(self.sigma_table)
        self._host_log_sigmas = None  # float64 twin, read once on first use
        self._cfg_inputs = None       # context ‖ uncond and doubled kwargs

    def _area_mask(self, area, strength: float, shape, mask=None,
                   mask_strength: float = 1.0, area_pct=None):
        return area_weight(area, strength, shape, mask=mask,
                           mask_strength=mask_strength, area_pct=area_pct)

    def _combine_conds(self, eps_c, x_in, t_vec, batch):
        """Area-weight-normalized blend of the primary cond's prediction with
        every extra cond's (one model call each — token lengths differ, so
        they cannot batch into one call without padding). An extra carrying
        ``timestep_range`` (start, end) contributes only while sampling
        progress is inside the window (the stock ConditioningSetTimestepRange
        + Combine multi-stage pattern)."""
        m0 = self._area_mask(self.cond_area, self.cond_strength, x_in.shape,
                             mask=self.cond_mask,
                             mask_strength=self.cond_mask_strength,
                             area_pct=self.cond_area_pct)
        num = m0 * eps_c
        den = m0 * jnp.ones_like(eps_c[..., :1])
        for e in self.extra_conds:
            ctx = broadcast_cond_batch(e["context"], batch)
            kw = dict(self.kwargs)
            pooled = e.get("pooled")
            if pooled is not None:
                kw["y"] = broadcast_cond_batch(pooled, batch)
            eps_e = self.model(x_in, t_vec, ctx, **kw)
            m = self._area_mask(
                e.get("area"), float(e.get("strength", 1.0)), x_in.shape,
                mask=e.get("mask"),
                mask_strength=float(e.get("mask_strength", 1.0)),
                area_pct=e.get("area_pct"),
            )
            rng_ = e.get("timestep_range")
            if rng_ is not None:
                from ..ops.basic import progress_window_gate

                m = m * progress_window_gate(
                    t_vec, rng_[0], rng_[1], x_in.ndim,
                    flow_time=(self.prediction == "flow"),
                )
            num = num + m * eps_e
            den = den + m * jnp.ones_like(eps_e[..., :1])
        # Uncovered pixels (every cond area-scoped away from them) fall back
        # to the primary prediction rather than dividing by zero.
        return jnp.where(den > 0, num / jnp.maximum(den, 1e-8), eps_c)

    def _timestep(self, sigma) -> jnp.ndarray:
        """Continuous timestep whose table sigma matches (log-space interpolation)."""
        return jnp.interp(
            jnp.log(sigma),
            self.log_sigmas,
            jnp.arange(len(self.log_sigmas), dtype=jnp.float32),
        )

    @property
    def use_cfg(self) -> bool:
        return self.cfg_scale != 1.0 and self.uncond_context is not None

    @property
    def plain(self) -> bool:
        """One model call an eval and no Python between it and x0: such an
        eval can be two compiled programs around the model (``fused_eval``).
        Multi-cond (extra conds, an area or a mask on the primary cond) calls
        the model again per cond and blends in Python."""
        return not (self.extra_conds or self.cond_area is not None
                    or self.cond_area_pct is not None
                    or self.cond_mask is not None)

    def eval_scalars(self, sigma: float) -> np.ndarray:
        """Host arithmetic of one eval at ``sigma``, float64 rounded once:
        ``(c_in, t, c_skip, c_out, cfg_scale)`` float32 — the
        model sees ``c_in·x`` at timestep ``t`` and
        ``x0 = c_skip·x + c_out·prediction``."""
        if self.prediction == "flow":
            c_in, t, c_skip, c_out = 1.0, sigma, 1.0, -sigma
        else:
            if self._host_log_sigmas is None:
                acp = np.asarray(self.alphas_cumprod, np.float64)
                self._host_log_sigmas = 0.5 * np.log((1.0 - acp) / acp)
            table = self._host_log_sigmas
            c_in = 1.0 / np.sqrt(sigma * sigma + 1.0)
            t = np.interp(np.log(sigma), table, np.arange(len(table)))
            c_skip, c_out = 1.0, -sigma
            if self.prediction == "v":
                c_skip, c_out = c_in * c_in, -sigma * c_in
        return np.array([c_in, t, c_skip, c_out, self.cfg_scale], np.float32)

    def _model_inputs(self, batch: int):
        """(context, kwargs) of the model call. Under CFG every per-batch
        kwarg doubles with the batch; uncond variants (e.g. SDXL's negative
        pooled y) ride the second half (sampling/cfg.py). Neither changes
        across steps, so outside a trace both are built once a run."""
        if not self.use_cfg:
            return self.context, self.kwargs
        if self._cfg_inputs is not None and self._cfg_inputs[0] == batch:
            return self._cfg_inputs[1:]
        ctx = jnp.concatenate([self.context, self.uncond_context], axis=0)
        kw = double_kwargs(self.kwargs, self.uncond_kwargs, batch)
        if not isinstance(ctx, jax.core.Tracer):
            self._cfg_inputs = (batch, ctx, kw)
        return ctx, kw

    def fused_eval(self, state, plan, keys, draw):
        """One StepPlan as prepare → model → finish (``plain`` only): new
        ``(x, xe, h1, h2)``. The model is called as in ``__call__``, once,
        from Python, so its own dispatch, span and counter are untouched."""
        x, xe, h1, h2 = state
        cfg = self.use_cfg
        scal = self.eval_scalars(plan.sigma_eval)
        x_in, t_vec = sampler_prepare(xe, scal, cfg=cfg)
        ctx, kw = self._model_inputs(xe.shape[0])
        pred = self.model(x_in, t_vec, ctx, **kw)
        return sampler_finish(
            pred, x, xe, h1, h2, scal, plan.coef, keys, draw,
            cfg=cfg, rescale=float(self.cfg_rescale),
        )

    def __call__(self, x: jnp.ndarray, sigma) -> jnp.ndarray:
        """x0 at ``sigma``. A host float (the eager loops pass nothing else)
        keeps the scalar arithmetic on the host; a traced or device ``sigma``
        (the whole-loop programs, the serving lanes) computes it in place."""
        batch = x.shape[0]
        host = isinstance(sigma, (float, np.floating))
        if host:
            scale, t, c_skip, c_out = self.eval_scalars(float(sigma))[:4]
            t_vec = jnp.full((batch,), t, jnp.float32)
            x_in = x if self.prediction == "flow" else x * scale
        elif self.prediction == "flow":
            # Flow time is the sigma: the model takes x raw and t = σ directly.
            scale = 1.0
            t_vec = jnp.full((batch,), sigma, jnp.float32)
            x_in = x
        else:
            scale = 1.0 / jnp.sqrt(sigma**2 + 1.0)
            t_vec = jnp.full((batch,), self._timestep(sigma), jnp.float32)
            x_in = x * scale
        ctx, kw = self._model_inputs(batch)
        if self.use_cfg:
            eps_both = self.model(
                jnp.concatenate([x_in, x_in], axis=0),
                jnp.concatenate([t_vec, t_vec], axis=0),
                ctx, **kw,
            )
            eps_c, eps_u = jnp.split(eps_both, 2, axis=0)
            if not self.plain:
                eps_c = self._combine_conds(eps_c, x_in, t_vec, batch)
            eps = eps_u + self.cfg_scale * (eps_c - eps_u)
            eps = rescale_guidance(eps, eps_c, self.cfg_rescale)
        else:
            eps = self.model(x_in, t_vec, ctx, **kw)
            if not self.plain:
                eps = self._combine_conds(eps, x_in, t_vec, batch)
        if host:
            return c_skip * x + c_out * eps
        if self.prediction == "v":
            return x / (sigma**2 + 1.0) - eps * sigma * scale
        # eps: x0 = x − σ·eps. flow: x0 = x − σ·v — the same expression.
        return x - sigma * eps


def host_sigmas(sigmas) -> np.ndarray:
    """The schedule as host float64: the ONE device read of an eager sampler
    run, made before its first step. Every scalar a step derives from the
    schedule is host arithmetic on this copy, so no step waits on the device
    for a number (a ``float(sigmas[i])`` sits in the queue behind the previous
    step's latent update and stalls the host until the device has drained)."""
    return np.asarray(sigmas, np.float64)


def ancestral_steps(s, s_next, eta: float = 1.0):
    """(sigma_down, sigma_up) for an ancestral step from ``s`` to ``s_next``
    (k-diffusion's get_ancestral_step): deterministic integration runs to
    sigma_down, then sigma_up of fresh noise restores the s_next level.
    Traced form, for the whole-loop programs (sampling/compiled.py); the eager
    loop's is ``lane_specs._ancestral`` on the host."""
    sigma_up = jnp.minimum(
        s_next,
        eta * jnp.sqrt(jnp.maximum(s_next**2 * (s**2 - s_next**2) / s**2, 0.0)),
    )
    sigma_down = jnp.sqrt(jnp.maximum(s_next**2 - sigma_up**2, 0.0))
    return sigma_down, sigma_up


# ---------------------------------------------------------------------------
# The planned step. A k-sampler step is one model eval plus a linear update of
# the state (x, xe, h1, h2) whose weights depend only on the schedule
# (sampling/lane_specs.py: float64 on the host, one [4, 6] float32 matrix an
# eval). The eager loop walks those plans and hands the device two compiled
# programs around the denoiser's own, with every coefficient an ARRAY argument
# so that first, middle and last steps run the same executables. The programs
# are named functions: the benchmark finds the denoiser and the decode by their
# XLA module names (jit_apply, jit__lambda), and these must match neither.
# ---------------------------------------------------------------------------


def _linear_update(x, xe, x0, h1, h2, coef, keys, draw):
    """New (x, xe, h1, h2) from one plan's ``coef`` rows over the basis
    (x, xe, x0, h1, h2[, noise]); the draw is ``keys[draw[0], draw[1]]``."""
    basis = [x, xe, x0, h1, h2]
    if keys is not None:
        basis.append(jax.random.normal(keys[draw[0], draw[1]], x.shape, x.dtype))
    return tuple(
        sum(coef[row, k] * term for k, term in enumerate(basis)).astype(x.dtype)
        for row in range(4)
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def sampler_prepare(xe, scal, *, cfg: bool):
    """Model input of one eval: ``c_in·xe`` and the timestep vector, doubled
    along the batch under CFG. ``scal`` = (c_in, t, ...)."""
    x_in = xe * scal[0]
    t_vec = jnp.full((xe.shape[0],), scal[1], jnp.float32)
    if cfg:
        x_in = jnp.concatenate([x_in, x_in], axis=0)
        t_vec = jnp.concatenate([t_vec, t_vec], axis=0)
    return x_in, t_vec


@functools.partial(jax.jit, static_argnames=("cfg", "rescale"))
def sampler_finish(pred, x, xe, h1, h2, scal, coef, keys, draw, *,
                   cfg: bool, rescale: float):
    """CFG split and combination (``rescale`` is the model's guidance-rescale
    phi, fixed for a run), x0 from the prediction (``c_skip·xe + c_out·pred``:
    eps, v and flow differ only in the two scalars) and the plan's linear
    update, in one program. ``scal`` = (c_in, t, c_skip, c_out, cfg_scale)."""
    if cfg:
        pred_c, pred_u = jnp.split(pred, 2, axis=0)
        pred = pred_u + scal[4] * (pred_c - pred_u)
        pred = rescale_guidance(pred, pred_c, rescale)
    x0 = scal[2] * xe + scal[3] * pred
    return _linear_update(x, xe, x0, h1, h2, coef, keys, draw)


@jax.jit
def sampler_update(x, xe, x0, h1, h2, coef, keys, draw):
    """The plan's linear update alone, for a denoiser that is called whole
    (multi-cond, or any ``denoise(x, sigma) -> x0`` callable)."""
    return _linear_update(x, xe, x0, h1, h2, coef, keys, draw)


@functools.partial(jax.jit, static_argnames=("n", "split"))
def sampler_step_keys(rng, *, n: int, split: bool):
    """[n, 2] per-step keys under the fold_in discipline (see
    ``sample_euler_ancestral``): row i is ``fold_in(rng, i)`` twice, or its
    ``split`` halves for dpmpp_sde's two draws a step. One program a run."""
    if not jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
        rng = jax.random.wrap_key_data(rng)
    ks = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(n))
    if split:
        return jax.vmap(jax.random.split)(ks)
    return jnp.stack([ks, ks], axis=1)


def takes_fused_step(denoise) -> bool:
    """Whether ``sample_planned`` gives ``denoise`` the two-program step: it
    has one (``EpsDenoiser.fused_eval``) and is one model call an eval."""
    return getattr(denoise, "fused_eval", None) is not None and denoise.plain


def sample_planned(name, denoise, x, sigmas, rng=None, callback=None, *,
                   prediction: str = "eps", **plan_kwargs):
    """Walk sampler ``name``'s StepPlans over ``sigmas``: the eager loop of
    every sampler in ``LANE_SPECS``. ``prediction="flow"`` picks the
    rectified-flow form of the samplers that have one; ``plan_kwargs`` (eta)
    go to the plan compiler. The callback fires once a completed σ-interval.

    A plain ``EpsDenoiser`` (one model call an eval, no Python of its own
    between the model and x0) takes the two-program step; anything else is
    called whole with a host-float sigma and followed by one update program.
    Nothing in the loop reads the device."""
    spec = LANE_SPECS[name]
    sig = host_sigmas(sigmas)
    plans = spec.compile_plans(sig, prediction, **plan_kwargs)
    # The eager loops computed in the float32 their device scalars carried.
    x = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    xe, h1 = x, jnp.zeros_like(x)
    h2 = h1
    keys = None
    if any(p.noise for p in plans):
        keys = sampler_step_keys(rng, n=len(sig) - 1, split=spec.split_keys)
    fused = takes_fused_step(denoise)
    for plan in plans:
        draw = None
        if keys is not None:
            draw = np.array([plan.step, plan.noise == "sde_end"], np.int32)
        if fused:
            x, xe, h1, h2 = denoise.fused_eval((x, xe, h1, h2), plan, keys, draw)
        else:
            x0 = denoise(xe, plan.sigma_eval)
            x, xe, h1, h2 = sampler_update(
                x, xe, x0, h1, h2, plan.coef, keys, draw)
        if plan.completes:
            # A completed step's next eval input IS its output latent
            # (lane_specs._mk), so a callback's replacement feeds both.
            x = xe = apply_callback(callback, plan.step, x)
    return x


def sample_euler(denoise, x, sigmas, callback=None):
    """Deterministic Euler over the sigma schedule."""
    return sample_planned("euler", denoise, x, sigmas, callback=callback)


def sample_euler_ancestral(denoise, x, sigmas, rng, eta: float = 1.0, callback=None):
    """Euler with ancestral noise injection (stochastic).

    RNG discipline (shared by every stochastic sampler here, their compiled
    twins, and the serving lanes): the step-``i`` key is ``fold_in(rng, i)``
    — a pure function of (request rng, step index), never of how many draws
    preceded it — so output is bit-identical whether the run executes alone,
    inside a compiled loop, or co-batched in a serving lane (round 10)."""
    return sample_planned("euler_ancestral", denoise, x, sigmas, rng,
                          callback, eta=eta)


def sample_euler_ancestral_rf(denoise, x, sigmas, rng, eta: float = 1.0,
                              callback=None):
    """Euler ancestral for rectified-flow schedules (the host's
    ``sample_euler_ancestral_RF``): under x_t = (1−t)·x0 + t·n the VE renoise
    ``x += σ_up·n`` would leave the (1−t)·x0 component unscaled, so the RF form
    rescales by the interpolant's alpha ratio and injects the variance that
    exactly restores the t_next marginal."""
    return sample_planned("euler_ancestral", denoise, x, sigmas, rng,
                          callback, prediction="flow", eta=eta)


def sample_dpmpp_2s_ancestral_rf(denoise, x, sigmas, rng, eta: float = 1.0,
                                 callback=None):
    """DPM-Solver++(2S) ancestral for rectified-flow schedules (the host's
    ``sample_dpmpp_2s_ancestral_RF``): the exponential-integrator time is the
    flow log-SNR λ = log((1−σ)/σ), the midpoint sits at λ + h/2 (pinned to
    σ = 0.9999 when σ = 1, where λ diverges), and the renoise rescales by the
    interpolant's alpha ratio like the RF Euler-ancestral form."""
    return sample_planned("dpmpp_2s_ancestral", denoise, x, sigmas, rng,
                          callback, prediction="flow", eta=eta)


def sample_lcm_rf(denoise, x, sigmas, rng, callback=None):
    """LCM on rectified-flow schedules: re-noising uses the flow interpolant
    ``x = t·n + (1−t)·x0`` (the host's CONST ``noise_scaling``) instead of the
    VE ``x0 + σ·n``."""
    return sample_planned("lcm", denoise, x, sigmas, rng, callback,
                          prediction="flow")


def sample_heun(denoise, x, sigmas, callback=None):
    """Heun's 2nd-order method (two model calls per step except the last)."""
    return sample_planned("heun", denoise, x, sigmas, callback=callback)


def sample_dpm_2(denoise, x, sigmas, callback=None):
    """DPM2 (k-diffusion ``sample_dpm_2``): explicit midpoint method — the
    second model call sits at the geometric mean of the step's sigmas."""
    return sample_planned("dpm_2", denoise, x, sigmas, callback=callback)


def sample_dpm_2_ancestral(denoise, x, sigmas, rng, eta: float = 1.0, callback=None):
    """DPM2 ancestral (k-diffusion ``sample_dpm_2_ancestral``): the midpoint
    step runs to sigma_down, then sigma_up of fresh noise is injected."""
    return sample_planned("dpm_2_ancestral", denoise, x, sigmas, rng,
                          callback, eta=eta)


def sample_dpmpp_2s_ancestral(denoise, x, sigmas, rng, eta: float = 1.0,
                              callback=None):
    """DPM-Solver++ (2S) ancestral (k-diffusion ``sample_dpmpp_2s_ancestral``):
    single-step 2nd order in exponential-integrator form (midpoint at
    r = 1/2 in log-sigma time), ancestral noise on every non-final step."""
    return sample_planned("dpmpp_2s_ancestral", denoise, x, sigmas, rng,
                          callback, eta=eta)


def sample_dpmpp_sde(denoise, x, sigmas, rng, eta: float = 1.0, callback=None):
    """DPM-Solver++ SDE (k-diffusion ``sample_dpmpp_sde``, r = 1/2): 2nd-order
    single-step with ancestral-style noise injected BOTH at the midpoint model
    call and at the step end — two model calls and two noise draws per step.
    Per-step keys: ``k_mid, k_end = split(fold_in(rng, i))`` — the fold_in
    discipline (see sample_euler_ancestral), with the two draws split from the
    step key (the compiled twin and the serving lanes consume the same)."""
    return sample_planned("dpmpp_sde", denoise, x, sigmas, rng, callback,
                          eta=eta)


def sample_dpmpp_2m(denoise, x, sigmas, callback=None):
    """DPM-Solver++ (2M): multistep 2nd order, one model call per step."""
    return sample_planned("dpmpp_2m", denoise, x, sigmas, callback=callback)


def sample_dpmpp_2m_sde(denoise, x, sigmas, rng, eta: float = 1.0, callback=None):
    """DPM-Solver++ (2M) SDE: the stochastic 2M variant (k-diffusion's
    'dpmpp_2m_sde' with the default midpoint solver) — one model call per step,
    per-step noise injection scaled by the SDE's decay."""
    return sample_planned("dpmpp_2m_sde", denoise, x, sigmas, rng, callback,
                          eta=eta)


def sample_dpmpp_3m_sde(denoise, x, sigmas, rng, eta: float = 1.0, callback=None):
    """DPM-Solver++ (3M) SDE (k-diffusion's 'dpmpp_3m_sde'): third-order
    multistep in exponential-integrator form — one model call per step, the two
    previous x0 estimates building 1st/2nd difference corrections, per-step
    noise injection scaled by the SDE decay."""
    return sample_planned("dpmpp_3m_sde", denoise, x, sigmas, rng, callback,
                          eta=eta)


def lms_coefficient_matrix(sigmas, order: int = 4):
    """Adams-Bashforth coefficients for LMS over a concrete sigma schedule:
    ``C[i, j]`` weights the j-steps-back derivative at step i (zero-padded past
    the running order ``min(i+1, order)``). Shared by the eager loop below and
    the whole-loop compiled sampler (compiled.py), which needs them as one
    host-precomputed array — they depend only on the schedule, not the latent."""
    sig = np.asarray(sigmas, np.float64)

    def lms_coeff(order_, i, j):
        # integral over [sigma_i, sigma_i+1] of the Lagrange basis poly for ds.
        def poly(tau):
            prod = 1.0
            for k in range(order_):
                if k == j:
                    continue
                prod *= (tau - sig[i - k]) / (sig[i - j] - sig[i - k])
            return prod

        from numpy.polynomial.legendre import leggauss

        nodes, weights = leggauss(16)
        a, b = sig[i], sig[i + 1]
        tau = 0.5 * (b - a) * nodes + 0.5 * (b + a)
        return float(0.5 * (b - a) * np.sum(weights * np.vectorize(poly)(tau)))

    n = len(sig) - 1
    C = np.zeros((n, order), np.float64)
    for i in range(n):
        cur = min(i + 1, order)
        for j in range(cur):
            C[i, j] = lms_coeff(cur, i, j)
    return C


def sample_lms(denoise, x, sigmas, order: int = 4, callback=None):
    """Linear multistep (Katherine Crowson's LMS): Adams-Bashforth over the
    sigma schedule with numerically integrated coefficients."""
    sig = host_sigmas(sigmas)
    C = lms_coefficient_matrix(sig, order).astype(np.float32)
    ds = []
    for i in range(len(sig) - 1):
        x0 = denoise(x, sig[i])
        d = (x - x0) / np.float32(sig[i])
        ds.append(d)
        if len(ds) > order:
            ds.pop(0)
        cur = min(i + 1, order)
        x = x + sum(C[i, j] * d_ for j, d_ in zip(range(cur), reversed(ds)))
        x = apply_callback(callback, i, x)
    return x


def sample_lcm(denoise, x, sigmas, rng, callback=None):
    """Latent Consistency Model sampling (the host KSampler's ``lcm`` entry):
    each step takes the model's x0 prediction directly and re-noises it to the
    next sigma with FRESH noise — one jump per step, no ODE integration."""
    return sample_planned("lcm", denoise, x, sigmas, rng, callback)


def sample_ddpm(denoise, x, sigmas, rng, callback=None):
    """Ancestral DDPM in sigma space (k-diffusion's ``sample_ddpm`` /
    generic_step_sampler with the DDPM posterior step): the model's eps
    estimate drives the exact DDPM posterior mean in ᾱ-space, with posterior
    variance noise on every non-final step. x rides in k-diffusion's sigma
    scaling (x = √(1+σ²)·x_ᾱ) between steps."""
    return sample_planned("ddpm", denoise, x, sigmas, rng, callback)


def unipc_coeff_table(sigmas, order: int = 3, variant: str = "bh1"):
    """Host-precomputed per-step UniPC quantities (float64) — the analogue of
    ``lms_coefficient_matrix``: they depend only on the concrete schedule, so
    the eager loop and the whole-loop compiled twin consume the same table.

    UniPC (Zhao et al. 2023) in k-diffusion sigma space: with λ = -log σ the
    VP-space α factors cancel and the exponential-integrator base step is
    exactly the dpmpp one, ``(σ_next/σ)·x - expm1(-h)·m0``. Row i holds
    ``[h_phi_1, B_h, rp0, rp1, rc0, rc1, rc_t, rki0, rki1]`` for the step
    σ_i→σ_{i+1} at running order p = min(order, i+1, n-i) (warm-up ramp and
    the official lower_order_final ramp-down): predictor weights ``rp*`` for
    the older-history differences, corrector weights ``rc*`` plus the fresh
    ``rc_t·(m_t − m0)`` term, and ``rki*`` the 1/r_k factors that form those
    differences. Unused slots are zero, so consumers need no order branches.
    ``B_h`` encodes the variant (bh1: hh; bh2: expm1(hh)) — the runtime update
    is variant-agnostic."""
    sig = np.asarray(sigmas, np.float64)
    lam = -np.log(np.maximum(sig, 1e-10))
    n = len(sig) - 1
    table = np.zeros((n, 9))
    for i in range(n):
        p = max(1, min(order, i + 1, n - i))
        h = lam[i + 1] - lam[i]
        hh = -h
        h_phi_1 = np.expm1(hh)
        B_h = hh if variant == "bh1" else np.expm1(hh)
        rks, rkinv = [], []
        for j in range(1, p):
            rk = (lam[i - j] - lam[i]) / h
            rks.append(rk)
            rkinv.append(1.0 / rk)
        rks.append(1.0)  # the D1_t column
        R = np.array([[rk**k for rk in rks] for k in range(p)])
        b = np.zeros(p)
        fact = 1.0
        h_phi_k = h_phi_1 / hh - 1.0
        for k in range(1, p + 1):
            b[k - 1] = h_phi_k * fact / B_h
            fact *= k + 1
            h_phi_k = h_phi_k / hh - 1.0 / fact
        # Order 2 predictor is hardcoded to 0.5 in the official UniPC (and the
        # host KSampler's port of it) — "for order 2, we use a simplified
        # version" — not the 1×1 solve, which differs by O(h).
        if p == 1:
            rhos_p = np.zeros(0)
        elif p == 2:
            rhos_p = np.array([0.5])
        else:
            rhos_p = np.linalg.solve(R[:-1, :-1], b[:-1])
        rhos_c = np.linalg.solve(R, b) if p > 1 else np.array([0.5])
        row = table[i]
        row[0], row[1] = h_phi_1, B_h
        row[2 : 2 + len(rhos_p)] = rhos_p
        row[4 : 4 + len(rhos_c) - 1] = rhos_c[:-1]
        row[6] = rhos_c[-1]
        row[7 : 7 + len(rkinv)] = rkinv
    return table


def _sample_unipc(denoise, x, sigmas, callback=None, variant="bh1", order=3):
    """UniPC multistep predictor-corrector (data-prediction form). One model
    call per step: the corrector reuses the evaluation at the predictor's
    point, which then becomes the next step's history entry — the official
    multistep flow. Final (σ→0) step returns m0 directly."""
    sig = host_sigmas(sigmas)
    C = unipc_coeff_table(sig, order, variant).astype(np.float32)
    n = len(sig) - 1
    hist = [denoise(x, sig[0])]
    for i in range(n):
        m0 = hist[-1]
        if sig[i + 1] == 0.0:
            x = apply_callback(callback, i, m0)
            continue
        hphi1, Bh, rp0, rp1, rc0, rc1, rct, rki0, rki1 = C[i]
        D1_1 = (hist[-2] - m0) * rki0 if len(hist) >= 2 else 0.0
        D1_2 = (hist[-3] - m0) * rki1 if len(hist) >= 3 else 0.0
        base = np.float32(sig[i + 1] / sig[i]) * x - hphi1 * m0
        x_pred = base - Bh * (rp0 * D1_1 + rp1 * D1_2)
        m_t = denoise(x_pred, sig[i + 1])
        x = base - Bh * (rc0 * D1_1 + rc1 * D1_2 + rct * (m_t - m0))
        hist.append(m_t)
        if len(hist) > order:
            hist.pop(0)
        x = apply_callback(callback, i, x)
    return x


def sample_uni_pc(denoise, x, sigmas, callback=None):
    """UniPC, bh1 variant (the host KSampler's ``uni_pc`` entry)."""
    return _sample_unipc(denoise, x, sigmas, callback, variant="bh1")


def sample_uni_pc_bh2(denoise, x, sigmas, callback=None):
    """UniPC, bh2 variant (the host KSampler's ``uni_pc_bh2`` entry)."""
    return _sample_unipc(denoise, x, sigmas, callback, variant="bh2")


# One registry for the sigma-space samplers; stochastic ones (extra rng arg)
# are listed in RNG_SAMPLERS so dispatchers know the signature.
SAMPLERS = {
    "euler": sample_euler,
    "euler_ancestral": sample_euler_ancestral,
    "heun": sample_heun,
    "dpm_2": sample_dpm_2,
    "dpm_2_ancestral": sample_dpm_2_ancestral,
    "lms": sample_lms,
    "dpmpp_2s_ancestral": sample_dpmpp_2s_ancestral,
    "dpmpp_sde": sample_dpmpp_sde,
    "dpmpp_2m": sample_dpmpp_2m,
    "dpmpp_2m_sde": sample_dpmpp_2m_sde,
    "dpmpp_3m_sde": sample_dpmpp_3m_sde,
    "lcm": sample_lcm,
    "ddpm": sample_ddpm,
    "uni_pc": sample_uni_pc,
    "uni_pc_bh2": sample_uni_pc_bh2,
}
RNG_SAMPLERS = frozenset(
    {"euler_ancestral", "dpm_2_ancestral", "dpmpp_2s_ancestral", "dpmpp_sde",
     "dpmpp_2m_sde", "dpmpp_3m_sde", "lcm", "ddpm"}
)

# prediction="flow" renoising policy (host CONST-dispatch parity):
# - FLOW_VARIANTS: samplers the host swaps for an RF-specific form — we do too.
# - FLOW_REJECT: ddpm's alpha-bar posterior is an eps-schedule construction
#   with no flow meaning; reject loudly rather than produce garbage.
# - Everything else runs its generic form on the flow schedule (deterministic
#   samplers are exact there; the remaining SDE family keeps the generic
#   lambda-space noise the host also uses for them).
FLOW_VARIANTS = {
    "euler_ancestral": sample_euler_ancestral_rf,
    "dpmpp_2s_ancestral": sample_dpmpp_2s_ancestral_rf,
    "lcm": sample_lcm_rf,
}
FLOW_REJECT = frozenset({"ddpm"})
