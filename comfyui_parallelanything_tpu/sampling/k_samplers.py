"""k-diffusion-family samplers: Euler, Euler-ancestral, Heun, DPM++ 2M.

The reference is driven by its host's KSampler — every sampler in that menu calls the
(monkey-patched) ``diffusion_model.forward`` once or twice per step
(any_device_parallel.py:1287). To stand alone, this framework carries the standard
sigma-space sampler set itself. Host-side step loops like ddim.py/flow.py: each model
call routes through the (possibly parallelized) forward, so the DP/pipeline scheduler
sees exactly the per-step batched calls it is designed for.

Conventions (eps-prediction SD family, k-diffusion/EDM parameterization):
``sigma_t = sqrt((1-ᾱ_t)/ᾱ_t)``; model input is ``x/sqrt(sigma²+1)`` at the discrete
timestep nearest in log-sigma; denoised prediction ``x0 = x - sigma·eps``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .cfg import apply_callback, double_kwargs, rescale_guidance
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
        self.sigma_table = model_sigmas(alphas_cumprod)
        self.log_sigmas = jnp.log(self.sigma_table)

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

    def __call__(self, x: jnp.ndarray, sigma: jnp.ndarray) -> jnp.ndarray:
        batch = x.shape[0]
        if self.prediction == "flow":
            # Flow time is the sigma: the model takes x raw and t = σ directly.
            scale = 1.0
            t_vec = jnp.full((batch,), sigma, jnp.float32)
            x_in = x
        else:
            scale = 1.0 / jnp.sqrt(sigma**2 + 1.0)
            t_vec = jnp.full((batch,), self._timestep(sigma), jnp.float32)
            x_in = x * scale
        use_cfg = self.cfg_scale != 1.0 and self.uncond_context is not None
        if use_cfg:
            # Every per-batch kwarg doubles with the batch; uncond variants (e.g.
            # SDXL's negative pooled y) ride the second half (sampling/cfg.py).
            kw = double_kwargs(self.kwargs, self.uncond_kwargs, batch)
            eps_both = self.model(
                jnp.concatenate([x_in, x_in], axis=0),
                jnp.concatenate([t_vec, t_vec], axis=0),
                jnp.concatenate([self.context, self.uncond_context], axis=0),
                **kw,
            )
            eps_c, eps_u = jnp.split(eps_both, 2, axis=0)
            if (self.extra_conds or self.cond_area is not None
                    or self.cond_area_pct is not None
                    or self.cond_mask is not None):
                eps_c = self._combine_conds(eps_c, x_in, t_vec, batch)
            eps = eps_u + self.cfg_scale * (eps_c - eps_u)
            eps = rescale_guidance(eps, eps_c, self.cfg_rescale)
        else:
            eps = self.model(x_in, t_vec, self.context, **self.kwargs)
            if (self.extra_conds or self.cond_area is not None
                    or self.cond_area_pct is not None
                    or self.cond_mask is not None):
                eps = self._combine_conds(eps, x_in, t_vec, batch)
        if self.prediction == "v":
            return x / (sigma**2 + 1.0) - eps * sigma * scale
        # eps: x0 = x − σ·eps. flow: x0 = x − σ·v — the same expression.
        return x - sigma * eps


def sample_euler(denoise, x, sigmas, callback=None):
    """Deterministic Euler over the sigma schedule."""
    for i in range(len(sigmas) - 1):
        x0 = denoise(x, sigmas[i])
        d = (x - x0) / sigmas[i]
        x = x + d * (sigmas[i + 1] - sigmas[i])
        x = apply_callback(callback, i, x)
    return x


def ancestral_steps(s, s_next, eta: float = 1.0):
    """(sigma_down, sigma_up) for an ancestral step from ``s`` to ``s_next``
    (k-diffusion's get_ancestral_step): deterministic integration runs to
    sigma_down, then sigma_up of fresh noise restores the s_next level."""
    sigma_up = jnp.minimum(
        s_next,
        eta * jnp.sqrt(jnp.maximum(s_next**2 * (s**2 - s_next**2) / s**2, 0.0)),
    )
    sigma_down = jnp.sqrt(jnp.maximum(s_next**2 - sigma_up**2, 0.0))
    return sigma_down, sigma_up


def sample_euler_ancestral(denoise, x, sigmas, rng, eta: float = 1.0, callback=None):
    """Euler with ancestral noise injection (stochastic).

    RNG discipline (shared by every stochastic sampler here, their compiled
    twins, and the serving lanes): the step-``i`` key is ``fold_in(rng, i)``
    — a pure function of (request rng, step index), never of how many draws
    preceded it — so output is bit-identical whether the run executes alone,
    inside a compiled loop, or co-batched in a serving lane (round 10)."""
    for i in range(len(sigmas) - 1):
        s, s_next = sigmas[i], sigmas[i + 1]
        x0 = denoise(x, s)
        sigma_down, sigma_up = ancestral_steps(s, s_next, eta)
        d = (x - x0) / s
        x = x + d * (sigma_down - s)
        if float(s_next) > 0:
            sub = jax.random.fold_in(rng, i)
            x = x + sigma_up * jax.random.normal(sub, x.shape, x.dtype)
        x = apply_callback(callback, i, x)
    return x


def sample_euler_ancestral_rf(denoise, x, sigmas, rng, eta: float = 1.0,
                              callback=None):
    """Euler ancestral for rectified-flow schedules (the host's
    ``sample_euler_ancestral_RF``): under x_t = (1−t)·x0 + t·n the VE renoise
    ``x += σ_up·n`` would leave the (1−t)·x0 component unscaled, so the RF form
    rescales by the interpolant's alpha ratio and injects the variance that
    exactly restores the t_next marginal."""
    for i in range(len(sigmas) - 1):
        s, s_next = sigmas[i], sigmas[i + 1]
        x0 = denoise(x, s)
        if float(s_next) == 0.0:
            x = x0
        else:
            downstep = 1.0 + (s_next / s - 1.0) * eta
            sd = s_next * downstep
            alpha_ip1 = 1.0 - s_next
            alpha_down = 1.0 - sd
            renoise = jnp.sqrt(jnp.maximum(
                s_next**2 - sd**2 * alpha_ip1**2 / alpha_down**2, 0.0
            ))
            ratio = sd / s
            x = ratio * x + (1.0 - ratio) * x0
            sub = jax.random.fold_in(rng, i)
            x = (alpha_ip1 / alpha_down) * x + renoise * jax.random.normal(
                sub, x.shape, x.dtype
            )
        x = apply_callback(callback, i, x)
    return x


def sample_dpmpp_2s_ancestral_rf(denoise, x, sigmas, rng, eta: float = 1.0,
                                 callback=None):
    """DPM-Solver++(2S) ancestral for rectified-flow schedules (the host's
    ``sample_dpmpp_2s_ancestral_RF``): the exponential-integrator time is the
    flow log-SNR λ = log((1−σ)/σ), the midpoint sits at λ + h/2 (pinned to
    σ = 0.9999 when σ = 1, where λ diverges), and the renoise rescales by the
    interpolant's alpha ratio like the RF Euler-ancestral form."""
    for i in range(len(sigmas) - 1):
        s, s_next = sigmas[i], sigmas[i + 1]
        x0 = denoise(x, s)
        downstep = 1.0 + (s_next / s - 1.0) * eta
        sd = s_next * downstep
        alpha_ip1 = 1.0 - s_next
        alpha_down = 1.0 - sd
        renoise = jnp.sqrt(jnp.maximum(
            s_next**2 - sd**2 * alpha_ip1**2 / alpha_down**2, 0.0
        ))
        if float(s_next) == 0.0:
            d = (x - x0) / s
            x = x + d * (sd - s)
        else:
            if float(s) >= 1.0:
                sigma_mid = jnp.float32(0.9999)
            else:
                t_i = jnp.log((1.0 - s) / s)
                t_down = jnp.log((1.0 - sd) / sd)
                h = t_down - t_i
                sigma_mid = 1.0 / (jnp.exp(t_i + 0.5 * h) + 1.0)
            u = (sigma_mid / s) * x + (1.0 - sigma_mid / s) * x0
            x0_2 = denoise(u, sigma_mid)
            x = (sd / s) * x + (1.0 - sd / s) * x0_2
        if float(s_next) > 0:
            sub = jax.random.fold_in(rng, i)
            x = (alpha_ip1 / alpha_down) * x + renoise * jax.random.normal(
                sub, x.shape, x.dtype
            )
        x = apply_callback(callback, i, x)
    return x


def sample_lcm_rf(denoise, x, sigmas, rng, callback=None):
    """LCM on rectified-flow schedules: re-noising uses the flow interpolant
    ``x = t·n + (1−t)·x0`` (the host's CONST ``noise_scaling``) instead of the
    VE ``x0 + σ·n``."""
    for i in range(len(sigmas) - 1):
        x0 = denoise(x, sigmas[i])
        x = x0
        if float(sigmas[i + 1]) > 0:
            sub = jax.random.fold_in(rng, i)
            t = sigmas[i + 1]
            x = t * jax.random.normal(sub, x.shape, x.dtype) + (1.0 - t) * x0
        x = apply_callback(callback, i, x)
    return x


def sample_heun(denoise, x, sigmas, callback=None):
    """Heun's 2nd-order method (two model calls per step except the last)."""
    for i in range(len(sigmas) - 1):
        s, s_next = sigmas[i], sigmas[i + 1]
        x0 = denoise(x, s)
        d = (x - x0) / s
        x_pred = x + d * (s_next - s)
        if float(s_next) == 0.0:
            x = x_pred
        else:
            x0_2 = denoise(x_pred, s_next)
            d2 = (x_pred - x0_2) / s_next
            x = x + 0.5 * (d + d2) * (s_next - s)
        x = apply_callback(callback, i, x)
    return x


def sample_dpm_2(denoise, x, sigmas, callback=None):
    """DPM2 (k-diffusion ``sample_dpm_2``): explicit midpoint method — the
    second model call sits at the geometric mean of the step's sigmas."""
    for i in range(len(sigmas) - 1):
        s, s_next = sigmas[i], sigmas[i + 1]
        x0 = denoise(x, s)
        d = (x - x0) / s
        if float(s_next) == 0.0:
            x = x + d * (s_next - s)
        else:
            sigma_mid = jnp.exp(0.5 * (jnp.log(s) + jnp.log(s_next)))
            x_2 = x + d * (sigma_mid - s)
            x0_2 = denoise(x_2, sigma_mid)
            d_2 = (x_2 - x0_2) / sigma_mid
            x = x + d_2 * (s_next - s)
        x = apply_callback(callback, i, x)
    return x


def sample_dpm_2_ancestral(denoise, x, sigmas, rng, eta: float = 1.0, callback=None):
    """DPM2 ancestral (k-diffusion ``sample_dpm_2_ancestral``): the midpoint
    step runs to sigma_down, then sigma_up of fresh noise is injected."""
    for i in range(len(sigmas) - 1):
        s, s_next = sigmas[i], sigmas[i + 1]
        x0 = denoise(x, s)
        sigma_down, sigma_up = ancestral_steps(s, s_next, eta)
        d = (x - x0) / s
        if float(sigma_down) == 0.0:
            x = x + d * (sigma_down - s)
        else:
            sigma_mid = jnp.exp(0.5 * (jnp.log(s) + jnp.log(sigma_down)))
            x_2 = x + d * (sigma_mid - s)
            x0_2 = denoise(x_2, sigma_mid)
            d_2 = (x_2 - x0_2) / sigma_mid
            x = x + d_2 * (sigma_down - s)
        if float(s_next) > 0:
            sub = jax.random.fold_in(rng, i)
            x = x + sigma_up * jax.random.normal(sub, x.shape, x.dtype)
        x = apply_callback(callback, i, x)
    return x


def sample_dpmpp_2s_ancestral(denoise, x, sigmas, rng, eta: float = 1.0,
                              callback=None):
    """DPM-Solver++ (2S) ancestral (k-diffusion ``sample_dpmpp_2s_ancestral``):
    single-step 2nd order in exponential-integrator form (midpoint at
    r = 1/2 in log-sigma time), ancestral noise on every non-final step."""
    for i in range(len(sigmas) - 1):
        s, s_next = sigmas[i], sigmas[i + 1]
        x0 = denoise(x, s)
        sigma_down, sigma_up = ancestral_steps(s, s_next, eta)
        if float(sigma_down) == 0.0:
            d = (x - x0) / s
            x = x + d * (sigma_down - s)
        else:
            t, t_next = -jnp.log(s), -jnp.log(sigma_down)
            h = t_next - t
            sigma_mid = jnp.exp(-(t + 0.5 * h))
            x_2 = (sigma_mid / s) * x - jnp.expm1(-0.5 * h) * x0
            x0_2 = denoise(x_2, sigma_mid)
            x = (sigma_down / s) * x - jnp.expm1(-h) * x0_2
        if float(s_next) > 0:
            sub = jax.random.fold_in(rng, i)
            x = x + sigma_up * jax.random.normal(sub, x.shape, x.dtype)
        x = apply_callback(callback, i, x)
    return x


def sample_dpmpp_sde(denoise, x, sigmas, rng, eta: float = 1.0, callback=None):
    """DPM-Solver++ SDE (k-diffusion ``sample_dpmpp_sde``, r = 1/2): 2nd-order
    single-step with ancestral-style noise injected BOTH at the midpoint model
    call and at the step end — two model calls and two noise draws per step.
    Per-step keys: ``k_mid, k_end = split(fold_in(rng, i))`` — the fold_in
    discipline (see sample_euler_ancestral), with the two draws split from the
    step key (the compiled twin and the serving lanes consume the same)."""
    r = 0.5
    for i in range(len(sigmas) - 1):
        s, s_next = sigmas[i], sigmas[i + 1]
        x0 = denoise(x, s)
        if float(s_next) == 0.0:
            d = (x - x0) / s
            x = x + d * (s_next - s)
        else:
            sub = jax.random.fold_in(rng, i)
            k_mid, k_end = jax.random.split(sub)
            t, t_next = -jnp.log(s), -jnp.log(s_next)
            h = t_next - t
            sigma_mid = jnp.exp(-(t + r * h))
            fac = 1.0 / (2.0 * r)
            # Step 1: to the midpoint's sigma_down, + its sigma_up of noise.
            sd1, su1 = ancestral_steps(s, sigma_mid, eta)
            t_down1 = -jnp.log(jnp.maximum(sd1, 1e-10))
            x_2 = (sd1 / s) * x - jnp.expm1(t - t_down1) * x0
            x_2 = x_2 + su1 * jax.random.normal(k_mid, x.shape, x.dtype)
            x0_2 = denoise(x_2, sigma_mid)
            # Step 2: full step from the blended denoised estimate.
            sd2, su2 = ancestral_steps(s, s_next, eta)
            t_down2 = -jnp.log(jnp.maximum(sd2, 1e-10))
            x0_blend = (1.0 - fac) * x0 + fac * x0_2
            x = (sd2 / s) * x - jnp.expm1(t - t_down2) * x0_blend
            x = x + su2 * jax.random.normal(k_end, x.shape, x.dtype)
        x = apply_callback(callback, i, x)
    return x


def sample_dpmpp_2m(denoise, x, sigmas, callback=None):
    """DPM-Solver++ (2M): multistep 2nd order, one model call per step."""
    old_x0 = None
    for i in range(len(sigmas) - 1):
        s, s_next = sigmas[i], sigmas[i + 1]
        x0 = denoise(x, s)
        t, t_next = -jnp.log(s), -jnp.log(jnp.maximum(s_next, 1e-10))
        h = t_next - t
        if old_x0 is None or float(s_next) == 0.0:
            x = (s_next / s) * x - jnp.expm1(-h) * x0
        else:
            h_last = t - (-jnp.log(sigmas[i - 1]))
            r = h_last / h
            x0_prime = (1 + 1 / (2 * r)) * x0 - (1 / (2 * r)) * old_x0
            x = (s_next / s) * x - jnp.expm1(-h) * x0_prime
        old_x0 = x0
        x = apply_callback(callback, i, x)
    return x


def sample_dpmpp_2m_sde(denoise, x, sigmas, rng, eta: float = 1.0, callback=None):
    """DPM-Solver++ (2M) SDE: the stochastic 2M variant (k-diffusion's
    'dpmpp_2m_sde' with the default midpoint solver) — one model call per step,
    per-step noise injection scaled by the SDE's decay."""
    old_x0 = None
    h_last = None
    for i in range(len(sigmas) - 1):
        s, s_next = sigmas[i], sigmas[i + 1]
        x0 = denoise(x, s)
        if float(s_next) == 0.0:
            x = x0
        else:
            t, t_next = -jnp.log(s), -jnp.log(s_next)
            h = t_next - t
            eta_h = eta * h
            x = (
                (s_next / s) * jnp.exp(-eta_h) * x
                + (-jnp.expm1(-h - eta_h)) * x0
            )
            if old_x0 is not None:
                r = h_last / h
                # midpoint correction
                x = x + 0.5 * (-jnp.expm1(-h - eta_h)) * (1 / r) * (x0 - old_x0)
            if eta > 0:
                sub = jax.random.fold_in(rng, i)
                x = x + s_next * jnp.sqrt(
                    jnp.maximum(-jnp.expm1(-2 * eta_h), 0.0)
                ) * jax.random.normal(sub, x.shape, x.dtype)
            h_last = h
        old_x0 = x0
        x = apply_callback(callback, i, x)
    return x


def sample_dpmpp_3m_sde(denoise, x, sigmas, rng, eta: float = 1.0, callback=None):
    """DPM-Solver++ (3M) SDE (k-diffusion's 'dpmpp_3m_sde'): third-order
    multistep in exponential-integrator form — one model call per step, the two
    previous x0 estimates building 1st/2nd difference corrections, per-step
    noise injection scaled by the SDE decay."""
    x0_1 = x0_2 = None  # previous two denoised estimates
    h_1 = h_2 = None    # previous two log-sigma step sizes
    for i in range(len(sigmas) - 1):
        s, s_next = sigmas[i], sigmas[i + 1]
        x0 = denoise(x, s)
        if float(s_next) == 0.0:
            # Final (or interior-zero) step: no history update — a None h must
            # never enter the multistep state (k-diffusion updates history only
            # on non-zero steps).
            x = apply_callback(callback, i, x0)
            continue
        else:
            t, t_next = -jnp.log(s), -jnp.log(s_next)
            h = t_next - t
            h_eta = h * (eta + 1.0)
            x = jnp.exp(-h_eta) * x + (-jnp.expm1(-h_eta)) * x0
            if h_2 is not None:
                r0, r1 = h_1 / h, h_2 / h
                d1_0 = (x0 - x0_1) / r0
                d1_1 = (x0_1 - x0_2) / r1
                d1 = d1_0 + (d1_0 - d1_1) * r0 / (r0 + r1)
                d2 = (d1_0 - d1_1) / (r0 + r1)
                phi_2 = jnp.expm1(-h_eta) / h_eta + 1.0
                phi_3 = phi_2 / h_eta - 0.5
                x = x + phi_2 * d1 - phi_3 * d2
            elif h_1 is not None:
                r = h_1 / h
                d = (x0 - x0_1) / r
                phi_2 = jnp.expm1(-h_eta) / h_eta + 1.0
                x = x + phi_2 * d
            if eta > 0:
                sub = jax.random.fold_in(rng, i)
                x = x + s_next * jnp.sqrt(
                    jnp.maximum(-jnp.expm1(-2.0 * eta * h), 0.0)
                ) * jax.random.normal(sub, x.shape, x.dtype)
        x0_1, x0_2 = x0, x0_1
        h_1, h_2 = h, h_1
        x = apply_callback(callback, i, x)
    return x


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
    C = lms_coefficient_matrix(sigmas, order)
    ds = []
    for i in range(len(sigmas) - 1):
        x0 = denoise(x, sigmas[i])
        d = (x - x0) / sigmas[i]
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
    for i in range(len(sigmas) - 1):
        x0 = denoise(x, sigmas[i])
        x = x0
        if float(sigmas[i + 1]) > 0:
            sub = jax.random.fold_in(rng, i)
            x = x + sigmas[i + 1] * jax.random.normal(sub, x.shape, x.dtype)
        x = apply_callback(callback, i, x)
    return x


def sample_ddpm(denoise, x, sigmas, rng, callback=None):
    """Ancestral DDPM in sigma space (k-diffusion's ``sample_ddpm`` /
    generic_step_sampler with the DDPM posterior step): the model's eps
    estimate drives the exact DDPM posterior mean in ᾱ-space, with posterior
    variance noise on every non-final step. x rides in k-diffusion's sigma
    scaling (x = √(1+σ²)·x_ᾱ) between steps."""
    for i in range(len(sigmas) - 1):
        s, s_next = sigmas[i], sigmas[i + 1]
        x0 = denoise(x, s)
        eps = (x - x0) / s
        acp = 1.0 / (s**2 + 1.0)          # ᾱ_t from sigma
        acp_prev = 1.0 / (s_next**2 + 1.0)
        alpha = acp / acp_prev
        x_a = x / jnp.sqrt(1.0 + s**2)     # ᾱ-space sample
        mu = jnp.sqrt(1.0 / alpha) * (
            x_a - (1.0 - alpha) * eps / jnp.sqrt(1.0 - acp)
        )
        if float(s_next) > 0:
            sub = jax.random.fold_in(rng, i)
            var = (1.0 - alpha) * (1.0 - acp_prev) / (1.0 - acp)
            mu = mu + jnp.sqrt(var) * jax.random.normal(sub, x.shape, x.dtype)
            x = mu * jnp.sqrt(1.0 + s_next**2)  # back to sigma scaling
        else:
            x = mu
        x = apply_callback(callback, i, x)
    return x


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
    C = unipc_coeff_table(sigmas, order, variant)
    n = len(sigmas) - 1
    hist = [denoise(x, sigmas[0])]
    for i in range(n):
        s, s_next = sigmas[i], sigmas[i + 1]
        m0 = hist[-1]
        if float(s_next) == 0.0:
            x = apply_callback(callback, i, m0)
            continue
        hphi1, Bh, rp0, rp1, rc0, rc1, rct, rki0, rki1 = (float(v) for v in C[i])
        D1_1 = (hist[-2] - m0) * rki0 if len(hist) >= 2 else 0.0
        D1_2 = (hist[-3] - m0) * rki1 if len(hist) >= 3 else 0.0
        base = (s_next / s) * x - hphi1 * m0
        x_pred = base - Bh * (rp0 * D1_1 + rp1 * D1_2)
        m_t = denoise(x_pred, s_next)
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
