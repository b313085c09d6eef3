"""k-diffusion sampler family: schedules, denoiser wrapper, and the four samplers
against a tractable analytic model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu.sampling import (
    SAMPLERS,
    SCHEDULER_NAMES,
    EpsDenoiser,
    karras_sigmas,
    make_sigmas,
    sampling_sigmas,
    sample_dpmpp_2m,
    sample_euler,
    sample_euler_ancestral,
    sample_heun,
    scaled_linear_schedule,
)
from comfyui_parallelanything_tpu.sampling.k_samplers import model_sigmas


class TestSchedules:
    def test_sampling_sigmas_descending_to_zero(self):
        sig = sampling_sigmas(10)
        s = np.asarray(sig)
        assert len(s) == 11
        assert np.all(np.diff(s) < 0) or (np.all(np.diff(s[:-1]) < 0) and s[-1] == 0)
        assert s[-1] == 0.0

    def test_karras_sigmas_range(self):
        sig = np.asarray(karras_sigmas(12, sigma_min=0.03, sigma_max=14.0))
        assert len(sig) == 13
        assert sig[0] == pytest.approx(14.0, rel=1e-5)
        assert sig[-2] == pytest.approx(0.03, rel=1e-5)
        assert sig[-1] == 0.0

    def test_model_sigmas_monotonic(self):
        table = np.asarray(model_sigmas(scaled_linear_schedule()))
        assert np.all(np.diff(table) > 0)

    @pytest.mark.parametrize("name", SCHEDULER_NAMES)
    def test_every_scheduler_descends_to_zero(self, name):
        # Shared contract of the whole KSampler menu: descending sigmas ending in
        # exactly 0, starting at (or within the last integer stride of) the
        # model's sigma_max. ddim_uniform's integer stride means its realized
        # step count can differ slightly from the request — like the reference.
        acp = scaled_linear_schedule()
        sig = np.asarray(make_sigmas(name, 12, acp))
        table = np.asarray(model_sigmas(acp))
        if name == "ddim_uniform":
            assert 11 <= len(sig) <= 15
            assert sig[0] == pytest.approx(float(table[-1]), rel=0.1)
            # Reference stride starts at table index 1 (not 0).
            assert sig[-2] == pytest.approx(float(table[1]), rel=1e-5)
        else:
            assert len(sig) == 13
            assert sig[0] == pytest.approx(float(table[-1]), rel=1e-4)
        if name == "kl_optimal":
            # Inclusive interpolation: last nonzero sigma is exactly sigma_min.
            assert sig[-2] == pytest.approx(float(table[0]), rel=1e-4)
        assert sig[-1] == 0.0
        assert np.all(np.diff(sig[:-1]) < 0), f"{name}: {sig}"

    def test_sgm_uniform_is_trailing(self):
        # The sgm spacing drops the final uniform point: its last nonzero sigma
        # sits a full stride above sigma_min, unlike "normal".
        acp = scaled_linear_schedule()
        normal = np.asarray(make_sigmas("normal", 10, acp))
        sgm = np.asarray(make_sigmas("sgm_uniform", 10, acp))
        assert sgm[-2] > normal[-2] * 5

    def test_beta_denser_at_ends(self):
        # Beta(0.6, 0.6) quantiles cluster TIMESTEPS at both schedule ends (the
        # sigma table's nonlinearity hides this in sigma space, so recover the
        # timestep of each emitted sigma from the table and compare strides).
        acp = scaled_linear_schedule()
        table = np.asarray(model_sigmas(acp))
        sig = np.asarray(make_sigmas("beta", 20, acp))[:-1]
        ts = np.array([int(np.abs(table - s).argmin()) for s in sig])
        strides = -np.diff(ts)
        assert strides[0] < strides[len(strides) // 2]
        assert strides[-1] < strides[len(strides) // 2]

    def test_beta_high_step_count_has_no_duplicates(self):
        # At >=150 steps the rounded Beta quantiles collide at the schedule ends;
        # the reference skips repeated timesteps — a repeated sigma would
        # divide-by-zero the multistep samplers (lms, dpm++ 2m sde).
        acp = scaled_linear_schedule()
        for n in (150, 250):
            sig = np.asarray(make_sigmas("beta", n, acp))
            assert np.all(np.diff(sig[:-1]) < 0), f"duplicate sigmas at {n} steps"

    def test_ddim_uniform_high_step_count_honors_request(self):
        # stride<=1 falls back to uniform trailing spacing — the realized count
        # must track the request, not balloon to the table length. (In the
        # integer-stride regime the reference-faithful overshoot remains, e.g.
        # 400 requested -> stride 2 -> 500 realized.)
        acp = scaled_linear_schedule()
        for n in (600, 999):
            sig = np.asarray(make_sigmas("ddim_uniform", n, acp))
            assert len(sig) == n + 1, (n, len(sig))
        assert len(np.asarray(make_sigmas("ddim_uniform", 400, acp))) == 501

    def test_unknown_scheduler_raises(self):
        with pytest.raises(ValueError, match="unknown scheduler"):
            make_sigmas("cosine", 10)


def _linear_eps_model(true_x0):
    """An oracle eps model: given x = x0 + sigma·eps (k-diffusion forward process),
    the model input is x/sqrt(sigma²+1); recover eps exactly from the known x0.

    eps(x_in, t) with x_in = (x0 + sigma·eps)/sqrt(sigma²+1):
    eps = (x_in·sqrt(sigma²+1) − x0)/sigma, where sigma comes from the timestep.
    """
    table = model_sigmas(scaled_linear_schedule())

    def model(x_in, t_vec, context=None, **kw):
        sigma = jnp.interp(t_vec[0], jnp.arange(len(table), dtype=jnp.float32), table)
        x = x_in * jnp.sqrt(sigma**2 + 1.0)
        return (x - true_x0) / sigma

    return model


class TestSamplersRecoverX0:
    """With an oracle eps model every deterministic sampler must recover x0
    (almost) exactly — the integration error term vanishes when x0 is constant."""

    @pytest.fixture()
    def problem(self):
        x0 = jax.random.normal(jax.random.key(0), (2, 4, 4, 3), jnp.float32)
        sigmas = sampling_sigmas(12)
        noise = jax.random.normal(jax.random.key(1), x0.shape, jnp.float32)
        x_init = x0 + sigmas[0] * noise
        denoise = EpsDenoiser(_linear_eps_model(x0))
        return x0, x_init, sigmas, denoise

    def test_euler(self, problem):
        x0, x_init, sigmas, denoise = problem
        out = sample_euler(denoise, x_init, sigmas)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0), rtol=1e-2, atol=1e-2)

    def test_heun(self, problem):
        x0, x_init, sigmas, denoise = problem
        out = sample_heun(denoise, x_init, sigmas)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0), rtol=1e-2, atol=1e-2)

    def test_dpmpp_2m(self, problem):
        x0, x_init, sigmas, denoise = problem
        out = sample_dpmpp_2m(denoise, x_init, sigmas)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0), rtol=1e-2, atol=1e-2)

    def test_euler_ancestral_converges_near_x0(self, problem):
        x0, x_init, sigmas, denoise = problem
        out = sample_euler_ancestral(denoise, x_init, sigmas, jax.random.key(2))
        # Stochastic: looser tolerance, but must land near the oracle x0.
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0), rtol=0.15, atol=0.15)

    def test_dpmpp_3m_sde_converges_near_x0(self, problem):
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            sample_dpmpp_3m_sde,
        )

        x0, x_init, sigmas, denoise = problem
        out = sample_dpmpp_3m_sde(denoise, x_init, sigmas, jax.random.key(3))
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0), rtol=0.15, atol=0.15)

    def test_dpmpp_3m_sde_eta_zero_deterministic_and_tight(self, problem):
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            sample_dpmpp_3m_sde,
        )

        x0, x_init, sigmas, denoise = problem
        a = sample_dpmpp_3m_sde(denoise, x_init, sigmas, jax.random.key(3), eta=0.0)
        b = sample_dpmpp_3m_sde(denoise, x_init, sigmas, jax.random.key(9), eta=0.0)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(x0), rtol=1e-2, atol=1e-2)

    def test_lcm_recovers_x0_exactly(self, problem):
        from comfyui_parallelanything_tpu.sampling.k_samplers import sample_lcm

        x0, x_init, sigmas, denoise = problem
        out = sample_lcm(denoise, x_init, sigmas, jax.random.key(4))
        # The final LCM step returns the model x0 prediction directly — with an
        # oracle denoiser that is exact regardless of the noisy trajectory.
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0), rtol=1e-5, atol=1e-5)

    def test_ddpm_converges_near_x0(self, problem):
        from comfyui_parallelanything_tpu.sampling.k_samplers import sample_ddpm

        x0, x_init, sigmas, denoise = problem
        out = sample_ddpm(denoise, x_init, sigmas, jax.random.key(5))
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0), rtol=0.15, atol=0.15)

    def test_dpm_2_recovers_x0(self, problem):
        from comfyui_parallelanything_tpu.sampling.k_samplers import sample_dpm_2

        x0, x_init, sigmas, denoise = problem
        out = sample_dpm_2(denoise, x_init, sigmas)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0), rtol=1e-2, atol=1e-2)

    def test_dpm_2_ancestral_converges_near_x0(self, problem):
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            sample_dpm_2_ancestral,
        )

        x0, x_init, sigmas, denoise = problem
        out = sample_dpm_2_ancestral(denoise, x_init, sigmas, jax.random.key(6))
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0), rtol=0.15, atol=0.15)

    def test_dpmpp_2s_ancestral_converges_near_x0(self, problem):
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            sample_dpmpp_2s_ancestral,
        )

        x0, x_init, sigmas, denoise = problem
        out = sample_dpmpp_2s_ancestral(denoise, x_init, sigmas, jax.random.key(7))
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0), rtol=0.15, atol=0.15)

    def test_dpmpp_2s_ancestral_eta_zero_deterministic_and_tight(self, problem):
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            sample_dpmpp_2s_ancestral,
        )

        x0, x_init, sigmas, denoise = problem
        a = sample_dpmpp_2s_ancestral(denoise, x_init, sigmas, jax.random.key(7),
                                      eta=0.0)
        b = sample_dpmpp_2s_ancestral(denoise, x_init, sigmas, jax.random.key(11),
                                      eta=0.0)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(x0), rtol=1e-2, atol=1e-2)

    def test_dpmpp_sde_converges_near_x0(self, problem):
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            sample_dpmpp_sde,
        )

        x0, x_init, sigmas, denoise = problem
        out = sample_dpmpp_sde(denoise, x_init, sigmas, jax.random.key(8))
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0), rtol=0.15, atol=0.15)

    @pytest.mark.parametrize("name", ["uni_pc", "uni_pc_bh2"])
    def test_unipc_recovers_x0(self, problem, name):
        x0, x_init, sigmas, denoise = problem
        out = SAMPLERS[name](denoise, x_init, sigmas)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0), rtol=1e-2, atol=1e-2)

    def test_unipc_variants_differ_midway(self, problem):
        # bh1 and bh2 share the base step but weight the corrections
        # differently — a truncated (non-terminal) run must show it.
        x0, x_init, sigmas, denoise = problem
        a = SAMPLERS["uni_pc"](denoise, x_init, sigmas[:6])
        b = SAMPLERS["uni_pc_bh2"](denoise, x_init, sigmas[:6])
        assert float(jnp.abs(a - b).max()) > 0

    def test_unipc_coeff_table_shape_and_order_ramp(self):
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            unipc_coeff_table,
        )

        sigmas = sampling_sigmas(8)
        C = unipc_coeff_table(sigmas, order=3)
        assert C.shape == (8, 9)
        # Step 0 runs order 1: no predictor/older-corrector weights, rc_t=0.5.
        assert C[0, 2] == 0 and C[0, 4] == 0 and C[0, 6] == 0.5
        # Step 1 runs order 2: the official UniPC hardcodes the order-2
        # predictor weight to exactly 0.5 (not the 1×1 solve).
        assert C[1, 2] == 0.5 and C[1, 3] == 0
        # The final step also ramps down to order 1 (lower_order_final); the
        # penultimate runs order 2 with the same hardcoded predictor weight.
        assert C[-1, 2] == 0 and C[-1, 7] == 0
        assert C[-2, 2] == 0.5
        # An interior step at full order has predictor + history weights.
        assert C[4, 2] != 0 and C[4, 3] != 0 and C[4, 7] != 0

    def test_flow_oracle_recovers_x0_across_k_samplers(self):
        # prediction="flow": the k-diffusion ODE d = (x − x0)/σ IS the flow
        # velocity, so with an oracle velocity model every deterministic
        # sampler must recover x0 on a flow-time schedule.
        from comfyui_parallelanything_tpu.sampling.flow import flow_timesteps

        x0 = jax.random.normal(jax.random.key(0), (2, 4, 4, 3), jnp.float32)

        def vmodel(x, t_vec, context=None, **kw):
            return (x - x0) / t_vec[0]  # exact velocity under x_t=(1−t)x0+tn

        denoise = EpsDenoiser(vmodel, prediction="flow")
        sigmas = flow_timesteps(10, shift=1.15)
        noise = jax.random.normal(jax.random.key(1), x0.shape)
        x_init = sigmas[0] * noise + (1.0 - sigmas[0]) * x0
        for name in ("euler", "heun", "dpm_2", "dpmpp_2m", "uni_pc", "lms"):
            out = SAMPLERS[name](denoise, x_init, sigmas)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(x0), rtol=1e-2, atol=1e-2,
                err_msg=name,
            )

    def test_registry_complete(self):
        from comfyui_parallelanything_tpu.sampling import RNG_SAMPLERS

        assert set(SAMPLERS) == {
            "euler", "euler_ancestral", "heun", "dpm_2", "dpm_2_ancestral",
            "lms", "dpmpp_2s_ancestral", "dpmpp_sde", "dpmpp_2m",
            "dpmpp_2m_sde", "dpmpp_3m_sde", "lcm", "ddpm", "uni_pc",
            "uni_pc_bh2",
        }
        assert RNG_SAMPLERS <= set(SAMPLERS)


class TestCFGRescale:
    def test_rescale_matches_cond_std(self):
        from comfyui_parallelanything_tpu.sampling.cfg import rescale_guidance

        rng = np.random.default_rng(17)
        cond = jnp.asarray(rng.normal(size=(2, 8, 8, 4)), jnp.float32)
        guided = cond * 3.0 + 1.0  # inflated std (what high cfg does)
        full = rescale_guidance(guided, cond, 1.0)
        # phi=1: per-sample std matches the cond prediction exactly.
        np.testing.assert_allclose(
            np.asarray(full).std(axis=(1, 2, 3)),
            np.asarray(cond).std(axis=(1, 2, 3)), rtol=1e-5,
        )
        # phi=0: identity. phi=0.5: halfway.
        np.testing.assert_array_equal(
            np.asarray(rescale_guidance(guided, cond, 0.0)), np.asarray(guided)
        )
        half = rescale_guidance(guided, cond, 0.5)
        np.testing.assert_allclose(
            np.asarray(half), 0.5 * np.asarray(full) + 0.5 * np.asarray(guided),
            rtol=1e-6,
        )

    def test_run_sampler_accepts_cfg_rescale(self):
        # e2e: rescale changes the output when CFG is active.
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        noise = jax.random.normal(jax.random.key(1), (2, 8, 8, 4))
        ctx = jax.random.normal(jax.random.key(2), (2, 4, 8))
        un = jax.random.normal(jax.random.key(3), (2, 4, 8))

        def model2(x, t, context=None, **kw):
            # PER-SAMPLE context scale (CFG doubles the batch, so a global mean
            # would give cond and uncond halves the identical value) so the two
            # halves differ in STD — a constant offset would leave the rescale
            # factor at exactly 1.
            s = 0.1 + 0.05 * context.mean(axis=(1, 2))[:, None, None, None]
            return x * s

        base = run_sampler(model2, noise, ctx, sampler="euler", steps=3,
                           cfg_scale=5.0, uncond_context=un)
        resc = run_sampler(model2, noise, ctx, sampler="euler", steps=3,
                           cfg_scale=5.0, uncond_context=un, cfg_rescale=0.7)
        assert not np.allclose(np.asarray(base), np.asarray(resc))


class TestCFGBatching:
    def test_cfg_doubles_batch_through_model(self):
        calls = []

        def model(x, t, context=None, **kw):
            calls.append(x.shape[0])
            return jnp.zeros_like(x)

        den = EpsDenoiser(
            model,
            context=jnp.ones((2, 4, 8)),
            cfg_scale=5.0,
            uncond_context=jnp.zeros((2, 4, 8)),
        )
        x = jnp.ones((2, 4, 4, 3))
        den(x, jnp.float32(1.0))
        assert calls == [4]  # cond ‖ uncond fused into one forward


class TestNewSamplers:
    @pytest.mark.parametrize("sampler", ["lms", "dpmpp_2m_sde"])
    def test_converges_on_perfect_denoiser(self, sampler):
        """A denoise fn that always returns the target x0 must be recovered."""
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            karras_sigmas,
            sample_dpmpp_2m_sde,
            sample_lms,
        )

        target = 0.3

        sigmas = karras_sigmas(8)
        noise = jax.random.normal(jax.random.key(0), (1, 4, 4, 4))
        x = noise * sigmas[0]
        denoise = lambda x_, s: jnp.full_like(x_, target)
        if sampler == "lms":
            out = sample_lms(denoise, x, sigmas)
        else:
            out = sample_dpmpp_2m_sde(denoise, x, sigmas, jax.random.key(1), eta=0.0)
        np.testing.assert_allclose(np.asarray(out), target, rtol=1e-2, atol=2e-2)

    def test_sde_eta_zero_deterministic(self):
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            karras_sigmas,
            sample_dpmpp_2m_sde,
        )

        sigmas = karras_sigmas(5)
        x = jax.random.normal(jax.random.key(2), (1, 4, 4, 4)) * sigmas[0]
        denoise = lambda x_, s: x_ * 0.5
        a = sample_dpmpp_2m_sde(denoise, x, sigmas, jax.random.key(3), eta=0.0)
        b = sample_dpmpp_2m_sde(denoise, x, sigmas, jax.random.key(9), eta=0.0)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_sde_noise_depends_on_rng(self):
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            karras_sigmas,
            sample_dpmpp_2m_sde,
        )

        sigmas = karras_sigmas(5)
        x = jax.random.normal(jax.random.key(2), (1, 4, 4, 4)) * sigmas[0]
        denoise = lambda x_, s: x_ * 0.5
        a = sample_dpmpp_2m_sde(denoise, x, sigmas, jax.random.key(3))
        b = sample_dpmpp_2m_sde(denoise, x, sigmas, jax.random.key(9))
        assert not np.allclose(np.asarray(a), np.asarray(b))

    def test_run_sampler_dispatch(self):
        from comfyui_parallelanything_tpu.sampling.runner import (
            SAMPLER_NAMES,
            run_sampler,
        )

        assert "lms" in SAMPLER_NAMES and "dpmpp_2m_sde" in SAMPLER_NAMES
        noise = jax.random.normal(jax.random.key(0), (1, 4, 4, 4))
        for s in ("lms", "dpmpp_2m_sde"):
            out = run_sampler(
                lambda x, t, c=None, **kw: 0.1 * x, noise, None, sampler=s,
                steps=3, rng=jax.random.key(1),
            )
            assert np.isfinite(np.asarray(out)).all()


class TestFlowPredictionRouting:
    """prediction="flow" routes the k-sampler menu onto flow-time schedules —
    the host KSampler's CONST model-sampling wrapper for FLUX/SD3/WAN."""

    def _vmodel(self):
        def vmodel(x, t, context=None, **kw):
            return 0.2 * x + 0.1 * jnp.sin(t)[:, None, None, None]

        return vmodel

    def test_euler_flow_equals_flow_euler(self):
        # k-euler with flow prediction integrates the SAME ODE flow_euler
        # does: d = (x − x0)/σ = v. On an identical schedule the outputs must
        # agree to fp tolerance. (run_sampler's k-branch uses the host's
        # "normal" CONST ladder, which ends at σ_min≈1e-3 rather than
        # flow_euler's raw linspace — so the ladder is pinned explicitly.)
        from comfyui_parallelanything_tpu.sampling.flow import flow_euler_sample
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            flow_sigma_table,
            make_sigmas,
            sample_euler,
        )

        sigmas = make_sigmas("normal", 7, sigma_table=flow_sigma_table(1.3))
        noise = jax.random.normal(jax.random.key(0), (2, 4, 4, 4))
        x_init = sigmas[0] * noise
        a = flow_euler_sample(self._vmodel(), x_init, None, ts=sigmas)
        b = sample_euler(
            EpsDenoiser(self._vmodel(), prediction="flow"), x_init, sigmas
        )
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def test_flow_guidance_kwarg_reaches_model(self):
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        seen = []

        def vmodel(x, t, context=None, guidance=None, **kw):
            seen.append(guidance)
            return 0.1 * x

        noise = jax.random.normal(jax.random.key(0), (2, 4, 4, 4))
        run_sampler(vmodel, noise, None, sampler="dpmpp_2m", steps=3,
                    prediction="flow", guidance=2.5)
        assert seen and all(
            g is not None and g.shape == (2,) and float(g[0]) == 2.5
            for g in seen
        )

    def test_flow_img2img_mixes_toward_init(self):
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        noise = jax.random.normal(jax.random.key(0), (1, 4, 4, 4))
        init = jnp.full((1, 4, 4, 4), 3.0)
        out = run_sampler(self._vmodel(), noise, None, sampler="euler",
                          steps=4, prediction="flow", init_latent=init,
                          denoise=0.4)
        # Low strength keeps the result near the init, not the noise.
        assert float(jnp.abs(out - init).mean()) < float(jnp.abs(out - noise).mean())

    def test_ddim_rejects_flow(self):
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        noise = jax.random.normal(jax.random.key(0), (1, 4, 4, 4))
        with pytest.raises(ValueError, match="alpha-bar"):
            run_sampler(self._vmodel(), noise, None, sampler="ddim", steps=3,
                        prediction="flow")

    def test_ddpm_rejects_flow(self):
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        noise = jax.random.normal(jax.random.key(0), (1, 4, 4, 4))
        with pytest.raises(ValueError, match="rectified-flow"):
            run_sampler(self._vmodel(), noise, None, sampler="ddpm", steps=3,
                        prediction="flow", rng=jax.random.key(1))

    def test_flow_scheduler_menu_honored(self):
        # The host applies its scheduler menu to CONST (flow) models; karras
        # and normal must produce different flow-time ladders and outputs.
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            flow_sigma_table,
            make_sigmas,
        )
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        table = flow_sigma_table(shift=1.2)
        normal = make_sigmas("normal", 8, sigma_table=table)
        karras = make_sigmas("karras", 8, sigma_table=table)
        for sig in (normal, karras):
            s = np.asarray(sig)
            assert (np.diff(s) < 0).all() and s[-1] == 0.0
            assert s[0] <= 1.0 + 1e-6  # flow time never exceeds 1
        assert not np.allclose(np.asarray(normal), np.asarray(karras))

        noise = jax.random.normal(jax.random.key(0), (1, 4, 4, 4))
        a = run_sampler(self._vmodel(), noise, None, sampler="euler", steps=6,
                        prediction="flow", scheduler="normal")
        b = run_sampler(self._vmodel(), noise, None, sampler="euler", steps=6,
                        prediction="flow", scheduler="karras")
        assert not np.allclose(np.asarray(a), np.asarray(b))

    def test_euler_ancestral_flow_uses_rf_renoise(self):
        # Oracle flow model: the RF ancestral form must converge near x0,
        # and its output must differ from the VE renoise math.
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            flow_sigma_table,
            make_sigmas,
            sample_euler_ancestral,
            sample_euler_ancestral_rf,
        )

        x0 = jax.random.normal(jax.random.key(0), (2, 4, 4, 3), jnp.float32)

        def vmodel(x, t_vec, context=None, **kw):
            return (x - x0) / t_vec[0]

        denoise = EpsDenoiser(vmodel, prediction="flow")
        sigmas = make_sigmas("normal", 10, sigma_table=flow_sigma_table())
        noise = jax.random.normal(jax.random.key(1), x0.shape)
        x_init = sigmas[0] * noise + (1.0 - sigmas[0]) * x0
        rf = sample_euler_ancestral_rf(denoise, x_init, sigmas, jax.random.key(2))
        np.testing.assert_allclose(np.asarray(rf), np.asarray(x0),
                                   rtol=0.15, atol=0.15)
        # With the oracle denoiser the terminal step returns x0 exactly for
        # BOTH forms — the renoise difference shows on a truncated (non-
        # terminal) trajectory.
        rf_mid = sample_euler_ancestral_rf(
            denoise, x_init, sigmas[:5], jax.random.key(2)
        )
        ve_mid = sample_euler_ancestral(
            denoise, x_init, sigmas[:5], jax.random.key(2)
        )
        assert not np.allclose(np.asarray(rf_mid), np.asarray(ve_mid))

    def test_dpmpp_2s_ancestral_flow_uses_rf_form(self):
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            flow_sigma_table,
            make_sigmas,
            sample_dpmpp_2s_ancestral,
            sample_dpmpp_2s_ancestral_rf,
        )

        x0 = jax.random.normal(jax.random.key(0), (2, 4, 4, 3), jnp.float32)

        def vmodel(x, t_vec, context=None, **kw):
            return (x - x0) / t_vec[0]

        denoise = EpsDenoiser(vmodel, prediction="flow")
        sigmas = make_sigmas("normal", 10, sigma_table=flow_sigma_table())
        noise = jax.random.normal(jax.random.key(1), x0.shape)
        x_init = sigmas[0] * noise + (1.0 - sigmas[0]) * x0
        rf = sample_dpmpp_2s_ancestral_rf(denoise, x_init, sigmas,
                                          jax.random.key(2))
        np.testing.assert_allclose(np.asarray(rf), np.asarray(x0),
                                   rtol=0.15, atol=0.15)
        # Renoise forms differ on a truncated (non-terminal) trajectory.
        rf_mid = sample_dpmpp_2s_ancestral_rf(denoise, x_init, sigmas[:5],
                                              jax.random.key(2))
        ve_mid = sample_dpmpp_2s_ancestral(denoise, x_init, sigmas[:5],
                                           jax.random.key(2))
        assert not np.allclose(np.asarray(rf_mid), np.asarray(ve_mid))

    def test_lcm_flow_recovers_x0_exactly(self):
        from comfyui_parallelanything_tpu.sampling.k_samplers import (
            flow_sigma_table,
            make_sigmas,
            sample_lcm_rf,
        )

        x0 = jax.random.normal(jax.random.key(0), (2, 4, 4, 3), jnp.float32)

        def vmodel(x, t_vec, context=None, **kw):
            return (x - x0) / t_vec[0]

        denoise = EpsDenoiser(vmodel, prediction="flow")
        sigmas = make_sigmas("normal", 8, sigma_table=flow_sigma_table())
        noise = jax.random.normal(jax.random.key(1), x0.shape)
        x_init = sigmas[0] * noise + (1.0 - sigmas[0]) * x0
        out = sample_lcm_rf(denoise, x_init, sigmas, jax.random.key(2))
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0),
                                   rtol=1e-4, atol=1e-4)

    def test_flux_config_declares_flow(self):
        from comfyui_parallelanything_tpu.models import (
            flux_dev_config,
            wan_1_3b_config,
        )

        assert flux_dev_config().prediction == "flow"
        assert wan_1_3b_config().prediction == "flow"


class TestMultiCond:
    """Stock ConditioningCombine/SetArea semantics: per-cond predictions blend
    area-weight-normalized (EpsDenoiser._combine_conds)."""

    @staticmethod
    def _mean_model(x, t_vec, context=None, **kw):
        # Prediction = per-row mean of the context: trivially shows which
        # cond(s) drove each pixel, and respects CFG's batched cond‖uncond.
        m = jnp.mean(context, axis=tuple(range(1, context.ndim)))
        return jnp.ones_like(x) * m.reshape((-1,) + (1,) * (x.ndim - 1))

    def test_area_cond_blends_inside_box_only(self):
        x = jnp.zeros((1, 8, 8, 4), jnp.float32)
        ctx0 = jnp.zeros((1, 3, 5), jnp.float32)
        ctx1 = jnp.ones((1, 7, 5), jnp.float32)  # different token length: own call
        d = EpsDenoiser(
            self._mean_model, ctx0,
            extra_conds=[{"context": ctx1, "area": (4, 4, 0, 0),
                          "strength": 1.0}],
        )
        x0 = d(x, jnp.float32(1.0))
        eps = -(np.asarray(x0))  # x0 = x − σ·eps with x = 0, σ = 1
        # Inside the box both conds contribute: (1·0 + 1·1)/2.
        np.testing.assert_allclose(eps[0, 0, 0, 0], 0.5, atol=1e-6)
        np.testing.assert_allclose(eps[0, 3, 3, 0], 0.5, atol=1e-6)
        # Outside only the primary does.
        np.testing.assert_allclose(eps[0, 7, 7, 0], 0.0, atol=1e-6)
        np.testing.assert_allclose(eps[0, 0, 6, 0], 0.0, atol=1e-6)

    def test_mask_cond_equals_equivalent_area_box(self):
        # A pixel-space mask covering exactly the area box must weight
        # identically to SetArea (the SetMask path resizes pixels → latent
        # cells; box (4,4,0,0) in an 8×8 latent == top-left 32×32 px of 64²).
        x = jnp.zeros((1, 8, 8, 4), jnp.float32)
        ctx0 = jnp.zeros((1, 3, 5), jnp.float32)
        ctx1 = jnp.ones((1, 7, 5), jnp.float32)
        mask = jnp.zeros((1, 64, 64)).at[:, :32, :32].set(1.0)
        d_mask = EpsDenoiser(
            self._mean_model, ctx0,
            extra_conds=[{"context": ctx1, "mask": mask, "strength": 1.0}],
        )
        d_area = EpsDenoiser(
            self._mean_model, ctx0,
            extra_conds=[{"context": ctx1, "area": (4, 4, 0, 0),
                          "strength": 1.0}],
        )
        np.testing.assert_allclose(
            np.asarray(d_mask(x, jnp.float32(1.0))),
            np.asarray(d_area(x, jnp.float32(1.0))), atol=1e-6,
        )

    def test_mask_and_area_compose(self):
        # SetMask then SetArea: the cond carries both — stock composes
        # (area crop × mask weight), so only the INTERSECTION contributes.
        x = jnp.zeros((1, 8, 8, 4), jnp.float32)
        ctx0 = jnp.zeros((1, 3, 5), jnp.float32)
        ctx1 = jnp.ones((1, 7, 5), jnp.float32)
        mask = jnp.zeros((1, 64, 64)).at[:, :, :32].set(1.0)  # left half
        d = EpsDenoiser(
            self._mean_model, ctx0,
            extra_conds=[{"context": ctx1, "mask": mask,
                          "area": (4, 8, 0, 0), "strength": 1.0}],  # top half
        )
        eps = -np.asarray(d(x, jnp.float32(1.0)))
        np.testing.assert_allclose(eps[0, 0, 0, 0], 0.5, atol=1e-6)  # both
        np.testing.assert_allclose(eps[0, 0, 7, 0], 0.0, atol=1e-6)  # top-right: area only
        np.testing.assert_allclose(eps[0, 7, 0, 0], 0.0, atol=1e-6)  # bottom-left: mask only

        # Area strength × mask strength MULTIPLY (stock get_area_and_mult):
        # weight 0.5 × 0.5 = 0.25 against primary weight 1 → 0.25/1.25.
        d2 = EpsDenoiser(
            self._mean_model, ctx0,
            extra_conds=[{"context": ctx1, "mask": mask,
                          "area": (4, 8, 0, 0), "strength": 0.5,
                          "mask_strength": 0.5}],
        )
        eps2 = -np.asarray(d2(x, jnp.float32(1.0)))
        np.testing.assert_allclose(eps2[0, 0, 0, 0], 0.25 / 1.25, atol=1e-6)

    def test_primary_cond_mask_scopes_primary(self):
        # SetMask on the PRIMARY positive: outside the mask no cond covers
        # the pixel → falls back to the primary prediction (the divide-by-
        # zero guard), inside it's primary-as-usual.
        x = jnp.zeros((1, 8, 8, 4), jnp.float32)
        ctx0 = jnp.ones((1, 3, 5), jnp.float32)
        mask = jnp.zeros((1, 64, 64)).at[:, :32, :].set(1.0)
        d = EpsDenoiser(self._mean_model, ctx0, cond_mask=mask)
        out = d(x, jnp.float32(1.0))
        eps = -np.asarray(out)
        np.testing.assert_allclose(eps[0, 0, 0, 0], 1.0, atol=1e-6)
        np.testing.assert_allclose(eps[0, 7, 7, 0], 1.0, atol=1e-6)

    def test_full_frame_combine_averages(self):
        x = jnp.zeros((1, 4, 4, 2), jnp.float32)
        d = EpsDenoiser(
            self._mean_model, jnp.zeros((1, 3, 5)),
            extra_conds=[{"context": jnp.ones((1, 3, 5))}],
        )
        eps = -np.asarray(d(x, jnp.float32(1.0)))
        np.testing.assert_allclose(eps, 0.5, atol=1e-6)

    def test_strengths_weight_the_blend(self):
        x = jnp.zeros((1, 4, 4, 2), jnp.float32)
        d = EpsDenoiser(
            self._mean_model, jnp.zeros((1, 3, 5)),
            extra_conds=[{"context": jnp.ones((1, 3, 5)), "strength": 3.0}],
        )
        eps = -np.asarray(d(x, jnp.float32(1.0)))
        np.testing.assert_allclose(eps, 0.75, atol=1e-6)  # (0·1 + 1·3)/(1+3)

    def test_cfg_applies_extras_to_cond_half_only(self):
        x = jnp.zeros((1, 4, 4, 2), jnp.float32)
        d = EpsDenoiser(
            self._mean_model, jnp.zeros((1, 3, 5)),
            cfg_scale=2.0, uncond_context=jnp.full((1, 3, 5), -1.0),
            extra_conds=[{"context": jnp.ones((1, 3, 5))}],
        )
        eps = -np.asarray(d(x, jnp.float32(1.0)))
        # cond = (0+1)/2 = 0.5 blended; uncond = −1; cfg: −1 + 2·(0.5 − (−1)).
        np.testing.assert_allclose(eps, 2.0, atol=1e-5)

    def test_multi_cond_rejected_on_ddim_and_flow_euler(self):
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        with pytest.raises(ValueError, match="k-sampler"):
            run_sampler(
                lambda x, t, c=None, **k: x, jnp.zeros((1, 4, 4, 4)),
                jnp.zeros((1, 3, 5)), sampler="ddim", steps=2,
                extra_conds=[{"context": jnp.ones((1, 3, 5))}],
            )

    def test_non_divisor_extra_cond_batch_raises(self):
        # Direct run_sampler/EpsDenoiser API callers (no node-layer
        # pre-validation) get the same clear error the node layer raises, not
        # a silent 1x repeat followed by an XLA shape mismatch.
        x = jnp.zeros((3, 4, 4, 2), jnp.float32)
        d = EpsDenoiser(
            self._mean_model, jnp.zeros((3, 3, 5)),
            extra_conds=[{"context": jnp.ones((2, 3, 5))}],
        )
        with pytest.raises(ValueError, match="does not divide"):
            d(x, jnp.float32(1.0))

    def test_timestep_range_gates_extras(self):
        # Stock SetTimestepRange + Combine: the extra prompt contributes only
        # inside its progress window. eps family: progress = 1 - t/999.
        x = jnp.zeros((1, 4, 4, 2), jnp.float32)
        d = EpsDenoiser(
            self._mean_model, jnp.zeros((1, 3, 5)),
            extra_conds=[{"context": jnp.ones((1, 3, 5)),
                          "timestep_range": (0.0, 0.5)}],
        )
        # x0 = x - sigma*eps with x = 0, so eps = -x0/sigma.
        # Early sampling: sigma high -> t near table top -> progress ~0: ON.
        s_hi = float(d.sigma_table[-1])
        eps_early = -np.asarray(d(x, d.sigma_table[-1])) / s_hi
        np.testing.assert_allclose(eps_early, 0.5, atol=1e-5)
        # Late sampling: sigma low -> progress ~1: OFF (primary only).
        s_lo = float(d.sigma_table[0])
        eps_late = -np.asarray(d(x, d.sigma_table[0])) / s_lo
        np.testing.assert_allclose(eps_late, 0.0, atol=1e-5)


class TestAreaPercentage:
    @staticmethod
    def _mean_model(x, t_vec, context=None, **kw):
        m = jnp.mean(context, axis=tuple(range(1, context.ndim)))
        return jnp.ones_like(x) * m.reshape((-1,) + (1,) * (x.ndim - 1))

    def test_fractional_box_equals_pixel_box(self):
        # area_pct (0.5, 0.5, 0, 0) on an 8x8 latent == area (4, 4, 0, 0).
        x = jnp.zeros((1, 8, 8, 4), jnp.float32)
        ctx0 = jnp.zeros((1, 3, 5), jnp.float32)
        ctx1 = jnp.ones((1, 7, 5), jnp.float32)
        d_pct = EpsDenoiser(
            self._mean_model, ctx0,
            extra_conds=[{"context": ctx1,
                          "area_pct": (0.5, 0.5, 0.0, 0.0),
                          "strength": 1.0}],
        )
        d_px = EpsDenoiser(
            self._mean_model, ctx0,
            extra_conds=[{"context": ctx1, "area": (4, 4, 0, 0),
                          "strength": 1.0}],
        )
        np.testing.assert_allclose(
            np.asarray(d_pct(x, jnp.float32(1.0))),
            np.asarray(d_px(x, jnp.float32(1.0))), atol=1e-6,
        )

    def test_primary_pct_scopes(self):
        x = jnp.zeros((1, 8, 8, 4), jnp.float32)
        d = EpsDenoiser(self._mean_model, jnp.ones((1, 3, 5)),
                        cond_area_pct=(0.5, 1.0, 0.0, 0.0))
        out = d(x, jnp.float32(1.0))
        assert np.isfinite(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# The eager loop: schedule scalars on the host, two programs around the
# denoiser, no read inside the loop.
# ---------------------------------------------------------------------------


def _toy_apply(params, x, t, context=None, **kw):
    """A smooth stand-in denoiser whose output depends on the input, the
    timestep and the conditioning (so CFG's two halves differ)."""
    shape = (-1,) + (1,) * (x.ndim - 1)
    out = params["w"] * x + 0.1 * jnp.sin(t * params["f"]).reshape(shape)
    if context is not None:
        out = out + 0.05 * jnp.mean(context, axis=(1, 2)).reshape(shape)
    return out


def _toy_numpy(x, t, context, w, f):
    shape = (-1,) + (1,) * (x.ndim - 1)
    out = w * x + 0.1 * np.sin(np.asarray(t, np.float64) * f).reshape(shape)
    if context is not None:
        out = out + 0.05 * np.mean(context, axis=(1, 2)).reshape(shape)
    return out


def _toy_problem(prediction, cfg, ends_at_zero):
    """(model fn, run_sampler kwargs, float64 ``denoise(x, sigma)``, sigmas)."""
    from comfyui_parallelanything_tpu.sampling.k_samplers import flow_sigma_table

    r = np.random.default_rng(5)
    w, f = 0.3, (6.0 if prediction == "flow" else 0.01)
    params = {"w": jnp.float32(w), "f": jnp.float32(f)}
    ctx = r.normal(size=(2, 5, 8)).astype(np.float32)
    unc = r.normal(size=(2, 5, 8)).astype(np.float32)
    acp = np.asarray(scaled_linear_schedule(), np.float64)
    if prediction == "flow":
        sigmas = make_sigmas("normal", 7, sigma_table=flow_sigma_table(1.3))
    else:
        sigmas = make_sigmas("karras", 7, scaled_linear_schedule())
    sigmas = np.asarray(sigmas)
    if not ends_at_zero:
        sigmas = sigmas[:6]
    table = 0.5 * np.log((1.0 - acp) / acp)

    def denoise(x, sigma):
        if prediction == "flow":
            x_in, t = x, sigma
        else:
            x_in = x / np.sqrt(sigma**2 + 1.0)
            t = np.interp(np.log(sigma), table, np.arange(len(table)))
        t = np.full((x.shape[0],), t)
        pred = _toy_numpy(x_in, t, ctx.astype(np.float64), w, f)
        if cfg != 1.0:
            pred_u = _toy_numpy(x_in, t, unc.astype(np.float64), w, f)
            pred = pred_u + cfg * (pred - pred_u)
        return x - sigma * pred

    kwargs = dict(prediction=prediction, cfg_scale=cfg, sigmas=sigmas)
    if cfg != 1.0:
        kwargs["uncond_context"] = jnp.asarray(unc)
    model = lambda x, t, c=None, **kw: _toy_apply(params, x, t, c, **kw)  # noqa: E731
    return model, jnp.asarray(ctx), kwargs, denoise, sigmas.astype(np.float64)


def _reference_noise(rng, split, shape):
    """Step i's draws as the program makes them: fold_in(rng, i), its split
    halves for dpmpp_sde."""
    def noise(i, col):
        key = jax.random.fold_in(rng, i)
        if split:
            key = jax.random.split(key)[col]
        return np.asarray(jax.random.normal(key, shape, jnp.float32), np.float64)

    return noise


_LOOP_CASES = [(name, "eps") for name in SAMPLERS] + [
    (name, "flow") for name in SAMPLERS if name != "ddpm"  # FLOW_REJECT
]


class TestHostScheduleLoop:
    @pytest.mark.parametrize("name,prediction", _LOOP_CASES)
    def test_loop_matches_float64_transcription(self, name, prediction):
        """run_sampler's eager loop against k-diffusion written out in float64
        numpy: with and without CFG, on a schedule that ends at 0 and one that
        does not."""
        from k_sampler_numpy import REFERENCE, REFERENCE_FLOW

        from comfyui_parallelanything_tpu.sampling import RNG_SAMPLERS
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        ref = REFERENCE[name]
        if prediction == "flow":
            ref = REFERENCE_FLOW.get(name, ref)
        rng = jax.random.key(11)
        noise = jax.random.normal(jax.random.key(3), (2, 4, 4, 3), jnp.float32)
        for cfg in (1.0, 3.0):
            for ends_at_zero in (True, False):
                model, ctx, kwargs, denoise, sig = _toy_problem(
                    prediction, cfg, ends_at_zero)
                out = run_sampler(model, noise, ctx, sampler=name,
                                  steps=len(sig) - 1, rng=rng, **kwargs)
                x = np.asarray(noise, np.float64) * sig[0]
                # run_sampler hands the stochastic samplers fold_in(rng, 1).
                draws = _reference_noise(jax.random.fold_in(rng, 1),
                                         name == "dpmpp_sde", noise.shape)
                want = ref(denoise, x, sig, draws if name in RNG_SAMPLERS else None)
                err = np.abs(np.asarray(out, np.float64) - want).max()
                # VE ancestral noise on a flow ladder's last step (0.2 → 0.0013)
                # leaves sigma_down = 8e-6 and a midpoint derivative weighted
                # 160-fold: float32 cancellation, in any form of the step.
                tol = 4e-5 if (name, prediction) == ("dpm_2_ancestral", "flow") else 1e-5
                assert err <= tol * np.abs(want).max(), (cfg, ends_at_zero, err)

    @pytest.mark.parametrize("name", list(SAMPLERS))
    def test_no_device_to_host_read_inside_the_loop(self, name, monkeypatch):
        """Between the first and the last step nothing reads a device value.
        The CPU backend does not honour transfer_guard_device_to_host, so the
        array's own conversions are armed instead."""
        import jax._src.array as jarray

        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        armed = [False]

        def guard(real):
            def method(self, *a, **kw):
                assert not armed[0], f"device value read inside the loop: {real.__name__}"
                return real(self, *a, **kw)
            return method

        for attr in ("__float__", "__bool__", "__int__", "__index__",
                     "__array__", "item", "tolist"):
            monkeypatch.setattr(jarray.ArrayImpl, attr,
                                guard(getattr(jarray.ArrayImpl, attr)))
        noise = jax.random.normal(jax.random.key(3), (2, 4, 4, 3), jnp.float32)
        for prediction in ("eps", "flow"):
            if (name, prediction) not in _LOOP_CASES:
                continue
            model, ctx, kwargs, _, sig = _toy_problem(prediction, 3.0, True)
            n = len(sig) - 1

            def cb(i, x):
                armed[0] = i < n - 1

            with jax.transfer_guard_device_to_host("disallow"):
                out = run_sampler(model, noise, ctx, sampler=name, steps=n,
                                  rng=jax.random.key(2), callback=cb, **kwargs)
            assert not armed[0]
            assert np.isfinite(np.asarray(out)).all()


def _host_line_events(trace_dir):
    """Names on the python thread's line of a CPU profile, in time order."""
    import glob

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    (plane,) = [p for p in data.planes if p.name == "/host:CPU"]
    events = [e for line in plane.lines for e in line.events
              if e.name.startswith(("PjitFunction(", "boundary:"))
              or e.name == "PjRtCpuExecutable::Execute"]
    return [e.name for e in sorted(events, key=lambda e: e.start_ns)]


class TestProgramsAStep:
    @pytest.mark.parametrize("sampler,prediction", [("dpmpp_2m", "eps"),
                                                    ("euler", "flow")])
    def test_a_step_is_two_named_programs_around_the_model(
            self, sampler, prediction, tmp_path):
        """The benchmark cells' two paths (CFG on): a step executes at most 4
        XLA programs besides the model's own, every step after the second runs
        executables that exist, and no new module's name matches the patterns
        by which the benchmark finds the denoiser and the decode."""
        import json
        import re
        from pathlib import Path

        from comfyui_parallelanything_tpu.models.api import DiffusionModel
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler
        from comfyui_parallelanything_tpu.utils.telemetry import (
            compile_snapshot,
            watch_compiles,
        )

        cfg = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                          / "configs" / "sd15.json").read_text())
        patterns = [p for group in ("denoiser", "decode")
                    for p in cfg["trace_modules"][group]]
        assert patterns

        def apply(params, x, t, context=None, **kw):  # the program's own name
            return _toy_apply(params, x, t, context, **kw)

        model = DiffusionModel(
            apply=apply, name="toy",
            params={"w": jnp.float32(0.3), "f": jnp.float32(0.01)})
        r = np.random.default_rng(0)
        noise = jnp.asarray(r.normal(size=(2, 8, 8, 4)).astype(np.float32))
        ctx = jnp.asarray(r.normal(size=(2, 6, 16)).astype(np.float32))
        watch_compiles()
        compiles = []

        def cb(i, x):
            compiles.append(compile_snapshot()["compiles"])
            with jax.profiler.TraceAnnotation(f"boundary:{i}"):
                pass

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            run_sampler(model, noise, ctx, sampler=sampler, steps=6,
                        cfg_scale=7.0, uncond_context=jnp.zeros_like(ctx),
                        prediction=prediction, callback=cb).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        assert len(compiles) == 6 and compiles[1] == compiles[-1], compiles
        # Programs by step: the PjitFunction that each Execute ran, between
        # one boundary and the next.
        steps, current, last = [], [], None
        for name in _host_line_events(tmp_path):
            if name.startswith("boundary:"):
                steps.append(current)
                current = []
            elif name.startswith("PjitFunction("):
                last = name[len("PjitFunction("):-1]
            else:
                current.append(last)
        assert len(steps) == 6
        for programs in steps[1:]:
            assert programs.count("apply") == 1, programs
            own = [p for p in programs if p != "apply"]
            assert 2 <= len(own) <= 4, programs
            assert {"sampler_prepare", "sampler_finish"} <= set(own), programs
            for p in own:
                for pattern in patterns:
                    assert not re.search(pattern, f"jit_{p}("), (p, pattern)
        assert any(re.search(p, "jit_apply(") for p in patterns)


class TestRunAhead:
    def test_host_stays_one_step_ahead(self):
        """With a forward slow enough to see: when step i's callback fires the
        latent of step i − 1 is ready (the loop waited on it, behind the
        queued forward of step i), and step i's own is still being computed —
        one step ahead, no more. The flow path read nothing back and ran every
        step ahead of the device before. (The CPU backend dispatches a program
        that calls back to the host synchronously, so the forward cannot be
        made to block on an event here; readiness is asked of the arrays.)"""
        from comfyui_parallelanything_tpu.models.api import DiffusionModel
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        def apply(params, x, t, context=None, **kw):
            w = params["w"]
            for _ in range(6):  # some tens of milliseconds on the CPU
                w = jnp.tanh(w @ params["w"])
            return 0.3 * x + 1e-9 * jnp.mean(w)

        model = DiffusionModel(
            apply=apply, name="slow",
            params={"w": jnp.full((1024, 1024), 1e-3, jnp.float32)})
        noise = jax.random.normal(jax.random.key(0), (2, 8, 8, 4))
        steps = 6
        for measured in (False, True):  # the first round compiles
            latents, behind_ready, own_ready = [], [], []

            def cb(i, x):
                if latents:
                    behind_ready.append(latents[-1].is_ready())
                own_ready.append(x.is_ready())
                latents.append(x)

            run_sampler(model, noise, None, sampler="euler", steps=steps,
                        prediction="flow", callback=cb).block_until_ready()
            if measured:
                assert behind_ready == [True] * (steps - 1)
                assert not all(own_ready), own_ready

    def test_spans_counters_and_interrupt(self):
        from comfyui_parallelanything_tpu.models.api import DiffusionModel
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler
        from comfyui_parallelanything_tpu.utils import progress, tracing
        from comfyui_parallelanything_tpu.utils.metrics import registry

        model = DiffusionModel(
            apply=_toy_apply, name="toy-spans",
            params={"w": jnp.float32(0.3), "f": jnp.float32(0.01)})
        r = np.random.default_rng(0)
        noise = jnp.asarray(r.normal(size=(2, 8, 8, 4)).astype(np.float32))
        ctx = jnp.asarray(r.normal(size=(2, 6, 16)).astype(np.float32))
        cfg = dict(cfg_scale=7.0, uncond_context=jnp.zeros_like(ctx))

        def calls():
            return registry.get("pa_denoiser_calls_total",
                                {"program": "model-apply:toy-spans"}) or 0.0

        def loops(path, sampler="dpmpp_2m"):
            return registry.get("pa_sampler_loop_total",
                                {"path": path, "sampler": sampler}) or 0.0

        tracing.disable()
        tracing.tracer.clear()
        tracing.enable()
        try:
            c0, p0, e0 = calls(), loops("planned"), loops("eager")
            run_sampler(model, noise, ctx, sampler="dpmpp_2m", steps=20, **cfg)
            names = [e["name"] for e in tracing.export()["traceEvents"]
                     if e.get("ph") == "X"]
            assert names.count("step") == 20 and names.count("denoise") == 20
            assert calls() - c0 == 20
            assert (loops("planned") - p0, loops("eager") - e0) == (1, 0)
        finally:
            tracing.disable()
            tracing.tracer.clear()
        # Multi-cond calls the model again from Python: the denoiser is called
        # whole, and the run counts as eager. So does a sampler with no plan.
        run_sampler(model, noise, ctx, sampler="dpmpp_2m", steps=3,
                    extra_conds=[{"context": ctx * 0.5}], **cfg)
        assert (loops("planned") - p0, loops("eager") - e0) == (1, 1)
        u0 = loops("eager", "uni_pc")
        run_sampler(model, noise, ctx, sampler="uni_pc", steps=3, **cfg)
        assert loops("eager", "uni_pc") - u0 == 1
        # An interrupt raised at step 3 stops the run there: at most one more
        # forward is dispatched than steps were reported.
        c1 = calls()

        def hook(value, max_value):
            if value == 3:
                progress.request_interrupt()

        prev = progress.set_progress_hook(hook)
        try:
            with pytest.raises(progress.Interrupted):
                run_sampler(model, noise, ctx, sampler="dpmpp_2m", steps=20, **cfg)
        finally:
            progress.set_progress_hook(prev)
            progress.clear_interrupt()
        assert 3 <= calls() - c1 <= 4
