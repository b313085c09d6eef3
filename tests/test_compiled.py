"""Whole-loop compiled sampling (sampling/compiled.py): every sampler's scan
program must match its eager twin step-for-step, on bare models and on a
parallel chain over the virtual mesh, including CFG, img2img, and the traced
inpaint-mask hook; non-traceable cases must fall back to the eager loops."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu import DeviceChain, parallelize
from comfyui_parallelanything_tpu.sampling.runner import run_sampler

SHAPE = (2, 8, 8, 4)


def _toy_model(calls=None):
    def f(x, t, context=None, **kwargs):
        if calls is not None:
            calls.append(1)
        h = 0.12 * x * jnp.cos(t)[:, None, None, None]
        if context is not None:
            h = h + 0.01 * context.sum(axis=(1, 2))[:, None, None, None]
        if kwargs.get("y") is not None:
            h = h + 0.001 * kwargs["y"][:, None, None, :]
        return h

    return f


def _noise(seed=0, shape=SHAPE):
    return jax.random.normal(jax.random.key(seed), shape)


def _ctx(seed=3, batch=SHAPE[0]):
    return jax.random.normal(jax.random.key(seed), (batch, 6, 16))


ALL_SAMPLERS = [
    "euler", "euler_ancestral", "heun", "dpm_2", "dpm_2_ancestral", "lms",
    "dpmpp_2s_ancestral", "dpmpp_sde", "dpmpp_2m", "dpmpp_2m_sde",
    "dpmpp_3m_sde", "lcm", "ddpm", "uni_pc", "uni_pc_bh2", "ddim",
    "flow_euler",
]


def _run(sampler, compile_loop, model=None, **kw):
    model = model or _toy_model()
    args = dict(
        sampler=sampler, steps=5, rng=jax.random.key(7),
        compile_loop=compile_loop,
    )
    args.update(kw)
    return run_sampler(model, _noise(), _ctx(), **args)


class TestEagerCompiledEquivalence:
    @pytest.mark.parametrize("sampler", ALL_SAMPLERS)
    def test_plain(self, sampler):
        a = _run(sampler, False)
        b = _run(sampler, True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("sampler", ["euler", "dpmpp_2m", "ddim", "flow_euler"])
    def test_cfg(self, sampler):
        kw = dict(cfg_scale=4.0, uncond_context=_ctx(seed=9), cfg_rescale=0.3)
        a = _run(sampler, False, **kw)
        b = _run(sampler, True, **kw)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("sampler", ["euler", "euler_ancestral", "ddim",
                                         "flow_euler"])
    def test_img2img_and_mask(self, sampler):
        mask = jnp.zeros((1, 8, 8, 1)).at[:, :4].set(1.0)
        kw = dict(
            init_latent=jnp.full(SHAPE, 0.5), denoise=0.6, latent_mask=mask,
        )
        a = _run(sampler, False, **kw)
        b = _run(sampler, True, **kw)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("sampler", ["euler", "dpmpp_2m", "uni_pc",
                                         "euler_ancestral",
                                         "dpmpp_2s_ancestral", "lcm"])
    def test_flow_prediction(self, sampler):
        # Flow-time k-sampling (FLUX/SD3/WAN routing): the compiled loop must
        # match eager on the flow schedule, including the flow mask blend.
        mask = jnp.zeros((1, 8, 8, 1)).at[:, :4].set(1.0)
        kw = dict(prediction="flow", shift=1.2,
                  init_latent=jnp.full(SHAPE, 0.5), latent_mask=mask)
        a = _run(sampler, False, **kw)
        b = _run(sampler, True, **kw)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)

    def test_v_prediction_and_scheduler(self):
        kw = dict(prediction="v", scheduler="sgm_uniform")
        a = _run("dpmpp_2m", False, **kw)
        b = _run("dpmpp_2m", True, **kw)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)

    def test_batch_kwarg_doubles_through_cfg(self):
        y = jnp.linspace(0.0, 1.0, SHAPE[0] * 4).reshape(SHAPE[0], 4)
        kw = dict(cfg_scale=3.0, uncond_context=_ctx(seed=9),
                  uncond_kwargs={"y": -y}, y=y)
        a = _run("euler", False, **kw)
        b = _run("euler", True, **kw)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


class TestParallelChain:
    @pytest.mark.parametrize("sampler", ["euler", "dpmpp_2m"])
    def test_matches_eager_on_mesh(self, cpu_devices, sampler):
        def apply_fn(params, x, t, context=None, **kwargs):
            h = x * params["a"] * jnp.cos(t)[:, None, None, None]
            if context is not None:
                h = h + 0.01 * context.sum(axis=(1, 2))[:, None, None, None]
            return h

        params = {"a": jnp.float32(0.12)}
        pm = parallelize(
            (apply_fn, params), DeviceChain.even([f"cpu:{i}" for i in range(8)])
        )
        noise, ctx = _noise(), _ctx()
        a = run_sampler(pm, noise, ctx, sampler=sampler, steps=4,
                        cfg_scale=3.0, uncond_context=_ctx(seed=9))
        b = run_sampler(pm, noise, ctx, sampler=sampler, steps=4,
                        cfg_scale=3.0, uncond_context=_ctx(seed=9),
                        compile_loop=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)

    def test_traceable_none_for_hybrid_chain(self, cpu_devices):
        # A multi-platform-group chain needs host-side scatter — not one XLA
        # program. Fake two groups by platform-splitting the chain the way
        # test_hybrid does: simplest honest proxy is to check the single-group
        # invariant directly.
        def apply_fn(params, x, t, context=None, **kwargs):
            return x * params["a"]

        pm = parallelize((apply_fn, {"a": jnp.float32(0.5)}),
                         DeviceChain.even([f"cpu:{i}" for i in range(4)]))
        assert pm.traceable() is not None
        # Force a second platform group to simulate a hybrid chain.
        import copy

        g2 = copy.copy(pm._groups[0])
        pm._groups.append(g2)
        try:
            assert pm.traceable() is None
        finally:
            pm._groups.pop()

    def test_compile_loop_falls_back_with_callback(self):
        seen = []

        def cb(i, x):
            seen.append(i)

        out = _run("euler", True, callback=cb)
        assert seen == [0, 1, 2, 3, 4]  # eager loop ran the python callback
        assert np.isfinite(np.asarray(out)).all()


class TestCompileCaching:
    def test_second_call_does_not_retrace(self):
        calls = []
        model = _toy_model(calls)
        _run("euler", True, model=model)
        first = len(calls)
        assert first > 0  # traced through the python fn
        _run("euler", True, model=model)
        assert len(calls) == first  # cache hit: no re-trace

    def test_eager_path_not_cached_across_models(self):
        # Sanity: two distinct model objects each trace once.
        c1, c2 = [], []
        _run("euler", True, model=_toy_model(c1))
        _run("euler", True, model=_toy_model(c2))
        assert len(c1) > 0 and len(c2) > 0
