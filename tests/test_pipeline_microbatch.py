"""GPipe-style microbatched pipeline throughput (beyond the reference, whose
pipeline mode is batch==1 layer placement only — SURVEY §2e): batch>1 streams
through the stage chain as microbatches, overlapped by XLA's async per-device
queues; outputs must equal the single-device forward exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu import DeviceChain, ParallelConfig, parallelize
from comfyui_parallelanything_tpu.models.flux import FluxConfig, build_flux

TINY = FluxConfig(
    in_channels=16,  # patchified dim: p^2 * C for 4-channel latents, patch 2
    hidden_size=32,
    num_heads=2,
    depth=2,
    depth_single_blocks=4,
    context_in_dim=16,
    vec_in_dim=8,
    axes_dim=(4, 6, 6),
    guidance_embed=False,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def model():
    return build_flux(TINY, jax.random.key(0), sample_shape=(1, 8, 8, 4), txt_len=8)


def _inputs(batch, seed=1):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(batch, 8, 8, 4)), jnp.float32)
    t = jnp.asarray(r.uniform(0.1, 1.0, size=(batch,)), jnp.float32)
    ctx = jnp.asarray(r.normal(size=(batch, 8, TINY.context_in_dim)), jnp.float32)
    y = jnp.asarray(r.normal(size=(batch, TINY.vec_in_dim)), jnp.float32)
    return x, t, ctx, y


class TestMicrobatchedPipeline:
    def test_matches_single_device(self, model, cpu_devices):
        pm = parallelize(
            model,
            DeviceChain.even([f"cpu:{i}" for i in range(4)]),
            ParallelConfig(pipeline_microbatches=4),
        )
        x, t, ctx, y = _inputs(8)
        got = pm(x, t, ctx, y=y)
        assert pm._pipeline_runner is not None
        assert pm._pipeline_runner.n_stages > 1  # stages actually placed
        want = jax.jit(model.apply)(model.params, x, t, ctx, y=y)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_uneven_microbatches(self, model, cpu_devices):
        # batch 7 over 3 microbatches: largest-remainder sizes, exact concat.
        pm = parallelize(
            model,
            DeviceChain.even([f"cpu:{i}" for i in range(4)]),
            ParallelConfig(pipeline_microbatches=3),
        )
        x, t, ctx, y = _inputs(7, seed=2)
        got = pm(x, t, ctx, y=y)
        want = jax.jit(model.apply)(model.params, x, t, ctx, y=y)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_uneven_batch_pads_to_uniform_chunks(self, model, cpu_devices):
        # Uneven largest-remainder sizes would compile every stage program
        # twice; the router pads to mb * ceil(batch/mb) so all chunks share
        # ONE shape, then slices the concat back.
        pm = parallelize(
            model,
            DeviceChain.even([f"cpu:{i}" for i in range(4)]),
            ParallelConfig(pipeline_microbatches=3),
        )
        x, t, ctx, y = _inputs(7, seed=5)
        pm(x, t, ctx, y=y)  # build the runner
        orig = pm._pipeline_runner
        seen = []

        class Spy:
            n_stages = orig.n_stages

            def __call__(self, xi, ti, ci=None, **kw):
                seen.append(xi.shape[0])
                return orig(xi, ti, ci, **kw)

        pm._pipeline_runner = Spy()
        got = pm(x, t, ctx, y=y)
        assert seen == [3, 3, 3]  # uniform chunk shapes (7 -> 9 padded)
        want = jax.jit(model.apply)(model.params, x, t, ctx, y=y)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_no_spec_falls_through_to_dp(self, cpu_devices):
        def f(p, x, t, context=None, **kw):
            return x * p["a"]

        pm = parallelize(
            (f, {"a": jnp.float32(2.0)}),
            DeviceChain.even([f"cpu:{i}" for i in range(4)]),
            ParallelConfig(pipeline_microbatches=4),
        )
        x = jnp.ones((8, 4))
        out = pm(x, jnp.ones((8,)))
        assert pm._pipeline_runner is None  # no spec -> DP handled it
        np.testing.assert_allclose(np.asarray(out), 2.0 * np.asarray(x))

    def test_batch_below_microbatch_count_routes_normally(self, model, cpu_devices):
        pm = parallelize(
            model,
            DeviceChain.even([f"cpu:{i}" for i in range(4)]),
            ParallelConfig(pipeline_microbatches=8),
        )
        x, t, ctx, y = _inputs(4, seed=3)  # batch 4 < mb 8 -> DP path
        got = pm(x, t, ctx, y=y)
        want = jax.jit(model.apply)(model.params, x, t, ctx, y=y)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_default_config_unchanged_routing(self, model, cpu_devices):
        pm = parallelize(model, DeviceChain.even([f"cpu:{i}" for i in range(4)]))
        x, t, ctx, y = _inputs(8, seed=4)
        got = pm(x, t, ctx, y=y)
        assert pm._pipeline_runner is None  # DP, not pipeline
        want = jax.jit(model.apply)(model.params, x, t, ctx, y=y)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )
