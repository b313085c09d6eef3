"""Weight-streaming executor (parallel/streaming.py + the orchestrator's
weights-don't-fit routing rung).

The contract under test, all off-hardware (the round-3 lesson: no code path
may execute first on an chip):

- streamed execution matches resident execution on the virtual 8-device mesh
  for BOTH a toy-FLUX topology and an SD1.5 topology (the UNet's staged
  PipelineSpec, models/unet.py);
- the residency accounting bounds peak streamed-weight bytes at ≤ 2 stages
  for a model whose total weights exceed the configured HBM budget;
- a streaming OOM re-carves at smaller stage size (the stream-mode demotion)
  instead of falling back to a full-pytree placement that cannot exist;
- streaming survives the full sampler: the eager denoise loop drives the
  per-stage programs every step, and ``compile_loop=True`` falls back (one
  XLA program would close over the full pytree).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu import DeviceChain, ParallelConfig, parallelize
from comfyui_parallelanything_tpu.models import build_unet, sd15_config
from comfyui_parallelanything_tpu.models.flux import FluxConfig, build_flux
from comfyui_parallelanything_tpu.models.loader import carve_stages, params_nbytes
from comfyui_parallelanything_tpu.parallel.streaming import (
    StreamingRunner,
    build_streaming_runner,
)

TINY_FLUX = FluxConfig(
    in_channels=16,  # 4 latent ch x 2x2 patch
    hidden_size=64, num_heads=4, depth=2, depth_single_blocks=4,
    context_in_dim=32, vec_in_dim=16, axes_dim=(4, 6, 6),
    guidance_embed=False, dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def flux_model():
    return build_flux(
        TINY_FLUX, jax.random.key(0), sample_shape=(1, 8, 8, 4), txt_len=16
    )


@pytest.fixture(scope="module")
def unet_model():
    cfg = sd15_config(
        model_channels=32, channel_mult=(1, 2), transformer_depth=(1, 1),
        attention_levels=(0, 1), context_dim=48, num_heads=4, norm_groups=8,
        dtype=jnp.float32,
    )
    return build_unet(cfg, jax.random.key(0), sample_shape=(1, 16, 16, 4))


def _flux_inputs(batch):
    x = jax.random.normal(jax.random.key(1), (batch, 8, 8, 4))
    t = jnp.linspace(900.0, 1.0, batch)
    ctx = jax.random.normal(
        jax.random.key(2), (batch, 16, TINY_FLUX.context_in_dim)
    )
    y = jax.random.normal(jax.random.key(3), (batch, TINY_FLUX.vec_in_dim))
    return x, t, ctx, y


def _stream_pm(model, budget_frac=3, **cfg_kw):
    budget = params_nbytes(model.params) // budget_frac
    return parallelize(
        model, DeviceChain.even(["cpu:0"]),
        ParallelConfig(
            weight_sharding="stream", hbm_budget_bytes=budget, **cfg_kw
        ),
    )


class TestStreamedMatchesResident:
    def test_flux_topology_vs_8dev_mesh(self, flux_model, cpu_devices):
        """Streamed single-chip output == the resident 8-device DP output ==
        the bare apply, within bf16-scale tolerances (CLAUDE.md)."""
        batch = 8
        x, t, ctx, y = _flux_inputs(batch)
        bare = jax.jit(flux_model.apply)(flux_model.params, x, t, ctx, y=y)
        resident = parallelize(
            flux_model, DeviceChain.even([f"cpu:{i}" for i in range(8)])
        )
        res = resident(x, t, ctx, y=y)
        pm = _stream_pm(flux_model)
        assert pm.is_streaming
        got = pm(x, t, ctx, y=y)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(bare), rtol=2e-3, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(res), rtol=2e-3, atol=1e-4
        )

    def test_sd15_topology(self, unet_model):
        """The UNet's staged PipelineSpec (skip connections in the carry)
        streams correctly — SD-family models stream too, not just the
        block-list DiTs."""
        x = jax.random.normal(jax.random.key(1), (2, 16, 16, 4))
        t = jnp.linspace(900.0, 1.0, 2)
        ctx = jax.random.normal(jax.random.key(2), (2, 7, 48))
        want = jax.jit(unet_model.apply)(unet_model.params, x, t, ctx)
        pm = _stream_pm(unet_model)
        got = pm(x, t, ctx)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=1e-4
        )
        assert pm._stream_runner.n_stages >= 2

    def test_overlap_off_debug_mode(self, flux_model):
        x, t, ctx, y = _flux_inputs(2)
        want = jax.jit(flux_model.apply)(flux_model.params, x, t, ctx, y=y)
        pm = _stream_pm(flux_model, stream_overlap=False)
        got = pm(x, t, ctx, y=y)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=1e-4
        )

    def test_batch_one_also_streams(self, flux_model):
        # batch==1 must NOT fall into pipeline block placement (which would
        # place the full pytree across devices) — streaming owns every batch.
        x, t, ctx, y = _flux_inputs(1)
        pm = _stream_pm(flux_model)
        got = pm(x, t, ctx, y=y)
        want = jax.jit(flux_model.apply)(flux_model.params, x, t, ctx, y=y)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=1e-4
        )
        assert pm._pipeline_runner is None


class TestResidencyBound:
    def test_peak_le_two_stages_when_weights_exceed_budget(self, flux_model):
        """The acceptance bound: for a model whose total weights exceed the
        configured HBM budget, peak streamed-weight bytes ≤ 2 stages."""
        total = params_nbytes(flux_model.params)
        budget = total // 3  # weights 3x the budget — cannot sit resident
        pm = parallelize(
            flux_model, DeviceChain.even(["cpu:0"]),
            ParallelConfig(hbm_budget_bytes=budget),  # replicate → auto-route
        )
        assert pm.is_streaming, "weights-don't-fit auto-routing must engage"
        x, t, ctx, y = _flux_inputs(2)
        pm(x, t, ctx, y=y)
        runner = pm._stream_runner
        tracker = runner.tracker
        assert runner.streamed_nbytes > budget  # the premise: doesn't fit
        assert runner.n_stages >= 2
        assert tracker.peak_bytes <= 2 * runner.max_stage_nbytes
        # Every stage retired: nothing left in the ring between calls.
        assert tracker.live_bytes == 0 and not tracker.live_tags
        # Resident prepare/finalize params are accounted separately and are
        # small next to the streamed stack.
        assert 0 < tracker.resident_bytes < runner.streamed_nbytes

    def test_two_calls_keep_the_bound(self, flux_model):
        pm = _stream_pm(flux_model)
        x, t, ctx, y = _flux_inputs(2)
        pm(x, t, ctx, y=y)
        pm(x, t, ctx, y=y)
        runner = pm._stream_runner
        assert runner.tracker.peak_bytes <= 2 * runner.max_stage_nbytes
        assert runner.tracker.live_bytes == 0

    def test_carve_stages_contiguous_and_bounded(self, flux_model):
        spec = flux_model.pipeline_spec
        sizes = [
            params_nbytes({k: flux_model.params[k] for k in seg.param_keys})
            for seg in spec.segments
        ]
        cap = max(sizes)  # every stage can hold >= 1 segment
        ranges = carve_stages(spec, flux_model.params, max_stage_bytes=cap)
        assert ranges[0][0] == 0 and ranges[-1][1] == len(spec.segments)
        for (s0, e0), (s1, _) in zip(ranges, ranges[1:]):
            assert e0 == s1  # contiguous, no overlap
        for s, e in ranges:
            # multi-segment stages respect the cap (single-segment stages are
            # the atomic unit and may exceed it by construction)
            if e - s > 1:
                assert sum(sizes[s:e]) <= cap


class TestStreamDemotion:
    def test_oom_recarves_to_more_stages(self, flux_model, monkeypatch):
        # Generous budget → coarse carve (few stages), so a re-carve has room
        # to halve the stage size before bottoming out at one segment each.
        pm = _stream_pm(flux_model, budget_frac=1)
        x, t, ctx, y = _flux_inputs(2)
        first = pm._get_streaming_runner()
        n0 = first.n_stages
        calls = {"n": 0}
        orig = StreamingRunner.__call__

        def flaky(self, *a, **kw):
            if self is first and calls["n"] == 0:
                calls["n"] += 1
                raise RuntimeError("RESOURCE_EXHAUSTED: fake streaming OOM")
            return orig(self, *a, **kw)

        monkeypatch.setattr(StreamingRunner, "__call__", flaky)
        got = pm(x, t, ctx, y=y)
        assert pm._stream_runner is not first
        assert pm._stream_runner.n_stages > n0
        want = jax.jit(flux_model.apply)(flux_model.params, x, t, ctx, y=y)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=1e-4
        )

    def test_recarve_bottoms_out_at_one_segment_per_stage(self, flux_model):
        runner = StreamingRunner(
            flux_model.pipeline_spec, flux_model.params,
            jax.devices("cpu")[0], max_stage_bytes=1,
        )
        assert runner.n_stages == len(flux_model.pipeline_spec.segments)
        assert runner.recarved() is None

    def test_recarve_refuses_no_progress_carve(self, flux_model):
        """When the byte cap is pinned by a lone oversized segment, halving
        it reproduces the identical carve — recarved() must return None
        (progress guarantee) or the _stream_call retry loop would respin a
        deterministic OOM forever."""
        spec = flux_model.pipeline_spec
        sizes = [
            params_nbytes({k: flux_model.params[k] for k in seg.param_keys})
            for seg in spec.segments
        ]
        # Cap below every segment: one segment per stage EXCEPT forced via a
        # cap just under the max segment — the max segment sits alone while
        # smaller neighbors still merge only if they fit; construct the
        # pinned case directly with cap = max segment size - 1.
        runner = StreamingRunner(
            spec, flux_model.params, jax.devices("cpu")[0],
            max_stage_bytes=max(sizes) - 1,
        )
        deeper = runner.recarved()
        # Either a strictly finer carve exists, or None — never an equal one.
        if deeper is not None:
            assert deeper.n_stages > runner.n_stages
        else:
            assert runner.max_stage_nbytes == max(sizes)

    def test_non_oom_errors_propagate(self, flux_model, monkeypatch):
        pm = _stream_pm(flux_model)
        monkeypatch.setattr(
            StreamingRunner, "__call__",
            lambda self, *a, **kw: (_ for _ in ()).throw(
                RuntimeError("unrelated failure")
            ),
        )
        with pytest.raises(RuntimeError, match="unrelated"):
            pm(*_flux_inputs(2)[:3], y=_flux_inputs(2)[3])


class TestRoutingAndGuards:
    def test_stream_requires_pipeline_spec(self):
        def f(p, x, t, context=None, **kw):
            return x * p["s"]

        with pytest.raises(ValueError, match="PipelineSpec"):
            parallelize(
                (f, {"s": jnp.float32(2.0)}), DeviceChain.even(["cpu:0"]),
                ParallelConfig(weight_sharding="stream"),
            )

    def test_no_auto_route_when_weights_fit(self, flux_model):
        pm = parallelize(
            flux_model, DeviceChain.even(["cpu:0"]),
            ParallelConfig(
                hbm_budget_bytes=params_nbytes(flux_model.params) * 10
            ),
        )
        assert not pm.is_streaming

    def test_traceable_and_single_stay_streamed(self, flux_model):
        pm = _stream_pm(flux_model)
        assert pm.traceable() is None  # no one-program path may exist
        x, t, ctx, y = _flux_inputs(2)
        got = pm.single(x, t, ctx, y=y)  # escape hatch streams too
        want = jax.jit(flux_model.apply)(flux_model.params, x, t, ctx, y=y)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=1e-4
        )

    def test_cleanup_drops_runner(self, flux_model):
        pm = _stream_pm(flux_model)
        pm(*_flux_inputs(1)[:3], y=_flux_inputs(1)[3])
        pm.cleanup()
        assert pm._stream_runner is None

    def test_build_streaming_runner_none_without_spec(self):
        assert build_streaming_runner(
            None, {}, jax.devices("cpu")[0]
        ) is None


class TestSamplerSurvivesStreaming:
    def test_full_sampler_eager_and_compile_loop_fallback(self, flux_model):
        """The whole denoise loop drives the per-stage programs each step;
        compile_loop=True silently (logged) falls back to the same eager
        path — both match the resident model's sampler output."""
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        batch = 2
        noise = jax.random.normal(jax.random.key(5), (batch, 8, 8, 4))
        _, _, ctx, y = _flux_inputs(batch)
        want = run_sampler(
            flux_model, noise, ctx, sampler="dpmpp_2m", steps=3, y=y
        )
        pm = _stream_pm(flux_model)
        eager = run_sampler(pm, noise, ctx, sampler="dpmpp_2m", steps=3, y=y)
        np.testing.assert_allclose(
            np.asarray(eager), np.asarray(want), rtol=2e-3, atol=1e-4
        )
        compiled = run_sampler(
            pm, noise, ctx, sampler="dpmpp_2m", steps=3, y=y,
            compile_loop=True,
        )
        np.testing.assert_allclose(
            np.asarray(compiled), np.asarray(want), rtol=2e-3, atol=1e-4
        )
        # The residency bound held across every sampler step.
        runner = pm._stream_runner
        assert runner.tracker.peak_bytes <= 2 * runner.max_stage_nbytes
        assert runner.tracker.live_bytes == 0
