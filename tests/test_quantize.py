"""Weight-only int8 quantization: accuracy, byte budget, and transparency
through the whole parallel layer (DP sharding, FSDP leaf sharding, pipeline
staging) — the QuantTensor pytree must never need a special case downstream."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu import DeviceChain, ParallelConfig, parallelize
from comfyui_parallelanything_tpu.models import (
    QuantTensor,
    build_flux,
    dequantize_params,
    param_bytes,
    quantize_model,
    quantize_params,
)
from comfyui_parallelanything_tpu.models.flux import FluxConfig


TINY = FluxConfig(
    in_channels=16,
    hidden_size=64,
    num_heads=4,
    depth=1,
    depth_single_blocks=2,
    context_in_dim=32,
    vec_in_dim=16,
    axes_dim=(4, 6, 6),
    guidance_embed=False,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def flux_model():
    return build_flux(TINY, jax.random.key(0), sample_shape=(1, 8, 8, 4), txt_len=8)


class TestQuantizeParams:
    def test_round_trip_error_bounded(self):
        w = jax.random.normal(jax.random.key(1), (256, 512)) * jnp.linspace(
            0.1, 3.0, 512
        )  # per-channel dynamic range — what per-channel scales exist for
        q = quantize_params({"w": w}, min_size=1)["w"]
        assert isinstance(q, QuantTensor)
        assert q.q.dtype == jnp.int8
        back = np.asarray(q.dequantize(jnp.float32))
        err = np.abs(back - np.asarray(w))
        # symmetric int8: error ≤ scale/2 per channel = absmax/254
        bound = np.abs(np.asarray(w)).max(axis=0) / 254.0 + 1e-8
        assert (err <= bound[None, :] + 1e-6).all()

    def test_small_and_1d_leaves_untouched(self):
        params = {"bias": jnp.ones((64,)), "norm": jnp.ones((8, 8))}
        out = quantize_params(params, min_size=2**10)
        assert not any(
            isinstance(l, QuantTensor)
            for l in jax.tree.leaves(
                out, is_leaf=lambda x: isinstance(x, QuantTensor)
            )
            if isinstance(l, QuantTensor)
        )
        assert out["bias"] is params["bias"]

    def test_bytes_roughly_halve(self, flux_model):
        # f32 model → int8 payload + f32 scales: large-leaf bytes drop 4×, the
        # whole tree must shrink by well over 2× (norms/biases stay f32).
        before = param_bytes(flux_model.params)
        after = param_bytes(quantize_params(flux_model.params, min_size=2**10))
        assert after < before / 2

    def test_idempotent(self, flux_model):
        q1 = quantize_params(flux_model.params, min_size=2**10)
        q2 = quantize_params(q1, min_size=2**10)
        a = jax.tree.leaves(q1)
        b = jax.tree.leaves(q2)
        assert all(x is y for x, y in zip(a, b))


class TestQuantizedModel:
    def _inputs(self, batch):
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=(batch, 8, 8, 4)), jnp.float32)
        t = jnp.linspace(1.0, 0.1, batch)
        ctx = jnp.asarray(rng.normal(size=(batch, 8, TINY.context_in_dim)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(batch, TINY.vec_in_dim)), jnp.float32)
        return x, t, ctx, y

    def test_forward_close_to_full_precision(self, flux_model):
        qm = quantize_model(flux_model, min_size=2**10, dtype=jnp.float32)
        x, t, ctx, y = self._inputs(2)
        full = np.asarray(jax.jit(flux_model.apply)(flux_model.params, x, t, ctx, y=y))
        quant = np.asarray(jax.jit(qm.apply)(qm.params, x, t, ctx, y=y))
        # int8 weights: relative output error stays in the few-percent regime.
        scale = np.abs(full).mean() + 1e-6
        assert np.abs(quant - full).mean() / scale < 0.05

    def test_int8_sampler_run_close_to_bf16(self, flux_model):
        # bound int8-vs-full-precision error END-TO-END
        # through a sampler run, not just one forward — quantization noise
        # compounds across steps, and this is the regime the flux_16_int8
        # bench rung runs in.
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        qm = quantize_model(flux_model, min_size=2**10, dtype=jnp.float32)
        noise = jax.random.normal(jax.random.key(3), (2, 8, 8, 4))
        ctx = jax.random.normal(jax.random.key(4), (2, 8, TINY.context_in_dim))
        y = jax.random.normal(jax.random.key(5), (2, TINY.vec_in_dim))
        kw = dict(sampler="flow_euler", steps=6, shift=1.0, y=y)
        full = np.asarray(run_sampler(flux_model, noise, ctx, **kw))
        quant = np.asarray(run_sampler(qm, noise, ctx, **kw))
        assert np.isfinite(quant).all()
        scale = np.abs(full).mean() + 1e-6
        rel = np.abs(quant - full).mean() / scale
        assert rel < 0.10, rel  # compounded over 6 steps, still small

    def test_parallelized_dp(self, flux_model, cpu_devices):
        qm = quantize_model(flux_model, min_size=2**10, dtype=jnp.float32)
        pm = parallelize(qm, DeviceChain.even([f"cpu:{i}" for i in range(8)]))
        x, t, ctx, y = self._inputs(8)
        out = pm(x, t, ctx, y=y)
        assert out.shape == (8, 8, 8, 4)
        assert len(out.sharding.device_set) == 8
        single = np.asarray(jax.jit(qm.apply)(qm.params, x, t, ctx, y=y))
        np.testing.assert_allclose(np.asarray(out), single, rtol=2e-3, atol=2e-3)

    def test_parallelized_fsdp(self, flux_model, cpu_devices):
        # The tiny flux model's leaves sit under the FSDP min-size (so they
        # replicate), but the quantized model must still run the fsdp path.
        qm = quantize_model(flux_model, min_size=2**10, dtype=jnp.float32)
        pm = parallelize(
            qm,
            DeviceChain.even([f"cpu:{i}" for i in range(8)]),
            ParallelConfig(weight_sharding="fsdp"),
        )
        x, t, ctx, y = self._inputs(8)
        out = pm(x, t, ctx, y=y)
        assert out.shape == (8, 8, 8, 4)

    def test_fsdp_shards_large_int8_payload(self, cpu_devices):
        # QuantTensor children (int8 payload + scales) shard like any leaves
        # once they clear the FSDP min-size.
        def f(p, x, t, context=None, **kw):
            w = p["w"]
            if hasattr(w, "dequantize"):
                w = w.dequantize(jnp.float32)
            return x @ w

        params = {"w": jax.random.normal(jax.random.key(2), (1024, 1024))}
        from comfyui_parallelanything_tpu.models import quantize_params

        qp = quantize_params(params, min_size=1)
        pm = parallelize(
            (f, qp),
            DeviceChain.even([f"cpu:{i}" for i in range(8)]),
            ParallelConfig(weight_sharding="fsdp"),
        )
        out = pm(jnp.ones((8, 1024)), jnp.zeros((8,)))
        assert out.shape == (8, 1024)
        sharded_int8 = [
            l for l in jax.tree.leaves(pm._groups[0].params)
            if l.dtype == jnp.int8 and len(l.addressable_shards) == 8
            and l.addressable_shards[0].data.size < l.size
        ]
        assert sharded_int8, "expected the int8 payload to be genuinely sharded"

    def test_pipeline_batch1(self, flux_model, cpu_devices):
        qm = quantize_model(flux_model, min_size=2**10, dtype=jnp.float32)
        pm = parallelize(qm, DeviceChain.even([f"cpu:{i}" for i in range(4)]))
        x, t, ctx, y = self._inputs(1)
        out = pm(x, t, ctx, y=y)
        assert out.shape == (1, 8, 8, 4)
        assert pm._pipeline_runner is not None and pm._pipeline_runner.n_stages >= 2
        single = np.asarray(jax.jit(qm.apply)(qm.params, x, t, ctx, y=y))
        np.testing.assert_allclose(np.asarray(out), single, rtol=2e-3, atol=2e-3)

    def test_compile_loop_on_quantized_model(self, flux_model):
        # The whole-loop compiled sampler must trace straight through a
        # QuantTensor pytree (dequantize-in-jit) and match the eager loop —
        # the exact combination the flux_16_int8 bench rung runs.
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        qm = quantize_model(flux_model, min_size=2**10, dtype=jnp.float32)
        noise = jax.random.normal(jax.random.key(6), (2, 8, 8, 4))
        ctx = jax.random.normal(jax.random.key(7), (2, 8, TINY.context_in_dim))
        y = jax.random.normal(jax.random.key(8), (2, TINY.vec_in_dim))
        kw = dict(sampler="euler", steps=3, y=y)
        eager = run_sampler(qm, noise, ctx, **kw)
        compiled = run_sampler(qm, noise, ctx, compile_loop=True, **kw)
        np.testing.assert_allclose(
            np.asarray(eager), np.asarray(compiled), rtol=2e-4, atol=2e-5
        )

    def test_bench_synth_int8_rung_logic(self):
        # The flux_16_int8 bench rung synthesizes int8 params straight from
        # abstract shapes (no high-precision pytree ever exists); validate the
        # same code path at tiny scale: structure matches quantize_params'
        # rule, and the dequantize-in-jit forward runs.
        import bench
        from comfyui_parallelanything_tpu.models import flux_abstract_params
        from comfyui_parallelanything_tpu.models.flux import FluxModel

        sds = flux_abstract_params(TINY, sample_shape=(1, 8, 8, 4), txt_len=8)
        params = bench._synth_int8_params(sds, min_size=2**10)
        leaves = jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QuantTensor)
        )
        qts = [l for l in leaves if isinstance(l, QuantTensor)]
        assert qts and all(l.q.dtype == jnp.int8 for l in qts)
        ref = quantize_params(
            jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), sds),
            min_size=2**10,
        )
        assert jax.tree.structure(
            params, is_leaf=lambda x: isinstance(x, QuantTensor)
        ) == jax.tree.structure(ref, is_leaf=lambda x: isinstance(x, QuantTensor))

        module = FluxModel(TINY)
        out = jax.jit(
            lambda p, x, t, c, y: module.apply(
                {"params": dequantize_params(p, jnp.float32)}, x, t, c, y=y
            )
        )(
            params,
            jnp.ones((1, 8, 8, 4)), jnp.ones((1,)),
            jnp.ones((1, 8, TINY.context_in_dim)), jnp.ones((1, TINY.vec_in_dim)),
        )
        assert out.shape == (1, 8, 8, 4)
        assert np.isfinite(np.asarray(out)).all()

    def test_dequantize_params_inverse_shape(self, flux_model):
        q = quantize_params(flux_model.params, min_size=2**10)
        back = dequantize_params(q, jnp.float32)
        for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(flux_model.params)):
            assert a.shape == b.shape
