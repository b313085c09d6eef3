"""FSDP weight sharding: per-leaf largest-axis sharding over the data mesh, numerics
identical to replicate mode. Beyond-reference capability — a FLUX-dev-class model in
bf16 cannot hold a full replica per v5e chip (reference README.md:167 'full model per
device' is physically impossible there)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from comfyui_parallelanything_tpu import DeviceChain, ParallelConfig, parallelize
from comfyui_parallelanything_tpu.models import build_unet, sd15_config
from comfyui_parallelanything_tpu.parallel.mesh import (
    AXIS_DATA,
    build_mesh,
    fsdp_spec,
    place_params_fsdp,
)


class TestFsdpSpec:
    def test_large_divisible_shards_largest_axis(self):
        assert fsdp_spec((512, 1024), AXIS_DATA, 8) == P(None, AXIS_DATA)
        assert fsdp_spec((2048, 256), AXIS_DATA, 8) == P(AXIS_DATA, None)

    def test_small_replicates(self):
        assert fsdp_spec((64,), AXIS_DATA, 8) == P()

    def test_indivisible_replicates(self):
        assert fsdp_spec((1000, 999), AXIS_DATA, 8, min_size=1) == P(AXIS_DATA, None)
        assert fsdp_spec((999, 1001), AXIS_DATA, 8, min_size=1) == P()

    def test_scalar_replicates(self):
        assert fsdp_spec((), AXIS_DATA, 8) == P()


class TestFsdpPlacement:
    def test_leaves_actually_sharded(self, cpu_devices):
        mesh = build_mesh(cpu_devices, {AXIS_DATA: 8})
        params = {
            "big": jnp.ones((1024, 512)),
            "small": jnp.ones((16,)),
        }
        placed = place_params_fsdp(params, mesh)
        # big shards over 8 devices; each device holds 1/8 of the rows or cols.
        shard_shapes = {s.data.shape for s in placed["big"].addressable_shards}
        assert shard_shapes in ({(128, 512)}, {(1024, 64)})
        assert len(placed["small"].sharding.device_set) == 8  # replicated

    def test_streamed_put_matches_direct_device_put(self, cpu_devices):
        # streamed_tree_put (the int8-placement OOM fix)
        # must be value- and sharding-identical to a whole-pytree device_put;
        # a tiny in-flight cap forces several drain cycles through the loop.
        import numpy as np

        from comfyui_parallelanything_tpu.parallel.mesh import (
            replicated,
            streamed_tree_put,
        )

        mesh = build_mesh(cpu_devices, {AXIS_DATA: 8})
        params = {f"w{i}": jnp.full((64, 64), float(i)) for i in range(6)}
        sharding = replicated(mesh)
        streamed = streamed_tree_put(
            params, lambda _: sharding, max_inflight_bytes=1
        )
        direct = jax.device_put(params, sharding)
        for k in params:
            assert streamed[k].sharding == direct[k].sharding
            np.testing.assert_array_equal(
                np.asarray(streamed[k]), np.asarray(direct[k])
            )


class TestFsdpEndToEnd:
    def test_fsdp_matches_replicate(self, cpu_devices):
        cfg = sd15_config(
            model_channels=32, channel_mult=(1, 2), num_res_blocks=1,
            attention_levels=(1,), transformer_depth=(0, 1), num_heads=4,
            context_dim=64, norm_groups=8, dtype=jnp.float32,
        )
        model = build_unet(cfg, jax.random.key(0), sample_shape=(1, 16, 16, 4))
        chain = DeviceChain.even([f"cpu:{i}" for i in range(8)])
        pm_rep = parallelize(model, chain)
        pm_fsdp = parallelize(
            model, chain, ParallelConfig(weight_sharding="fsdp")
        )
        x = jax.random.normal(jax.random.key(1), (8, 16, 16, 4), jnp.float32)
        ctx = jax.random.normal(jax.random.key(2), (8, 12, 64), jnp.float32)
        t = jnp.linspace(999.0, 1.0, 8)
        a = pm_rep(x, t, ctx)
        b = pm_fsdp(x, t, ctx)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)

    def test_fsdp_single_fallback_stays_sharded(self, cpu_devices):
        # batch==1 (no pipeline spec on a bare-fn model) routes through single();
        # under fsdp the params must NOT be copied whole to the lead device — the
        # fallback runs on the group mesh with replicated inputs.
        def f(p, x, t, context=None, **kw):
            return x @ p["w"]

        params = {"w": jnp.ones((1024, 1024))}
        chain = DeviceChain.even([f"cpu:{i}" for i in range(8)])
        pm = parallelize(
            (f, params), chain, ParallelConfig(weight_sharding="fsdp")
        )
        out = pm(jnp.ones((1, 1024)), jnp.zeros((1,)))
        assert out.shape == (1, 1024)
        assert pm._lead_params is None  # no full-pytree lead copy happened

    def test_full_size_flux_dev_fsdp_byte_math(self, cpu_devices):
        # The stated reason FSDP exists: flux-dev bf16 (~24 GB) cannot replicate
        # on a 16 GB v5e chip (parallel/mesh.py fsdp_spec docstring). Prove the
        # placement math on the REAL 19/38-depth 12B-param config — abstract
        # shapes (eval_shape, zero bytes materialized) + the exact per-device
        # shard bytes the FSDP policy produces.
        from comfyui_parallelanything_tpu.models import (
            flux_abstract_params,
            flux_dev_config,
        )
        from comfyui_parallelanything_tpu.parallel.mesh import sharded_byte_math

        cfg = flux_dev_config(dtype=jnp.bfloat16)
        assert (cfg.depth, cfg.depth_single_blocks) == (19, 38)
        shapes = flux_abstract_params(cfg, sample_shape=(1, 4, 4, 16), txt_len=4)
        n_params = sum(s.size for s in jax.tree.leaves(shapes))
        assert n_params > 10e9  # genuinely the 12B-class pytree
        # Exact per-device bytes from shard shapes (bf16 checkpoint layout: 2
        # bytes/param — the load path the converters produce).
        per_device, total = sharded_byte_math(
            shapes, build_mesh(cpu_devices, {AXIS_DATA: 8}), AXIS_DATA
        )
        assert total > 20 * 2**30  # the full replica genuinely overflows a v5e
        # Sharded 8-way it fits with room to spare; replication slack (small
        # norms/biases live whole on every chip) stays under 5%.
        assert per_device < total / 8 * 1.05
        assert per_device < 4 * 2**30

    def test_full_width_flux_fsdp_places_and_steps(self, cpu_devices):
        # The mechanics proof at full layer width: materialize a full-WIDTH
        # (hidden 3072, 24 heads) flux pytree directly into its FSDP sharding —
        # the unsharded pytree never exists — verify real buffer bytes are 1/8
        # per device, and run one denoise step through the orchestrator. (The
        # full 57-block 12B forward is not runnable on the virtual mesh: eight
        # host threads each all-gathering full weights needs >8x the pytree in
        # one host's RAM; on a real v5e-8 each chip holds 1/8 + one block's
        # gather. Depth is the only reduction here — every tensor shape that
        # matters to sharding is full-size.)
        from comfyui_parallelanything_tpu.models import (
            build_flux,
            flux_abstract_params,
            flux_dev_config,
        )
        from comfyui_parallelanything_tpu.parallel.mesh import (
            materialize_params_sharded,
        )

        cfg = flux_dev_config(depth=1, depth_single_blocks=2, dtype=jnp.bfloat16)
        shapes = flux_abstract_params(cfg, sample_shape=(1, 4, 4, 16), txt_len=4)
        shapes = jax.tree.map(
            lambda sd: jax.ShapeDtypeStruct(sd.shape, jnp.bfloat16), shapes
        )
        mesh = build_mesh(cpu_devices, {AXIS_DATA: 8})
        params = materialize_params_sharded(shapes, mesh, AXIS_DATA)
        total = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
        per_dev = {}
        for leaf in jax.tree.leaves(params):
            for sh in leaf.addressable_shards:
                per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) + sh.data.nbytes
        assert len(per_dev) == 8
        for b in per_dev.values():
            assert b < total / 8 * 1.05
        model = build_flux(cfg, params=params, sample_shape=(1, 4, 4, 16), txt_len=4)
        pm = parallelize(
            model,
            DeviceChain.even([f"cpu:{i}" for i in range(8)]),
            ParallelConfig(weight_sharding="fsdp"),
        )
        x = jnp.ones((8, 4, 4, 16), jnp.float32)
        t = jnp.linspace(1.0, 0.1, 8)
        ctx = jnp.ones((8, 4, cfg.context_in_dim), jnp.float32)
        y = jnp.ones((8, cfg.vec_in_dim), jnp.float32)
        out = pm(x, t, ctx, y=y, guidance=jnp.full((8,), 3.5, jnp.float32))
        assert out.shape == (8, 4, 4, 16)
        assert np.isfinite(np.asarray(out, np.float32)).all()

    def test_fsdp_params_use_less_per_device_memory(self, cpu_devices):
        # Structural check: at least the large kernels are sharded, not replicated.
        cfg = sd15_config(
            model_channels=32, channel_mult=(1, 2), num_res_blocks=1,
            attention_levels=(1,), transformer_depth=(0, 1), num_heads=4,
            context_dim=64, norm_groups=8, dtype=jnp.float32,
        )
        model = build_unet(cfg, jax.random.key(0), sample_shape=(1, 16, 16, 4))
        chain = DeviceChain.even([f"cpu:{i}" for i in range(8)])
        pm = parallelize(model, chain, ParallelConfig(weight_sharding="fsdp"))
        leaves = jax.tree.leaves(pm._groups[0].params)
        sharded = [
            l for l in leaves
            if l.size >= 2**16 and len(l.addressable_shards) == 8
            and l.addressable_shards[0].data.size < l.size
        ]
        assert sharded, "expected at least one genuinely sharded large parameter"


class TestStreamedPutPeakBound:
    def test_inflight_bytes_bounded_on_flux_dev_int8_shapes(self, monkeypatch):
        """The flux_16_int8 placement OOM fix pinned without hardware: over a
        FLUX-dev-shaped int8 pytree (exact leaf
        shapes via jax.eval_shape — no buffers materialize), the un-drained
        transfer queue must never exceed max_inflight_bytes + one leaf. Byte
        math only; device_put/block_until_ready are instrumented stubs."""
        from types import SimpleNamespace

        from comfyui_parallelanything_tpu.models.flux import (
            FluxModel,
            flux_dev_config,
        )
        from comfyui_parallelanything_tpu.parallel import mesh as mesh_mod

        cfg = flux_dev_config()  # FULL depth 19/38 — shapes only
        module = FluxModel(cfg)

        def init():
            x = jnp.zeros((1, 8, 8, 16), jnp.float32)  # NHWC latent, 16 tokens
            t = jnp.zeros((1,), jnp.float32)
            ctx = jnp.zeros((1, 16, cfg.context_in_dim), jnp.float32)
            y = jnp.zeros((1, cfg.vec_in_dim), jnp.float32)
            return module.init(jax.random.key(0), x, t, ctx, y=y)

        shapes = jax.eval_shape(init)["params"]
        # int8 quantization: ~1 byte per element (scales are negligible).
        leaves = [
            SimpleNamespace(nbytes=int(np.prod(l.shape)) or 1)
            for l in jax.tree.leaves(shapes)
        ]
        total = sum(l.nbytes for l in leaves)
        biggest = max(l.nbytes for l in leaves)
        assert total > 8 << 30  # sanity: genuinely flux-dev-sized (int8 ~11GB)

        state = {"outstanding": 0, "peak": 0}

        def fake_put(leaf, sharding):
            state["outstanding"] += leaf.nbytes
            state["peak"] = max(state["peak"], state["outstanding"])
            return leaf

        def fake_block(x):
            state["outstanding"] = 0
            return x

        monkeypatch.setattr(jax, "device_put", fake_put)
        monkeypatch.setattr(jax, "block_until_ready", fake_block)
        cap = mesh_mod._MAX_INFLIGHT_BYTES
        mesh_mod.streamed_tree_put(leaves, lambda _: None)
        # Ceiling: the drain triggers AFTER the leaf that crosses the cap.
        assert state["peak"] <= cap + biggest
        # And the bound is meaningful: far below all-concurrent staging.
        assert state["peak"] * 4 < total
