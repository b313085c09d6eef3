"""Wrap-anything genericity (models/generic.py): a third-party flax module
following the reference's block-list naming convention
(any_device_parallel.py:1156) gets batch==1 pipeline mode with NO framework
edits — spec auto-derived from the params pytree; plus the explicit
pipeline_spec hint on (apply, params) tuples, and the reference's fallback
(no block lists -> data parallel only)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu import (
    DeviceChain,
    derive_pipeline_spec,
    parallelize,
    wrap_flax_module,
)


class _ToyBlock(nn.Module):
    """carry -> carry, the unit the reference wraps in ParallelBlock (24-87)."""

    width: int

    @nn.compact
    def __call__(self, carry):
        h = nn.Dense(self.width)(carry["h"])
        return {**carry, "h": carry["h"] + nn.gelu(h)}


class NovelDiT(nn.Module):
    """A model family this framework has never seen: setup-style ``layers``
    list (one of the reference's discovery names) + prepare/finalize."""

    width: int = 16
    depth: int = 4

    def setup(self):
        self.embed = nn.Dense(self.width)
        self.layers = [_ToyBlock(self.width) for _ in range(self.depth)]
        self.head = nn.Dense(4)

    def prepare(self, x, t, context=None, **kwargs):
        h = self.embed(x) * jnp.cos(t)[:, None]
        if context is not None:
            h = h + context.sum(axis=(1, 2))[:, None]
        return {"h": h}

    def finalize(self, carry, out_shape):
        return self.head(carry["h"])

    def __call__(self, x, timesteps, context=None, **kwargs):
        carry = self.prepare(x, timesteps, context, **kwargs)
        for blk in self.layers:
            carry = blk(carry)
        return self.finalize(carry, x.shape)


@pytest.fixture(scope="module")
def novel():
    module = NovelDiT()
    x = jnp.ones((1, 4))
    params = module.init(jax.random.key(0), x, jnp.ones((1,)))["params"]
    return module, params


def _inputs(batch=1):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(batch, 4)), jnp.float32)
    t = jnp.asarray(rng.uniform(0, 1, size=(batch,)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(batch, 3, 2)), jnp.float32)
    return x, t, c


class TestDerive:
    def test_spec_derived_from_layers_list(self, novel):
        module, params = novel
        spec = derive_pipeline_spec(module, params)
        assert spec is not None
        assert len(spec.segments) == 4
        assert [s.param_keys for s in spec.segments] == [
            (f"layers_{i}",) for i in range(4)
        ]
        assert "embed" in spec.prepare_keys and "head" in spec.finalize_keys

    def test_no_convention_no_spec(self):
        class Flat(nn.Module):
            @nn.compact
            def __call__(self, x, t, context=None):
                return nn.Dense(4)(x)

        m = Flat()
        p = m.init(jax.random.key(0), jnp.ones((1, 4)), jnp.ones((1,)))["params"]
        assert derive_pipeline_spec(m, p) is None
        # wrap still works — data-parallel only, the reference's own fallback
        # when no known block list is found (1156-1166).
        dm = wrap_flax_module(m, p)
        assert dm.pipeline_spec is None

    def test_wrap_forward_matches_module(self, novel):
        module, params = novel
        dm = wrap_flax_module(module, params, name="novel")
        x, t, c = _inputs(2)
        np.testing.assert_allclose(
            np.asarray(dm(x, t, c)),
            np.asarray(module.apply({"params": params}, x, t, c)),
            rtol=1e-5, atol=1e-6,
        )
        assert dm.block_lists == {"layers": 4}


class TestPipelinePath:
    def test_batch_one_rides_auto_derived_pipeline(self, novel, cpu_devices):
        module, params = novel
        dm = wrap_flax_module(module, params)
        pm = parallelize(dm, DeviceChain.even([f"cpu:{i}" for i in range(4)]))
        x, t, c = _inputs(1)
        got = pm(x, t, c)
        # The batch==1 routing built and used the pipeline runner (not single).
        assert pm._pipeline_runner is not None
        assert pm._pipeline_runner.n_stages > 1
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(module.apply({"params": params}, x, t, c)),
            rtol=1e-5, atol=1e-6,
        )

    def test_explicit_spec_hint_on_tuple(self, novel, cpu_devices):
        # The (apply, params) form cannot carry attributes; the explicit
        # pipeline_spec argument is the segments hint.
        module, params = novel
        spec = derive_pipeline_spec(module, params)

        def apply_fn(p, x, t, context=None, **kw):
            return module.apply({"params": p}, x, t, context, **kw)

        pm = parallelize(
            (apply_fn, params),
            DeviceChain.even([f"cpu:{i}" for i in range(4)]),
            pipeline_spec=spec,
        )
        x, t, c = _inputs(1)
        got = pm(x, t, c)
        assert pm._pipeline_runner is not None
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(module.apply({"params": params}, x, t, c)),
            rtol=1e-5, atol=1e-6,
        )
