"""Integration tests for the orchestrator routing table + SPMD data-parallel path on
the virtual 8-device CPU mesh — the sharded-vs-single equivalence deliverable of
SURVEY §7 step 3 (and the routing parity of parallel_forward, 1287-1315)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu import DeviceChain, ParallelConfig, parallelize
from comfyui_parallelanything_tpu.parallel.orchestrator import (
    ParallelModel,
    _PlatformGroup,
)
from comfyui_parallelanything_tpu.parallel.mesh import build_mesh, place_params


def toy_apply(params, x, t, context=None, **kwargs):
    """A stand-in diffusion forward: forward(x, timesteps, context, **kwargs), batch
    on dim0 (the convention at any_device_parallel.py:1287)."""
    h = x @ params["w"] + params["b"]
    h = h * jnp.cos(t)[:, None]
    if context is not None:
        h = h + context.sum(axis=-1, keepdims=True)
    if "y" in kwargs and kwargs["y"] is not None:
        h = h + kwargs["y"]
    return h


@pytest.fixture(scope="module")
def toy():
    rng = np.random.default_rng(0)
    params = {
        "w": jnp.asarray(rng.normal(size=(4, 4)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32),
    }
    return toy_apply, params


def _inputs(batch, with_context=True, seed=1):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(batch, 4)), jnp.float32)
    t = jnp.asarray(rng.uniform(0, 1, size=(batch,)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(batch, 3)), jnp.float32) if with_context else None
    return x, t, c


def even_chain(n):
    return DeviceChain.even([f"cpu:{i}" for i in range(n)])


class TestDataParallel:
    def test_sharded_matches_single_device(self, toy):
        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(8))
        assert isinstance(pm, ParallelModel)
        x, t, c = _inputs(16)
        got = pm(x, t, c)
        want = apply_fn(params, x, t, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_uneven_batch_padding(self, toy):
        # batch=21 on 8 devices: pad to 24, slice back — the Z_Image Turbo batch.
        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(8))
        x, t, c = _inputs(21)
        got = pm(x, t, c)
        assert got.shape == (21, 4)
        want = apply_fn(params, x, t, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_kwargs_split_and_broadcast(self, toy):
        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(4))
        x, t, c = _inputs(8)
        y = jnp.ones((8, 4))
        got = pm(x, t, c, y=y)
        want = apply_fn(params, x, t, c, y=y)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_output_is_batch_sharded(self, toy):
        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(8))
        x, t, c = _inputs(16)
        got = pm(x, t, c)
        # The result is a global array; XLA kept it sharded (no host gather).
        assert isinstance(got, jax.Array)


class TestRouting:
    def test_batch_smaller_than_devices_shrinks_mesh(self, toy):
        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(8))
        x, t, c = _inputs(4)
        got = pm(x, t, c)
        want = apply_fn(params, x, t, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_batch_smaller_strict_parity_single_device(self, toy):
        # Reference parity: batch < devices → single device (1307-1315).
        apply_fn, params = toy
        cfg = ParallelConfig(pad_small_batches=False)
        pm = parallelize((apply_fn, params), even_chain(8), cfg)
        x, t, c = _inputs(4)
        got = pm(x, t, c)
        want = apply_fn(params, x, t, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_workload_split_disabled_single_device(self, toy):
        apply_fn, params = toy
        cfg = ParallelConfig(workload_split=False)
        pm = parallelize((apply_fn, params), even_chain(8), cfg)
        x, t, c = _inputs(16)
        got = pm(x, t, c)
        want = apply_fn(params, x, t, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_batch_one_no_pipeline_falls_to_single(self, toy):
        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(8))
        x, t, c = _inputs(1)
        got = pm(x, t, c)
        want = apply_fn(params, x, t, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


class TestSetupSemantics:
    def test_zero_percentage_chain_returns_model_unchanged(self, toy):
        # Parity: sum(pct) <= 0 aborts, model returned untouched (1019-1027).
        apply_fn, params = toy
        chain = DeviceChain((type(next(iter(even_chain(1)))) ("cpu", 0.0),))
        model = (apply_fn, params)
        out = parallelize(model, chain)
        assert out is model

    def test_invalid_devices_skipped(self, toy):
        apply_fn, params = toy
        chain = DeviceChain.from_pairs([("cpu:0", 50), ("cpu:99", 50)])
        pm = parallelize((apply_fn, params), chain)
        assert isinstance(pm, ParallelModel)
        assert pm.devices == ("cpu:0",)

    def test_duplicate_devices_merge(self, toy):
        apply_fn, params = toy
        chain = DeviceChain.from_pairs([("cpu:0", 25), ("cpu:0", 25), ("cpu:1", 50)])
        pm = parallelize((apply_fn, params), chain)
        assert pm.devices == ("cpu:0", "cpu:1")
        assert pm.weights == (0.5, 0.5)

    def test_object_model_unwrap(self, toy):
        apply_fn, params = toy

        @dataclasses.dataclass
        class Model:
            params: object

            def apply(self, params, x, t, context=None, **kw):
                return toy_apply(params, x, t, context, **kw)

        pm = parallelize(Model(params), even_chain(2))
        assert isinstance(pm, ParallelModel)

    def test_bad_model_type_raises(self):
        with pytest.raises(TypeError):
            parallelize(42, even_chain(2))

    def test_rebalance_shifts_weights_after_memory_change(self, toy, monkeypatch):
        # Parity (deferred): the reference re-reads free VRAM every step and blends
        # 0.7*user + 0.3*mem (737-766, 1317-1322); here rebalance() does the same
        # on demand between sampler runs.
        from comfyui_parallelanything_tpu.parallel import orchestrator as orch

        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(4))
        assert pm.weights == (0.25, 0.25, 0.25, 0.25)
        # Synthetic memory pressure: devices 2/3 report half the free bytes.
        fake = {0: 8 << 30, 1: 8 << 30, 2: 4 << 30, 3: 4 << 30}
        monkeypatch.setattr(orch, "free_memory_bytes", lambda d: fake[d.id])
        new = pm.rebalance()
        np.testing.assert_allclose(sum(new), 1.0, rtol=1e-6)
        np.testing.assert_allclose(new[0], 0.7 * 0.25 + 0.3 * (8 / 24), rtol=1e-6)
        np.testing.assert_allclose(new[2], 0.7 * 0.25 + 0.3 * (4 / 24), rtol=1e-6)
        assert pm._pipeline_runner is None  # stage placement re-balances lazily
        # Blend is against the ORIGINAL user weights — a second rebalance with the
        # same readings is a fixed point, not a compounding drift.
        again = pm.rebalance()
        np.testing.assert_allclose(again, new, rtol=1e-6)
        # Execution stays correct after the shift.
        x, t, c = _inputs(8)
        got = pm(x, t, c)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(apply_fn(params, x, t, c)), rtol=1e-5, atol=1e-6
        )

    def test_rebalance_noop_when_auto_balance_off(self, toy, monkeypatch):
        # Parity: the reference gates the per-step VRAM re-blend on
        # auto_balance_ref (any_device_parallel.py:1317-1322) — with it off,
        # explicit user weights must survive rebalance() untouched.
        from comfyui_parallelanything_tpu.parallel import orchestrator as orch

        apply_fn, params = toy
        chain = DeviceChain.from_pairs(
            [("cpu:0", 60.0), ("cpu:1", 25.0), ("cpu:2", 10.0), ("cpu:3", 5.0)]
        )
        pm = parallelize(
            (apply_fn, params), chain, ParallelConfig(auto_memory_balance=False)
        )
        before = pm.weights
        np.testing.assert_allclose(before, (0.60, 0.25, 0.10, 0.05), rtol=1e-6)
        fake = {0: 8 << 30, 1: 1 << 30, 2: 1 << 30, 3: 1 << 30}
        monkeypatch.setattr(orch, "free_memory_bytes", lambda d: fake[d.id])
        assert pm.rebalance() == before
        assert pm.weights == before

    def test_reentrant_rewrap(self, toy):
        # Parity: setup_parallel on an already-parallel model tears down the old
        # setup and rebuilds with the new chain (any_device_parallel.py:1006-1013).
        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(8))
        x, t, c = _inputs(16)
        pm(x, t, c)
        old_groups = pm._groups
        pm2 = parallelize(pm, even_chain(4))
        # Old wrapper was torn down...
        assert not pm.active
        assert all(g.params is None for g in old_groups)
        # ...and the new one routes over the new chain with correct results.
        assert isinstance(pm2, ParallelModel)
        assert pm2.devices == ("cpu:0", "cpu:1", "cpu:2", "cpu:3")
        assert pm2.active
        got = pm2(x, t, c)
        want = apply_fn(params, x, t, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
        assert len(got.sharding.device_set) == 4

    def test_reentrant_rewrap_unusable_chain_returns_torn_down_model(self, toy):
        # Reference ordering: the re-entrancy teardown (1006-1013) runs before the
        # weight-normalization abort (1019-1027) — an unusable new chain still
        # leaves the previous setup torn down, and the model keeps working via the
        # single-device path.
        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(8))
        out = parallelize(pm, [("cpu:0", 0.0)])
        assert out is pm
        assert not pm.active
        x, t, c = _inputs(4)
        got = pm(x, t, c)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(apply_fn(params, x, t, c)), rtol=1e-5, atol=1e-6
        )

    def test_gc_teardown_honors_purge_flags(self, toy, monkeypatch):
        # Parity: weakref.finalize(model, cleanup_parallel_model, ...) at
        # any_device_parallel.py:1459 — dropping every reference to the wrapped
        # MODEL must still honor purge_cache/purge_models.
        import gc

        from comfyui_parallelanything_tpu.parallel import orchestrator as orch

        purges = []
        monkeypatch.setattr(
            orch, "aggressive_cleanup",
            lambda clear_compile_cache=False: purges.append(clear_compile_cache),
        )
        apply_fn, params = toy
        pm = parallelize(
            (apply_fn, params), even_chain(2),
            ParallelConfig(purge_cache=True, purge_models=True),
        )
        fin = pm._finalizer
        del pm
        gc.collect()
        assert not fin.alive
        assert True in purges  # purge_models=True → compile caches cleared

        # purge_cache=False → GC teardown does NOT purge.
        purges.clear()
        pm2 = parallelize(
            (apply_fn, params), even_chain(2), ParallelConfig(purge_cache=False)
        )
        del pm2
        gc.collect()
        assert purges == []

    def test_explicit_cleanup_detaches_finalizer(self, toy):
        import gc

        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(2))
        pm.cleanup()
        assert not pm._finalizer.alive  # detached: no double-teardown at GC
        del pm
        gc.collect()

    def test_cleanup(self, toy):
        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(4))
        x, t, c = _inputs(8)
        pm(x, t, c)
        pm.cleanup()
        assert not pm.active
        # Post-teardown calls still work, routed single-device (the reference restores
        # the original forward at teardown, 224-229).
        got = pm(x, t, c)
        want = apply_fn(params, x, t, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


class TestReviewRegressions:
    """Regressions for the findings of the first code review: container inputs under
    padding, static (non-array) kwargs, dict outputs under padding, and post-OOM
    memory behavior."""

    def test_container_input_with_padding(self, toy):
        # list-shaped x with batch=21 on 8 devices → pad path must tree-map, not
        # jnp-op the list.
        _, params = toy

        def apply_fn(params, x, t, context=None, **kw):
            a, b = x
            return a @ params["w"] + b @ params["w"]

        pm = parallelize((apply_fn, params), even_chain(8))
        rng = np.random.default_rng(3)
        a = jnp.asarray(rng.normal(size=(21, 4)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(21, 4)), jnp.float32)
        t = jnp.linspace(0, 1, 21)
        got = pm([a, b], t)
        want = apply_fn(params, [a, b], t)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_static_string_kwarg_all_routes(self, toy):
        # Non-array kwargs must bake as jit statics on both DP and single routes.
        _, params = toy

        def apply_fn(params, x, t, context=None, mode="linear", **kw):
            h = x @ params["w"]
            if mode == "double":
                h = h * 2.0
            return h

        x, t, _ = _inputs(16, with_context=False)
        for cfg in [ParallelConfig(), ParallelConfig(workload_split=False)]:
            pm = parallelize((apply_fn, params), even_chain(8), cfg)
            got = pm(x, t, mode="double")
            want = apply_fn(params, x, t, mode="double")
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
            )

    def test_dict_output_unpadded(self, toy):
        # Dict outputs must be sliced back to the true batch after padding.
        _, params = toy

        def apply_fn(params, x, t, context=None, **kw):
            return {"sample": x @ params["w"], "aux": jnp.float32(1.0)}

        pm = parallelize((apply_fn, params), even_chain(8))
        x, t, _ = _inputs(21, with_context=False)
        got = pm(x, t)
        assert got["sample"].shape == (21, 4)

    def test_dict_output_hybrid_concat(self, toy):
        _, params = toy

        def apply_fn(params, x, t, context=None, **kw):
            return {"sample": x @ params["w"]}

        devs = jax.devices("cpu")
        groups = []
        for dev_slice, w, name in [(devs[:4], 0.5, "cpu"), (devs[4:8], 0.5, "cpu2")]:
            mesh = build_mesh(dev_slice, {"data": len(dev_slice)})
            groups.append(
                _PlatformGroup(
                    platform=name,
                    devices=list(dev_slice),
                    device_strs=[f"cpu:{d.id}" for d in dev_slice],
                    device_weights=[w / 4] * 4,
                    mesh=mesh,
                    params=place_params(params, mesh),
                )
            )
        pm = ParallelModel(
            apply_fn=apply_fn,
            params=params,
            chain=even_chain(8),
            config=ParallelConfig(auto_memory_balance=False),
            groups=groups,
            weights=(0.5, 0.5),
        )
        x, t, _ = _inputs(16, with_context=False)
        got = pm(x, t)
        assert got["sample"].shape == (16, 4)
        want = apply_fn(params, x, t)
        np.testing.assert_allclose(
            np.asarray(got["sample"]), np.asarray(want["sample"]), rtol=1e-5, atol=1e-6
        )

    def test_demote_frees_replicas_then_single_works(self, toy):
        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(8))
        x, t, c = _inputs(16)
        pm(x, t, c)
        pm._demote()
        assert not pm.active
        assert all(g.params is None for g in pm._groups)
        got = pm(x, t, c)
        want = apply_fn(params, x, t, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
        pm.reactivate()
        assert pm.active
        got2 = pm(x, t, c)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(want), rtol=1e-5, atol=1e-6)


class TestHybridMultiGroup:
    def test_auto_reactivation_after_n_steps(self, toy):
        # reactivate_after=N resumes parallel execution
        # after N single-device steps instead of serializing the rest of a run.
        apply_fn, params = toy
        pm = parallelize(
            (apply_fn, params), even_chain(4), ParallelConfig(reactivate_after=3)
        )
        pm._demote()
        assert not pm.active
        x, t, c = _inputs(8)
        expect = np.asarray(apply_fn(params, x, t, c))
        for i in range(3):
            got = pm(x, t, c)  # N=3 single-device steps run demoted
            assert not pm.active
        got = pm(x, t, c)  # next call reactivates, runs parallel again
        assert pm.active
        assert pm._groups[0].params is not None
        np.testing.assert_allclose(np.asarray(got), expect, rtol=1e-5, atol=1e-6)

    def test_reactivate_rolls_back_partial_placement(self, toy, monkeypatch):
        # A placement failure on a later group must free the groups placed in
        # the same attempt — a failed retry can't pin extra replicas through
        # the memory-pressured demoted period.
        import copy

        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(4))
        # Fake a second platform group so reactivate places two groups.
        g2 = copy.copy(pm._groups[0])
        g2.devices = list(pm._groups[0].devices)
        pm._groups.append(g2)
        pm._demote()
        assert all(g.params is None for g in pm._groups)
        calls = []

        def fake_place(p, mesh):
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("RESOURCE_EXHAUSTED: fake")
            return p

        monkeypatch.setattr(pm, "_place", fake_place)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            pm.reactivate()
        assert not pm.active
        assert all(g.params is None for g in pm._groups)  # rolled back
        pm._groups.pop()

    def test_cleaned_up_model_never_auto_reactivates(self, toy):
        # cleanup() is terminal: neither the step counter nor rebalance() may
        # resurrect placements the user explicitly tore down.
        apply_fn, params = toy
        pm = parallelize(
            (apply_fn, params), even_chain(4), ParallelConfig(reactivate_after=1)
        )
        pm.cleanup()
        x, t, c = _inputs(8)
        for _ in range(3):
            pm(x, t, c)
        assert not pm.active
        pm.rebalance()
        assert not pm.active

    def test_cleanup_on_demoted_model_purges(self, toy, monkeypatch):
        # A demoted model still holds a lead copy / compile caches — cleanup()
        # must run the purge even though active is already False.
        from comfyui_parallelanything_tpu.parallel import orchestrator as orch

        purges = []
        monkeypatch.setattr(
            orch, "aggressive_cleanup",
            lambda clear_compile_cache=False: purges.append(clear_compile_cache),
        )
        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(2))
        pm._demote()
        x, t, c = _inputs(4)
        pm(x, t, c)  # builds the lead-device fallback placement
        assert pm._lead_params is not None
        purges.clear()
        pm.cleanup()
        assert pm._lead_params is None
        assert purges  # purge_cache honored despite prior demotion

    def test_demotion_permanent_by_default(self, toy):
        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(4))
        pm._demote()
        x, t, c = _inputs(8)
        for _ in range(5):
            pm(x, t, c)
        assert not pm.active  # reference-documented default: manual reactivate

    def test_rebalance_reactivates_demoted_chain(self, toy):
        apply_fn, params = toy
        pm = parallelize((apply_fn, params), even_chain(4))
        pm._demote()
        pm.rebalance()
        assert pm.active

    def test_two_group_weighted_dispatch(self, toy):
        """Exercise the heterogeneous two-program path by hand-building two platform
        groups out of CPU devices (70/30 weighted host scatter + async concat)."""
        apply_fn, params = toy
        devs = jax.devices("cpu")
        groups = []
        for dev_slice, w, name in [(devs[:4], 0.7, "cpu"), (devs[4:8], 0.3, "cpu2")]:
            mesh = build_mesh(dev_slice, {"data": len(dev_slice)})
            groups.append(
                _PlatformGroup(
                    platform=name,
                    devices=list(dev_slice),
                    device_strs=[f"cpu:{d.id}" for d in dev_slice],
                    device_weights=[w / 4] * 4,
                    mesh=mesh,
                    params=place_params(params, mesh),
                )
            )
        pm = ParallelModel(
            apply_fn=apply_fn,
            params=params,
            chain=even_chain(8),
            config=ParallelConfig(auto_memory_balance=False),
            groups=groups,
            weights=(0.7, 0.3),
        )
        x, t, c = _inputs(20)
        got = pm(x, t, c)
        assert got.shape == (20, 4)
        want = apply_fn(params, x, t, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
