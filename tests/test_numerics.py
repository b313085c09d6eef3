"""Numerics sentinel (utils/numerics.py): non-finite quarantine, latent
fingerprints, drift auditing — all off-hardware.

The contracts under test:

- disabled is a no-op: a serving round with the sentinel off emits no stats,
  no digests, no ``pa_numerics_*`` metrics (the single-flag-check contract);
- fingerprint invariance: a lane's per-eval digest stack is bitwise-equal
  across occupancy (solo vs co-batched), bucket width, execution mode
  (compiled lane program vs width-1 eager StepPlan walk), and the 8-device
  mesh dp placement — for EVERY registered sampler × {eps, flow}, reusing
  the round-10 equivalence harness (tests/test_serving.py);
- NaN quarantine: ``PA_FAIL_INJECT=nan:<lane>`` poisons one lane of a
  4-lane mixed-sampler co-batched dispatch → exactly that lane retires with
  :class:`NonFiniteLatent` and a postmortem bundle naming the first
  non-finite block/step/σ, while survivors stay BITWISE identical to their
  uninjected co-batched runs (the select-mask retirement discipline);
- the per-block bisection names a poisoned PipelineSpec segment; the
  streaming runner names a poisoned stage;
- the drift gate (scripts/numerics_audit.py) passes on stable fingerprints,
  fails on drift or non-finite events, and SKIPs an empty ledger.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu import DeviceChain, parallelize
from comfyui_parallelanything_tpu.models.api import (
    DiffusionModel,
    PipelineSegment,
    PipelineSpec,
)
from comfyui_parallelanything_tpu.sampling.lane_specs import LANE_SPECS
from comfyui_parallelanything_tpu.sampling.runner import run_sampler
from comfyui_parallelanything_tpu.serving import ContinuousBatchingScheduler
from comfyui_parallelanything_tpu.utils import numerics
from comfyui_parallelanything_tpu.utils.metrics import registry

# The round-10 serving equivalence harness — reused on purpose (the ISSUE's
# fingerprint matrix rides the same tiny model, inputs, and manual-pump
# handshake the lane-vs-solo matrix pinned).
from test_serving import (
    LANE_MATRIX,
    LANE_MATRIX_FLOW,
    TOL,
    _wait_enqueued,
    mk_inputs,
    tiny_model,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class StreamingStyleModel:
    """Not single-program traceable → width-1 eager StepPlan walk."""

    is_streaming = True

    def __call__(self, x, t, context=None, **kw):
        return tiny_model(x, t, context)


@pytest.fixture
def sentinel_on():
    numerics.enable()
    numerics.sentinel.reset()
    try:
        yield numerics.sentinel
    finally:
        numerics.sentinel.reset()
        numerics.disable()


def _serve(plans, *, width=4, model=tiny_model, mkfn=mk_inputs):
    """Run each plan through run_sampler against a manual-pump scheduler;
    returns (results, errors) keyed by plan index."""
    s = ContinuousBatchingScheduler(max_width=width, auto=False).install()
    try:
        results, errors = {}, {}

        def worker(j, kw):
            kw = dict(kw)
            noise, ctx = mkfn(kw.pop("seed"))
            try:
                results[j] = run_sampler(model, noise, ctx, **kw)
            except BaseException as e:  # noqa: BLE001 — assertion target
                errors[j] = e

        threads = [
            threading.Thread(target=worker, args=(j, p), daemon=True)
            for j, p in enumerate(plans)
        ]
        for t in threads:
            t.start()
        _wait_enqueued(s, len(plans))
        s.drain()
        for t in threads:
            t.join(30)
        return results, errors
    finally:
        s.uninstall()
        s.shutdown()


def _digests(sampler: str, steps: int | None = None) -> list[list[int]]:
    """Fingerprint stacks recorded for ``sampler`` (optionally filtered by
    σ-interval count — the ragged co-batch partner also records one)."""
    return [r["digests"] for r in numerics.sentinel.recent_fingerprints()
            if r.get("sampler") == sampler
            and (steps is None or r.get("steps") == steps)]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


class TestHelpers:
    def test_array_stats_counts_nonfinite_and_masks_magnitudes(self):
        x = jnp.asarray([[1.0, -2.0], [3.0, 4.0]])
        st = numerics.stats_to_dict(np.asarray(numerics.array_stats(x)))
        assert st["nonfinite"] == 0
        assert st["max_abs"] == pytest.approx(4.0)
        assert st["mean"] == pytest.approx(1.5)
        bad = x.at[0, 0].set(jnp.nan).at[1, 1].set(jnp.inf)
        st2 = numerics.stats_to_dict(np.asarray(numerics.array_stats(bad)))
        assert st2["nonfinite"] == 2
        assert np.isfinite(st2["max_abs"])  # poisoned entries masked out

    def test_lane_stats_counts_extra_state(self):
        x = jnp.zeros((3, 4))
        xe = jnp.zeros((3, 4)).at[1, 2].set(jnp.nan)
        st = np.asarray(numerics.lane_stats(x, extra=xe))
        assert st.shape == (3, 4)
        assert list(st[:, 0]) == [0.0, 1.0, 0.0]

    def test_digest_value_sensitive_and_lane_local(self):
        r = np.random.default_rng(0)
        x = jnp.asarray(r.normal(size=(2, 8, 8, 4)).astype(np.float32))
        d0 = int(np.asarray(numerics.digest(x[0])))
        d1 = int(np.asarray(numerics.digest(x[1])))
        assert d0 != d1
        ld = np.asarray(numerics.lane_digest(x))
        # lane-local positions: stacked digest == each slice's own digest
        assert [int(ld[0]), int(ld[1])] == [d0, d1]
        # bf16-quantized: a change below bf16 resolution is invisible, a
        # bf16-visible change flips the digest
        assert int(np.asarray(numerics.digest(x[0] * (1.0 + 1e-6)))) == d0
        assert int(np.asarray(numerics.digest(x[0] * 1.5))) != d0

    def test_fingerprint_format(self):
        fp = numerics.latent_fingerprint(jnp.ones((2, 3)))
        assert fp.startswith("bf16:2x3:") and len(fp.split(":")[-1]) == 8

    def test_bisect_names_poisoned_pipeline_segment(self):
        def prepare(params, x, t, context=None, **kw):
            return {"h": x * params["p"]}

        def seg(key):
            def fn(params, carry):
                return {"h": carry["h"] * params[key]}

            return fn

        params = {
            "p": jnp.ones((4,)),
            "s0": jnp.ones((4,)),
            "s1": jnp.full((4,), jnp.inf),  # the poisoned block
            "s2": jnp.ones((4,)),
        }
        spec = PipelineSpec(
            prepare_keys=("p",), prepare=prepare,
            segments=(
                PipelineSegment(("s0",), seg("s0"), "blk0"),
                PipelineSegment(("s1",), seg("s1"), "blk1"),
                PipelineSegment(("s2",), seg("s2"), "blk2"),
            ),
            finalize_keys=(), finalize=lambda p, c, shape: c["h"],
        )
        model = DiffusionModel(
            apply=lambda p, x, t, c=None, **kw: x, params=params,
            pipeline_spec=spec,
        )
        log_sig = jnp.log(jnp.linspace(10.0, 0.01, 50))[::-1]
        out = numerics.bisect_nonfinite(
            model, jnp.ones((1, 4)), 5.0, "eps", log_sig, None
        )
        assert out["block"] == "blk1" and out["segment_index"] == 1
        # poisoned INPUT short-circuits before any stage runs
        out2 = numerics.bisect_nonfinite(
            model, jnp.full((1, 4), jnp.nan), 5.0, "eps", log_sig, None
        )
        assert out2["block"] == "lane-input"


# ---------------------------------------------------------------------------
# disabled path
# ---------------------------------------------------------------------------


class TestDisabledNoOp:
    def test_default_off(self):
        assert numerics.on() is False

    def test_serving_round_emits_nothing_when_off(self):
        numerics.sentinel.reset()
        before = registry.get("pa_numerics_nonfinite_total",
                              {"where": "serving-lane"})
        res, err = _serve([dict(sampler="dpmpp_2m", steps=3, seed=301)])
        assert not err and res[0].shape == (1, 8, 8, 4)
        assert numerics.sentinel.event_count == 0
        assert numerics.sentinel.recent_fingerprints() == []
        after = registry.get("pa_numerics_nonfinite_total",
                             {"where": "serving-lane"})
        assert before == after  # no metric touched

    def test_injection_unarmed_without_evidence_redirect(self, monkeypatch):
        monkeypatch.setenv("PA_FAIL_INJECT", "nan:0")
        monkeypatch.delenv("PA_LEDGER_DIR", raising=False)
        monkeypatch.delenv("PA_EVIDENCE_DIR", raising=False)
        assert numerics.fail_inject_lane() is None


# ---------------------------------------------------------------------------
# fingerprint invariance matrix (the (request, step) digest stack must be
# identical across every execution configuration)
# ---------------------------------------------------------------------------


def _matrix_kw(sampler: str, prediction: str):
    kw = dict(sampler=sampler, steps=4,
              seed=700 + LANE_MATRIX.index(sampler))
    if prediction == "flow":
        kw.update(prediction="flow", shift=1.15, seed=kw["seed"] + 50)
    if LANE_SPECS[sampler].needs_rng:
        kw["rng"] = jax.random.key(9)
    return kw


class TestFingerprintInvariance:
    @pytest.mark.parametrize("sampler", LANE_MATRIX)
    def test_eps_digest_stack_invariant(self, sentinel_on, sampler):
        """Solo vs co-batched (ragged euler partner): same per-eval digest
        stack AND bitwise-equal outputs (the PR 5 occupancy contract — the
        fingerprint's invariance domain is occupancy/width/sharding, where
        the program is literally the same computation with masked lanes).
        The width-1 eager StepPlan walk is a DIFFERENT XLA program, so it is
        held to the PR 5 equivalence contract instead (bf16-scale TOL): its
        digests still land in the sentinel ring (asserted non-empty) but
        exact digest equality across programs is not a promise the bf16
        quantization can keep for every element near a rounding boundary."""
        kw = _matrix_kw(sampler, "eps")
        solo_res, _ = _serve([kw])
        solo = _digests(sampler, steps=4)[-1]
        co_res, _ = _serve([kw, dict(sampler="euler", steps=6, seed=99)])
        co = _digests(sampler, steps=4)[-1]
        assert co == solo, f"{sampler}: digest stack changed with occupancy"
        np.testing.assert_array_equal(np.asarray(solo_res[0]),
                                      np.asarray(co_res[0]))
        n_before = len(_digests(sampler, steps=4))
        eager_res, _ = _serve([kw], model=StreamingStyleModel())
        assert len(_digests(sampler, steps=4)) == n_before + 1
        np.testing.assert_allclose(np.asarray(eager_res[0]),
                                   np.asarray(solo_res[0]), **TOL)

    @pytest.mark.parametrize("sampler", LANE_MATRIX_FLOW)
    def test_flow_digest_stack_invariant(self, sentinel_on, sampler):
        kw = _matrix_kw(sampler, "flow")
        _serve([kw])
        solo = _digests(sampler, steps=4)[-1]
        _serve([kw, dict(sampler="euler", steps=5, prediction="flow",
                         shift=1.15, seed=98)])
        assert _digests(sampler, steps=4)[-1] == solo

    def test_width_invariance(self, sentinel_on):
        kw = _matrix_kw("dpmpp_2m_sde", "eps")
        _serve([kw], width=4)
        d4 = _digests("dpmpp_2m_sde")[-1]
        _serve([kw], width=8)
        assert _digests("dpmpp_2m_sde")[-1] == d4

    def test_mesh_dp_invariance(self, sentinel_on, cpu_devices):
        """8-device mesh dp: solo vs co-batched digest stacks equal — the
        order-independent modular digest cannot see the sharding."""
        rng = np.random.default_rng(0)
        params = {
            "w": jnp.asarray(rng.normal(size=(4, 4)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32),
        }

        def toy_apply(p, x, t, context=None, **kw):
            h = jnp.tanh(x @ p["w"] * 0.1 + p["b"]) * 0.8
            h = h * jnp.cos(t * 1e-3)[:, None]
            return h + 0.01 * context.sum(axis=-1, keepdims=True)

        pm = parallelize(
            (toy_apply, params),
            DeviceChain.even([f"cpu:{i}" for i in range(8)]),
        )

        def mk(seed):
            r = np.random.default_rng(seed)
            return (jnp.asarray(r.normal(size=(2, 4)), jnp.float32),
                    jnp.asarray(r.normal(size=(2, 6)), jnp.float32))

        kw = dict(sampler="heun", steps=3, seed=41)
        _serve([kw], width=8, model=pm, mkfn=mk)
        solo = _digests("heun")[-1]
        _serve([kw, dict(sampler="euler", steps=5, seed=42)],
               width=8, model=pm, mkfn=mk)
        assert _digests("heun")[-1] == solo

    def test_compiled_loop_emits_fingerprint(self, sentinel_on):
        noise, ctx = mk_inputs(801)
        run_sampler(tiny_model, noise, ctx, sampler="euler", steps=3,
                    compile_loop=True)
        recs = [r for r in numerics.sentinel.recent_fingerprints()
                if r.get("where") == "loop:k:euler"]
        assert recs and len(recs[-1]["digests"]) == 1
        assert numerics.sentinel.event_count == 0

    def test_compiled_loop_records_nonfinite_event(self, sentinel_on):
        def nan_model(x, t, context=None, **kw):
            return x * jnp.inf

        noise, ctx = mk_inputs(802)
        run_sampler(nan_model, noise, ctx, sampler="euler", steps=2,
                    compile_loop=True)
        assert numerics.sentinel.event_count >= 1
        assert numerics.sentinel.last_event["where"] == "compiled-loop"


# ---------------------------------------------------------------------------
# NaN-injection quarantine
# ---------------------------------------------------------------------------


MIXED_PLANS = (
    dict(sampler="euler", steps=4, seed=711),
    dict(sampler="heun", steps=3, seed=712),
    dict(sampler="dpmpp_2m", steps=6, seed=713),
    dict(sampler="euler_ancestral", steps=5, seed=714),
)


def _mixed_plans():
    plans = [dict(p) for p in MIXED_PLANS]
    plans[3]["rng"] = jax.random.key(2)
    return plans


class TestQuarantine:
    def test_nan_injection_quarantines_one_lane_survivors_bitwise(
            self, sentinel_on, monkeypatch, tmp_path):
        """Acceptance: NaN injected into one lane of a 4-lane mixed-sampler
        co-batched dispatch → that lane quarantined (NonFiniteLatent to its
        submitter, postmortem bundle naming the first non-finite
        block/step/σ), surviving lanes bitwise-unchanged vs their uninjected
        co-batched runs."""
        clean, err0 = _serve(_mixed_plans())
        assert not err0 and len(clean) == 4
        monkeypatch.setenv("PA_LEDGER_DIR", str(tmp_path))
        monkeypatch.setenv("PA_FAIL_INJECT", "nan:2")
        numerics.sentinel.reset()  # re-arm the one-shot injection
        res, errs = _serve(_mixed_plans())
        assert len(errs) == 1 and len(res) == 3, (errs, res)
        [bad] = errs.values()
        assert isinstance(bad, numerics.NonFiniteLatent)
        assert "quarantined" in str(bad) and "σ_eval" in str(bad)
        for j, out in res.items():
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(clean[j]))
        q = numerics.sentinel.last_quarantine
        assert q is not None and q["lane"] == 2
        first = q["first_nonfinite"]
        assert first["block"] == "lane-input"  # the injected NaN itself
        assert first["step"] == 0 and first["sigma"] > 0
        assert q["bundle"] and os.path.isdir(q["bundle"])
        with open(os.path.join(q["bundle"], "error.json")) as f:
            bundle = json.load(f)
        extra = bundle["extra"]
        assert extra["first_nonfinite"]["block"] == "lane-input"
        assert extra["first_nonfinite"]["step"] == 0
        # Seating order races, so lane 2 holds SOME plan's sampler — the
        # bundle must name it, whichever it was.
        assert extra["sampler"] in {p["sampler"] for p in MIXED_PLANS}
        assert bundle["error_type"] == "NonFiniteLatent"
        assert numerics.sentinel.quarantined_count == 1
        assert registry.get("pa_numerics_quarantined_total",
                            {"bucket": q["bucket"]}) >= 1

    def test_injection_quarantines_width1_eager_lane(
            self, sentinel_on, monkeypatch, tmp_path):
        """The width-1 eager mode (streaming/hybrid models) runs the same
        quarantine discipline."""
        monkeypatch.setenv("PA_LEDGER_DIR", str(tmp_path))
        monkeypatch.setenv("PA_FAIL_INJECT", "nan:0")
        res, errs = _serve([dict(sampler="dpmpp_2m", steps=4, seed=721)],
                           model=StreamingStyleModel())
        assert not res and len(errs) == 1
        assert isinstance(errs[0], numerics.NonFiniteLatent)
        q = numerics.sentinel.last_quarantine
        assert q["first_nonfinite"]["block"] == "lane-input"
        assert q["bundle"] and os.path.isdir(q["bundle"])

    def test_freed_slot_reseats_after_quarantine(
            self, sentinel_on, monkeypatch, tmp_path):
        """A quarantined lane's slot is reusable: a later request seats in it
        and completes (state-pytree re-init on seat)."""
        monkeypatch.setenv("PA_LEDGER_DIR", str(tmp_path))
        monkeypatch.setenv("PA_FAIL_INJECT", "nan:0")
        s = ContinuousBatchingScheduler(max_width=1, auto=False).install()
        try:
            results, errors = {}, {}

            def worker(j, seed, steps):
                noise, ctx = mk_inputs(seed)
                try:
                    results[j] = run_sampler(tiny_model, noise, ctx,
                                             sampler="euler", steps=steps)
                except BaseException as e:  # noqa: BLE001
                    errors[j] = e

            ta = threading.Thread(target=worker, args=(0, 731, 4), daemon=True)
            ta.start()
            _wait_enqueued(s, 1)
            s.pump()  # injection fires → lane 0 quarantined
            ta.join(20)  # the submitter re-raises NonFiniteLatent and exits
            assert isinstance(errors.get(0), numerics.NonFiniteLatent)
            tb = threading.Thread(target=worker, args=(1, 732, 3), daemon=True)
            tb.start()
            _wait_enqueued(s, 1)
            s.drain()
            ta.join(20)
            tb.join(20)
            assert 1 in results and results[1].shape == (1, 8, 8, 4)
        finally:
            s.uninstall()
            s.shutdown()


# ---------------------------------------------------------------------------
# streaming per-stage stats
# ---------------------------------------------------------------------------


class TestStreamingStats:
    def _toy_spec_and_params(self, poison: bool):
        def prepare(params, x, t, context=None, **kw):
            return {"h": x * params["p"]}

        def seg(key):
            def fn(params, carry):
                return {"h": carry["h"] * params[key]}

            return fn

        params = {
            "p": jnp.ones((4,)),
            "s0": jnp.ones((4,)),
            "s1": jnp.full((4,), jnp.inf) if poison else jnp.ones((4,)),
        }
        spec = PipelineSpec(
            prepare_keys=("p",), prepare=prepare,
            segments=(
                PipelineSegment(("s0",), seg("s0"), "blk0"),
                PipelineSegment(("s1",), seg("s1"), "blk1"),
            ),
            finalize_keys=(), finalize=lambda p, c, shape: c["h"],
        )
        return spec, params

    def test_poisoned_stage_is_named(self, sentinel_on):
        from comfyui_parallelanything_tpu.parallel.streaming import (
            StreamingRunner,
        )

        spec, params = self._toy_spec_and_params(poison=True)
        runner = StreamingRunner(spec, params, jax.devices("cpu")[0],
                                 n_stages=2)
        out = runner(jnp.ones((1, 4)), jnp.ones((1,)))
        assert not np.isfinite(np.asarray(out)).all()
        assert numerics.sentinel.event_count >= 1
        ev = numerics.sentinel.last_event
        assert ev["where"] in ("stream-stage", "stream-output")
        assert "blk1" in ev["blocks"]

    def test_healthy_stream_records_nothing(self, sentinel_on):
        from comfyui_parallelanything_tpu.parallel.streaming import (
            StreamingRunner,
        )

        spec, params = self._toy_spec_and_params(poison=False)
        runner = StreamingRunner(spec, params, jax.devices("cpu")[0],
                                 n_stages=2)
        runner(jnp.ones((1, 4)), jnp.ones((1,)))
        assert numerics.sentinel.event_count == 0


# ---------------------------------------------------------------------------
# drift gate (scripts/numerics_audit.py) + health/trace surfaces
# ---------------------------------------------------------------------------


def _audit():
    sys.path.insert(0, os.path.join(_REPO, "scripts"))
    import numerics_audit

    return numerics_audit


def _bench_rec(fp: str, nfe=0, ts=1, **kw):
    return {"schema": "pa-perf-ledger/v1", "kind": "bench", "rung": "smoke",
            "platform": "cpu", "value": 5.0, "latent_fingerprint": fp,
            "nonfinite_events": nfe, "ts": ts, **kw}


class TestAuditGate:
    def test_ok_drift_and_skip(self, tmp_path, capsys):
        audit = _audit()
        stable = [_bench_rec("bf16:1:aaaaaaaa", ts=1),
                  _bench_rec("bf16:1:aaaaaaaa", ts=2)]
        assert audit.check(stable, {}, ledger_dir=str(tmp_path)) == 0
        gate = json.loads((tmp_path / "numerics_gate.json").read_text())
        assert gate["status"] == "ok"
        drifted = stable + [_bench_rec("bf16:1:bbbbbbbb", ts=3)]
        assert audit.check(drifted, {}, ledger_dir=str(tmp_path)) == 1
        gate = json.loads((tmp_path / "numerics_gate.json").read_text())
        assert gate["status"] == "drift"
        assert audit.check([], {}, ledger_dir=str(tmp_path)) == 0
        gate = json.loads((tmp_path / "numerics_gate.json").read_text())
        assert gate["status"] == "skip"
        capsys.readouterr()

    def test_golden_beats_prior_and_nonfinite_fails(self, tmp_path, capsys):
        audit = _audit()
        golden = {"smoke/cpu": {"fingerprint": "bf16:1:aaaaaaaa"}}
        # prior drifted but golden matches the latest → OK (the golden is
        # the contract, not the noisy history)
        recs = [_bench_rec("bf16:1:cccccccc", ts=1),
                _bench_rec("bf16:1:aaaaaaaa", ts=2)]
        assert audit.check(recs, golden, ledger_dir=str(tmp_path)) == 0
        # a poisoned latest fails even with a matching fingerprint
        recs.append(_bench_rec("bf16:1:aaaaaaaa", nfe=3, ts=3))
        assert audit.check(recs, golden, ledger_dir=str(tmp_path)) == 1
        capsys.readouterr()

    def test_stale_and_dryrun_never_compared(self, tmp_path, capsys):
        audit = _audit()
        recs = [_bench_rec("bf16:1:aaaaaaaa", ts=1),
                _bench_rec("bf16:1:dddddddd", ts=2, stale=True),
                _bench_rec("bf16:1:eeeeeeee", ts=3, dryrun=True)]
        assert audit.check(recs, {}, ledger_dir=str(tmp_path)) == 0
        capsys.readouterr()

    def test_bank_then_check_roundtrip(self, tmp_path, capsys):
        audit = _audit()
        ledger = tmp_path / "perf_ledger.jsonl"
        with open(ledger, "w") as f:
            f.write(json.dumps(_bench_rec("bf16:1:abcd1234")) + "\n")
        golden_path = str(tmp_path / "numerics_golden.json")
        recs = audit._load_jsonl(str(ledger))
        assert audit.bank(recs, golden_path) == 0
        golden = audit._load_golden(golden_path)
        assert golden["smoke/cpu"]["fingerprint"] == "bf16:1:abcd1234"
        assert audit.check(recs, golden, ledger_dir=str(tmp_path)) == 0
        capsys.readouterr()

    def test_cli_check_without_a_backend(self, tmp_path):
        """The gate is jax-free: runs (and passes) in a child whose env
        points at a temp ledger, never importing jax."""
        with open(tmp_path / "perf_ledger.jsonl", "w") as f:
            f.write(json.dumps(_bench_rec("bf16:1:12341234")) + "\n")
            f.write(json.dumps(_bench_rec("bf16:1:12341234", ts=2)) + "\n")
        env = dict(os.environ, PA_LEDGER_DIR=str(tmp_path))
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "scripts",
                                          "numerics_audit.py"), "--check"],
            env=env, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "OK" in proc.stdout


class TestSurfaces:
    def test_health_snapshot_numerics_section(self, sentinel_on, monkeypatch,
                                              tmp_path):
        from comfyui_parallelanything_tpu.utils.telemetry import (
            health_snapshot,
        )

        monkeypatch.setenv("PA_LEDGER_DIR", str(tmp_path))
        numerics.sentinel.record_event("unit-test", detail="x")
        snap = health_snapshot()
        n = snap["numerics"]
        assert n["enabled"] is True
        assert n["nonfinite_events"] == 1
        assert n["quarantined_lanes"] == 0
        assert n["last_event"]["where"] == "unit-test"
        assert n["fingerprint_gate"] is None  # gate never ran in this dir
        (tmp_path / "numerics_gate.json").write_text(
            json.dumps({"status": "ok", "ts": 1.0, "groups": {}})
        )
        assert health_snapshot()["numerics"]["fingerprint_gate"]["status"] \
            == "ok"

    def test_publish_gauges(self, sentinel_on):
        numerics.sentinel.publish_gauges()
        assert registry.get("pa_numerics_sentinel_enabled") == 1.0
        assert registry.get("pa_numerics_nonfinite_events") == 0.0

    def test_trace_summary_counts_numerics_spans(self, sentinel_on):
        from comfyui_parallelanything_tpu.utils import tracing

        sys.path.insert(0, os.path.join(_REPO, "scripts"))
        import trace_summary

        tracing.enable()
        try:
            numerics.sentinel.record_event("stream-stage", stage=1)
            numerics.sentinel.record_event("serving-lane", lane=0)
            numerics.sentinel.record_quarantine(bucket="b", lane=0, step=2)
            events = [e for e in tracing.export()["traceEvents"]
                      if e.get("ph") == "X"]
        finally:
            tracing.disable()
        s = trace_summary.summarize(events)
        assert s["numerics"]["nonfinite_events"] == 2
        assert s["numerics"]["quarantines"] == 1
        assert s["numerics"]["nonfinite_by_where"] == {
            "serving-lane": 1, "stream-stage": 1,
        }
