"""Roofline attribution layer (round 13): the analytic cost model, the
calibration round-trip, the measured-side bucket decomposition, the
instrument_jit integration (+ its disabled-path no-op), the capacity-weighted
fleet ring, and the scripts/roofline_report.py gate.

The calibration acceptance is the round-trip: synthetic ledger records →
fitted per-(program, platform, shape-bucket) scales → calibrated predictions
within bound of the measurements they were fitted on. The attribution
acceptance is conservation: buckets non-negative, summing to the wall. The
stdlib mirror in scripts/trace_summary.py is drift-pinned against
utils/roofline.attribution_from_trace on the same fixture (the
trace_summary/trace_aggregates discipline)."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from comfyui_parallelanything_tpu.fleet import (
    FleetRegistry,
    HashRing,
    ledger_capacity_weights,
)
from comfyui_parallelanything_tpu.utils import roofline, telemetry, tracing

REPO = Path(__file__).resolve().parent.parent

ATTR_BUCKETS = ("compute_s", "exposed_transfer_s", "comms_s", "host_gap_s")


# ---------------------------------------------------------------------------
# the analytic cost model
# ---------------------------------------------------------------------------


class TestCostModel:
    def test_platform_spec_resolution(self):
        v5e = roofline.platform_spec("TPU v5e", "tpu")
        assert v5e["generation"] == "v5e"
        assert v5e["peak_flops"] == 197e12 and v5e["hbm_bw"] == 819e9
        # What a v5e chip actually reports as its device_kind.
        assert roofline.platform_spec("TPU v5 lite", "tpu")["peak_flops"] \
            == 197e12
        # An accelerator the table does not list is an error, never the CPU
        # pseudo-spec (nor a generation read from the environment).
        with pytest.raises(ValueError, match="no roofline spec"):
            roofline.platform_spec("TPU v99", "tpu")
        with pytest.raises(ValueError, match="no roofline spec"):
            roofline.platform_spec("", "gpu")

    def test_cpu_pseudo_spec_is_deterministic(self):
        a = roofline.platform_spec("", "cpu")
        b = roofline.platform_spec("unknown-backend", "cpu")
        assert a["generation"] == "cpu-pseudo"
        assert {k: a[k] for k in ("peak_flops", "hbm_bw", "ici_bw")} \
            == {k: b[k] for k in ("peak_flops", "hbm_bw", "ici_bw")}

    def test_compute_vs_memory_bound(self):
        spec = roofline.platform_spec("TPU v5e", "tpu")
        compute = roofline.predict_time_s(197e12, 1e9, spec)
        assert compute["bound"] == "compute"
        assert compute["predicted_s"] == pytest.approx(1.0)
        memory = roofline.predict_time_s(1e9, 819e9, spec)
        assert memory["bound"] == "memory"
        assert memory["predicted_s"] == pytest.approx(1.0, rel=1e-6)

    def test_spmd_divides_work_over_mesh(self):
        spec = roofline.platform_spec("TPU v5e", "tpu")
        one = roofline.predict_time_s(197e12, 0, spec, n_devices=1)
        eight = roofline.predict_time_s(197e12, 0, spec, n_devices=8)
        assert eight["predicted_s"] == pytest.approx(
            one["predicted_s"] / 8
        )

    def test_collective_term(self):
        spec = roofline.platform_spec("TPU v5e", "tpu")
        # Ring model: each chip moves (n-1)/n of the payload over its link.
        assert roofline.collective_time_s(200e9, 2, spec) \
            == pytest.approx(0.5)
        assert roofline.collective_time_s(200e9, 1, spec) == 0.0
        # DCN link: the multi-host regime is slower by the link ratio.
        assert roofline.collective_time_s(200e9, 2, spec, link="dcn") \
            > roofline.collective_time_s(200e9, 2, spec, link="ici")
        pred = roofline.predict_time_s(
            1e9, 1e6, spec, n_devices=4, collective_bytes=800e9
        )
        assert pred["bound"] == "comms"
        assert pred["predicted_s"] == pytest.approx(
            pred["comms_s"] + max(pred["compute_s"], pred["memory_s"])
        )


# ---------------------------------------------------------------------------
# calibration store
# ---------------------------------------------------------------------------


def _bench_record(rung="smoke", platform="cpu", value=5.0, raw=0.5,
                  flops=1e9, **extra):
    return {
        "schema": "pa-perf-ledger/v1", "kind": "bench", "rung": rung,
        "platform": platform, "value": value,
        "predicted_step_raw_s": raw, "model_flops_per_step": flops,
        **extra,
    }


class TestCalibration:
    def test_scale_hierarchy(self):
        platform, bucket = "cpu", roofline.shape_bucket(1e9)
        calib = {
            roofline.calib_key("rung:smoke", platform, bucket):
                {"scale": 2.0, "n": 3},
            roofline.calib_key("rung:smoke", platform, "*"):
                {"scale": 3.0, "n": 5},
            roofline.calib_key("*", platform, "*"): {"scale": 4.0, "n": 9},
        }
        assert roofline.calibration_scale(
            calib, "rung:smoke", platform, bucket
        ) == 2.0
        # bucket miss → the program's any-bucket scale
        assert roofline.calibration_scale(
            calib, "rung:smoke", platform, roofline.shape_bucket(1e15)
        ) == 3.0
        # unknown program → the platform-wide learned optimism
        assert roofline.calibration_scale(
            calib, "rung:never-seen", platform, bucket
        ) == 4.0
        # empty store → uncalibrated
        assert roofline.calibration_scale({}, "x", "cpu", bucket) == 1.0

    def test_fit_and_round_trip(self, tmp_path):
        records = [_bench_record(value=v) for v in (5.0, 5.2, 4.8)]
        scales = roofline.fit_calibration(records)
        key = roofline.calib_key(
            "rung:smoke", "cpu", roofline.shape_bucket(1e9)
        )
        assert scales[key]["n"] == 3
        # conservative p25 of the measured/raw ratios (9.6, 10.0, 10.4):
        # calibrated predictions sit BELOW typical measurements so an
        # honest speedup doesn't trip the fixed (0, 1.2] gate band
        assert scales[key]["scale"] == pytest.approx(9.6)
        path = tmp_path / "roofline_calib.json"
        assert roofline.save_calibration(scales, str(path)) == str(path)
        loaded = roofline.load_calibration(str(path))
        # The round-trip acceptance: the calibrated prediction lands within
        # bound of the measurements it was fitted on.
        scale = roofline.calibration_scale(
            loaded, "rung:smoke", "cpu", roofline.shape_bucket(1e9)
        )
        calibrated = 0.5 * scale
        assert abs(calibrated - 5.0) <= 0.1 * 5.0

    def test_fit_uses_program_rows_and_skips_unfittable(self):
        records = [
            # program-level rows with a measurement fit per program
            {"schema": "pa-perf-ledger/v1", "kind": "bench",
             "platform": "cpu", "roofline_programs": {
                 "loop:k:euler": {"predicted_raw_s": 0.01, "measured_s": 0.1,
                                  "flops": 1e8, "platform": "cpu"}}},
            # stale / dryrun-marked / error / kind=dryrun records are never
            # fitted — virtual-mesh CPU timings must not calibrate real
            # predictions
            _bench_record(value=500.0, stale=True),
            _bench_record(value=500.0, dryrun=True),
            {"schema": "pa-perf-ledger/v1", "kind": "error", "value": 1.0},
            {"schema": "pa-perf-ledger/v1", "kind": "dryrun",
             "platform": "cpu", "roofline_programs": {
                 "loop:k:euler": {"predicted_raw_s": 0.01,
                                  "measured_s": 99.0, "flops": 1e8,
                                  "platform": "cpu"}}},
        ]
        scales = roofline.fit_calibration(records)
        key = roofline.calib_key(
            "loop:k:euler", "cpu", roofline.shape_bucket(1e8)
        )
        assert scales[key]["scale"] == pytest.approx(10.0)
        assert scales[key]["n"] == 1  # the dryrun's 99.0 ratio never fed in
        assert not any(k.startswith("rung:") for k in scales)

    def test_load_missing_is_empty(self, tmp_path):
        assert roofline.load_calibration(str(tmp_path / "nope.json")) == {}


# ---------------------------------------------------------------------------
# measured-side attribution
# ---------------------------------------------------------------------------


def _ev(name, ts, dur, cat="stream", **args):
    return {"ph": "X", "name": name, "cat": cat, "ts": ts, "dur": dur,
            "tid": 1, "args": args}


class TestAttribution:
    def test_streamed_window(self):
        t0 = 1000.0
        events = [
            _ev("stream-run", t0, 1000.0),
            _ev("stream-stage-compute", t0 + 100, 400.0),
            _ev("stream-stage-compute", t0 + 550, 300.0),
            _ev("stream-prefetch-wait", t0 + 20, 80.0),
        ]
        attr = roofline.attribution_from_trace(events)
        assert attr["compute_s"] == pytest.approx(7e-4)
        assert attr["exposed_transfer_s"] == pytest.approx(8e-5)
        assert attr["comms_s"] == 0.0
        assert attr["wall_s"] == pytest.approx(1e-3)
        # conservation: buckets are non-negative and sum to the wall
        assert all(attr[b] >= 0 for b in ATTR_BUCKETS)
        assert sum(attr[b] for b in ATTR_BUCKETS) \
            == pytest.approx(attr["wall_s"], rel=1e-6)

    def test_step_window_with_comms_and_last_steps(self):
        t0 = 0.0
        events = [
            _ev("step", t0, 100.0, cat="bench"),          # warmup — dropped
            _ev("step", t0 + 1000, 100.0, cat="bench"),
            _ev("fleet-hop", t0 + 1120, 50.0, cat="fleet"),
            _ev("step", t0 + 1200, 100.0, cat="bench"),
        ]
        attr = roofline.attribution_from_trace(events, last_steps=2)
        # dispatch window: host gaps measured (100µs gap, 50µs of it filled
        # by the fleet hop), compute is the residual
        assert attr["comms_s"] == pytest.approx(5e-5)
        assert attr["host_gap_s"] == pytest.approx(5e-5)
        assert attr["compute_s"] == pytest.approx(2e-4)
        assert attr["wall_s"] == pytest.approx(3e-4)
        # an externally pinned wall (the chained loop's readback extends
        # past the last dispatch) widens only the residual COMPUTE bucket —
        # the device was working through that opaque wait, the host was not
        pinned = roofline.attribution_from_trace(
            events, wall_s=1e-3, last_steps=2
        )
        assert pinned["wall_s"] == pytest.approx(1e-3)
        assert pinned["host_gap_s"] == attr["host_gap_s"]
        assert pinned["compute_s"] == pytest.approx(9e-4)
        assert sum(pinned[b] for b in ATTR_BUCKETS) \
            == pytest.approx(1e-3, rel=1e-6)

    def test_empty_trace_is_none(self):
        assert roofline.attribution_from_trace([]) is None
        assert roofline.attribution_from_trace(
            [_ev("lane-wait", 0, 10.0, cat="serving")]
        ) is None

    def test_fractions(self):
        attr = {"compute_s": 0.5, "exposed_transfer_s": 0.25,
                "comms_s": 0.0, "host_gap_s": 0.25, "wall_s": 1.0}
        fr = roofline.attribution_fractions(attr)
        assert fr["compute_fraction"] == 0.5
        assert fr["host_gap_fraction"] == 0.25
        assert roofline.attribution_fractions(None) is None

    def test_traced_streamed_run_buckets_sum_to_wall(self):
        """The acceptance on a REAL traced streamed run: a tiny
        StreamingRunner call under tracing, buckets summing to the
        stream-run wall."""
        import jax

        from comfyui_parallelanything_tpu.models.flux import (
            FluxConfig,
            build_flux,
        )
        from comfyui_parallelanything_tpu.models.loader import params_nbytes
        from comfyui_parallelanything_tpu.parallel.streaming import (
            build_streaming_runner,
        )

        cfg = FluxConfig(
            in_channels=16, hidden_size=64, num_heads=4, depth=1,
            depth_single_blocks=2, context_in_dim=32, vec_in_dim=16,
            axes_dim=(4, 6, 6), guidance_embed=False, dtype=jnp.float32,
        )
        model = build_flux(
            cfg, jax.random.key(0), sample_shape=(1, 8, 8, 4), txt_len=8
        )
        runner = build_streaming_runner(
            model.pipeline_spec, model.params, jax.devices("cpu")[0],
            hbm_budget_bytes=params_nbytes(model.params) // 3,
        )
        tracing.enable()
        try:
            out = runner(
                jnp.zeros((1, 8, 8, 4)), jnp.ones((1,)),
                jnp.zeros((1, 8, cfg.context_in_dim)),
                y=jnp.zeros((1, cfg.vec_in_dim)),
            )
            jax.block_until_ready(out)
            events = tracing.export()
        finally:
            tracing.disable()
        attr = roofline.attribution_from_trace(events)
        assert attr is not None and attr["compute_s"] > 0
        assert all(attr[b] >= 0 for b in ATTR_BUCKETS)
        total = sum(attr[b] for b in ATTR_BUCKETS)
        assert abs(total - attr["wall_s"]) <= 0.1 * attr["wall_s"]


# ---------------------------------------------------------------------------
# instrument_jit integration + flag discipline
# ---------------------------------------------------------------------------


class TestProgramRegistry:
    def test_instrumented_jit_records_prediction(self, monkeypatch):
        monkeypatch.setenv("PA_TELEMETRY_COST", "1")
        monkeypatch.delenv("PA_ROOFLINE", raising=False)
        roofline.programs.reset()
        fn = telemetry.instrument_jit(
            lambda a: (a @ a + a).sum(), "roofline-test-prog"
        )
        fn(jnp.ones((64, 64), jnp.float32))
        rows = roofline.programs.rows()
        assert "roofline-test-prog" in rows, sorted(rows)
        row = rows["roofline-test-prog"]
        assert row["predicted_s"] > 0 and row["predicted_raw_s"] > 0
        assert row["platform"] == "cpu"
        assert row["flops"] or row["bytes_accessed"]
        assert row["bound"] in ("compute", "memory", "comms")
        # the health document carries the same rows
        snap = roofline.programs.snapshot()
        assert snap["enabled"] and "roofline-test-prog" in snap["programs"]
        health = telemetry.health_snapshot()
        assert "roofline-test-prog" in health["roofline"]["programs"]

    def test_sharded_args_feed_the_collective_term(self, monkeypatch,
                                                   cpu_devices):
        """A program whose args are genuinely sharded over the mesh gets a
        nonzero collective_bytes estimate (the FSDP/TP all-gather volume);
        fully-replicated args contribute nothing."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from comfyui_parallelanything_tpu.parallel.mesh import build_mesh

        monkeypatch.setenv("PA_TELEMETRY_COST", "1")
        monkeypatch.delenv("PA_ROOFLINE", raising=False)
        roofline.programs.reset()
        mesh = build_mesh(cpu_devices[:8])
        sharded = jax.device_put(
            jnp.ones((8, 64), jnp.float32), NamedSharding(mesh, P("data"))
        )
        replicated = jax.device_put(
            jnp.ones((64, 64), jnp.float32), NamedSharding(mesh, P())
        )
        fn = telemetry.instrument_jit(
            lambda a, w: (a @ w).sum(), "roofline-sharded-prog"
        )
        fn(sharded, replicated)
        row = roofline.programs.rows()["roofline-sharded-prog"]
        assert row["n_devices"] == 8
        assert row["collective_bytes"] == sharded.nbytes  # not the replica
        assert row["comms_s"] > 0
        roofline.programs.reset()

    def test_disabled_path_is_noop(self, monkeypatch):
        """PA_ROOFLINE=0: no row, no prediction — and telemetry's own FLOPs
        accounting must be untouched (the tracer/sentinel flag discipline)."""
        monkeypatch.setenv("PA_TELEMETRY_COST", "1")
        monkeypatch.setenv("PA_ROOFLINE", "0")
        roofline.programs.reset()
        fn = telemetry.instrument_jit(
            lambda a: (a @ a).sum(), "roofline-off-prog"
        )
        fn(jnp.ones((32, 32), jnp.float32))
        assert "roofline-off-prog" not in roofline.programs.rows()
        assert not roofline.enabled()
        # telemetry cost accounting still ran
        prog = telemetry.compile_snapshot()["programs"].get(
            "roofline-off-prog"
        )
        assert prog is not None and prog["flops"]
        # publish_gauges is a no-op too
        roofline.publish_gauges()

    def test_refresh_calibration_reprices(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PA_LEDGER_DIR", str(tmp_path))
        roofline.programs.reset()
        row = roofline.programs.record(
            "reprice-prog", flops=1e9, bytes_accessed=1e6,
            n_devices=1, platform="cpu",
        )
        assert row["calib_scale"] == 1.0
        raw = row["predicted_raw_s"]
        scales = {
            roofline.calib_key("reprice-prog", "cpu",
                               roofline.shape_bucket(1e9)):
                {"scale": 7.0, "n": 1},
        }
        roofline.save_calibration(scales)
        roofline.programs.refresh_calibration()
        row2 = roofline.programs.rows()["reprice-prog"]
        assert row2["calib_scale"] == 7.0
        assert row2["predicted_s"] == pytest.approx(7.0 * raw)
        assert row2["predicted_raw_s"] == pytest.approx(raw)
        roofline.programs.reset()


class TestStepCost:
    def test_unified_accessor_sources_agree(self):
        def apply(p, x, t, ctx):
            return x @ p + t[:, None] + ctx.sum()

        cost = roofline.step_cost(
            apply, jnp.ones((64, 64), jnp.float32),
            jnp.ones((4, 64), jnp.float32), jnp.ones((4,), jnp.float32),
            jnp.ones((4, 8), jnp.float32),
        )
        assert cost["flops"] and cost["flops"] > 0
        assert cost["flops_source"] in ("hlo", "jaxpr")
        # the jaxpr walk always resolves on a dot_general
        assert cost["flops_jaxpr"] == pytest.approx(2 * 4 * 64 * 64, rel=0.5)
        if cost["flops_hlo"]:
            # both sources present → the discrepancy audit must be sane
            assert cost["flops_discrepancy_ratio"] is not None
            assert 0.2 <= cost["flops_discrepancy_ratio"] <= 5.0

    def test_analytic_flops_fallback_counts_dots(self):
        flops = roofline.analytic_flops(
            lambda p, x, t, c: x @ p,
            jnp.ones((16, 16)), jnp.ones((2, 16)), jnp.ones((2,)),
            jnp.ones((2, 4)),
        )
        assert flops == pytest.approx(2 * 2 * 16 * 16)


# ---------------------------------------------------------------------------
# capacity-weighted fleet ring (ROADMAP fleet-hardening item 2)
# ---------------------------------------------------------------------------


class TestCapacityWeightedRing:
    def _primary_share(self, ring: HashRing, n_keys: int = 3000) -> dict:
        counts: dict[str, int] = {}
        for i in range(n_keys):
            primary = ring.sequence(f"model-{i}")[0]
            counts[primary] = counts.get(primary, 0) + 1
        return {h: c / n_keys for h, c in counts.items()}

    def test_placement_distribution_follows_weights(self):
        ring = HashRing(vnodes=128)
        ring.rebuild(["a", "b", "c"], {"a": 2.0})
        share = self._primary_share(ring)
        # a holds 2 vnode shares of 4 total; b and c one each
        assert share["a"] == pytest.approx(0.5, abs=0.07)
        assert share["b"] == pytest.approx(0.25, abs=0.07)
        assert share["c"] == pytest.approx(0.25, abs=0.07)

    def test_equal_weights_fallback(self):
        ring = HashRing(vnodes=128)
        ring.rebuild(["a", "b", "c"])  # no history → equal split
        share = self._primary_share(ring)
        for h in ("a", "b", "c"):
            assert share[h] == pytest.approx(1 / 3, abs=0.07)

    def test_weight_change_moves_only_local_keys(self):
        ring = HashRing(vnodes=64)
        ring.rebuild(["a", "b", "c"])
        before = {f"m{i}": ring.sequence(f"m{i}")[0] for i in range(500)}
        ring.rebuild(["a", "b", "c"], {"a": 1.5})
        moved = sum(
            1 for k, h in before.items() if ring.sequence(k)[0] != h
        )
        # only keys adjacent to a's NEW vnodes move — and they move TO a
        assert 0 < moved < 250
        for k, h in before.items():
            now = ring.sequence(k)[0]
            if now != h:
                assert now == "a"

    def test_registry_uses_ledger_weights(self, tmp_path, monkeypatch):
        ledger = tmp_path / "perf_ledger.jsonl"
        # loadgen history: fast-host serves steps 2x faster than slow-host
        rec = {
            "schema": "pa-perf-ledger/v1", "kind": "loadgen",
            "hosts": {
                "fast-host": {"server_step_p50_s": 1.0},
                "slow-host": {"server_step_p50_s": 2.0},
            },
        }
        ledger.write_text(json.dumps(rec) + "\n")
        weights = ledger_capacity_weights(str(ledger))
        assert weights["fast-host"] == pytest.approx(4 / 3, abs=0.01)
        assert weights["slow-host"] == pytest.approx(2 / 3, abs=0.01)
        # the registry consumes them (explicitly here; by default it reads
        # the process ledger dir) and the ring share follows
        reg = FleetRegistry(vnodes=128, capacity_weights=weights,
                            capacity_from_ledger=False)
        reg.add_static("fast-host", "http://f:1")
        reg.add_static("slow-host", "http://s:1")
        counts = {"fast-host": 0, "slow-host": 0}
        for i in range(2000):
            counts[reg.sequence(f"model-{i}")[0]] += 1
        assert counts["fast-host"] > counts["slow-host"] * 1.4
        # no-history fallback: equal weights
        assert ledger_capacity_weights(str(tmp_path / "nope.jsonl")) == {}
        # the refresh hook rebuilds with new weights
        reg.set_capacity_weights({})
        counts2 = {"fast-host": 0, "slow-host": 0}
        for i in range(2000):
            counts2[reg.sequence(f"model-{i}")[0]] += 1
        assert abs(counts2["fast-host"] - counts2["slow-host"]) < 400

    def test_host_step_weights_sources(self):
        records = [
            {"kind": "loadgen", "hosts": {
                "h1": {"server_step_p50_s": 1.0},
                "h2": {"server_step_p50_s": 4.0},
            }},
            # stale loadgen and bench records never feed the ring: bench
            # s/it is rung-dependent (smoke vs flux_16 would compare two
            # identical hosts as 80x apart), so only the fleet's own
            # same-workload loadgen measurements qualify
            {"kind": "loadgen", "stale": True,
             "hosts": {"h2": {"server_step_p50_s": 400.0}}},
            {"kind": "bench", "host": "h3", "value": 0.1},
            {"kind": "error", "host": "h4", "value": 0.1},
        ]
        w = roofline.host_step_weights(records)
        assert set(w) == {"h1", "h2"}
        assert w["h1"] > w["h2"]  # h1 steps 4x faster
        assert roofline.host_step_weights([]) == {}

    def test_host_step_weights_never_mixes_metrics(self):
        # h-lat's only history is END-TO-END latency (queueing + HTTP
        # included) — comparing it against h-step's per-dispatch step time
        # would starve it; it must simply drop out (weight 1.0 default).
        records = [
            {"kind": "loadgen", "hosts": {
                "h-step": {"server_step_p50_s": 0.2},
                "h-lat": {"latency_p50_s": 2.0},
            }},
        ]
        w = roofline.host_step_weights(records)
        assert "h-lat" not in w and w == {"h-step": 1.0}
        # latency-only fleets still weight — consistently, on one metric
        lat_only = [{"kind": "loadgen", "hosts": {
            "a": {"latency_p50_s": 1.0}, "b": {"latency_p50_s": 3.0},
        }}]
        w2 = roofline.host_step_weights(lat_only)
        assert w2["a"] > 1.0 > w2["b"]


# ---------------------------------------------------------------------------
# scripts/roofline_report.py (the CI gate + the bank)
# ---------------------------------------------------------------------------


def _run_report(tmp_path, *args):
    return subprocess.run(
        [sys.executable, str(REPO / "scripts" / "roofline_report.py"),
         "--ledger", str(tmp_path), *args],
        capture_output=True, text=True, timeout=120, cwd=str(REPO),
    )


def _write_ledger(tmp_path, records):
    (tmp_path / "perf_ledger.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in records)
    )


def _good_record(**over):
    rec = {
        "schema": "pa-perf-ledger/v1", "kind": "bench", "rung": "smoke",
        "platform": "cpu", "value": 5.0, "unit": "s/it",
        "predicted_step_s": 0.5, "predicted_step_raw_s": 0.5,
        "roofline_ratio": 0.1, "model_flops_per_step": 1e9,
        "attribution": {"compute_s": 4.0, "exposed_transfer_s": 0.0,
                        "comms_s": 0.0, "host_gap_s": 1.0, "wall_s": 5.0},
    }
    rec.update(over)
    return rec


class TestRooflineReport:
    def test_empty_ledger_skips(self, tmp_path):
        proc = _run_report(tmp_path, "--check")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "SKIP" in proc.stdout

    def test_good_record_passes(self, tmp_path):
        _write_ledger(tmp_path, [_good_record()])
        proc = _run_report(tmp_path, "--check")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "OK" in proc.stdout

    def test_out_of_band_ratio_fails(self, tmp_path):
        _write_ledger(tmp_path, [_good_record(roofline_ratio=5.0)])
        proc = _run_report(tmp_path, "--check")
        assert proc.returncode == 1
        assert "roofline_ratio" in proc.stdout

    def test_negative_bucket_fails(self, tmp_path):
        bad = _good_record()
        bad["attribution"]["host_gap_s"] = -1.0
        _write_ledger(tmp_path, [bad])
        assert _run_report(tmp_path, "--check").returncode == 1

    def test_bucket_sum_mismatch_fails(self, tmp_path):
        bad = _good_record()
        bad["attribution"]["wall_s"] = 50.0
        _write_ledger(tmp_path, [bad])
        assert _run_report(tmp_path, "--check").returncode == 1

    def test_stale_and_preroofline_records_skipped(self, tmp_path):
        _write_ledger(tmp_path, [
            _good_record(roofline_ratio=5.0, stale=True),
            # pre-round-13 record: no roofline fields at all
            {"schema": "pa-perf-ledger/v1", "kind": "bench",
             "rung": "old", "platform": "cpu", "value": 3.0},
        ])
        proc = _run_report(tmp_path, "--check")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "SKIP" in proc.stdout

    def test_latest_record_wins(self, tmp_path):
        _write_ledger(tmp_path, [
            _good_record(roofline_ratio=5.0),  # older failure…
            _good_record(),                    # …fixed by the latest
        ])
        assert _run_report(tmp_path, "--check").returncode == 0

    def test_bank_fits_and_persists(self, tmp_path):
        _write_ledger(tmp_path, [_good_record() for _ in range(3)])
        proc = _run_report(tmp_path, "--bank")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        calib = json.loads(
            (tmp_path / "roofline_calib.json").read_text()
        )
        assert calib["schema"] == "pa-roofline-calib/v1"
        key = roofline.calib_key(
            "rung:smoke", "cpu", roofline.shape_bucket(1e9)
        )
        assert calib["scales"][key]["scale"] == pytest.approx(10.0)
        # summary mode reads both files without error
        assert _run_report(tmp_path).returncode == 0


# ---------------------------------------------------------------------------
# trace_summary drift pin (stdlib mirror vs the in-package math)
# ---------------------------------------------------------------------------


class TestTraceSummaryAttributionPin:
    def test_script_matches_roofline_attribution(self, tmp_path):
        tracing.enable()
        try:
            t0 = tracing.now_us()
            tracing.record("stream-run", t0, 1000.0, cat="stream")
            tracing.record("stream-stage-compute", t0 + 100, 400.0,
                           cat="stream", stage=0)
            tracing.record("stream-stage-compute", t0 + 550, 300.0,
                           cat="stream", stage=1)
            tracing.record("stream-prefetch-wait", t0 + 20, 60.0,
                           cat="stream", stage=0)
            export = tracing.export()
        finally:
            tracing.disable()
        path = tmp_path / "trace.json"
        path.write_text(json.dumps(export))
        expect = roofline.attribution_from_trace(export)
        assert expect is not None
        proc = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "trace_summary.py"),
             str(path), "--json"],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        got = json.loads(proc.stdout)["attribution"]
        for key in (*ATTR_BUCKETS, "wall_s"):
            assert got[key] == pytest.approx(expect[key]), key
        # the script additionally surfaces the two headline fractions
        assert got["comms_fraction"] == pytest.approx(
            expect["comms_s"] / expect["wall_s"], abs=1e-3
        )
        assert got["host_gap_fraction"] == pytest.approx(
            expect["host_gap_s"] / expect["wall_s"], abs=1e-3
        )
