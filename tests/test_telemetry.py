"""Resource telemetry & perf ledger (round 9):

- utils/telemetry.py compile observability: per-program compile accounting
  via the jax.monitoring listeners, instrument_jit attribution, HLO
  cost-analysis FLOPs, compile spans feeding the tracer;
- cross-process compile-cache accounting: a tmp JAX_COMPILATION_CACHE_DIR —
  first process records misses + compile time, a re-run in a fresh
  subprocess records hits with compile_time_s ≈ 0;
- devices/memory.py telemetry surface: deterministic CPU pseudo-limit,
  utilization math off-hardware, pa_hbm_* gauges, ResidencyTracker gauges,
  the HbmWatermark;
- the perf ledger (schema stamps, append) and scripts/perf_ledger.py's
  regression gate (passes on banked records unchanged, flags an injected
  2x step-time regression and a peak-HBM regression, skips stale/dryrun);
- postmortem bundles (write_postmortem artifact set, OOM classifier) and
  bench.py's forced-failure path end to end (PA_FAIL_INJECT: error JSON
  line with null resource fields + a bundle holding trace/metrics/memory/
  logs);
- GET /health on the workflow server;
- the static-analysis guard: no bare print()/time.time() in the package
  outside the explicit allowlist (the PARITY print-site → span/log/metric
  vocabulary, enforced).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from comfyui_parallelanything_tpu.devices.memory import (
    ResidencyTracker,
    device_memory_stats,
    memory_snapshot,
    publish_memory_gauges,
)
from comfyui_parallelanything_tpu.utils import telemetry, tracing
from comfyui_parallelanything_tpu.utils.metrics import registry

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _clean_state():
    telemetry.compile_registry.reset()
    telemetry.watermark.reset()
    yield
    telemetry.compile_registry.reset()
    telemetry.watermark.reset()
    tracing.disable()
    tracing.tracer.clear()


def _cpu_env(extra: dict | None = None) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    ).strip()
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


@pytest.mark.usefixtures("no_compile_cache")  # these count compile events
class TestCompileRegistry:
    def test_instrumented_jit_attributes_compiles(self, monkeypatch):
        monkeypatch.setenv("PA_TELEMETRY_COST", "1")  # conftest defaults it off
        telemetry.watch_compiles()
        fn = telemetry.instrument_jit(
            lambda x: (x @ x).sum(), "t-reg-prog"
        )
        out = fn(jnp.ones((32, 32)))
        assert float(out) == pytest.approx(32.0 * 32 * 32)
        snap = telemetry.compile_snapshot()
        prog = snap["programs"]["t-reg-prog"]
        assert prog["compiles"] >= 1
        assert prog["compile_time_s"] > 0
        # HLO cost analysis attached on the first compile: a 32x32x32 matmul
        # is ~2*32^3 FLOPs plus the reduction.
        assert prog["flops"] and prog["flops"] > 2 * 32**3
        assert snap["compiles"] >= prog["compiles"]
        # Second call, same shapes: no new compile for this program.
        n = prog["compiles"]
        fn(jnp.ones((32, 32)))
        assert telemetry.compile_registry.compiles_of("t-reg-prog") == n
        # New shape: a fresh compile under the same program name.
        fn(jnp.ones((16, 16)))
        assert telemetry.compile_registry.compiles_of("t-reg-prog") > n
        # The metrics twin landed.
        assert registry.get(
            "pa_compile_total", {"program": "t-reg-prog"}
        ) >= 1

    def test_unattributed_compiles_still_counted(self):
        telemetry.watch_compiles()
        before = telemetry.compile_snapshot()["compiles"]
        jax.jit(lambda x: x * 3 + 7)(jnp.ones((5,)))  # bare jit, no wrapper
        snap = telemetry.compile_snapshot()
        assert snap["compiles"] > before
        assert "(unattributed)" in snap["programs"]

    def test_compile_span_recorded_when_tracing(self):
        telemetry.watch_compiles()
        tracing.enable()
        telemetry.instrument_jit(
            lambda x: jnp.tanh(x) * 2, "t-span-prog"
        )(jnp.ones((8, 8)))
        xs = [e for e in tracing.export()["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "compile"]
        assert any(
            e["args"].get("program") == "t-span-prog" and e["dur"] > 0
            for e in xs
        )

    def test_donated_loop_program_still_accounted(self):
        """The loop-jit cache (sampling/compiled.py) instruments its donated
        programs — run_sampler(compile_loop=True) must leave a loop:* entry
        in the registry."""
        from comfyui_parallelanything_tpu.sampling.compiled import (
            clear_compiled_loops,
        )
        from comfyui_parallelanything_tpu.sampling.runner import run_sampler

        telemetry.watch_compiles()
        clear_compiled_loops()

        def model(x, t, context=None, **kw):
            return x * 0.9

        run_sampler(
            model, jnp.ones((1, 4, 4, 4)), jnp.ones((1, 3, 8)),
            sampler="euler", steps=2, compile_loop=True,
        )
        progs = telemetry.compile_snapshot()["programs"]
        assert "loop:k:euler" in progs
        assert progs["loop:k:euler"]["compiles"] >= 1


_XPROC_SCRIPT = r"""
import json, os, sys
import jax, jax.numpy as jnp
from comfyui_parallelanything_tpu.utils import enable_compilation_cache, telemetry
telemetry.watch_compiles()
assert enable_compilation_cache() == sys.argv[1]
fn = telemetry.instrument_jit(lambda x: (x @ x + x).sum(), "xproc-prog")
fn(jnp.ones((256, 256)))
print(json.dumps(telemetry.compile_snapshot()))
"""


class TestCrossProcessCompileCache:
    def test_miss_then_hit_across_processes(self, tmp_path):
        """The satellite contract: a tmp JAX_COMPILATION_CACHE_DIR — the
        first run records misses and real compile time; an identical re-run
        in a FRESH subprocess records hits with compile_time_s ≈ 0 (a
        persistent-cache hit skips backend compile entirely, so no compile
        duration is ever recorded for the program)."""
        cache = tmp_path / "xla-cache"
        env = _cpu_env({
            # Sub-second test programs must still persist (the production
            # threshold of 0.5s would skip them and fake a second-run miss).
            "PA_COMPILE_CACHE_MIN_S": "0",
            "JAX_COMPILATION_CACHE_DIR": str(cache),
        })

        def run():
            proc = subprocess.run(
                [sys.executable, "-c", _XPROC_SCRIPT, str(cache)],
                env=env, cwd=str(REPO), capture_output=True, text=True,
                timeout=300,
            )
            assert proc.returncode == 0, proc.stderr[-2000:]
            return json.loads(proc.stdout.strip().splitlines()[-1])

        first = run()
        prog1 = first["programs"]["xproc-prog"]
        assert prog1["cache_misses"] >= 1 and prog1["cache_hits"] == 0
        assert prog1["compiles"] >= 1 and prog1["compile_time_s"] > 0
        assert os.listdir(cache), "nothing persisted to the cache dir"
        second = run()
        prog2 = second["programs"]["xproc-prog"]
        assert prog2["cache_hits"] >= 1 and prog2["cache_misses"] == 0
        assert prog2["compile_time_s"] == pytest.approx(0.0, abs=0.02), (
            "a cache hit must not pay (or book) a backend compile"
        )


class TestMemoryTelemetry:
    def test_deterministic_cpu_fallback(self, monkeypatch):
        monkeypatch.setenv("PA_CPU_FAKE_HBM_BYTES", str(1 << 31))
        dev = jax.devices("cpu")[0]
        s = device_memory_stats(dev)
        assert s["source"] == "fallback"
        assert s["bytes_limit"] == 1 << 31  # the pseudo-limit, exactly
        assert s["device"] == "cpu:0"

    def test_utilization_math_off_hardware(self, monkeypatch):
        monkeypatch.setenv("PA_CPU_FAKE_HBM_BYTES", str(1 << 30))
        dev = jax.devices("cpu")[0]
        before = device_memory_stats(dev)["bytes_in_use"]
        big = jax.device_put(jnp.ones((512, 512), jnp.float32), dev)
        big.block_until_ready()
        snap = memory_snapshot([dev])[0]
        delta = snap["bytes_in_use"] - before
        assert delta >= big.nbytes  # our MiB shows up in the accounting
        # utilization is bytes_in_use / pseudo-limit, rounded to 6 places
        assert snap["utilization"] == round(
            snap["bytes_in_use"] / (1 << 30), 6
        )
        del big

    def test_publish_memory_gauges(self):
        devs = jax.devices("cpu")[:2]
        snap = publish_memory_gauges(devs)
        assert len(snap) == 2
        for s in snap:
            lbl = {"device": s["device"]}
            assert registry.get("pa_hbm_bytes_limit", lbl) == s["bytes_limit"]
            assert registry.get("pa_hbm_bytes_in_use", lbl) == s["bytes_in_use"]

    def test_residency_tracker_gauges(self):
        t = ResidencyTracker()
        t.add_resident(100)
        t.place("s0", 1000)
        t.place("s1", 2000)
        t.publish_gauges("cpu:7", bound_bytes=4000)
        lbl = {"device": "cpu:7"}
        assert registry.get("pa_hbm_stream_live_bytes", lbl) == 3000
        assert registry.get("pa_hbm_stream_peak_bytes", lbl) == 3000
        assert registry.get("pa_hbm_stream_resident_bytes", lbl) == 100
        assert registry.get("pa_hbm_stream_bound_bytes", lbl) == 4000
        t.retire("s0")
        t.publish_gauges("cpu:7")
        assert registry.get("pa_hbm_stream_live_bytes", lbl) == 2000
        assert registry.get("pa_hbm_stream_peak_bytes", lbl) == 3000

    def test_watermark(self):
        dev = jax.devices("cpu")[0]
        assert telemetry.watermark.peak_bytes == 0
        keep = jax.device_put(jnp.ones((256, 256)), dev)
        keep.block_until_ready()
        snap = telemetry.watermark.sample([dev])
        assert len(snap) == 1
        assert telemetry.watermark.peak_bytes >= keep.nbytes
        peak = telemetry.watermark.peak_bytes
        del keep
        telemetry.watermark.sample([dev])
        # The watermark is a high-water mark: freeing memory never lowers it.
        assert telemetry.watermark.peak_bytes == peak
        assert registry.get("pa_hbm_peak_bytes") == peak


class TestPerfLedger:
    def test_append_stamps_schema(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PA_LEDGER_DIR", str(tmp_path / "led"))
        path = telemetry.append_ledger_record(
            {"rung": "smoke", "value": 1.25, "platform": "cpu"}, "bench"
        )
        assert path == str(tmp_path / "led" / "perf_ledger.jsonl")
        [line] = open(path).read().strip().splitlines()
        rec = json.loads(line)
        assert rec["schema"] == telemetry.LEDGER_SCHEMA
        assert rec["kind"] == "bench" and rec["value"] == 1.25
        assert rec["ts"] > 0 and rec["pid"] == os.getpid()

    def _gate(self, ledger_dir, baseline, *extra):
        return subprocess.run(
            [sys.executable, str(REPO / "scripts" / "perf_ledger.py"),
             "--check", "--ledger", str(ledger_dir),
             "--baseline", str(baseline), *extra],
            capture_output=True, text=True, timeout=120,
        )

    def _seed(self, tmp_path, ledger_lines, banked_lines):
        led = tmp_path / "ledger"
        led.mkdir(exist_ok=True)
        with open(led / "perf_ledger.jsonl", "w") as f:
            for r in ledger_lines:
                f.write(json.dumps({
                    "schema": telemetry.LEDGER_SCHEMA, "kind": "bench", **r
                }) + "\n")
        banked = tmp_path / "BASELINE_measured.json"
        with open(banked, "w") as f:
            for r in banked_lines:
                f.write(json.dumps(r) + "\n")
        return led, banked

    BANKED = [
        {"rung": "sd15_16", "platform": "tpu", "value": 2.5, "ts": 1.0,
         "peak_hbm_bytes": 10 * 2**30},
        {"rung": "sd15_16", "platform": "tpu", "value": 2.6, "ts": 2.0,
         "peak_hbm_bytes": 10 * 2**30},
    ]

    def test_passes_on_banked_records_unchanged(self, tmp_path):
        led, banked = self._seed(tmp_path, [
            {"rung": "sd15_16", "platform": "tpu", "value": 2.55,
             "peak_hbm_bytes": 10 * 2**30, "ts": 3.0},
        ], self.BANKED)
        proc = self._gate(led, banked)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "OK    sd15_16/tpu [banked]" in proc.stdout

    def test_flags_2x_step_time_regression(self, tmp_path):
        led, banked = self._seed(tmp_path, [
            {"rung": "sd15_16", "platform": "tpu", "value": 5.1,
             "peak_hbm_bytes": 10 * 2**30, "ts": 3.0},
        ], self.BANKED)
        proc = self._gate(led, banked)
        assert proc.returncode == 1
        assert "REGRESSION  sd15_16/tpu" in proc.stdout
        assert "step time" in proc.stdout

    def test_flags_peak_hbm_regression(self, tmp_path):
        led, banked = self._seed(tmp_path, [
            {"rung": "sd15_16", "platform": "tpu", "value": 2.5,
             "peak_hbm_bytes": 14 * 2**30, "ts": 3.0},
        ], self.BANKED)
        proc = self._gate(led, banked)
        assert proc.returncode == 1
        assert "peak HBM" in proc.stdout

    def test_hbm_gate_live_when_banked_records_predate_round9(self, tmp_path):
        """Banked evidence without peak_hbm_bytes (everything banked before
        round 9) must not disarm the HBM half of the gate: the HBM baseline
        resolves independently, falling back to the prior ledger records."""
        led, banked = self._seed(tmp_path, [
            {"rung": "sd15_16", "platform": "tpu", "value": 2.5,
             "peak_hbm_bytes": 1 * 2**30, "ts": 3.0},
            {"rung": "sd15_16", "platform": "tpu", "value": 2.5,
             "peak_hbm_bytes": 5 * 2**30, "ts": 4.0},
        ], [
            {"rung": "sd15_16", "platform": "tpu", "value": 2.5, "ts": 1.0},
        ])
        proc = self._gate(led, banked)
        assert proc.returncode == 1, proc.stdout
        assert "peak HBM" in proc.stdout

    def test_stale_dryrun_error_records_never_compared(self, tmp_path):
        led, banked = self._seed(tmp_path, [
            {"rung": "sd15_16", "platform": "tpu", "value": 99.0,
             "stale": True, "ts": 3.0},
            {"rung": "sd15_16", "platform": "tpu", "value": 99.0,
             "dryrun": True, "ts": 4.0},
            {"rung": "sd15_16", "platform": "tpu", "value": 99.0,
             "kind": "error", "ts": 5.0},
        ], self.BANKED)
        proc = self._gate(led, banked)
        assert proc.returncode == 0, proc.stdout
        assert "no comparable bench records" in proc.stdout

    def test_ledger_prior_fallback_when_nothing_banked(self, tmp_path):
        led, banked = self._seed(tmp_path, [
            {"rung": "smoke", "platform": "cpu", "value": 5.0, "ts": 1.0},
            {"rung": "smoke", "platform": "cpu", "value": 5.2, "ts": 2.0},
            {"rung": "smoke", "platform": "cpu", "value": 11.0, "ts": 3.0},
        ], [])
        proc = self._gate(led, banked)
        assert proc.returncode == 1
        assert "ledger[2]" in proc.stdout  # baseline = the 2 prior records
        # A lone record with no history is a SKIP, not a failure.
        led2, banked2 = self._seed(tmp_path, [
            {"rung": "smoke", "platform": "cpu", "value": 5.0, "ts": 1.0},
        ], [])
        proc = self._gate(led2, banked2)
        assert proc.returncode == 0
        assert "SKIP" in proc.stdout


class TestPostmortem:
    def test_looks_like_oom(self):
        assert telemetry.looks_like_oom(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating"))
        assert telemetry.looks_like_oom("XlaRuntimeError: Out of memory")
        assert not telemetry.looks_like_oom(ValueError("bad shape"))

    def test_bundle_artifacts(self, tmp_path, monkeypatch):
        from comfyui_parallelanything_tpu.utils.logging import get_logger

        monkeypatch.setenv("PA_LEDGER_DIR", str(tmp_path))
        tracing.enable()
        with tracing.span("prompt", prompt_id="pm-test"):
            pass
        get_logger().warning("flight-recorder breadcrumb %d", 42)
        err = RuntimeError("RESOURCE_EXHAUSTED: synthetic")
        path = telemetry.write_postmortem(
            "unit/test tag", error=err, extra={"rung": "smoke"}
        )
        assert path and path.startswith(str(tmp_path / "postmortem"))
        names = sorted(os.listdir(path))
        assert names == ["error.json", "logs.txt", "memory.json",
                         "metrics.prom", "trace.json"]
        info = json.load(open(os.path.join(path, "error.json")))
        assert info["error_type"] == "RuntimeError"
        assert info["oom"] is True
        assert "traceback" not in info or isinstance(info["traceback"], str)
        assert info["extra"] == {"rung": "smoke"}
        assert "compile" in info and "peak_hbm_bytes" in info
        trace = json.load(open(os.path.join(path, "trace.json")))
        assert any(
            e.get("name") == "prompt" for e in trace["traceEvents"]
        )
        assert "flight-recorder breadcrumb 42" in open(
            os.path.join(path, "logs.txt")).read()
        mem = json.load(open(os.path.join(path, "memory.json")))
        assert mem["devices"] and mem["devices"][0]["bytes_limit"] > 0
        # Two bundles in the same second must not collide.
        path2 = telemetry.write_postmortem("unit/test tag", error=err)
        assert path2 != path and os.path.isdir(path2)


class TestBenchForcedFailure:
    def test_injected_oom_produces_error_line_and_bundle(self, tmp_path):
        """The acceptance path end to end: PA_FAIL_INJECT=oom fails the CPU
        smoke child mid-run — the outer still prints exactly one JSON line
        (error schema, resource fields present as nulls) pointing at a
        postmortem bundle with trace + metrics + memory snapshots, and the
        ledger records the failed attempt as kind=error."""
        env = _cpu_env({
            "PA_EVIDENCE_DIR": str(tmp_path),
            "PA_FAIL_INJECT": "oom",
            "BENCH_FORCE_CPU": "1",
        })
        # The smoke child enables the persistent compile cache: it is the
        # run's (conftest: a temporary directory, inherited — never the
        # checkout's .jax_cache), so the 570 tiny programs of the model's
        # eager init are read back where another test's child compiled them.
        assert env["JAX_COMPILATION_CACHE_DIR"]
        proc = subprocess.run(
            [sys.executable, str(REPO / "bench.py")],
            env=env, cwd=str(REPO), capture_output=True, text=True,
            timeout=900,
        )
        assert proc.returncode == 1
        lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
        assert len(lines) == 1, lines
        rec = json.loads(lines[0])
        assert "RESOURCE_EXHAUSTED" in rec["error"]
        for field in ("compile_time_s", "compile_cache_hits",
                      "compile_cache_misses", "peak_hbm_bytes"):
            assert field in rec and rec[field] is None
        bundle = rec["postmortem"]
        assert bundle and os.path.isdir(bundle)
        assert bundle.startswith(str(tmp_path)), (
            "bundle escaped the redirected evidence dir"
        )
        names = sorted(os.listdir(bundle))
        assert {"error.json", "memory.json", "metrics.prom",
                "trace.json"} <= set(names)
        info = json.load(open(os.path.join(bundle, "error.json")))
        assert info["oom"] is True
        # The bundle captured the run's actual telemetry: programs were
        # compiled (or read back from the run's cache) before the injected
        # failure, and warmup steps were traced.
        assert info["compile"]["compiles"] + info["compile"]["cache_hits"] > 0
        trace = json.load(open(os.path.join(bundle, "trace.json")))
        assert any(e.get("name") == "step"
                   for e in trace["traceEvents"] if e.get("ph") == "X")
        ledger = tmp_path / "ledger" / "perf_ledger.jsonl"
        kinds = [json.loads(l)["kind"]
                 for l in open(ledger).read().strip().splitlines()]
        assert "error" in kinds


class _EchoNode:
    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"x": ("INT", {"default": 0})}}

    RETURN_TYPES = ("INT",)
    FUNCTION = "run"

    def run(self, x):
        return (x + 1,)


class TestHealthEndpoint:
    @pytest.fixture
    def server(self, tmp_path):
        from comfyui_parallelanything_tpu.server import make_server

        srv, q = make_server(
            port=0, output_dir=str(tmp_path / "out"),
            class_mappings={"Echo": _EchoNode},
        )
        thread = threading.Thread(target=srv.serve_forever, daemon=True)
        thread.start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        yield base, q
        srv.shutdown()
        q.shutdown()

    def test_health_document(self, server):
        import urllib.request

        base, q = server
        with urllib.request.urlopen(base + "/health", timeout=30) as r:
            health = json.loads(r.read())
        assert health["schema"] == telemetry.HEALTH_SCHEMA
        assert health["ts"] > 0
        assert "cpu" in health["devices"]
        assert health["hbm"] and health["hbm"][0]["bytes_limit"] > 0
        assert 0.0 <= health["hbm_utilization_max"] <= 1.0
        assert set(health["queue"]) >= {"pending", "running", "workers",
                                        "completed", "serving"}
        assert health["queue"]["workers"] == q.workers
        assert "compiles" in health["compile"]

    def test_metrics_carries_hbm_gauges(self, server):
        import urllib.request

        base, _ = server
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            text = r.read().decode()
        assert re.search(r"^pa_hbm_bytes_limit\{", text, re.M)
        assert re.search(r"^pa_hbm_bytes_in_use\{", text, re.M)


class TestObservabilityLint:
    """Round 16: the static-analysis guard moved into scripts/palint.py
    (ONE lint engine — six passes, this file's old print/time.time checks
    among them as the `observability` pass). The central allowlists became
    per-line `# palint: allow[observability] <why>` pragmas next to the
    code, with the engine enforcing the staleness discipline the old
    `test_allowlist_entries_still_exist` carried (a pragma that suppresses
    nothing, or has no justification, is itself a finding). This test is
    the thin subprocess gate; tests/test_palint.py covers the passes."""

    def test_palint_check_green(self, tmp_path):
        env = dict(os.environ, PA_LEDGER_DIR=str(tmp_path))
        proc = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "palint.py"), "--check"],
            capture_output=True, text=True, env=env, timeout=120,
        )
        assert proc.returncode == 0, (
            "palint --check failed — fix the violation or justify it with "
            "an in-line pragma:\n" + proc.stdout + proc.stderr
        )
