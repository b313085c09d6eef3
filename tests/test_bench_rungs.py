"""bench.py rung plumbing: bf16 weight synthesis and sequential microbatching.

The TPU ladder's big rungs run bf16-STORED weights synthesized host-side from
abstract shapes (``bench._bf16_build`` — flax init would materialize f32, a
21.5 GiB init-time OOM for the 6 + 26-block FLUX-class rung on a 16 GiB v5e) and split the
batch into sequential microbatches (``bench._make_step`` — full-batch-21
activations do not fit the chip). Validate both
at tiny scale: synthesis produces an all-bf16 working model, and the chunked
step is numerically identical to the full-batch call.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
from comfyui_parallelanything_tpu import DeviceChain, parallelize
from comfyui_parallelanything_tpu.models import build_flux
from comfyui_parallelanything_tpu.models.flux import FluxConfig

TINY = FluxConfig(
    in_channels=16,  # 4 latent ch x 2x2 patch
    hidden_size=64, num_heads=4, depth=1, depth_single_blocks=2,
    context_in_dim=32, vec_in_dim=16, axes_dim=(4, 6, 6),
    guidance_embed=False, dtype=jnp.float32,
)


def test_bf16_build_synthesizes_all_bf16_params():
    model = bench._bf16_build(
        build_flux, TINY, sample_shape=(1, 8, 8, 4), txt_len=8
    )
    leaves = jax.tree.leaves(model.params)
    assert leaves and all(l.dtype == jnp.bfloat16 for l in leaves)
    # The synthesized model must actually run.
    out = model.apply(
        model.params,
        jnp.ones((2, 8, 8, 4)),
        jnp.ones((2,)),
        jnp.ones((2, 8, TINY.context_in_dim)),
        y=jnp.ones((2, TINY.vec_in_dim)),
    )
    assert out.shape == (2, 8, 8, 4)
    assert np.isfinite(np.asarray(out, dtype=np.float32)).all()


class TestMakeStep:
    def _setup(self, batch):
        model = build_flux(
            TINY, jax.random.key(0), sample_shape=(1, 8, 8, 4), txt_len=8
        )
        pm = parallelize(model, DeviceChain.even(["cpu:0"]))
        x = jax.random.normal(jax.random.key(1), (batch, 8, 8, 4))
        t = jnp.linspace(999.0, 1.0, batch)
        ctx = jax.random.normal(
            jax.random.key(2), (batch, 8, TINY.context_in_dim)
        )
        kwargs = {
            "y": jax.random.normal(jax.random.key(3), (batch, TINY.vec_in_dim))
        }
        return pm, x, t, ctx, kwargs

    def test_chunked_step_matches_full_batch(self):
        batch = 6
        pm, x, t, ctx, kwargs = self._setup(batch)
        full = bench._make_step(pm, batch, 1, t, ctx, kwargs)(x)
        chunked = bench._make_step(pm, batch, 3, t, ctx, kwargs)(x)
        assert chunked.shape == full.shape
        # Batch entries are independent in the forward, so sequential
        # microbatches must reproduce the full-batch result to bf16-matmul
        # tolerance (CLAUDE.md: this CPU backend runs f32 dots at bf16).
        np.testing.assert_allclose(
            np.asarray(chunked, dtype=np.float32),
            np.asarray(full, dtype=np.float32),
            rtol=3e-2, atol=3e-2,
        )

    def test_indivisible_chunks_rejected(self):
        pm, x, t, ctx, kwargs = self._setup(6)
        with pytest.raises(ValueError, match="not divisible"):
            bench._make_step(pm, 6, 4, t, ctx, kwargs)

    def test_bench_chunked_rungs_divide_evenly(self):
        # The declared ladder chunk counts (flux_single_heavy_21: 3x7, flux_16_int8: 4x4)
        # must divide their batches — checked without building the 12 GiB
        # models by reading the rung declarations.
        assert 21 % 3 == 0 and 16 % 4 == 0

    def test_flux_stream_rung_registered(self):
        # The weight-streaming flagship rung (weights exceed usable HBM).
        assert "flux_stream" in bench._RUNGS

    def test_zimage_int8_fallback_rung_registered(self):
        # The int8-weight variant of the batch-21 shape, under the name of
        # what it runs: a FLUX-class MMDiT at 6 + 26 blocks. No rung carries
        # Z-Image's name (the published model is models/zimage.py, measured
        # through benchmark/), and none claims its baseline.
        assert "flux_single_heavy_21_int8" in bench._RUNGS
        assert "flux_single_heavy_21" in bench._RUNGS
        assert not [r for r in bench._RUNGS if "zimage" in r]


def test_flux_stream_rung_rehearsed_off_hardware(tmp_path):
    """The flux_stream run path end to end in a subprocess — tiny workload,
    fake evidence dir, small stream budget so the carve produces real stages
    (never let a code path execute first on the chip). Must emit exactly one JSON line with the streaming rung's
    label, the microbatched step, and non-null FLOPs wiring."""
    import json
    import os
    import re
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    ).strip()
    env["PA_BENCH_TINY"] = "1"
    env["PA_EVIDENCE_DIR"] = str(tmp_path)
    # The compile cache is the run's (conftest: a temporary directory the
    # child inherits by JAX_COMPILATION_CACHE_DIR, never the checkout's
    # .jax_cache): what another test's child compiled is read back, and the
    # streaming stages' own programs are the misses. The write threshold is
    # pinned to 0 so that a miss is recorded for every tiny program
    # regardless of host speed — the hit/miss assertion below needs an event.
    assert env["JAX_COMPILATION_CACHE_DIR"]
    env["PA_COMPILE_CACHE_MIN_S"] = "0"
    env["PA_STREAM_HBM_BUDGET"] = "400000"  # tiny → forces a multi-stage carve
    env["BENCH_CONFIG"] = "flux_stream"
    repo = os.path.dirname(bench.__file__)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--inner"],
        env=env, cwd=repo, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "sec/it denoise step [flux_stream]"
    assert rec["model_flops_per_step"], "MFU wiring must be non-null"
    assert rec["microbatch_chunks"] == 2  # tiny rungs declare 2 chunks
    assert rec["dryrun"] is True
    # The streaming executor actually served the run (stderr carries the
    # placement log with the stage count).
    assert "weight streaming enabled" in proc.stderr
    # Resource accounting (round 9, utils/telemetry.py): every fresh line
    # carries compile + HBM accounting, and the run appended a ledger record.
    assert rec["compile_time_s"] > 0
    assert rec["compile_cache_hits"] + rec["compile_cache_misses"] > 0
    assert rec["peak_hbm_bytes"] > 0
    ledger = os.path.join(str(tmp_path), "ledger", "perf_ledger.jsonl")
    assert os.path.exists(ledger)
    lrec = json.loads(open(ledger).read().strip().splitlines()[-1])
    assert lrec["kind"] == "bench" and lrec["rung"] == "flux_stream"
    assert lrec["schema"] == "pa-perf-ledger/v1"


def _run_outer(tmp_path, **extra):
    """The outer ``python bench.py`` in a CPU-only environment."""
    import os
    import re
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    ).strip()
    env["PA_EVIDENCE_DIR"] = str(tmp_path)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla-cache")
    env.update(extra)
    repo = os.path.dirname(bench.__file__)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        env=env, cwd=repo, capture_output=True, text=True, timeout=420,
    )


class TestNoFallbackWithoutChip:
    """A TPU rung that finds no TPU is an error: exactly one error line, exit
    code 1 — never a CPU smoke in its place, never an older record."""

    @pytest.mark.parametrize("extra", [
        {"BENCH_CONFIG": "sd15_16"},
        {},  # a bare run means the default TPU rung, not a CPU smoke
        {"BENCH_CONFIG": "sd15_16", "BENCH_FORCE_CPU": "1"},
    ], ids=["tpu-rung", "bare", "forced-cpu-with-tpu-rung"])
    def test_chipless_tpu_attempt_exits_1_with_one_error_line(
            self, tmp_path, extra):
        import json

        proc = _run_outer(tmp_path, **extra)
        assert proc.returncode == 1, proc.stderr[-2000:]
        lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
        assert len(lines) == 1, f"exactly one JSON line required: {lines}"
        rec = json.loads(lines[0])
        assert rec["error"] and rec["platform"] == "none"
        assert "stale" not in rec and rec["value"] == 0

    def test_peak_table_refuses_unlisted_accelerator(self):
        assert bench._peak_bf16("TPU v5 lite") == 197e12
        with pytest.raises(ValueError, match="no roofline spec"):
            bench._peak_bf16("TPU v99")
