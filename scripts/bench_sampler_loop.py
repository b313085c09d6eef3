"""Eager vs whole-loop-compiled sampler benchmark.

Quantifies what ``run_sampler(compile_loop=True)`` buys on real hardware: the
eager path re-enters the jitted forward from Python every denoise step (the
reference's hot-loop shape, any_device_parallel.py:1287), paying per-step
dispatch and a fresh latent allocation; the compiled path runs the whole loop
as one lax.scan XLA program with the latent donated.

    python scripts/bench_sampler_loop.py          # default: sd15-class, 20 steps
    BENCH_STEPS=30 python scripts/bench_sampler_loop.py

Appends JSON lines to SAMPLER_LOOP_BENCH.json.
"""

from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from comfyui_parallelanything_tpu.models import build_unet, sd15_config
    from comfyui_parallelanything_tpu.sampling.runner import run_sampler
    from comfyui_parallelanything_tpu.utils import enable_compilation_cache

    from bench import evidence_dir

    enable_compilation_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    if os.environ.get("PA_BENCH_TINY") == "1":
        on_tpu = False  # dry-run: record flows as TPU, workload stays smoke-size
    if on_tpu:
        batch, latent, ctx_len = 8, 64, 77   # 512² SD1.5-class
        cfg = sd15_config(dtype=jnp.bfloat16)
    else:
        batch, latent, ctx_len = 4, 16, 24   # CPU smoke
        cfg = sd15_config(
            model_channels=64, channel_mult=(1, 2), transformer_depth=(1, 1),
            attention_levels=(0, 1), context_dim=64, num_heads=4, norm_groups=16,
            dtype=jnp.float32,
        )
    model = build_unet(cfg, jax.random.key(0), sample_shape=(1, latent, latent, 4))
    noise = jax.random.normal(jax.random.key(1), (batch, latent, latent, 4))
    ctx = jax.random.normal(jax.random.key(2), (batch, ctx_len, cfg.context_dim))

    rec = {
        "workload": f"sd15-class b={batch} {latent * 8}px {steps} steps dpmpp_2m",
        "platform": dev.platform, "device_kind": dev.device_kind,
        "steps": steps, "ts": time.time(),
    }
    # Chained timing closed by a host readback: each run feeds its output
    # back as the next run's noise (utils/metrics.chained_time). Values may
    # blow up over chained runs with random weights; TPU arithmetic is
    # value-independent, so timing is unaffected.
    from comfyui_parallelanything_tpu.utils.metrics import chained_time

    iters = 3
    for key, flag in (("eager_s", False), ("compiled_s", True)):
        sec, _ = chained_time(
            lambda v, _flag=flag: run_sampler(
                model, v, ctx, sampler="dpmpp_2m", steps=steps,
                compile_loop=_flag,
            ).astype(noise.dtype),
            noise, iters,
        )
        rec[key] = round(sec, 4)
    rec["compiled_speedup"] = round(rec["eager_s"] / rec["compiled_s"], 3)
    print(json.dumps(rec))
    with open(os.path.join(evidence_dir(), "SAMPLER_LOOP_BENCH.json"), "a") as f:
        f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
