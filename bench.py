"""Benchmark entry point — prints ONE JSON line for the driver.

Two-process design: the outer process never imports jax — a chip belongs to
one process at a time, so the parent must stay off it — and starts exactly ONE
child that runs the benchmark. A child that fails, or that finds no TPU, gives
one error line and exit code 1: there is no probe process, no CPU substitute
for a TPU rung and no re-emit of an older record. ``BENCH_CONFIG=smoke`` (or
``BENCH_FORCE_CPU=1``) is the explicit CPU run for tests; its line carries
``platform: "cpu"``.

Workloads; select with BENCH_CONFIG (default ``sd15_16`` on a TPU):

- ``sd15_16``  — SD1.5-class UNet, bf16, batch=16, 1024² pixels (128² latents).
- ``sdxl_8``   — SDXL-class UNet, bf16, batch=8, 1024².
- ``flux_single_heavy_21`` — a FLUX-class MMDiT at 6 double + 26 single blocks
  (models/flux.py ``flux_single_heavy_config``: no published model), batch=21,
  1024². It stood in for Z_Image when that architecture was not public
  (``zimage_21`` until PR 34); it is public now (Tongyi-MAI, Apache-2.0) and
  nothing like this shape — the published S3-DiT is models/zimage.py and is
  measured through ``benchmark/`` (the cell ``zimage-turbo-b1-1024.closed-unique``).
  The reference's own benchmark run (/root/reference/README.md:46-60: 26.00 s/it
  on one RTX 3090 at batch 21) is matched by NO rung here.
- ``flux_16``  — FLUX-class MMDiT, batch=16, 1024² (the BASELINE.json north-star
  shape). Full flux-dev (12B) needs FSDP over a v5e-8 pod slice; on a single chip
  this rung runs the dev *topology* at reduced depth so the shape (4096 img tokens
  of joint attention, bf16, pallas flash path) is what's measured.
- ``flux_16_int8`` — FULL 19/38 flux-dev topology with int8-stored weights
  (fits one v5e chip): the measured replacement for flux_16's analytic
  full-depth extrapolation.
- ``flux_stream`` — FULL 19/38 flux-dev, int8, WEIGHT-STREAMED on one chip
  (parallel/streaming.py): host-pinned params double-buffered through HBM —
  the rung for chips whose usable HBM is below even the int8 replica.
  PA_STREAM_HBM_BUDGET overrides the carve budget (bytes).
- ``wan_video``— WAN-class video DiT, 16 frames 480p-latent batch=1 (sequence-
  dominant workload; temporal tokens ≈ video "batch").
- ``hybrid_sd15`` — SD1.5-class UNet, batch=8, 512², on a heterogeneous
  tpu:0(70%)+cpu(30%) chain: the two-platform weighted host-scatter path
  (SURVEY §7 hard part 1).
- ``smoke``    — reduced-width SD1.5 topology on CPU (tests only).

``vs_baseline`` — the reference's published single-GPU 26.00 s/it (Z_Image Turbo,
batch 21) over our s/it — is ``null`` on every rung: no rung runs that model
at that batch, and dividing the Z_Image baseline by another workload's s/it is
cross-workload noise, not a speedup. ``mfu`` is analytic model FLOPs/step (XLA HLO
cost analysis) / s/it / aggregate chip peak bf16 FLOP/s (the peak comes from
``utils/roofline.PLATFORM_SPECS``; a chip that table does not list is an error).
"""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.abspath(__file__))

_TINY = os.environ.get("PA_BENCH_TINY") == "1"
_FAIL_INJECT = os.environ.get("PA_FAIL_INJECT") or os.environ.get(
    "PA_FAULT_PLAN")
if (_TINY or _FAIL_INJECT) and not os.environ.get("PA_EVIDENCE_DIR"):
    raise RuntimeError(
        "PA_BENCH_TINY / PA_FAIL_INJECT / PA_FAULT_PLAN require "
        "PA_EVIDENCE_DIR: a tiny-workload or injected-failure run must "
        "never write into the repo's real evidence artifacts (the perf "
        "ledger and postmortem bundles follow the evidence dir; "
        "utils/faults.py enforces the same arming rule in-process)"
    )

# Default rung of a bare ``python bench.py`` on a TPU.
_DEFAULT_TPU_RUNG = "sd15_16"


def evidence_dir() -> str:
    """Root for the append-only evidence artifacts (the perf ledger,
    postmortem bundles, kernel/sampler-loop bench records). Tests and
    tiny-workload rehearsals point this at a temp dir so they can never
    pollute the real record."""
    return os.environ.get("PA_EVIDENCE_DIR") or _REPO


def _ledger_append(record: dict, kind: str) -> None:
    """Outer-process perf-ledger append. Stdlib twin of
    ``comfyui_parallelanything_tpu.utils.telemetry.append_ledger_record`` —
    the outer process must never import the package (its ``__init__`` pulls
    jax, and a parent that touches jax holds the chip its child needs), so
    the schema stamp lives in both places on purpose; ``scripts/perf_ledger.py`` validates the shared
    ``schema`` field either way. Best-effort: a full disk must not cost the
    driver its one JSON line."""
    import time

    ledger = os.environ.get("PA_LEDGER_DIR") or os.path.join(
        evidence_dir(), "ledger"
    )
    rec = dict(record)
    rec["schema"] = "pa-perf-ledger/v1"
    rec["kind"] = kind
    rec.setdefault("ts", time.time())
    rec.setdefault("pid", os.getpid())
    try:
        os.makedirs(ledger, exist_ok=True)
        with open(os.path.join(ledger, "perf_ledger.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError:
        pass

_REF_SINGLE_GPU_S_IT = 26.00  # /root/reference/README.md:54-56 (Z_Image batch=21)

# Pinned timing protocol: part of the evidence schema — every JSON line
# records these plus the 1-minute load average, so a drifted number is
# auditable against host load.
TPU_BENCH_ITERS = 10
SMOKE_BENCH_ITERS = 5
BENCH_WARMUP_STEPS = 2


def _loadavg_1m():
    """1-minute load average, or None on platforms without getloadavg."""
    try:
        return round(os.getloadavg()[0], 2)
    except (AttributeError, OSError):
        return None


def _bf16_build(build_fn, cfg, **build_kw):
    """Build a model with bf16-STORED weights synthesized host-side from
    abstract shapes — no f32 pytree is ever materialized on any device.

    Two bugs this kills at once: (a) flax ``init`` stores params at the default
    ``param_dtype`` f32, so the "bf16" rung labels were silently benching f32
    weight storage (2x the HBM reads on every matmul — the usual TPU
    bottleneck); (b) the 6 + 26-block FLUX-class rung is 5.77B params = 21.5 GiB at f32, an
    init-time OOM on a 16 GiB v5e chip, while its bf16 inference layout
    (10.8 GiB) fits. Weights are zeros: matmul/attention timing is
    value-independent, the same argument as ``_synth_int8_params``."""
    import jax
    import jax.numpy as jnp

    sds = jax.eval_shape(
        lambda key: build_fn(cfg, rng=key, **build_kw).params, jax.random.key(0)
    )
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        params = jax.tree.map(
            lambda l: jnp.zeros(l.shape, jnp.bfloat16)
            if l.dtype == jnp.float32 else jnp.zeros(l.shape, l.dtype),
            sds,
        )
    return build_fn(cfg, params=params, **build_kw)


def _rung_sd15_16(jnp, rng):
    from comfyui_parallelanything_tpu.models import build_unet, sd15_config

    batch, latent, ctx_len = 16, 128, 77
    cfg = sd15_config(dtype=jnp.bfloat16)
    model = _bf16_build(build_unet, cfg, sample_shape=(1, latent, latent, 4))
    return (model, batch, (batch, latent, latent, 4), ctx_len, cfg.context_dim,
            {}, "SD1.5 UNet bf16 batch=16 1024x1024")


def _rung_sdxl_8(jnp, rng):
    from comfyui_parallelanything_tpu.models import build_unet, sdxl_config

    batch, latent, ctx_len = 8, 128, 77
    cfg = sdxl_config(dtype=jnp.bfloat16)
    model = _bf16_build(build_unet, cfg, sample_shape=(1, latent, latent, 4))
    kwargs = {"y": jnp.zeros((batch, cfg.adm_in_channels), jnp.float32)}
    return (model, batch, (batch, latent, latent, 4), ctx_len, cfg.context_dim,
            kwargs, "SDXL UNet bf16 batch=8 1024x1024")


def _rung_flux_single_heavy_21(jnp, rng):
    from comfyui_parallelanything_tpu.models import build_flux, flux_single_heavy_config

    batch, latent, ctx_len = 21, 128, 128
    cfg = flux_single_heavy_config(dtype=jnp.bfloat16)
    model = _bf16_build(
        build_flux, cfg, sample_shape=(1, 16, 16, 16), txt_len=ctx_len
    )
    # 3 sequential microbatches of 7: 10.8 GiB bf16 weights + full-batch-21
    # activations do not fit a 16 GiB v5e; 21 images per iteration either way.
    return (model, batch, (batch, latent, latent, 16), ctx_len, cfg.context_in_dim,
            {}, "FLUX-class MMDiT 6+26 blocks bf16 batch=21 (3x7 microbatch) "
                "1024x1024 (no published model has this shape)", 3)


def _int8_synth_model(jnp, cfg, sample_shape, txt_len, name):
    """Flux-family model with int8-SYNTHESIZED weights (zeros; matmul timing
    is value-independent) built from abstract shapes — no high-precision
    pytree is ever materialized. Dequantize happens inside jit: int8 HBM
    reads, on-chip widening (models/quantize.py). Shared by the int8 rungs.
    Carries the staged pipeline spec (stage closures rebound through the same
    dequantize wrapper, the models/quantize.quantize_model pattern) so the
    weight-streaming rung can carve it."""
    import dataclasses as _dc

    from comfyui_parallelanything_tpu.models import flux_abstract_params
    from comfyui_parallelanything_tpu.models.api import DiffusionModel
    from comfyui_parallelanything_tpu.models.flux import (
        FluxModel,
        _flux_pipeline_spec,
    )
    from comfyui_parallelanything_tpu.models.quantize import dequantize_params

    sds = flux_abstract_params(cfg, sample_shape=sample_shape, txt_len=txt_len)
    params = _synth_int8_params(sds)
    module = FluxModel(cfg)

    def apply(p, x, t, context=None, **kw):
        return module.apply(
            {"params": dequantize_params(p, jnp.bfloat16)}, x, t, context, **kw
        )

    def wrap_stage(fn):
        def wrapped(p, *a, **k):
            return fn(dequantize_params(p, jnp.bfloat16), *a, **k)

        return wrapped

    spec = _flux_pipeline_spec(module, cfg)
    spec = _dc.replace(
        spec,
        prepare=wrap_stage(spec.prepare),
        segments=tuple(
            _dc.replace(seg, fn=wrap_stage(seg.fn)) for seg in spec.segments
        ),
        finalize=wrap_stage(spec.finalize),
    )
    return DiffusionModel(
        apply=apply, params=params, name=name, config=cfg, pipeline_spec=spec
    )


def _rung_flux_single_heavy_21_int8(jnp, rng):
    """The batch=21, 1024² shape with int8-STORED weights — for a chip whose
    usable HBM the bf16 rung's weights + overhead alone exceed. Same 6 + 26
    FLUX-class topology, same 21 images per iteration; weights dequantize to
    bf16 inside jit, so compute is still bf16 and the workload label carries
    the weight-precision caveat."""
    from comfyui_parallelanything_tpu.models import flux_single_heavy_config

    batch, latent, ctx_len = 21, 128, 128
    cfg = flux_single_heavy_config(dtype=jnp.bfloat16)
    model = _int8_synth_model(
        jnp, cfg, sample_shape=(1, 16, 16, 16), txt_len=ctx_len,
        name="flux-single-heavy-int8",
    )
    return (model, batch, (batch, latent, latent, 16), ctx_len,
            cfg.context_in_dim, {},
            "FLUX-class MMDiT 6+26 blocks int8 weights/bf16 compute batch=21 "
            "(3x7 microbatch) 1024x1024 (no published model has this shape; "
            "NOT weight-precision like-for-like)", 3)


def _rung_flux_16(jnp, rng):
    from comfyui_parallelanything_tpu.models import build_flux, flux_dev_config

    batch, latent, ctx_len = 16, 128, 512
    # Dev topology (double+single blocks, guidance embed, 24 heads x 128) at
    # depth that fits one v5e chip; full 19/38-depth dev runs FSDP multi-chip.
    cfg = flux_dev_config(depth=4, depth_single_blocks=8, dtype=jnp.bfloat16)
    model = _bf16_build(
        build_flux, cfg, sample_shape=(1, 32, 32, 16), txt_len=ctx_len
    )
    kwargs = {
        "y": jnp.zeros((batch, cfg.vec_in_dim), jnp.float32),
        "guidance": jnp.full((batch,), 3.5, jnp.float32),
    }
    return (model, batch, (batch, latent, latent, 16), ctx_len, cfg.context_in_dim,
            kwargs, "FLUX-class MMDiT bf16 batch=16 1024x1024 (reduced depth 4/8)")


def _synth_int8_params(sds, min_size: int = 2**16):
    """Materialize a quantized parameter pytree directly from abstract shapes,
    on host CPU: large >=2-D leaves become ``QuantTensor(int8 zeros, const
    scale)`` (the same min-size/channel-axis rule as quantize_params), small
    leaves bf16 zeros. Matmul timing is value-independent, so zeros measure the
    same compute as real weights — and a 12B high-precision pytree is never
    materialized anywhere."""
    import jax
    import jax.numpy as jnp

    from comfyui_parallelanything_tpu.models.quantize import (
        QuantTensor,
        int8_eligible,
    )

    cpu = jax.devices("cpu")[0]

    def synth(leaf):
        shape = tuple(leaf.shape)
        with jax.default_device(cpu):
            if int8_eligible(shape, min_size):
                scale_shape = tuple(1 for _ in shape[:-1]) + (shape[-1],)
                return QuantTensor(
                    q=jnp.zeros(shape, jnp.int8),
                    scale=jnp.full(scale_shape, 1e-2, jnp.float32),
                )
            return jnp.zeros(shape, jnp.bfloat16)

    return jax.tree.map(synth, sds)


def _rung_flux_16_int8(jnp, rng):
    """FULL 19/38 flux-dev topology, int8-stored weights — the measured
    replacement for flux_16's analytic depth bridge: a ~12 GB int8 replica
    fits a 16 GB v5e chip, so full-depth s/it can be measured, not
    extrapolated by FLOP ratio. Weights are synthesized
    directly as int8 (zeros; matmul timing is value-independent) from abstract
    shapes — a 12B f32/bf16 pytree is never materialized anywhere. Dequantize
    happens inside jit: int8 HBM reads, on-chip widening (models/quantize.py).
    """
    from comfyui_parallelanything_tpu.models import flux_dev_config

    batch, latent, ctx_len = 16, 128, 512
    cfg = flux_dev_config(dtype=jnp.bfloat16)
    model = _int8_synth_model(
        jnp, cfg, sample_shape=(1, 32, 32, 16), txt_len=ctx_len,
        name="flux-dev-int8",
    )
    kwargs = {
        "y": jnp.zeros((batch, cfg.vec_in_dim), jnp.float32),
        "guidance": jnp.full((batch,), 3.5, jnp.float32),
    }
    # 4 sequential microbatches of 4: ~12 GiB int8 weights + dequant temps +
    # full-batch-16 activations do not fit the 16 GiB chip; 16 images per
    # iteration either way, and 4x4608 token-rows per matmul still fills
    # the MXU.
    return (model, batch, (batch, latent, latent, 16), ctx_len, cfg.context_in_dim,
            kwargs, "FLUX-dev MMDiT FULL depth 19/38, int8 weights, batch=16 "
                    "(4x4 microbatch) 1024x1024 (measured full depth, single chip)",
            4)


def _rung_flux_stream(jnp, rng):
    """FULL 19/38 flux-dev topology, int8 weights, STREAMED through one chip —
    the north-star shape (batch=16 @1024²) as a measurement instead of a
    blank: ~12 GiB of int8 weights exceed the chip's usable HBM (<10.8 GiB,
    round-5 HBM finding), so no resident placement can ever run it
    single-chip. The weight-streaming executor (parallel/streaming.py) keeps
    params host-pinned and double-buffers per-stage sub-pytrees through HBM —
    int8 on the wire (half the bf16 transfer bytes), dequantized on-chip
    inside each stage program. run_inner routes this rung through
    ``ParallelConfig(weight_sharding="stream")`` on the lead chip."""
    from comfyui_parallelanything_tpu.models import flux_dev_config

    batch, latent, ctx_len = 16, 128, 512
    cfg = flux_dev_config(dtype=jnp.bfloat16)
    model = _int8_synth_model(
        jnp, cfg, sample_shape=(1, 32, 32, 16), txt_len=ctx_len,
        name="flux-dev-int8-stream",
    )
    kwargs = {
        "y": jnp.zeros((batch, cfg.vec_in_dim), jnp.float32),
        "guidance": jnp.full((batch,), 3.5, jnp.float32),
    }
    # 4 sequential microbatches of 4 (the flux_16_int8 activation-peak
    # lesson); the streamed schedule re-runs per chunk, so transfer overlap
    # is measured under the same per-iteration image count as the resident
    # rungs.
    return (model, batch, (batch, latent, latent, 16), ctx_len,
            cfg.context_in_dim, kwargs,
            "FLUX-dev MMDiT FULL depth 19/38, int8 weights STREAMED "
            "(host-pinned, double-buffered), batch=16 (4x4 microbatch) "
            "1024x1024 (single chip; weights exceed HBM)", 4)


def _rung_wan_video(jnp, rng):
    from comfyui_parallelanything_tpu.models import build_wan, wan_1_3b_config

    batch, ctx_len = 1, 128
    cfg = wan_1_3b_config(depth=8, dtype=jnp.bfloat16)
    frames, lat_h, lat_w = 16, 30, 52  # ~480p latent video, 16 frames
    model = _bf16_build(
        build_wan, cfg, sample_shape=(1, frames, lat_h, lat_w, cfg.in_channels),
        txt_len=ctx_len,
    )
    return (model, batch, (batch, frames, lat_h, lat_w, cfg.in_channels), ctx_len,
            cfg.text_dim, {},
            f"WAN-class video DiT bf16 {frames}f {lat_h}x{lat_w} latents")


def _rung_hybrid_sd15(jnp, rng):
    """Heterogeneous tpu:0 + cpu weighted chain (SURVEY §7 hard part 1) on real
    hardware: the one rung that exercises the two-program host-scatter path
    (orchestrator._data_parallel multi-group branch) off the virtual mesh. The
    TPU carries 70%, the host CPU 30% — the reference's CPU+GPU hybrid chain
    configuration (README.md:133-134) in TPU terms. Small model + 512² so the
    CPU side cannot wedge a window."""
    from comfyui_parallelanything_tpu.models import build_unet, sd15_config

    batch, latent, ctx_len = 8, 64, 77
    cfg = sd15_config(dtype=jnp.bfloat16)
    model = _bf16_build(build_unet, cfg, sample_shape=(1, latent, latent, 4))
    return (model, batch, (batch, latent, latent, 4), ctx_len, cfg.context_dim,
            {}, "SD1.5 UNet bf16 batch=8 512x512 hybrid tpu:0(70)+cpu(30)")


def _rung_smoke(jnp, rng):
    from comfyui_parallelanything_tpu.models import build_unet, sd15_config

    batch, latent, ctx_len = 8, 32, 24
    cfg = sd15_config(
        model_channels=64,
        channel_mult=(1, 2, 4),
        transformer_depth=(1, 1, 1),
        context_dim=256,
        dtype=jnp.bfloat16,
    )
    model = build_unet(cfg, rng, sample_shape=(1, latent, latent, 4))
    return (model, batch, (batch, latent, latent, 4), ctx_len, cfg.context_dim,
            {}, "SD1.5-topology smoke batch=8 256x256")


# Single source of truth for rung names: the outer process validates BENCH_CONFIG
# against this dict, the inner dispatches through it — they cannot drift.
_RUNGS = {
    "sd15_16": _rung_sd15_16,
    "sdxl_8": _rung_sdxl_8,
    "flux_single_heavy_21": _rung_flux_single_heavy_21,
    "flux_single_heavy_21_int8": _rung_flux_single_heavy_21_int8,
    "flux_16": _rung_flux_16,
    "flux_16_int8": _rung_flux_16_int8,
    "flux_stream": _rung_flux_stream,
    "wan_video": _rung_wan_video,
    "hybrid_sd15": _rung_hybrid_sd15,
    "smoke": _rung_smoke,
}
_KNOWN_CONFIGS = tuple(_RUNGS)


def _build(config_name):
    import jax
    import jax.numpy as jnp

    if config_name not in _RUNGS:
        raise ValueError(f"unknown BENCH_CONFIG {config_name!r}")
    if os.environ.get("PA_BENCH_TINY") == "1" and config_name != "smoke":
        # Tiny rehearsal: every rung runs the smoke-size model (the control
        # flow is under test, not the workload), with a 2-way microbatch so
        # the sequential-chunk path is exercised too.
        built = _rung_smoke(jnp, jax.random.key(0))
        label = f"TINY-DRYRUN[{config_name}] {built[6]}"
        return built[:6] + (label, 2)
    return _RUNGS[config_name](jnp, jax.random.key(0))


def _cost_flops(lowered):
    """FLOPs from a Lowered's XLA HLO cost analysis, or None if unavailable."""
    cost = lowered.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    flops = (cost or {}).get("flops")
    return float(flops) if flops and flops > 0 else None


def _step_cost(model, x, t, ctx, kwargs):
    """Analytic model FLOPs + bytes for one denoise step via the ONE shared
    accessor (``utils/roofline.step_cost``): XLA HLO cost analysis of a CPU
    lowering (dot/conv counts are backend-independent) with the exact jaxpr walk as
    fallback and cross-check — the unification that keeps ``mfu`` and
    ``roofline_ratio`` counting the same step (the record carries
    ``flops_source`` and the hlo/jaxpr discrepancy ratio when both
    resolved). Returns the accessor's dict; every field None on failure."""
    try:
        from comfyui_parallelanything_tpu.utils import roofline

        return roofline.step_cost(
            model.apply, model.params, x, t, ctx, kwargs
        )
    except Exception:
        return {"flops": None, "bytes_accessed": None, "flops_hlo": None,
                "flops_jaxpr": None, "flops_source": None,
                "flops_discrepancy_ratio": None}


def _full_flux_flops(batch, latent, ctx_len):
    """Analytic FLOPs/step of the FULL 19/38-depth flux-dev at this rung's
    shapes, from abstract (never-materialized) params — the analytic bridge from
    the reduced-depth flux_16 measurement to the full model the
    BASELINE.json north-star is defined on."""
    import jax
    import jax.numpy as jnp

    from comfyui_parallelanything_tpu.models import flux_abstract_params, flux_dev_config
    from comfyui_parallelanything_tpu.models.flux import FluxModel

    try:
        cfg = flux_dev_config(dtype=jnp.bfloat16)
        module = FluxModel(cfg)
        sds = flux_abstract_params(cfg, sample_shape=(1, 32, 32, 16), txt_len=ctx_len)
        args = (
            jax.ShapeDtypeStruct((batch, latent, latent, 16), jnp.float32),
            jax.ShapeDtypeStruct((batch,), jnp.float32),
            jax.ShapeDtypeStruct((batch, ctx_len, cfg.context_in_dim), jnp.float32),
            jax.ShapeDtypeStruct((batch, cfg.vec_in_dim), jnp.float32),
            jax.ShapeDtypeStruct((batch,), jnp.float32),
        )
        return _cost_flops(
            jax.jit(
                lambda p, x, t, c, y, g: module.apply(
                    {"params": p}, x, t, c, y=y, guidance=g
                )
            ).lower(sds, *args)
        )
    except Exception:
        return None


def _peak_bf16(device_kind):
    """Peak bf16 FLOP/s of a TPU chip from the one spec table
    (``utils/roofline.PLATFORM_SPECS``); raises for a chip it does not list."""
    from comfyui_parallelanything_tpu.utils import roofline

    return roofline.platform_spec(device_kind, "tpu")["peak_flops"]


def _plan_summary(pm):
    """Compact plan view for the JSON line (None when the planner is off,
    the chain was ineligible, or the summary layer fails — the one line
    outranks its plan field)."""
    try:
        from comfyui_parallelanything_tpu.parallel import planner

        return planner.plan_summary(getattr(pm, "plan", None))
    except Exception:
        return None


def _make_step(pm, batch, n_chunks, t, ctx, kwargs):
    """One denoise-step callable mapping latents -> latents (the shape
    ``chained_time`` chains). ``n_chunks > 1`` runs the batch as that many
    sequential microbatches and concatenates — identical images-per-iteration,
    activation peak divided by ``n_chunks`` (how a 16 GiB chip runs a batch
    sized for the reference's 24 GiB GPU). ``batch`` must divide evenly."""
    import jax.numpy as jnp

    if n_chunks == 1:
        return lambda v: pm(v, t, ctx, **kwargs)
    if batch % n_chunks:
        raise ValueError(f"batch {batch} not divisible by n_chunks {n_chunks}")

    def _slice_batch(a, sl):
        return a[sl] if hasattr(a, "shape") and a.shape[:1] == (batch,) else a

    def step(v):
        size = batch // n_chunks
        outs = []
        for i in range(n_chunks):
            sl = slice(i * size, (i + 1) * size)
            kw = {k: _slice_batch(a, sl) for k, a in kwargs.items()}
            outs.append(pm(v[sl], t[sl], ctx[sl], **kw))
        return jnp.concatenate(outs, axis=0)

    return step


def run_inner() -> None:
    """The measured benchmark, wrapped by the flight recorder: on ANY failure
    a postmortem bundle (trace rings, metrics, per-device memory, recent
    logs — utils/telemetry.py) is dumped and its path surfaced on stderr as
    ``POSTMORTEM_BUNDLE=<path>`` for the outer process to attach to the
    failure record; the exception then propagates and the outer
    process reports the failure (one error line, exit 1)."""
    try:
        _run_inner()
    except BaseException as e:
        if isinstance(e, SystemExit) and not e.code:
            raise
        try:
            from comfyui_parallelanything_tpu.utils import telemetry

            tag = os.environ.get("BENCH_CONFIG", "default")
            path = telemetry.write_postmortem(f"bench-{tag}", error=e)
            if path:
                sys.stderr.write(f"POSTMORTEM_BUNDLE={path}\n")
        except Exception:
            pass
        raise


def _run_inner() -> None:
    import jax
    import jax.numpy as jnp

    # Persistent XLA compilation cache ($JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache); the enable also installs the compile-event
    # watchers.
    from comfyui_parallelanything_tpu.utils import (
        enable_compilation_cache,
        telemetry,
    )

    enable_compilation_cache()
    telemetry.watermark.reset()

    from comfyui_parallelanything_tpu import (
        DeviceChain,
        ParallelConfig,
        parallelize,
    )

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    is_tpu = platform == "tpu"
    config_name = os.environ.get("BENCH_CONFIG", _DEFAULT_TPU_RUNG)
    if not is_tpu and config_name != "smoke" and not _TINY:
        # No CPU substitute for a TPU rung: the smoke rung and the
        # dryrun-labelled tiny rehearsals are the only CPU runs.
        raise RuntimeError(
            f"rung {config_name!r} needs a TPU; JAX found {jax.devices()}"
        )

    built = _build(config_name)
    model, batch, x_shape, ctx_len, ctx_dim, kwargs, workload = built[:7]
    # Optional 8th element: sequential microbatch count. The big single-chip
    # rungs OOM at full batch (bf16 weights 10.8-12 GiB + the fused
    # single-block projection's (B, 4224, 21504) activation on a 16 GiB v5e);
    # splitting the batch into N sequential chunks divides the activation peak
    # by N while keeping the workload identical — the same B images per
    # iteration, exactly how a 16 GiB chip should run a batch sized for the
    # reference's 24 GiB RTX 3090.
    n_chunks = built[7] if len(built) > 7 else 1
    # BENCH_MICROBATCH: re-run a rung with a deeper sequential split after
    # an OOM without a code change. Values that don't divide the batch round
    # up to the next divisor.
    override = os.environ.get("BENCH_MICROBATCH")
    if override:
        want = max(int(override), n_chunks)
        # Next divisor of batch at or above the request; an over-deep request
        # clamps to fully-sequential (batch chunks of 1) instead of crashing.
        n_chunks = next(
            (c for c in range(want, batch + 1) if batch % c == 0), batch
        )

    kx, kc = jax.random.split(jax.random.key(1))
    x = jax.random.normal(kx, x_shape, jnp.float32)
    t = jnp.linspace(999.0, 1.0, batch)
    ctx = jax.random.normal(kc, (batch, ctx_len, ctx_dim), jnp.float32)

    # Analytic step cost BEFORE the wrap (it was always computed for MFU —
    # now it doubles as the planner's hints): the auto-parallel planner
    # (parallel/planner.py) scores candidate plans against the rung's real
    # per-dispatch FLOPs/bytes instead of a weights-derived estimate.
    cost = _step_cost(model, x, t, ctx, kwargs)
    plan_hints = {
        "rung": config_name,
        "flops": (cost["flops"] / n_chunks) if cost["flops"] else None,
        "bytes_accessed": (
            cost["bytes_accessed"] / n_chunks
            if cost["bytes_accessed"] else None
        ),
        "batch": batch // n_chunks,
    }

    if config_name == "flux_stream":
        # Weight-streaming rung: ONE chip, params host-pinned, stages
        # double-buffered (parallel/streaming.py). The explicit stream mode
        # pins the rung's meaning (the weights-don't-fit auto-routing would
        # pick it anyway on a chip whose budget the pytree exceeds) while
        # the planner still searches the stage-CARVE axis within it;
        # PA_STREAM_HBM_BUDGET overrides the carve budget — the off-hardware
        # rehearsal forces multi-stage carving on a tiny model with it.
        chain = DeviceChain.even([f"{platform}:{jax.devices()[0].id}"])
        budget = os.environ.get("PA_STREAM_HBM_BUDGET")
        pm = parallelize(
            model, chain,
            ParallelConfig(
                weight_sharding="stream",
                hbm_budget_bytes=int(budget) if budget else None,
            ),
            plan_hints=plan_hints,
        )
    elif config_name == "hybrid_sd15" and is_tpu and platform != "cpu":
        # The heterogeneous rung: lead TPU chip at 70%, host CPU at 30% — a
        # two-platform chain, so parallelize builds two SPMD groups and the
        # weighted host scatter (SURVEY §7 hard part 1) actually runs.
        chain = DeviceChain.from_pairs(
            [(f"{platform}:{jax.devices()[0].id}", 70.0), ("cpu", 30.0)]
        )
        pm = parallelize(model, chain, plan_hints=plan_hints)
    else:
        chain = DeviceChain.even([f"{platform}:{d.id}" for d in jax.devices()])
        pm = parallelize(model, chain, plan_hints=plan_hints)

    step = _make_step(pm, batch, n_chunks, t, ctx, kwargs)

    # Span tracing (round 8, utils/tracing.py): every benchmarked iteration
    # runs traced — per-span cost is ~µs against multi-second denoise steps —
    # so every JSON line carries the trace-derived aggregates
    # (stream_overlap_efficiency / lane_wait_p95 / host_gap_ms) and
    # PA_TRACE_OUT (the --trace-out flag) can dump the full Perfetto
    # timeline without a second run.
    from comfyui_parallelanything_tpu.utils import tracing

    tracing.enable()
    # Numerics sentinel (round 11, utils/numerics.py): OPT-IN for bench runs
    # (PA_NUMERICS=1) — with the flag on, the streaming rung's per-stage
    # finite checks run inside the timed iterations, which would shift
    # sec/it against pre-sentinel ledger baselines. Default-off keeps the
    # pinned timing protocol untouched; the fingerprint and final-output
    # stats below are flag-independent (computed after the loop), so every
    # line still carries latent_fingerprint/nonfinite_events either way.
    from comfyui_parallelanything_tpu.utils import numerics

    if os.environ.get("PA_NUMERICS", "") not in ("", "0", "false"):
        numerics.enable()
    numerics.sentinel.reset()
    inner_step = step
    # Fault injection (round 14, utils/faults.py — the unified registry
    # absorbing this file's old ad-hoc parser): a deterministic mid-run
    # failure (``mid-step-crash`` site) so the postmortem/forensics path is
    # rehearsed off-hardware — the round-3 lesson applied to the flight
    # recorder itself. The legacy ``PA_FAIL_INJECT=oom`` alias fires from
    # step 3 on (the historical contract: the bundle holds real warmup
    # spans/samples); ``PA_FAULT_PLAN`` schedules arbitrary steps.
    # ``nan:<lane>`` values parse to the ``lane-nan`` site (the serving
    # quarantine rehearsal) and never fire here. Arming requires the
    # PA_EVIDENCE_DIR redirect — enforced at module load above AND by the
    # registry's own rule.
    from comfyui_parallelanything_tpu.utils import faults

    _step_no = [0]

    def step(v):
        _step_no[0] += 1
        _act = faults.check("mid-step-crash", key=f"{config_name}:{_step_no[0]}")
        if _act is not None:
            raise faults.oom_error(_act)
        with tracing.span("step", cat="bench", rung=config_name):
            out = inner_step(v)
        # HBM watermark sampling during WARMUP steps only: memory_stats() is
        # a host call (and the fallback walks live arrays), so sampling
        # inside the timed loop would inflate sec/it against baselines
        # banked before round 9 — the exact protocol drift the pinned
        # iteration counts exist to prevent. Warmup runs the identical
        # program, so the peak it observes is the steady-state peak; one
        # more sample lands after the timed loop below.
        if _step_no[0] <= BENCH_WARMUP_STEPS:
            telemetry.watermark.sample()
        return out

    # Warmup/compile + timed denoise-step iterations: chained_time chains
    # each iteration's output into the next input and closes with a host
    # readback (utils/metrics.py), so the timing cannot end before the device
    # does. The protocol is PINNED and recorded in the JSON line (iteration
    # count + warmup steps) together with the load average, so a drifted
    # number is auditable against host load.
    from comfyui_parallelanything_tpu.utils.metrics import chained_time

    iters = TPU_BENCH_ITERS if is_tpu else SMOKE_BENCH_ITERS
    if os.environ.get("PA_BENCH_TINY") == "1":
        iters = 3  # dry-run: control flow under test, not timing fidelity
    sec_it, final_out = chained_time(step, x, iters, warmup=BENCH_WARMUP_STEPS)
    # Post-loop watermark sample (the warmup-phase samples above kept the
    # host call out of the timed iterations): on real devices memory_stats'
    # running peak covers the timed steps too.
    telemetry.watermark.sample()

    # Numerics audit fields (utils/numerics.py), computed post-loop on the
    # chained final output: the latent fingerprint (bf16-quantized digest —
    # deterministic per rung, what scripts/numerics_audit.py --check diffs
    # against its golden bank) and the run's non-finite event count (sentinel
    # events — e.g. a streamed stage gone bad — plus a poisoned final
    # output). Best-effort: the one JSON line outranks its audit fields.
    latent_fingerprint = None
    try:
        import numpy as _np

        fstats = numerics.stats_to_dict(
            _np.asarray(numerics.array_stats(final_out))
        )
        if fstats["nonfinite"]:
            numerics.sentinel.record_event(
                "bench-final", rung=config_name, **fstats
            )
        latent_fingerprint = numerics.latent_fingerprint(final_out)
    except Exception:
        pass
    nonfinite_events = numerics.sentinel.event_count

    trace_events = tracing.export()
    trace_aggs = tracing.trace_aggregates(trace_events)
    trace_out = os.environ.get("PA_TRACE_OUT")
    if trace_out:
        with open(trace_out, "w") as f:
            json.dump(trace_events, f)
        sys.stderr.write(f"bench: trace written to {trace_out}\n")

    # MFU: analytic step FLOPs / time / aggregate peak. TPU only (CPU peak is
    # not meaningful for MXU utilization). ``cost`` was computed before the
    # wrap (it seeded the planner's hints).
    mfu = None
    flops = cost["flops"]
    peak = _peak_bf16(jax.devices()[0].device_kind) if is_tpu else None
    if flops and peak:
        mfu = round(flops / sec_it / (peak * n_dev), 4)

    # Roofline attribution (utils/roofline.py, this round): the calibrated
    # analytic prediction for this rung's step — max(compute, memory) over
    # the platform roofline, scaled by the banked (rung, platform,
    # shape-bucket) calibration when one exists — the predicted_step_s /
    # roofline_ratio pair every line carries, plus the measured-side bucket
    # decomposition of the timed window from the trace spans. DP forwards
    # run collective-free, so the bench prediction carries no comms term;
    # the per-program registry rows (ledger only) price their own meshes.
    predicted_step_s = predicted_step_raw_s = roofline_ratio = None
    attribution = None
    try:
        from comfyui_parallelanything_tpu.utils import roofline

        if flops and roofline.enabled():
            spec = roofline.platform_spec(
                jax.devices()[0].device_kind, platform
            )
            pred = roofline.predict_time_s(
                flops, cost["bytes_accessed"], spec, n_devices=n_dev
            )
            scale = roofline.calibration_scale(
                roofline.load_calibration(), f"rung:{config_name}",
                platform, roofline.shape_bucket(flops),
            )
            predicted_step_raw_s = round(pred["predicted_s"], 6)
            predicted_step_s = round(pred["predicted_s"] * scale, 6)
            if sec_it > 0:
                roofline_ratio = round(predicted_step_s / sec_it, 4)
        if roofline.enabled():
            attribution = roofline.attribution_from_trace(
                trace_events, wall_s=sec_it * iters, last_steps=iters
            )
    except Exception:
        pass

    # No rung runs the reference's benchmark model (Z_Image Turbo) at its
    # batch of 21, and dividing its 26.00 s/it by another workload's s/it is
    # cross-workload noise: null everywhere (_REF_SINGLE_GPU_S_IT stays as the
    # number a like-for-like rung would divide).
    vs_baseline = None

    from comfyui_parallelanything_tpu.ops.attention import (
        chunk_config,
        get_attention_backend,
        resolved_backends,
    )

    _comp = telemetry.compile_snapshot()
    record = {
        "metric": f"sec/it denoise step [{config_name}]",
        "value": round(sec_it, 4),
        "unit": "s/it",
        "vs_baseline": vs_baseline,
        "platform": platform,
        "n_devices": n_dev,
        "mfu": mfu,
        "model_flops_per_step": flops,
        "workload": f"{workload} ({platform} x{n_dev})",
        "microbatch_chunks": n_chunks,
        "images_per_sec": round(batch / sec_it, 3),
        # Pinned protocol + host-load context (the smoke-drift audit trail).
        "bench_iters": iters,
        "warmup_steps": BENCH_WARMUP_STEPS,
        "loadavg_1m": _loadavg_1m(),
        # Trace-derived aggregates (utils/tracing.py): stream compute
        # occupancy of the streamed-run wall clock (null off the stream
        # rung), serving lane-wait p95 (null without serving traffic), and
        # the mean host gap between step spans — where host scheduling
        # overhead shows up before any device profile is opened.
        **trace_aggs,
        # Resource accounting (utils/telemetry.py, round 9): where the
        # compiles and the bytes went. compile_time_s is total in-process
        # XLA backend-compile wall time; hits/misses are the persistent
        # compilation cache's (a warm .jax_cache turns the 20-40s
        # first-compile into hits); peak_hbm_bytes is the per-iteration
        # watermark (deterministic pseudo-accounting off-hardware).
        "compile_time_s": _comp["compile_time_s"],
        "compile_cache_hits": _comp["cache_hits"],
        "compile_cache_misses": _comp["cache_misses"],
        "peak_hbm_bytes": telemetry.watermark.peak_bytes or None,
        # Numerics audit (utils/numerics.py): the rung's deterministic
        # latent fingerprint (drift-gated by scripts/numerics_audit.py) and
        # non-finite events observed this run (0 on a healthy rung).
        "latent_fingerprint": latent_fingerprint,
        "nonfinite_events": nonfinite_events,
        # Which attention path(s) actually served the run, resolved at trace
        # time ("pallas", "xla", or "pallas+xla" when different shapes picked
        # differently) — so the evidence never hides an XLA fallback behind an
        # "auto" setting. Falls back to the configured setting if the model
        # has no attention at all.
        "attention_backend": "+".join(resolved_backends()) or get_attention_backend(),
        # The XLA family's chunk threshold this run was served under, and
        # whether the degradation ladder had shrunk it.
        "attn_chunk": chunk_config(),
        # Roofline attribution (utils/roofline.py): the calibrated analytic
        # step prediction, its ratio against the measured step (sane band
        # (0, 1.2] — gated by scripts/roofline_report.py --check), the raw
        # (uncalibrated) prediction the calibration fit reads back, the
        # measured-side compute/exposed-transfer/host-gap/comms bucket
        # decomposition of the timed window, and which FLOPs source priced
        # it (hlo vs jaxpr, + their discrepancy ratio when both resolved).
        "predicted_step_s": predicted_step_s,
        "predicted_step_raw_s": predicted_step_raw_s,
        "roofline_ratio": roofline_ratio,
        "attribution": attribution,
        "flops_source": cost["flops_source"],
        "flops_discrepancy_ratio": cost["flops_discrepancy_ratio"],
        # Auto-parallel planner (parallel/planner.py): the plan this rung's
        # wrap routed through — chosen candidate, shadow hand-plan score,
        # divergence — null with PA_PLANNER=0 or on ineligible chains
        # (hybrid multi-group).
        "plan": _plan_summary(pm),
    }
    if _TINY:
        record["dryrun"] = True
    if config_name == "flux_16" and flops:
        # Analytic bridge to the full 19/38-depth model (compute-bound regime:
        # time scales with matmul FLOPs at fixed shapes/arithmetic class).
        full = _full_flux_flops(batch, x_shape[1], ctx_len)
        if full:
            record["full_model_flops_per_step"] = full
            record["extrapolated_full_depth_s_it"] = round(sec_it * full / flops, 4)
    # Perf-ledger record (utils/telemetry.py): the regression gate's input —
    # one schema-versioned line per measured run, rung-stamped. The ledger
    # twin additionally carries the per-program roofline rows (predictions
    # for every instrumented program this run compiled — the calibration
    # fit's program-level input), which stay off the stdout line to keep
    # the driver contract lean.
    # kind="plan" ledger record (parallel/planner.py + scripts/plan_report.py
    # --check): the decision with its measured actual — predicted-vs-actual
    # error banked per rung, and the raw prediction fit_calibration reads
    # back so the planner sharpens per platform. Appended BEFORE the bench
    # record so the ledger's last line stays the bench record (the
    # rehearsal tests' contract).
    try:
        plan_decision = getattr(pm, "plan", None)
        if plan_decision is not None:
            from comfyui_parallelanything_tpu.parallel import planner

            plan_ledger = planner.ledger_record(
                plan_decision, actual_s=sec_it / n_chunks
            )
            if _TINY:
                plan_ledger["dryrun"] = True
            telemetry.append_ledger_record(plan_ledger, "plan")
    except Exception:
        pass

    ledger_rec = {**record, "rung": config_name}
    try:
        from comfyui_parallelanything_tpu.utils import roofline

        prog_rows = roofline.program_rows_for_ledger()
        if (prog_rows and "parallel-apply" in prog_rows
                and config_name != "flux_stream"):
            # Program-level measured_s — what the calibration fit pairs
            # against predicted_raw_s per program. The resident rungs'
            # timed step is exactly n_chunks sequential dispatches of the
            # DP step program, so per-dispatch wall is its honest measured
            # cost. The streamed rung's step runs the stage programs
            # instead (stage-index→program joins await the planner item).
            prog_rows["parallel-apply"]["measured_s"] = round(
                sec_it / n_chunks, 6
            )
        ledger_rec["roofline_programs"] = prog_rows
    except Exception:
        pass
    telemetry.append_ledger_record(ledger_rec, "bench")
    print(json.dumps(record))


def _postmortem_path(stderr: str) -> str | None:
    """The inner child's ``POSTMORTEM_BUNDLE=<path>`` marker, if it dumped
    one before dying (run_inner's flight-recorder wrapper)."""
    import re

    m = None
    for m in re.finditer(r"POSTMORTEM_BUNDLE=(\S+)", stderr or ""):
        pass  # last marker wins (retries can dump more than one)
    return m.group(1) if m else None


def _run_child(env, config, timeout):
    """Run the inner benchmark in a subprocess.

    Returns ``(json_line_or_None, stderr_tail, postmortem_path_or_None)`` —
    the stderr tail is preserved so a failed child's traceback survives into
    the round's artifacts, and the postmortem marker is extracted BEFORE the
    tail truncation (the traceback printed after it can exceed the tail)."""
    env = dict(env)
    if config is not None:
        env["BENCH_CONFIG"] = config
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--inner"],
            env=env, cwd=_REPO, capture_output=True, text=True, timeout=timeout,
        )
    except subprocess.TimeoutExpired as e:
        stderr = e.stderr or ""
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        tail = (f"inner benchmark timed out after {timeout}s; "
                f"stderr tail:\n{stderr.strip()[-2000:]}")
        return None, tail, _postmortem_path(stderr)
    return (_last_json_line(proc.stdout), proc.stderr.strip()[-2000:],
            _postmortem_path(proc.stderr))


def _last_json_line(stdout):
    for line in reversed(stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "metric" in parsed:
                return line
    return None


# Fields of a result line that an error line carries as nulls, never omits —
# the schema stays uniform for every consumer.
_LATE_SCHEMA_FIELDS = (
    "stream_overlap_efficiency", "lane_wait_p95", "host_gap_ms",
    "compile_time_s", "compile_cache_hits", "compile_cache_misses",
    "peak_hbm_bytes", "latent_fingerprint", "nonfinite_events",
    # Roofline attribution (round 13): prediction, ratio, measured-side
    # bucket breakdown, and the FLOPs-source audit fields.
    "predicted_step_s", "predicted_step_raw_s", "roofline_ratio",
    "attribution", "flops_source", "flops_discrepancy_ratio",
    # Auto-parallel planner (round 18): the plan the wrap routed through.
    "plan",
)


def _error_line(error, metric="error", postmortem=None):
    """The one failure-path JSON schema — every error exit goes through here so
    the driver always sees a consistent field set (including the trace-derived
    aggregate and resource-accounting fields every bench line now carries,
    null here). ``postmortem`` is the failure bundle's path when the inner
    child managed to dump one."""
    rec = {
        "metric": metric, "value": 0, "unit": "", "vs_baseline": None,
        "platform": "none", "n_devices": 0, "error": error[:300],
        "loadavg_1m": _loadavg_1m(),
    }
    for field in _LATE_SCHEMA_FIELDS:
        rec[field] = None
    if postmortem:
        rec["postmortem"] = postmortem
    return json.dumps(rec)


def _pop_trace_out_flag() -> None:
    """Honor ``--trace-out PATH`` (and ``--trace-out=PATH``) by exporting
    PA_TRACE_OUT for the inner child (both spellings also work set directly
    in the environment). Parsed by hand: bench.py's only other argv surface
    is the ``--inner`` sentinel, and argparse would reject it."""
    argv = sys.argv
    for i, a in enumerate(list(argv)):
        if a == "--trace-out" and i + 1 < len(argv):
            os.environ["PA_TRACE_OUT"] = os.path.abspath(argv[i + 1])
            del argv[i:i + 2]
            return
        if a.startswith("--trace-out="):
            os.environ["PA_TRACE_OUT"] = os.path.abspath(a.split("=", 1)[1])
            del argv[i]
            return


def main() -> None:
    _pop_trace_out_flag()
    if "--inner" in sys.argv:
        run_inner()
        return
    try:
        _orchestrate()
    except SystemExit:
        raise
    except Exception as e:  # noqa: BLE001 — the driver contract is one JSON line, always
        print(_error_line(str(e)))
        sys.exit(1)


def _orchestrate() -> None:
    requested = os.environ.get("BENCH_CONFIG")
    if requested is not None and requested not in _KNOWN_CONFIGS:
        # Misconfiguration must surface as an error, not a plausible smoke line.
        print(_error_line(
            f"unknown BENCH_CONFIG {requested!r}; known: {list(_KNOWN_CONFIGS)}"
        ))
        sys.exit(1)

    env = dict(os.environ)
    if os.environ.get("BENCH_FORCE_CPU") == "1" or requested == "smoke":
        # The explicit CPU run (tests): always the smoke rung — the real
        # rungs are TPU-sized — and labelled platform "cpu" by the child.
        if requested not in (None, "smoke"):
            print(_error_line(
                f"BENCH_FORCE_CPU=1 runs only the smoke rung, not {requested!r}"
            ))
            sys.exit(1)
        requested = "smoke"
        env["JAX_PLATFORMS"] = "cpu"

    # ONE child owns the device; this process never touches jax.
    line, err, postmortem = _run_child(env, requested, timeout=1800)
    if line is not None:
        print(line)
        return
    sys.stderr.write(f"bench: child failed. Inner stderr tail:\n{err}\n")
    # The failed attempt is ledger history (kind=error — the regression gate
    # never compares it) with its forensics pointer.
    _ledger_append({
        "rung": requested, "error": "benchmark child failed",
        "stderr_tail": err[-500:], "postmortem": postmortem,
        "loadavg_1m": _loadavg_1m(),
    }, "error")
    print(_error_line(
        "benchmark child failed; last stderr: " + err[-200:],
        metric="sec/it denoise step [unavailable]",
        postmortem=postmortem,
    ))
    sys.exit(1)


if __name__ == "__main__":
    main()
