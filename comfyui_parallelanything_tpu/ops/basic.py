"""Shared primitive ops for the model zoo."""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


def rms_normalize(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm in f32 with a learned scale, returned in x's dtype — the q/k norm used
    by the MMDiT families (FLUX QKNorm, WAN self/cross q/k norm)."""
    xf = x.astype(jnp.float32)
    normed = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (normed * scale).astype(x.dtype)


def modulate(x: jnp.ndarray, shift: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """adaLN modulation ``x·(1+scale)+shift`` computed in f32, returned in x's dtype."""
    xf = x.astype(jnp.float32)
    return (xf * (1.0 + scale) + shift).astype(x.dtype)


def timestep_embedding(
    t: jnp.ndarray, dim: int, max_period: float = 10000.0, time_factor: float = 1.0
) -> jnp.ndarray:
    """Sinusoidal timestep embedding, (B,) -> (B, dim).

    The classic DDPM/transformer embedding used by every model family in scope (the
    reference's models compute this inside their torch UNet/DiT; it lives once here).
    Computed in float32 for stability, cast by callers.
    """
    t = time_factor * jnp.asarray(t, jnp.float32)
    half = dim // 2
    freqs = jnp.exp(-jnp.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    args = t[:, None] * freqs[None, :]
    emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    if dim % 2:
        emb = jnp.concatenate([emb, jnp.zeros_like(emb[:, :1])], axis=-1)
    return emb


def progress_window_gate(
    t_vec: jnp.ndarray, start: float, end: float, ndim: int,
    flow_time: bool = False,
) -> jnp.ndarray:
    """Per-batch sampling-progress window gate in {0, 1}, shaped (B, 1, ...)
    to broadcast over a rank-``ndim`` batch tensor (rank-safe for video's 5D
    latents). Progress runs 0 → 1 over the denoise: flow time IS the noise
    level (progress = 1 − t); the eps/v families carry table timesteps
    (progress = 1 − t/999 — the stock percent-window linear-in-t
    approximation). Shared by ControlNet's start/end percents
    (models/controlnet.apply_control) and ConditioningSetTimestepRange
    (sampling/k_samplers.EpsDenoiser) so the two gates cannot drift."""
    t = t_vec.astype(jnp.float32)
    progress = 1.0 - (t if flow_time else t / 999.0)
    on = (progress >= float(start)) & (progress <= float(end))
    return on.astype(jnp.float32).reshape((-1,) + (1,) * (ndim - 1))


# Nearest ×2 upsample + 3×3 convolution: ms of DEVICE time a call on one v5e
# (scripts/bench_kernels.py --upsample, the trace's module line, my chip run,
# PR 38; bfloat16, the low-resolution input (B, H, W, C), C -> C). ``plain`` is
# repeat ×2 then the 3×3 at the high resolution (what the UNets had; the
# decoder's ``jax.image.resize`` gather read within 4% of it at 1 x 1024²,
# 13–38% over it at 8 x 512²); ``dilated`` is the form shipped below; ``phases``
# four 2×2 convolutions stacked and reshaped into place, ``rows`` one 2×3
# convolution a row phase with 2·C output channels, ``one`` a single 2×2
# convolution with 4·C output channels and four offset slices (the three keep
# their code in the script). The floor is 16 tap-products a source pixel at
# 197 TFLOP/s.
#
#   shape (B, H, W, C)          plain  dilated  phases    rows     one   floor
#   decoder  (1, 128, 128, 512)  2.553   1.323   1.795   1.694   1.826   0.698
#   decoder  (1, 256, 256, 512) 12.315   5.890   9.093   9.104   9.432   2.791
#   decoder  (1, 512, 512, 256) 18.364   9.175  17.126  13.765  16.284   2.791
#   decoder  (8, 64, 64, 512)    3.916   2.130   2.973   3.689   3.356   1.395
#   decoder  (8, 128, 128, 512) 16.770   8.432  12.536  14.185  15.061   5.581
#   decoder  (8, 256, 256, 256) 20.717   9.470  19.528  18.421  22.546   5.581
#   sd15     (16, 8, 8, 1280)    0.661   0.472   0.413   0.833   0.508   0.273
#   sd15     (16, 16, 16, 1280)  2.622   1.554   1.448   2.196   1.593   1.090
#   sd15     (16, 32, 32, 640)   2.847   1.355   2.116   2.363   2.194   1.090
#   sdxl     (2, 32, 32, 1280)   1.665   1.049   1.055   1.692   1.123   0.545
#   sdxl     (2, 64, 64, 640)    1.797   0.922   1.161   1.425   1.077   0.545
#
# The whole decode program, latent (1, 128, 128, 16) / (8, 64, 64, 4): 87.55 /
# 127.27 ms with the gather, 69.24 / 95.65 dilated, 108.84 / 113.38 phases,
# 109.01 / 109.14 rows, 112.07 / 119.65 one — in the program the stacked forms'
# interleave passes cost more than their convolutions save, and their
# temporaries grow from 1.36 to 2.43 GB at 1 x 1024² (dilated: 1.35). ``phases``
# is ahead only at SD1.5's two smallest stages, by 0.06 and 0.11 ms of a 131 ms
# step: one form for every shape, no choice by size.


def upsample2x_conv3x3(
    x: jnp.ndarray, kernel: jnp.ndarray, bias: jnp.ndarray | None = None,
    dtype=None,
) -> jnp.ndarray:
    """Nearest ×2 upsample of ``x`` (B, H, W, C) followed by a 3×3, stride-1,
    SAME convolution with ``kernel`` (3, 3, C, O), computed from the
    LOW-resolution input: (B, 2H, 2W, O), the upsampled tensor never made.

    A nearest-×2 image is the zero-stuffed one under a 2×2 box, so the pair is
    ONE convolution of the zero-stuffed input (``lhs_dilation`` 2: no tensor,
    the convolution skips the holes) with the 4×4 kernel box ∗ w. Each output
    pixel meets 2×2 of its 16 taps on a source pixel — the four output phases'
    folded taps, rows (w₀, w₁+w₂) / (w₀+w₁, w₂) and the same along columns —
    16 tap-products a source pixel where upsample + 3×3 makes 36, written
    straight into the interleaved result. Zero padding comes out the same.

    The taps are folded in float32 from the kernel as it is resident, cast
    once to ``dtype`` (default: ``x``'s), accumulated in float32 as ``nn.Conv``
    does; the bias is added once. Counted once a trace:
    ``pa_upsample_conv_total{form}``."""
    from ..utils.metrics import registry

    registry.counter(
        "pa_upsample_conv_total", labels={"form": "phase"},
        help="nearest x2 upsample + 3x3 convolution pairs by the form they "
             "took, counted like pa_attention_route_total: once a trace "
             "(ops/basic.upsample2x_conv3x3)",
    )
    dtype = x.dtype if dtype is None else dtype
    k = kernel.astype(jnp.float32)
    for axis in (0, 1):  # box ∗ w along rows, then columns: 3 taps -> 4
        lo, hi = [(0, 0)] * 4, [(0, 0)] * 4
        lo[axis], hi[axis] = (0, 1), (1, 0)
        k = jnp.pad(k, lo) + jnp.pad(k, hi)
    y = jax.lax.conv_general_dilated(
        x.astype(dtype), k.astype(dtype), window_strides=(1, 1),
        padding=((2, 2), (2, 2)), lhs_dilation=(2, 2),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    )
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype)


class UpsampleConv(nn.Module):
    """``nn.Conv(features, (3, 3), padding=1)`` applied to the nearest-×2
    upsample of its input, through :func:`upsample2x_conv3x3`: the parameters
    (``kernel`` (3, 3, C, features), ``bias``), their initialisers and their
    float32 residency are ``nn.Conv``'s, so a module that names it as it named
    its convolution keeps its parameter tree and its checkpoint keys."""

    features: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.linear.default_kernel_init,
            (3, 3, x.shape[-1], self.features))
        bias = self.param("bias", nn.initializers.zeros_init(), (self.features,))
        return upsample2x_conv3x3(x, kernel, bias, self.dtype)
