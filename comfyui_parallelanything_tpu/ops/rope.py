"""Multi-axis rotary position embeddings (FLUX-style).

FLUX's MMDiT positions tokens with per-axis RoPE over (seq, h, w) id triples with
per-axis dims like (16, 56, 56) summing to the head dim — the reference's config
scraper lists ``axes_dim``/``theta`` among the FLUX ctor kwargs it must preserve when
replicating (any_device_parallel.py:286-296). Computed in f32, applied in compute dtype.
"""

from __future__ import annotations

import jax.numpy as jnp


def axis_rope_freqs(ids: jnp.ndarray, axes_dim: tuple[int, ...], theta: float = 10000.0):
    """cos/sin tables for multi-axis RoPE.

    ids: (B, S, n_axes) integer positions per token per axis.
    Returns (cos, sin), each (B, S, sum(axes_dim)//2) f32.
    """
    parts_cos, parts_sin = [], []
    for i, dim in enumerate(axes_dim):
        half = dim // 2
        freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        angles = ids[..., i].astype(jnp.float32)[..., None] * freqs  # (B, S, half)
        parts_cos.append(jnp.cos(angles))
        parts_sin.append(jnp.sin(angles))
    return jnp.concatenate(parts_cos, axis=-1), jnp.concatenate(parts_sin, axis=-1)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate pairs: x is (B, S, H, D); cos/sin are (B, S, D//2).

    Interleaved-pair convention: (x_even, x_odd) rotated by the per-pair angle.
    """
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    x_pairs = xf.reshape(*xf.shape[:-1], -1, 2)
    x_even, x_odd = x_pairs[..., 0], x_pairs[..., 1]
    c = cos[:, :, None, :]  # broadcast over heads
    s = sin[:, :, None, :]
    out_even = x_even * c - x_odd * s
    out_odd = x_even * s + x_odd * c
    out = jnp.stack([out_even, out_odd], axis=-1).reshape(xf.shape)
    return out.astype(orig_dtype)


def half_rope_freqs(positions: jnp.ndarray, dim: int, theta: float):
    """cos/sin tables of the half-split convention (HF ``rotate_half``; the
    Qwen / Llama towers): positions (B, S) → (cos, sin), each (B, S, dim//2)
    f32, angle ``position · theta^(−2k/dim)`` for pair k."""
    return axis_rope_freqs(positions[..., None], (dim,), theta)


def apply_rope_halves(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate pairs (x[k], x[k + D/2]): x is (B, S, H, D); cos/sin (B, S, D//2).
    The half-split convention, against :func:`apply_rope`'s interleaved one."""
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = xf[..., :half], xf[..., half:]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)
