"""Weight-only int8 quantization for diffusion model pytrees.

The reference preserves fp8-*stored* checkpoints through cloning and downcasts
them per device capability (any_device_parallel.py:93-124, 688-699) — its only
compression story. Here the TPU-native equivalent is symmetric per-channel int8
weight quantization applied after load:

- each large weight leaf becomes a ``QuantTensor(q=int8, scale=f32)`` pytree
  node (per-output-channel scales: ``w ≈ q · scale``);
- ``QuantTensor`` is a registered pytree, so placement (``jax.device_put`` with
  shardings), FSDP leaf sharding, pipeline sub-pytree staging, and donation all
  treat the int8 payload like any other leaf — no special cases anywhere in the
  parallel layer;
- the model's ``apply`` dequantizes inside jit: XLA reads the int8 bytes from
  HBM (half the bf16 traffic for weight-bound regimes) and widens on-chip.

Why it matters on a v5e: a flux-dev-class bf16 replica (~24 GB) does not fit a
16 GB chip; at int8 (~12 GB) it does — so quantization turns "must shard (FSDP)"
into "may replicate", trading a bounded quantization error (per-channel symmetric
int8 on conv/dense kernels is well inside diffusion sampling tolerance) for the
all-gather traffic FSDP would pay every step.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantTensor:
    """Symmetric per-channel int8 weight: ``w ≈ q.astype(f32) * scale``.

    ``scale`` broadcasts against ``q`` (kept with a trailing axis of the same
    rank, size 1 everywhere except the channel axis)."""

    q: Any      # int8, original shape
    scale: Any  # f32, broadcastable to q's shape

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    def dequantize(self, dtype=jnp.bfloat16):
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)


def _quantize_leaf(w, channel_axis: int) -> QuantTensor:
    wf = jnp.asarray(w, jnp.float32)
    reduce_axes = tuple(i for i in range(wf.ndim) if i != channel_axis)
    absmax = jnp.max(jnp.abs(wf), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return QuantTensor(q=q, scale=scale)


def int8_eligible(shape, min_size: int = 2**16) -> bool:
    """THE min-size/rank rule deciding which leaves quantize to int8.
    Shared by ``quantize_params``, bench's ``_synth_int8_params`` synthesis,
    and the dryrun's abstract flux_stream byte profile (§19b) — one rule,
    so the synthesized/abstract byte budgets can never drift from what
    quantization actually stores."""
    shape = tuple(shape)
    size = 1
    for s in shape:
        size *= int(s)
    return len(shape) >= 2 and size >= min_size


def synth_int8_nbytes(shapes, min_size: int = 2**16) -> int:
    """Stored bytes of an ABSTRACT pytree (ShapeDtypeStructs / shape stubs)
    under the int8 synthesis rule: eligible leaves count int8 bytes plus
    the per-output-channel f32 scale vector, the rest bf16 — sizes a
    12B-class checkpoint without materializing anything."""
    total = 0
    for leaf in jax.tree.leaves(shapes):
        shape = tuple(getattr(leaf, "shape", ()))
        size = 1
        for s in shape:
            size *= int(s)
        if int8_eligible(shape, min_size):
            total += size + int(shape[-1]) * 4  # int8 q + f32 scale row
        else:
            total += size * 2  # bf16
    return total


def quantize_params(params, min_size: int = 2**16):
    """Quantize every large ≥2-D weight leaf to per-channel int8.

    Channel axis = the last axis (flax Dense kernels are (in, out), convs
    (k..., in, out) — the output channel is last in both). Small leaves (norms,
    biases, embeddings under ``min_size``) stay in their original dtype: they
    are a rounding error of the byte budget and the most precision-sensitive.
    """

    def leaf(w):
        if isinstance(w, QuantTensor):
            return w
        shape = tuple(getattr(w, "shape", ()))
        if not int8_eligible(shape, min_size):
            return w
        return _quantize_leaf(w, channel_axis=len(shape) - 1)

    return jax.tree.map(leaf, params, is_leaf=lambda x: isinstance(x, QuantTensor))


def dequantize_params(params, dtype=jnp.bfloat16):
    """QuantTensor leaves → real arrays (inside jit: int8 HBM reads, on-chip
    widening; XLA fuses the multiply into the consumer where profitable)."""
    return jax.tree.map(
        lambda l: l.dequantize(dtype) if isinstance(l, QuantTensor) else l,
        params,
        is_leaf=lambda x: isinstance(x, QuantTensor),
    )


def param_bytes(params) -> int:
    """Total stored bytes of a (possibly quantized) pytree."""
    return sum(
        int(l.size) * l.dtype.itemsize for l in jax.tree.leaves(params)
    )


def quantize_model(model, min_size: int = 2**16, dtype=jnp.bfloat16):
    """DiffusionModel → DiffusionModel with int8-stored weights.

    The returned model's ``apply`` dequantizes inside the traced computation, so
    every downstream consumer — ``parallelize``, pipelines, samplers — works
    unchanged; only the stored bytes (and HBM weight traffic) halve."""
    import dataclasses as _dc

    base_apply = model.apply

    def apply(params, *args, **kwargs):
        return base_apply(dequantize_params(params, dtype), *args, **kwargs)

    from .loader import residency

    residency.ensure(model.params)  # quantized from its tensors
    q_params = quantize_params(model.params, min_size)

    # Pipeline staging: stage programs receive per-stage sub-pytrees and call
    # spec closures bound to the ORIGINAL module apply — rebind them through the
    # same dequantize wrapper.
    spec = model.pipeline_spec
    if spec is not None:
        def wrap_stage(fn):
            def wrapped(params, *a, **k):
                return fn(dequantize_params(params, dtype), *a, **k)
            return wrapped

        spec = _dc.replace(
            spec,
            prepare=wrap_stage(spec.prepare),
            segments=tuple(
                _dc.replace(seg, fn=wrap_stage(seg.fn)) for seg in spec.segments
            ),
            finalize=wrap_stage(spec.finalize),
        )

    return _dc.replace(model, apply=apply, params=q_params, pipeline_spec=spec)
