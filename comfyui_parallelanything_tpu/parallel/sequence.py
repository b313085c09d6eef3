"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Absent in the reference — its only split axis is batch dim0 (torch.split at
any_device_parallel.py:1224/1256; SURVEY §5.7) — but first-class here: the reference's
own flagship workloads (FLUX 1024² ⇒ 4096 image tokens, WAN-class video ⇒ tens of
thousands) make sequence length the natural second sharding axis on TPU, and the mesh
vocabulary already reserves ``seq`` for it (parallel/mesh.py).

Two standard schemes, both SPMD via ``shard_map`` over a ``seq`` mesh axis:

- **Ring attention** (blockwise attention with a k/v ring): q stays put; k/v shards
  rotate around the ring with ``lax.ppermute`` while a flash-style online softmax
  accumulates (running max / normalizer), so no device ever holds the full sequence.
  ICI-bandwidth-friendly: each step moves one k/v block to the next neighbor.
- **Ulysses** (all-to-all head scatter): ``lax.all_to_all`` re-shards tokens→heads,
  each device runs *full-sequence* attention for its head slice (hitting the fused
  single-device kernel), then all-to-all back. Needs num_heads % n_shards == 0.

Both compute attention identically to ``ops.attention`` (same f32 softmax) up to
floating-point reduction order.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from .mesh import AXIS_SEQ

Method = Literal["ring", "ulysses"]


# --------------------------------------------------------------------------------------
# Ring attention (per-shard body; runs inside shard_map)
# --------------------------------------------------------------------------------------


def _ring_attention_local(q, k, v, *, axis_name: str, n_shards: int, scale: float):
    """Local shard body: q (B, Sq, H, D) fixed; k/v (B, Sk, H, D) rotate the ring.

    Online-softmax accumulation in f32 (flash-attention recurrence): running max
    ``m``, normalizer ``l``, weighted value accumulator ``acc``.
    """
    B, Sq, H, D = q.shape
    qf = q.astype(jnp.float32)

    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def step(carry, _):
        k_blk, v_blk, m, l, acc = carry
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32)
        ) * scale  # (B, H, Sq, Sk)
        blk_max = jnp.max(logits, axis=-1)  # (B, H, Sq)
        new_m = jnp.maximum(m, blk_max)
        correction = jnp.exp(m - new_m)  # (B, H, Sq)
        p = jnp.exp(logits - new_m[..., None])  # (B, H, Sq, Sk)
        l = l * correction + jnp.sum(p, axis=-1)
        acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
        )
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, new_m, l, acc), None

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    acc0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    (_, _, _, l, acc), _ = lax.scan(
        step, (k, v, m0, l0, acc0), None, length=n_shards
    )
    out = acc / l[..., None]  # (B, H, Sq, D)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B, Sq, H, D)


def _ulysses_local(q, k, v, *, axis_name: str, scale: float):
    """Local shard body: re-shard tokens→heads, full-seq attention, shard back.

    In: (B, S/n, H, D). all_to_all(split H, concat S) → (B, S, H/n, D).
    ``attention_local`` (not ``attention``) — the dispatching wrapper would re-enter
    the sequence-parallel route inside this shard_map body.
    """
    from ..ops.attention import attention_local

    def scatter(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def gather(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    out = attention_local(scatter(q), scatter(k), scatter(v), scale=scale)
    return gather(out)


# --------------------------------------------------------------------------------------
# Public entry
# --------------------------------------------------------------------------------------


def sequence_parallel_attention(
    q,
    k,
    v,
    mesh: Mesh,
    *,
    axis: str = AXIS_SEQ,
    method: Method = "ring",
    scale: float | None = None,
):
    """Attention over (B, S, H, D) inputs with S sharded on ``mesh`` axis ``axis``.

    Inputs may be unsharded host arrays (they are constrained into the sequence
    sharding) or already sharded; output carries the same sequence sharding.
    ``method="ring"`` rotates k/v blocks over ICI; ``method="ulysses"`` does two
    all-to-alls and computes full-sequence attention per head slice.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    _validate_shapes(q, k, mesh.shape[axis], method)
    fn = _compiled_attention(mesh, axis, method, float(scale))
    sharding = NamedSharding(mesh, P(None, axis, None, None))
    q, k, v = (lax.with_sharding_constraint(t, sharding) for t in (q, k, v))
    return fn(q, k, v)


@functools.lru_cache(maxsize=64)
def _sharded_attention_fn(mesh: Mesh, axis: str, method: str, scale: float):
    """The shard_map-wrapped (un-jitted) attention program — traceable, so it can be
    inlined inside a larger jitted model forward (the sequence_parallel context)."""
    n_shards = mesh.shape[axis]
    spec = P(None, axis, None, None)  # (B, S, H, D), S sharded
    if method == "ring":
        body = functools.partial(
            _ring_attention_local, axis_name=axis, n_shards=n_shards, scale=scale
        )
    elif method == "ulysses":
        body = functools.partial(_ulysses_local, axis_name=axis, scale=scale)
    else:
        raise ValueError(f"unknown sequence-parallel method {method!r}")
    return shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )


@functools.lru_cache(maxsize=64)
def _compiled_attention(mesh: Mesh, axis: str, method: str, scale: float):
    """One jitted shard_map program per (mesh, axis, method, scale) — jit caches are
    keyed by function object, so rebuilding the closure per call would retrace and
    recompile on every sampler step."""
    return jax.jit(_sharded_attention_fn(mesh, axis, method, scale))


def _validate_shapes(q, k, n_shards: int, method: str) -> None:
    """Clear errors instead of opaque shard_map tracing failures. Both q's and k/v's
    sequence lengths must shard (cross-attention k/v carries the *text* length — e.g.
    77 CLIP tokens won't shard 4-way; pad the context to a multiple)."""
    for name, t in (("q", q), ("k/v", k)):
        if t.shape[1] % n_shards:
            raise ValueError(
                f"sequence-parallel attention: {name} sequence length {t.shape[1]} "
                f"not divisible by the seq mesh axis ({n_shards}); pad it to a "
                f"multiple"
            )
    if method == "ulysses" and q.shape[2] % n_shards:
        raise ValueError(
            f"ulysses needs num_heads ({q.shape[2]}) divisible by the "
            f"sequence-shard count ({n_shards})"
        )


def sharded_attention_inline(q, k, v, mesh: Mesh, axis: str, method: str, scale: float):
    """Sequence-parallel attention usable *inside* a traced model forward: constrains
    q/k/v to the sequence sharding and inlines the shard_map program (no nested
    dispatch). Used by ops.attention when a ``sequence_parallel`` context is active."""
    _validate_shapes(q, k, mesh.shape[axis], method)
    sharding = NamedSharding(mesh, P(None, axis, None, None))
    q, k, v = (lax.with_sharding_constraint(t, sharding) for t in (q, k, v))
    return _sharded_attention_fn(mesh, axis, method, float(scale))(q, k, v)
