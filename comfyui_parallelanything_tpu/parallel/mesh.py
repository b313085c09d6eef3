"""Mesh construction: the DEVICE_CHAIN → `jax.sharding.Mesh` bridge.

The reference's "mesh" is an implicit list of torch devices each holding a full replica
(any_device_parallel.py:1056-1128). Here the chain maps to a named device mesh and all
communication becomes XLA collectives over it (SURVEY §2f). Axis vocabulary:

- ``data``  — batch sharding (the reference's only split axis, dim0: 1222-1237)
- ``seq``   — sequence/context parallelism (ring attention / Ulysses; absent in the
  reference, first-class here)
- ``model`` — tensor parallelism (absent in the reference; the mesh abstraction must
  not preclude it, SURVEY §5.7)
- ``stage`` — pipeline stages for the batch==1 block-placement mode (1152-1198)

A chain with N devices builds a 1-D ``data`` mesh by default; callers may fold the same
devices into any 2-D ``(data, seq)`` / ``(data, model)`` layout.
"""

from __future__ import annotations

import contextlib
from collections.abc import Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DATA = "data"
AXIS_SEQ = "seq"
AXIS_MODEL = "model"
AXIS_STAGE = "stage"


def mesh_context(mesh: Mesh | None):
    """The context a program partitioned over ``mesh`` must be CALLED under
    (``jax.set_mesh``; a no-op for ``None``). A fused attention kernel is a
    Mosaic call, which the partitioner refuses to split on real chips; under a
    context mesh ``ops/pallas/flash_attention.over_data_axis`` shard_maps it
    over the data axis instead. The context mesh is part of jit's own cache
    key, so callers need no key of their own."""
    return jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()


def sharded_mesh_of(x) -> Mesh | None:
    """The mesh ``x`` is laid out on when it is a jax array spread over more
    than one device by a ``NamedSharding`` — else ``None``."""
    sharding = getattr(x, "sharding", None)
    if isinstance(sharding, NamedSharding) and sharding.mesh.size > 1:
        return sharding.mesh
    return None


def mesh_axis_names() -> tuple[str, ...]:
    """The canonical axis vocabulary, outermost first."""
    return (AXIS_DATA, AXIS_SEQ, AXIS_MODEL, AXIS_STAGE)


def build_mesh(
    devices: Sequence[jax.Device],
    axis_shape: dict[str, int] | None = None,
) -> Mesh:
    """Build a Mesh over ``devices``.

    ``axis_shape`` maps axis name → size, in the order given; sizes must multiply to
    ``len(devices)``. Default: a 1-D ``data`` mesh over all devices.
    """
    devs = list(devices)
    if not devs:
        raise ValueError("cannot build a mesh over zero devices")
    if axis_shape is None:
        axis_shape = {AXIS_DATA: len(devs)}
    sizes = tuple(axis_shape.values())
    if int(np.prod(sizes)) != len(devs):
        raise ValueError(
            f"axis sizes {axis_shape} do not multiply to device count {len(devs)}"
        )
    arr = np.array(devs, dtype=object).reshape(sizes)
    return Mesh(arr, tuple(axis_shape.keys()))


def replicated(mesh: Mesh) -> NamedSharding:
    """Sharding that replicates a value to every mesh device — the SPMD replacement for
    the reference's per-device model cloning (safe_model_clone, 586-722)."""
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, axis: str = AXIS_DATA, ndim: int | None = None) -> NamedSharding:
    """Sharding that splits dim0 over ``axis`` — the SPMD replacement for the
    reference's host-side torch.split scatter (1222-1250)."""
    del ndim  # dim0-only, like the reference; trailing dims unconstrained
    return NamedSharding(mesh, P(axis))


# Cap on transfer bytes in flight during big-pytree placement. A whole-pytree
# jax.device_put dispatches every leaf's transfer at once; on a 16 GiB chip a
# ~12 GiB model leaves no headroom for the staging the concurrent transfers
# need (a flux_16_int8 run once OOM'd while *placing* the int8 pytree).
# Draining the queue every N bytes is
# the reference's incremental key-by-key state-dict copy trick
# (any_device_parallel.py:639-665) applied to device_put.
_MAX_INFLIGHT_BYTES = 1 << 30


def streamed_tree_put(tree, sharding_for_leaf, max_inflight_bytes=_MAX_INFLIGHT_BYTES):
    """Place a pytree leaf-by-leaf with bounded in-flight transfer bytes.

    ``sharding_for_leaf`` maps each leaf to its target ``Sharding`` (or device).
    Transfers still overlap (XLA dispatch is async) but the queue is drained
    with ``block_until_ready`` whenever the un-acknowledged bytes exceed the
    cap, so placement-time device peak stays ~total + cap instead of
    total + all-concurrent staging.
    """
    leaves, treedef = jax.tree.flatten(tree)
    placed, inflight, inflight_bytes = [], [], 0
    for leaf in leaves:
        out = jax.device_put(leaf, sharding_for_leaf(leaf))
        placed.append(out)
        nbytes = getattr(out, "nbytes", 0)
        if nbytes:
            inflight.append(out)
            inflight_bytes += nbytes
        if inflight_bytes >= max_inflight_bytes:
            jax.block_until_ready(inflight)
            inflight, inflight_bytes = [], 0
    return jax.tree.unflatten(treedef, placed)


def place_params(params, mesh: Mesh) -> object:
    """Replicate a parameter pytree onto the mesh, streamed leaf-by-leaf.

    This is the entire replacement for the reference's replica build loop + incremental
    state-dict copy (1056-1128, 636-665): XLA broadcasts each buffer over ICI, there is
    no 2× host peak, and the pytree remains a single logical value.
    """
    sharding = replicated(mesh)
    return streamed_tree_put(params, lambda _: sharding)


def fsdp_spec(shape: tuple[int, ...], axis: str, n: int, min_size: int = 2**16) -> P:
    """FSDP PartitionSpec for one weight: shard the largest divisible dimension over
    ``axis``; small or indivisible weights replicate.

    Beyond-reference capability the hardware demands: a FLUX-dev-class model in bf16
    (~24 GB) cannot hold a full replica per 16 GB v5e chip, so the reference's
    replicate-everything DP (README.md:167 'full model per device') is physically
    impossible there. Sharding each weight over the data axis (ZeRO-3 / FSDP) keeps
    per-chip weight memory at 1/N; XLA inserts the all-gathers at use sites and
    overlaps them with compute.
    """
    if not shape:
        return P()
    total = 1
    for s in shape:
        total *= s
    if total < min_size:
        return P()  # not worth the all-gather choreography
    best = max(range(len(shape)), key=lambda i: (shape[i] % n == 0, shape[i]))
    if shape[best] % n:
        return P()
    spec = [None] * len(shape)
    spec[best] = axis
    return P(*spec)


def place_params_sharded(
    params, mesh: Mesh, axis: str, min_size: int = 2**16
) -> object:
    """Place a parameter pytree with per-leaf largest-divisible-axis sharding over
    ``axis`` (the shared policy behind both FSDP and GSPMD tensor parallelism —
    the two differ only in WHICH mesh axis carries the shards):

    - over the ``data`` axis (FSDP / ZeRO-3): batch computation needs whole
      weights, so XLA all-gathers them per use; per-chip weight memory is 1/N;
    - over the ``model`` axis (TP): the axis is unused by batch sharding, so XLA
      partitions the matmul contractions themselves (partial products +
      reduce-scatter/all-reduce) — Megatron-shaped execution without hand-written
      collectives (absent in the reference: "No model parallelism", README.md:212).
    """
    n = mesh.shape[axis]

    def sharding_for(leaf):
        spec = fsdp_spec(tuple(getattr(leaf, "shape", ())), axis, n, min_size)
        return NamedSharding(mesh, spec)

    return streamed_tree_put(params, sharding_for)


def place_params_fsdp(params, mesh: Mesh, axis: str = AXIS_DATA) -> object:
    """FSDP placement: ``place_params_sharded`` over the data axis."""
    return place_params_sharded(params, mesh, axis)


def sharded_shardings(shape_tree, mesh: Mesh, axis: str, min_size: int = 2**16):
    """Per-leaf ``NamedSharding`` tree for a ShapeDtypeStruct pytree, using the
    same largest-divisible-axis policy as ``place_params_sharded``."""
    n = mesh.shape[axis]
    return jax.tree.map(
        lambda sd: NamedSharding(mesh, fsdp_spec(tuple(sd.shape), axis, n, min_size)),
        shape_tree,
    )


def sharded_byte_math(
    shape_tree, mesh: Mesh, axis: str, itemsize: int = 2, min_size: int = 2**16
) -> tuple[int, int]:
    """(per_device_bytes, total_bytes) the FSDP policy would place, computed from
    abstract shapes alone — the big-model placement proof that needs zero RAM
    (used by both the driver dryrun and test_fsdp; ``itemsize=2`` = the bf16
    checkpoint layout the converters produce)."""
    shardings = sharded_shardings(shape_tree, mesh, axis, min_size)
    per_device = total = 0
    for sd, sh in zip(jax.tree.leaves(shape_tree), jax.tree.leaves(shardings)):
        per_device += int(np.prod(sh.shard_shape(tuple(sd.shape)), dtype=np.int64)) * itemsize
        total += int(np.prod(tuple(sd.shape), dtype=np.int64)) * itemsize
    return per_device, total


def materialize_params_sharded(
    shape_tree, mesh: Mesh, axis: str = AXIS_DATA, min_size: int = 2**16
):
    """Create a zero-valued parameter pytree *directly in* its FSDP sharding.

    This is the big-model creation path: a FLUX-dev-class pytree (~24 GB bf16)
    must never exist unsharded — not on the host, not on any single chip. Each
    leaf is produced by a jitted zeros program whose ``out_shardings`` is the
    FSDP spec, so every device only ever allocates its 1/N shard. Checkpoint
    loaders overwrite these buffers shard-by-shard (the reference's analogue is
    the incremental state-dict copy at any_device_parallel.py:636-665, which
    still needs a full host copy — this path needs none).
    """
    import jax.numpy as jnp

    shardings = sharded_shardings(shape_tree, mesh, axis, min_size)

    def init():
        return jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype), shape_tree)

    return jax.jit(init, out_shardings=shardings)()


def place_params_tp(params, mesh: Mesh, axis: str = AXIS_MODEL) -> object:
    """Tensor-parallel placement: ``place_params_sharded`` over the model axis."""
    return place_params_sharded(params, mesh, axis)
