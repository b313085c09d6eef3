"""Fused flash attention for TPU (Pallas).

The hot op of every model in scope: an SD-class UNet's self-attention is 4096
tokens at 512² (16,384 at 1024²), FLUX at 1024² is ~4.6k tokens of joint
attention, video models far more. The reference rides torch's bundled
flash/xformers kernels and merely toggles them off on old GPUs
(any_device_parallel.py:126-164); here the fused path is a Pallas kernel tuned
for the MXU/VMEM hierarchy:

- operands are read as the models hand them over, (B, S, H·D): no transpose,
  no lane padding in HBM. One grid step holds a lane-aligned GROUP of heads —
  the fewest whose widths add up to a multiple of 128 lanes (one 128-wide
  head, two 64-wide), else all of them (eight 40-wide: the block is then the
  array's full width) — and walks the group's heads over static lane slices.
- grid over (batch, head groups, query blocks, key blocks) — K/V stream
  through VMEM one ``block_k`` tile at a time, so VMEM holds
  O(block_q + block_k), NOT O(seq_k), and the same kernel covers WAN-video
  lengths. A UNet's 4096 keys are ONE block (a head group's whole K and V,
  3 MB each at SD1.5's width), and so is every row that ``tuning.route``
  finds short enough in bytes — FLUX's 4352 keys under a 128-wide head, 1.1
  MB each (PR 33): the key axis of the grid is then a single step and the
  softmax state never leaves the loop's values.
- inside a key block the softmax walks equal tiles of about ``_CHUNK_K`` keys
  (:func:`key_split`): the live logits tile is (block_q, tile) however long
  the block, and what is paid per tile and not per logit (the state's
  rescale, the accumulator's read-modify-write) is amortised over 2048 keys,
  not 256 (measured, v5e, 256 queries a block at SD1.5's (16, 4096, 8, 40):
  41.5 ms with 256-key blocks, 6.7 ms with the whole row; PERF.md §6, PR 25).
  Tiles that divide the block exactly are preferred, so a row that is a
  multiple of 128 keys is neither padded in HBM nor masked.
- online-softmax state (f32 running max/sum/acc) is carried across key blocks
  in VMEM scratch (the key axis is the innermost, sequential one); the output
  tile is written once, on the last key block. No S×S materialization — HBM
  traffic stays O(S·D).
- operands go to the MXU in their own dtype (bf16 on the chip: one pass) with
  the softmax scale folded into q; logits, max, sum and accumulator are f32;
  the probabilities are cast to the operand dtype for the second dot only.

Non-TPU backends run the same kernel in interpreter mode (tests) or should prefer the
plain XLA path (ops/attention.py handles the dispatch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

# m/l scratch rows are stored broadcast across a full 128-wide lane dimension —
# (block_q, 1) arrays lower poorly on the TPU vector unit.
_LANES = 128

# Keys per softmax tile inside a key block. Measured on the v5e at
# (16, 4096, 8, 40), ms a call (my chip runs, PR 25; PERF.md §6): tiles of 256
# keys 41.5, of 1024 keys 13.4, of 2048 keys 6.7; one 4096-key tile is no
# faster and doubles the live logits.
_CHUNK_K = 2048
# How far over _CHUNK_K a tile may go so that equal tiles divide a key block
# exactly (:func:`key_split`): a sixteenth, which takes in FLUX.1's 256 text +
# 4096 image keys, 4352 = 2 x 2176. Measurement beside the lane-aligned table
# in tuning.py; nothing longer was measured.
_CHUNK_STRETCH = 16


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, *state, scale: float, heads: int,
    head_dim: int, block_k: int, chunk_k: int, seq_k: int, mask_keys: bool,
):
    """One (batch row, head group, query block, key block) step. ``state`` is
    the (acc, m, l) scratch that carries the online softmax across key blocks,
    or nothing when the key axis is a single block."""
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    block_q = q_ref.shape[0]
    if state:
        acc_ref, m_ref, l_ref = state

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)

    for h in range(heads):
        lanes = slice(h * head_dim, (h + 1) * head_dim)
        # The scale is folded into q once a key block: one multiply a q
        # element, not one a logit. Operands stay in their own dtype.
        q = (q_ref[:, lanes].astype(jnp.float32) * scale).astype(q_ref.dtype)
        if state:
            m, l, acc = m_ref[h, :, :1], l_ref[h, :, :1], acc_ref[h]
        else:
            m = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
            l = jnp.zeros((block_q, 1), jnp.float32)
            acc = jnp.zeros((block_q, head_dim), jnp.float32)
        # Unrolled: the next tile's first dot overlaps this tile's softmax.
        for start in range(0, block_k, chunk_k):
            keys = slice(start, start + chunk_k)
            v_blk = v_ref[keys, lanes]
            s = jax.lax.dot_general(
                q, k_ref[keys, lanes], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (block_q, chunk_k)
            if mask_keys:
                # Out-of-range key columns (host pads seq_k up to a block).
                col = (j * block_k + start
                       + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
                s = jnp.where(col < seq_k, s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + p.sum(axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m = m_new
        if not state:
            o_ref[:, lanes] = (acc / l).astype(o_ref.dtype)
            continue
        acc_ref[h] = acc
        m_ref[h] = jnp.broadcast_to(m, m_ref.shape[1:])
        l_ref[h] = jnp.broadcast_to(l, l_ref.shape[1:])

        @pl.when(j == nk - 1)
        def _finish():
            o_ref[:, lanes] = (acc / l).astype(o_ref.dtype)


def over_data_axis(fn, q, k, v):
    """Run ``fn(q, k, v) -> out`` (all (B, S, H, D)) so that it survives a
    ``jit`` whose operands are sharded over the ``data`` mesh axis.

    A Mosaic kernel in a partitioned program is refused outright ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call in a
    shard_map") — the orchestrator's data-parallel step and a VAE decode of a
    sharded latent both hit it on real chips. Attention is independent per
    batch row, so under a context mesh (``jax.set_mesh`` — the callers that
    shard a batch set it around their jitted call) with a ``data`` axis the
    kernel runs inside a ``shard_map`` over that axis, each device on its own
    rows. No context mesh, or a batch the axis does not divide: ``fn`` as is.
    """
    from ...parallel.mesh import AXIS_DATA

    mesh = jax.sharding.get_abstract_mesh()
    n = dict(mesh.shape).get(AXIS_DATA, 1) if not mesh.empty else 1
    if n == 1 or AXIS_DATA in mesh.manual_axes or q.shape[0] % n:
        return fn(q, k, v)
    rows = P(AXIS_DATA)
    return jax.shard_map(
        fn, in_specs=(rows, rows, rows), out_specs=rows,
        axis_names=frozenset({AXIS_DATA}), check_vma=False,
    )(q, k, v)


def _pad_to(x, axis: int, multiple: int):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_q", "block_k", "interpret")
)
def flash_attention(
    q,
    k,
    v,
    scale: float | None = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool | None = None,
):
    """Flash attention on (B, S, H, D) q/k/v; returns (B, S_q, H, D).

    ``interpret`` is the caller's decision and never a silent one: on a TPU
    backend the kernel is always compiled (``interpret=True`` raises there);
    off a TPU the caller must say which it wants — ``True`` to run the Pallas
    interpreter (CPU tests), ``False`` to compile for a described TPU
    topology — and leaving it ``None`` raises.

    Batch-sharded operands are fine under a context mesh
    (:func:`over_data_axis`).
    """
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        if not on_tpu:
            raise ValueError(
                "flash_attention: no TPU backend — pass interpret=True (Pallas "
                "interpreter) or interpret=False (compile for a described "
                "topology) explicitly"
            )
        interpret = False
    elif interpret and on_tpu:
        raise ValueError("flash_attention never interprets on a TPU backend")
    kernel = functools.partial(_flash_attention, scale=scale, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    return over_data_axis(kernel, q, k, v)


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def key_split(seq_k: int, block_k: int) -> tuple[int, int]:
    """``(keys a key block, keys a softmax tile)`` of a call with ``seq_k``
    keys asked to take ``block_k`` a block. A block is walked in equal tiles,
    each a multiple of 128 keys where there are several: as many as
    ``_CHUNK_K`` keys a tile need if those divide the block exactly (4096 =
    2 x 2048, a ragged row's 4224 = 3 x 1408, FLUX-dev's 4608 = 3 x 1536),
    else one fewer if THOSE do and are at most a sixteenth longer (4352 =
    2 x 2176: nothing padded, nothing masked), else that many rounded up to
    128, the block growing with them (the caller pads K and V and the kernel
    masks). ``ceil(seq_k / keys a block)`` is the key axis of the grid: 1 where
    the row is one key block."""
    bk = min(block_k, _round_up(seq_k, 8))
    tiles = -(-bk // _CHUNK_K)
    if tiles == 1:
        return bk, bk
    for n in (tiles, tiles - 1):
        if (bk % (n * _LANES) == 0
                and bk // n <= _CHUNK_K + _CHUNK_K // _CHUNK_STRETCH):
            return bk, bk // n
    ck = _round_up(-(-bk // tiles), _LANES)
    return tiles * ck, ck


def _head_group(heads: int, head_dim: int) -> int:
    """Heads one grid step holds: the fewest whose widths fill whole 128-lane
    tiles (a block narrower than the array must be lane-aligned), else all —
    a block as wide as the array may have any width. Any head dim runs, with
    no padding in HBM: 40-wide heads ride eight to a 320-lane block."""
    for n in range(1, heads):
        if heads % n == 0 and (n * head_dim) % _LANES == 0:
            return n
    return heads


def _flash_attention(q, k, v, *, scale: float, block_q: int, block_k: int,
                     interpret: bool):
    batch, seq_q, heads, head_dim = q.shape
    seq_k = k.shape[1]
    group = _head_group(heads, head_dim)
    width = group * head_dim

    bq = min(block_q, _round_up(seq_q, 8))
    bk, ck = key_split(seq_k, block_k)
    # (B, S, H, D) -> (B, S, H·D): the layout the projections wrote, so XLA
    # folds this reshape into theirs and nothing is copied.
    q3 = _pad_to(q.reshape(batch, seq_q, heads * head_dim), 1, bq)
    k3 = _pad_to(k.reshape(batch, seq_k, heads * head_dim), 1, bk)
    v3 = _pad_to(v.reshape(batch, seq_k, heads * head_dim), 1, bk)
    padded_q, padded_k = q3.shape[1], k3.shape[1]
    nk = padded_k // bk

    lanes = _round_up(width, _LANES)
    item = q.dtype.itemsize
    state = [
        pltpu.VMEM((group, bq, head_dim), jnp.float32),
        pltpu.VMEM((group, bq, _LANES), jnp.float32),
        pltpu.VMEM((group, bq, _LANES), jnp.float32),
    ] if nk > 1 else []
    # Double-buffered q / out / k / v blocks, the carried state, and per live
    # logits tile its float32 logits and exp and the cast for the second dot;
    # two tiles are live (the unrolled loop overlaps them).
    state_bytes = 4 * group * bq * (_round_up(head_dim, _LANES) + 2 * _LANES)
    vmem = (2 * (2 * bq + 2 * bk) * lanes * item
            + (state_bytes if state else 0) + 2 * bq * ck * (8 + item))

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, heads=group, head_dim=head_dim,
            block_k=bk, chunk_k=ck, seq_k=seq_k, mask_keys=padded_k != seq_k,
        ),
        # Key blocks are the innermost (sequential) grid dim: scratch carries the
        # online-softmax state across them, and the output tile (whose index map
        # ignores j) stays resident in VMEM until its last visit.
        grid=(batch, heads // group, padded_q // bq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, width), lambda b, g, i, j: (b, i, g)),
            pl.BlockSpec((None, bk, width), lambda b, g, i, j: (b, j, g)),
            pl.BlockSpec((None, bk, width), lambda b, g, i, j: (b, j, g)),
        ],
        out_specs=pl.BlockSpec((None, bq, width), lambda b, g, i, j: (b, i, g)),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q.dtype),
        scratch_shapes=state,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            # The default scoped limit (16 MB) is less than a UNet block's
            # tiles; twice the reckoned need leaves the compiler its slack.
            vmem_limit_bytes=min(max(2 * vmem, 32 << 20), 100 << 20),
        ),
        interpret=interpret,
    )(q3, k3, v3)

    return out[:, :seq_q].reshape(batch, seq_q, heads, head_dim)
