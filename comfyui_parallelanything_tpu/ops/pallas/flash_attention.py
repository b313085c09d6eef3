"""Fused flash attention for TPU (Pallas).

The hot op of every model in scope: FLUX at 1024² is ~4.6k tokens of joint attention,
video models far more. The reference rides torch's bundled flash/xformers kernels and
merely toggles them off on old GPUs (any_device_parallel.py:126-164); here the fused
path is a Pallas kernel tuned for the MXU/VMEM hierarchy:

- grid over (batch·heads, query blocks, key blocks) — K/V stream through VMEM one
  ``block_k`` tile at a time, so VMEM holds O(block_q + block_k), NOT O(seq_k).
  This is what lets the same kernel cover WAN-video sequence lengths (tens of
  thousands of tokens): at 32k keys the old whole-row layout needed ~16 MB of
  VMEM per program just for K/V; streamed tiles stay ~1-2 MB at any length.
- online-softmax state (f32 running max/sum/acc) lives in VMEM scratch and is
  carried across the key-block grid dimension (the innermost, sequential one);
  the output tile is written once, on the last key block. No S×S
  materialization — HBM traffic stays O(S·D).
- bf16 in, f32 accumulate, caller dtype out.

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


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, scale: float,
    block_k: int, seq_k: int,
):
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32) * scale
    k_blk = k_ref[...].astype(jnp.float32)
    v_blk = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (block_q, block_k)
    # Mask out-of-range key columns (host pads seq_k up to a block_k multiple).
    col = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < seq_k, s, -jnp.inf)

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


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


def _flash_attention(q, k, v, *, scale: float, block_q: int, block_k: int,
                     interpret: bool):
    # Lane alignment: the MXU wants the head dim in 128-lane multiples. For
    # the 40/64-dim UNet-family heads, zero-pad D — exact, not approximate:
    # padded K columns add zero to every q·k logit, and padded V columns
    # produce zeros that are sliced away below. (Scale was already fixed from
    # the ORIGINAL head dim above.) Whether the padded FLOP tax beats chunked
    # XLA at a given shape is a tuning-table question (ops/pallas/tuning.py);
    # this function just makes any head dim runnable.
    orig_head_dim = q.shape[-1]
    lane_pad = (-orig_head_dim) % 128
    if lane_pad:
        pad_spec = ((0, 0), (0, 0), (0, 0), (0, lane_pad))
        q = jnp.pad(q, pad_spec)
        k = jnp.pad(k, pad_spec)
        v = jnp.pad(v, pad_spec)

    batch, seq_q, heads, head_dim = q.shape
    seq_k = k.shape[1]

    # (B, S, H, D) -> (B·H, S, D)
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(batch * heads, x.shape[1], head_dim)

    q3, k3, v3 = fold(q), fold(k), fold(v)
    bq = min(block_q, max(seq_q, 8))
    bk = min(block_k, max(seq_k, 8))
    q3 = _pad_to(q3, 1, bq)
    k3 = _pad_to(k3, 1, bk)
    v3 = _pad_to(v3, 1, bk)
    padded_q, padded_k = q3.shape[1], k3.shape[1]

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_k=bk, seq_k=seq_k),
        # Key blocks are the innermost (sequential) grid dim: scratch carries the
        # online-softmax state across them, and the output tile (whose index map
        # ignores j) stays resident in VMEM until its last visit.
        grid=(batch * heads, padded_q // bq, padded_k // bk),
        in_specs=[
            pl.BlockSpec((None, bq, head_dim), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, head_dim), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, head_dim), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, head_dim), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch * heads, padded_q, head_dim), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, head_dim), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q3, k3, v3)

    out = out[:, :seq_q, :]
    out = out.reshape(batch, heads, seq_q, head_dim).transpose(0, 2, 1, 3)
    if lane_pad:
        out = out[..., :orig_head_dim]
    return out
