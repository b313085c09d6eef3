"""The q/k prologue of a transformer block as one Pallas pass (TPU).

Between a block's qkv projection and its attention stand a per-head RMS norm
with a learned scale on q and on k and, where the model has one, the
interleaved-pair rotary: ``ops/basic.rms_normalize`` and
``ops/rope.apply_rope``. As XLA ops on the (B, S, H, D) view of what the
projection wrote, they lower (TPU compiler, described v5e, optimized HLO of one
block of each family; ISSUE 35) to float32 relayouts of q and k:

- SD3.5-medium, x (2, 4096, 1536), 64-wide heads, norm only — per tensor
  ``copy`` f32[2,4096,1536] (the slice of the qkv output, upcast and relaid) →
  ``add_rsqrt_fusion`` → a materialised ``broadcast`` f32[2,4096,24,64] of the
  rsqrt → a second ``copy`` f32[2,4096,1536] → ``multiply_convert_fusion``:
  about 350 MB moved where 50 (read 25, write 25) would do;
- Z-Image, x (1, 4128, 3840), and FLUX, x (1, 4352, 3072), 128-wide heads,
  norm + rotary — the pairs' ``reshape(..., -1, 2)`` / ``stack`` put the
  SEQUENCE on the lanes: ``copy`` f32[S,H,128]{0,2,1} → ``reshape`` to
  (2, 128) tiles → ``fusion`` → ``pad_maximum_fusion`` → ``reshape`` → ``copy``
  back: about 570–590 MB per tensor where 53–63 would do.

Here q and k are read ONCE, in the (B, S, H·D) layout the projection wrote,
and written once in the same layout, so the ``reshape`` to (B, S, H, D) on
both sides is a bitcast and the flash kernel (flash_attention.py) takes the
result as it is:

- grid over (batch, row blocks, column tiles); a step holds ``block_rows``
  rows by ``tile_lanes`` lanes (whole heads) of q and of k, and the rotary's
  tables are fetched once a row block (the tile axis is the innermost). The
  width is tiled because Mosaic unrolls a step's registers: 256 x 1024
  compiles in a quarter of the time of 256 rows by the full width and runs
  within 2–7% of it. Where q and k are column blocks of ONE array (a fused
  qkv projection, FLUX's ``linear1``) the same array is passed twice and the
  ``BlockSpec`` index along the width picks the column block: no slice is
  copied first.
- a tile is walked one 128-lane group at a time over static lane slices, as
  ``_flash_kernel`` walks heads: a 128-wide head, or two 64-wide. Per head
  the float32 mean of squares over D lanes, ``rsqrt(+ eps)``, times the
  learned scale — ``rms_normalize``'s arithmetic. The sums come off the MXU:
  ``(x·x) @ ones`` with a block-diagonal matrix of ones, which leaves each
  head's sum spread over that head's lanes (no lane reduction, no broadcast
  back); x·x of a bfloat16 x is exactly two bfloat16 terms, the MXU
  multiplies those exactly and adds in float32, so the statistics are
  float32's to its last bit or two. (Reduced over the lanes by the vector
  unit the same kernel takes 0.199 ms at SD3.5's class against 0.157: the
  lane reduction, not HBM, then sets its time.)
- the rotary: ``out = x · C + swap(x) · S`` with ``C = repeat(cos, 2)``, ``S = repeat(sin, 2) · (−1, +1)``
  (:func:`rope_tables`, (B, S, D) float32, built by XLA from the model's
  ``cos`` / ``sin``; identical across a program's blocks, so one instance
  survives CSE) and ``swap`` the exchange of each even lane with its odd
  neighbour — two lane rotations and a select on lane parity. The interleaved
  convention stays; no checkpoint is re-laid. The norm goes into the rotary
  in float32 and the result is rounded ONCE: what XLA makes of
  ``apply_rope(rms_normalize(x))`` inside one program on the chip too (it
  elides the bfloat16 round trip between the two), while the same functions
  called one after the other round twice — one unit of bfloat16 apart.

Measured on the v5e, ms of device time a call on q and k
(scripts/bench_kernels.py ``--prologue``, PR 35; the whole table and the
threshold it set stand in tuning.py beside ``QK_PROLOGUE_MIN_ELEMENTS``):
SD3.5's class 0.628 as XLA lowers the functions alone, 0.157 here; FLUX's
single block 1.545 → 0.188, its image stream 1.170 → 0.174; Z-Image's
2.211 → 0.236 — 66–78% of the HBM roofline (q and k read and written once).
``ops/attention.qk_prologue`` decides between this kernel and the jnp
functions; nothing else calls it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import over_data_axis

from .tuning import QK_PROLOGUE_TILE

_LANES = 128

# Rows and lanes a grid step (measured: tuning.py, beside the thresholds).
BLOCK_ROWS, TILE_LANES = QK_PROLOGUE_TILE


def rope_tables(cos, sin):
    """``(C, S)`` of the kernel's ``x · C + swap(x) · S`` from the per-pair
    ``cos`` / ``sin`` (B, S, D/2) of ``ops/rope.axis_rope_freqs``: each
    (B, S, D) float32, ``C = repeat(cos, 2)``, ``S = repeat(sin, 2)`` negated
    on the even lanes (``out_even = x_even·c − x_odd·s``,
    ``out_odd = x_odd·c + x_even·s``)."""
    c = jnp.repeat(cos.astype(jnp.float32), 2, axis=-1)
    s = jnp.repeat(sin.astype(jnp.float32), 2, axis=-1)
    sign = jnp.where(jnp.arange(s.shape[-1]) % 2 == 0, -1.0, 1.0)
    return c, s * sign


def qk_prologue(*refs, heads: int, head_dim: int, eps: float, rope: bool):
    """One (batch row, row block, column tile) step: q and k tiles (rows,
    heads·D) in, the same out. Named for the trace
    (``qk_prologue:tpu_custom_call``)."""
    if rope:
        q_ref, k_ref, qs_ref, ks_ref, c_ref, s_ref, oq_ref, ok_ref = refs
        c, s = c_ref[...], s_ref[...]
        even = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1) % 2 == 0
    else:
        q_ref, k_ref, qs_ref, ks_ref, oq_ref, ok_ref = refs
    # One 128-lane group (a 128-wide head, two 64-wide) at a time. Its heads'
    # sums of squares come off the MXU already spread over each head's lanes:
    # (x·x) @ a block-diagonal matrix of ones.
    row = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    ones = (row // head_dim == col // head_dim).astype(jnp.bfloat16)
    # The MXU multiplies bfloat16 exactly and adds in float32. x·x of a
    # bfloat16 x has 16 significant bits, so two bfloat16 terms hold it
    # exactly; of a float32 x three hold its 24.
    terms = 2 if q_ref.dtype == jnp.bfloat16 else 3
    for x_ref, scale_ref, o_ref in ((q_ref, qs_ref, oq_ref),
                                    (k_ref, ks_ref, ok_ref)):
        scale = scale_ref[...]  # (1, 128): the head's scale, once a head
        for g in range(heads * head_dim // _LANES):
            lanes = slice(g * _LANES, (g + 1) * _LANES)
            x = x_ref[:, lanes].astype(jnp.float32)
            rest, total = x * x, None
            for _ in range(terms):
                part = rest.astype(jnp.bfloat16)
                rest = rest - part.astype(jnp.float32)
                dot = jax.lax.dot_general(
                    part, ones, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                total = dot if total is None else total + dot
            y = (x * jax.lax.rsqrt(total * (1.0 / head_dim) + eps)) * scale
            if rope:
                # swap: lane 2i takes lane 2i+1's value and the reverse.
                swapped = jnp.where(even, pltpu.roll(y, head_dim - 1, 1),
                                    pltpu.roll(y, 1, 1))
                y = y * c + swapped * s
            o_ref[:, lanes] = y.astype(o_ref.dtype)


def supports(heads: int, head_dim: int, rope: bool) -> bool:
    """Shapes the kernel is written for: q and k are 128-lane-aligned column
    blocks (H·D a multiple of 128) of 64- or 128-wide heads — whole heads a
    128-lane group — and the rotary's lane rotation wants a whole group a
    head."""
    if (heads * head_dim) % _LANES:
        return False
    return head_dim == _LANES or (head_dim == 64 and not rope)


def heads_a_step(heads: int, head_dim: int, tile_lanes: int) -> int:
    """Heads one grid step walks: the most that divide ``heads`` and whose
    widths add up to at most ``tile_lanes`` lanes and to whole 128-lane tiles
    (64-wide heads go in pairs)."""
    for n in range(heads, 0, -1):
        if (heads % n == 0 and n * head_dim <= max(tile_lanes, 128)
                and (n * head_dim) % 128 == 0):
            return n
    return heads


@functools.partial(
    jax.jit,
    static_argnames=("heads", "eps", "block_rows", "tile_lanes", "interpret"))
def qk_prologue_call(q_src, k_src, q_scale, k_scale, tables=None, *, heads: int,
                     eps: float, block_rows: int = BLOCK_ROWS,
                     tile_lanes: int = TILE_LANES, interpret: bool = False):
    """RMS-normalise (and rotate) q and k in one pass.

    ``q_src`` is (B, S, n·H·D) with q in its FIRST H·D columns; ``k_src`` the
    same for k, or ``None``: k is then the SECOND column block of ``q_src`` (a
    fused qkv projection's output, read where it lies). ``q_scale`` /
    ``k_scale`` (D,); ``tables`` :func:`rope_tables` or None. Returns
    ``(q, k)``, each (B, S, H·D) in the source's dtype. ``interpret`` is the
    caller's decision, as in ``flash_attention``. Batch-sharded operands are
    fine under a context mesh (``flash_attention.over_data_axis``)."""
    kernel = functools.partial(
        _qk_prologue, q_scale=q_scale, k_scale=k_scale, heads=heads, eps=eps,
        block_rows=block_rows, tile_lanes=tile_lanes, interpret=interpret)
    return over_data_axis(kernel, q_src, k_src, tables)


def _qk_prologue(q_src, k_src, tables, *, q_scale, k_scale, heads: int,
                 eps: float, block_rows: int, tile_lanes: int, interpret: bool):
    head_dim = q_scale.shape[-1]
    batch, seq, _ = q_src.shape
    rows = min(block_rows, -(-seq // 16) * 16)
    group = heads_a_step(heads, head_dim, tile_lanes)
    tiles, width = heads // group, group * head_dim

    def column(col):
        # Column block ``col`` of the source (q: 0; k in a fused array: 1),
        # ``tiles`` column tiles each.
        return pl.BlockSpec((None, rows, width),
                            lambda b, i, j: (b, i, col * tiles + j))

    def lane_group(scale):
        # (D,) -> (1, 128): a 128-lane group's scales, head after head.
        return jnp.tile(scale.astype(jnp.float32), _LANES // head_dim)[None]

    scale_spec = pl.BlockSpec((1, _LANES), lambda b, i, j: (0, 0))
    operands = [q_src, q_src if k_src is None else k_src,
                lane_group(q_scale), lane_group(k_scale)]
    in_specs = [column(0), column(1 if k_src is None else 0),
                scale_spec, scale_spec]
    if tables is not None:
        # The same block for every column tile of a row block (the tile axis
        # is the innermost): fetched once a row block.
        table_spec = pl.BlockSpec((None, rows, head_dim),
                                  lambda b, i, j: (b, i, 0))
        operands += list(tables)
        in_specs += [table_spec, table_spec]
    item = q_src.dtype.itemsize
    # Double-buffered q / k in and out, the tables, and a head's float32
    # temporaries.
    vmem = (2 * 4 * rows * width * item + 2 * 2 * rows * head_dim * 4
            + 8 * rows * max(head_dim, 128) * 4)
    out = jax.ShapeDtypeStruct((batch, seq, heads * head_dim), q_src.dtype)
    return pl.pallas_call(
        functools.partial(qk_prologue, heads=group, head_dim=head_dim, eps=eps,
                          rope=tables is not None),
        name="qk_prologue",
        grid=(batch, -(-seq // rows), tiles),
        in_specs=in_specs,
        out_specs=(column(0), column(0)),
        out_shape=(out, out),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=min(max(2 * vmem, 32 << 20), 100 << 20),
        ),
        interpret=interpret,
    )(*operands)
