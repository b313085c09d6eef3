"""Which attention backend a call takes, and with which blocks: one rule.

:func:`route` reads the call's shape (both sequence lengths, head dim, B·H),
the operands' item size, whether the backend is a TPU, the process's pin and
the XLA family's logits threshold, and names the backend and the fused
kernel's blocks. Three rows: a sequence length that is not a multiple of 128
(``ragged_route``), a head dim that is not (``padded_dim_route``), and the
rest (``lane_aligned_route``: a head's whole row of keys as one key block as
far as VMEM carries it).
``ops/attention.attention_local`` executes what it says and the planner
(parallel/planner.py) records the same answer; nothing else decides. The
reference gates its fused backends on the GPU generation
(disable_flash_xformers, any_device_parallel.py:126-164); here the gate is the
shape, with the v5e measurements each threshold and block size was set from
beside the constant (``scripts/bench_kernels.py``; PERF.md §6, PRs 25, 26, 33).
A new threshold or block size is one more row of :func:`route`.
"""

from __future__ import annotations

from typing import NamedTuple

# What a pinned ``pallas`` takes at a shape the rule leaves to XLA.
DEFAULT_BLOCKS = (256, 256)


# -- Head dims that are not a multiple of 128 ----------------------------------
# The kernel reads (B, S, H·D) as it is and walks a group of heads over lane
# slices, so such a dim pays no padding in HBM, only idle MXU lanes. Measured
# on the v5e, bfloat16, ms a call (scripts/bench_kernels.py, my chip runs,
# PR 25; PERF.md §6): the XLA path ``auto`` would otherwise take against this
# kernel at PADDED_DIM_BLOCKS.
#
#   (batch, tokens, heads, head dim)        B·H·S_q·S_k    xla      fused
#   sd15-b8-512   (16,  4096,  8, 40)       2^31          18.485    6.739
#   sd15-b8-512   (16,  1024,  8, 80)       2^27           1.429    0.553
#   sdxl-b1-1024  ( 2,  4096, 10, 64)       2^28.3         3.658    1.077
#   sdxl-b1-1024  ( 2,  1024, 20, 64)       2^25.3         0.301    0.333  (loses)
#   sd15-b1-512   ( 2,  4096,  8, 40)       2^28           2.385    0.897
#   sd15-b2-1024  ( 4, 16384,  8, 40)       2^33          70.817   31.057
#
# What separates the five wins from the loss is how many logits XLA would
# write to HBM and read back: from 2^27 up (B·H of 128 at 1024 tokens, of 8 at
# 4096) the fused kernel is 2.3–3.4x faster; at 2^25.3 (B·H 40 at 1024 tokens)
# its fixed cost a call is not paid back. Between the two nothing was
# measured, so the threshold stands at the smallest measured win. Below
# PADDED_DIM_MIN_KEYS nothing was measured at all: cross-attention's 77 keys
# and the UNets' 256- and 64-token levels stay on XLA.
PADDED_DIM_MIN_KEYS = 1024
PADDED_DIM_MIN_LOGITS = 2**27
# 256 queries against a head group's whole K and V, up to 4096 keys a block
# (3 MB each at SD1.5's width). 512 queries are 3% faster at SD1.5's class
# (6.506) and take 30 s to compile instead of 14; longer rows (SD1.5 at 1024²:
# 16,384 keys) stream 4096 keys a block, where 256 queries are also fastest.
PADDED_DIM_BLOCKS = (256, 4096)


def padded_dim_route(seq_q: int, seq_k: int,
                     batch_heads: int) -> tuple[int, int] | None:
    """``(block_q, block_k)`` if the fused kernel serves a head dim that is
    not a multiple of 128 at these lengths and this B·H, else ``None``: the
    call stays with the XLA family."""
    if seq_k < PADDED_DIM_MIN_KEYS:
        return None
    if batch_heads * seq_q * seq_k < PADDED_DIM_MIN_LOGITS:
        return None
    return PADDED_DIM_BLOCKS


# -- Sequence lengths that are not a multiple of 128 -----------------------------
# SD3's joint attention runs over text and image tokens together: 77 + 4096 =
# 4173 at 1024², 77 + 1024 = 1101 at 512². The kernel pads such a row to its
# blocks itself and masks the padded keys (flash_attention.py), so the question
# is only whether the padded work is paid back. Measured on the v5e, bfloat16,
# ms a call (scripts/bench_kernels.py, my chip runs, PR 26; PERF.md §6): the
# XLA path ``auto`` would otherwise take (chunked over 2^27 logits; the whole
# logits tensor in HBM reads 7.279 at the joint class) against this kernel
# with the padded row as ONE key block, by queries a block:
#
#   (batch, tokens, heads, head dim)           B·H·S_q·S_k   xla    128    256    384
#   sd35m-b1-1024.joint4173 (2, 4173, 24, 64)  2^29.6       8.443  3.733  3.294  3.085
#   sd35m-b1-512.joint1101  (2, 1101, 24, 64)  2^25.8       0.696  0.507  0.463  0.413
#   sd35m-b1-1024.self4096  (2, 4096, 24, 64)  2^29.6       8.398  2.824  2.463  (not ragged)
#
# Read at 128-wide heads by PR 34 (Z-Image-Turbo's main layers: 4096 image +
# 32 caption tokens, 30 heads; my chip run), and NOT retuned:
#
#   zimage-b1-1024.joint4128 (1, 4128, 30, 128)  2^29.0     5.228  2.473  2.181  2.048
#
# (streamed 4096 keys a block 4.740). The same row padded to 4224 by the
# caller, on the lane-aligned form, reads 2.072 at 256 queries a block and
# 1.779 at 384: this row's pad and mask cost 0.27 ms a call there, 2.2 ms of
# that cell's 122.5 ms step (PERF.md section 6, PR 34).
#
# Read past RAGGED_ONE_BLOCK by PR 39 (Wan2.2-T2V-A14B's space-time
# self-attention at 49 frames of 832 x 480 and its cross-attention on 512 text
# rows; my chip run), and NOT retuned — by (queries x keys) a block, where the
# last streamed block is masked and 20,352 is the padded row as ONE block:
#
#   wan22-480p.self20280 (1, 20280, 40, 128)   2^34.0  xla 219.9 (chunked)
#       256x4096 63.011 (as routed)  384x4096 60.816  512x4096 60.849
#       256x2048 66.932  256x8192 72.158  256x20352 47.837 (floor 42.76)
#   wan22-480p.cross512  (1, 20280 x 512 keys, 40, 128)  2^28.6
#       xla 8.204 (chunked, as routed: 512 keys < PADDED_DIM_MIN_KEYS)
#       xla whole 6.723  256x512 3.563  512x512 3.131  1024x512 2.962
#
# The streamed row is 3.5x faster than XLA and a third slower than one block
# of 5.2 MB of K a head; the cross class would be 2.8x faster on the kernel
# (PERF.md section 7, "Left by PR 39").
#
# Both ragged classes win, the short one too (1.7x), where sdxl's 1024-token
# class at 2^25.3 logits lost by 10%: XLA pays for a ragged length as well.
# Below the short class nothing was measured, so the threshold stands there,
# at the smallest measured win (and, as above, at PADDED_DIM_MIN_KEYS keys). Streaming the joint class as two 4096-key
# blocks (the second nearly all mask) takes 7.119: a row of up to
# RAGGED_ONE_BLOCK keys is one key block, padded to the next 128-multiple
# (4224: 1.2% more keys, walked in three 1408-key softmax tiles); a longer one
# streams PADDED_DIM_BLOCKS' 4096 keys a block and masks the last. 384 queries
# a block divide both padded rows (4224 = 11 x 384, 1152 = 3 x 384), so no
# padded query rows are computed; where they do not divide the row, 256.
RAGGED_MIN_LOGITS = 2 * 24 * 1101 * 1101  # 2^25.8, the 512² joint class
RAGGED_ONE_BLOCK = 4352


def is_ragged(seq_q: int, seq_k: int) -> bool:
    return seq_q % 128 != 0 or seq_k % 128 != 0


def ragged_route(seq_q: int, seq_k: int,
                 batch_heads: int) -> tuple[int, int] | None:
    """``(block_q, block_k)`` if the fused kernel serves these lengths, of
    which one is not a multiple of 128, padded and masked; else ``None``: the
    call stays with the XLA family. Read from the call's shape alone, whatever
    the head dim."""
    if seq_k < PADDED_DIM_MIN_KEYS:
        return None
    if batch_heads * seq_q * seq_k < RAGGED_MIN_LOGITS:
        return None
    rows_q, row_k = (-(-n // 128) * 128 for n in (seq_q, seq_k))
    block_k = row_k if row_k <= RAGGED_ONE_BLOCK else PADDED_DIM_BLOCKS[1]
    return (384 if rows_q % 384 == 0 else 256), block_k


# -- Head dims that are a multiple of 128 ----------------------------------------
# One head is a grid step's whole lane group (FLUX / WAN's 128-wide heads, the
# VAE decoder's one 512-wide head), so the bytes of a row of keys for one head
# — keys x head dim x item size, what K takes in VMEM and V again — are what
# the call shows of its cost there. Measured on the v5e, bfloat16, ms a call
# (scripts/bench_kernels.py, my chip run, PR 33; PERF.md §6): the XLA path
# ``auto`` would otherwise take, this kernel at the 256 x 256 blocks the row
# had until then, streamed in key blocks of 2048 / 4096 / 8192 keys, and with
# the row as ONE key block — each at 256 queries a block; last, the row as one
# block again at 512 queries a block (other forms as noted):
#
#   (batch, queries, heads, head dim) keys  K a head   xla    256²   2048   4096   8192    one   512 queries
#   flux-schnell-b1-1024 (1,  4352, 24, 128)  1.1 MB   5.175   8.174    —      —      —    1.620   1.720 (pads 4352 to 4608)
#   flux-dev-1024        (1,  4608, 24, 128)  1.2 MB   4.910   9.144  2.492*   —      —    1.629   1.576
#   flux-dev-1024, b 4   (4,  4608, 24, 128)  1.2 MB  21.732  36.502    —      —      —    6.196   6.009
#   flux-schnell-b1-512  (1,  1280, 24, 128)  0.3 MB   0.326   0.809    —      —      —    0.307   0.355 (640 queries; 128: 0.301)
#   wan cross, 512 keys  (1, 16384, 12, 128)  0.1 MB   1.085   2.045    —      —      —    0.617   0.520
#   vae-b8-512           (8,  4096,  1, 512)  4 MB     2.050   3.008  1.750    —      —    1.609   1.593 (1024: 1.588)
#   wan-480p-16f         (1, 16384, 12, 128)  4 MB    27.747  56.793 12.113 11.136 10.696  8.863   8.798 (streamed 10.258)
#   wan-long-32k         (1, 32768, 12, 128)  8 MB   119.776 226.464    —   43.946 42.094 34.572     —   (streamed 40.326)
#   vae-b1-1024          (1, 16384,  1, 512)  16 MB    4.053   5.677  3.238  3.160  3.137  2.992     —   (streamed 3.075; 1024: 3.062)
#   zimage-b1-1024.refine (1, 4096, 30, 128)  1.0 MB   5.315   9.070    —      —      —    1.691   1.626 (PR 34: read, not retuned)
#   (* two blocks of 2304 keys; wan-long-32k in two blocks of 16,384: 41.863)
#
# The softmax tiles inside the one block (flash_attention.key_split; the same
# script with --chunk-k): FLUX's 4352 keys in 2 x 2176 (exact, as shipped)
# 1.620, in 3 x 1536 (padded to 4608 and masked: the split before PR 33)
# 1.634, in one tile 1.711, in 4 x 1152 (padded) 1.751; FLUX-dev's 4608 in
# 3 x 1536 (exact) 1.629, in 4 x 1152 (exact) 1.828.
#
# At 256 x 256 the kernel loses to XLA at every class; with the row as one key
# block it wins at every class, by 1.06x (1280 keys) to 3.5x: the online
# softmax's rescale and the accumulator's read-modify-write are paid once a
# key block, and with ONE block the state never leaves the loop's values (PR
# 25's finding at the UNets' classes). Streaming costs 5–27% over one block
# however long the blocks (wan-long-32k 41.9–43.9 in blocks of 4096 to 16,384
# keys against 34.6), so a row is one key block as far as VMEM carries it: up
# to LANE_ALIGNED_ONE_BLOCK_BYTES for one head's K, the largest measured that
# the kernel's VMEM reckoning (44 MB there, doubled for its limit) keeps
# under its 100 MB cap. The VAE's 16 MB row reads 2.992 as one block but
# reckons 78 MB: not taken, it streams PADDED_DIM_BLOCKS' 4096 keys a block
# (3.160: still 1.8x the 256 x 256 blocks). 512 queries a block are 1–3%
# faster on a one-block row they divide, 6% slower where they pad it (4352 =
# 8.5 x 512), and take twice as long to compile (20 s against 6 for one
# wan-long-32k call, per call site): 256, as PADDED_DIM_BLOCKS. What 512 would
# give a streamed row (3–8%) and a short row of keys under many queries (16%)
# is left: no cell runs either.
LANE_ALIGNED_ONE_BLOCK_BYTES = 8 << 20


def lane_aligned_route(seq_k: int, head_dim: int,
                       itemsize: int) -> tuple[int, int]:
    """``(block_q, block_k)`` of the fused kernel at a head dim that is a
    multiple of 128: the row of keys as ONE key block where one head's K is
    at most LANE_ALIGNED_ONE_BLOCK_BYTES, else streamed; read from the bytes
    the call shows, whatever model sent it."""
    block_q, streamed_k = PADDED_DIM_BLOCKS
    one_block = seq_k * head_dim * itemsize <= LANE_ALIGNED_ONE_BLOCK_BYTES
    return block_q, seq_k if one_block else streamed_k


# -- The q/k prologue (per-head RMS norm, and the rotary) -------------------------
# ops/pallas/qk_prologue.py against the jnp functions it stands in for
# (ops/basic.rms_normalize, ops/rope.apply_rope) as XLA lowers them alone.
# Measured on the v5e, bfloat16, ms of DEVICE time a call on q AND k, read
# from the profiler's trace: a call is shorter than its 0.45 ms dispatch, and
# looped inside one program it is charged the loop's carried copies (0.29 ms
# read that way where 0.20 is the kernel's). scripts/bench_kernels.py
# --prologue, my chip run, PR 35; PERF.md §6. "floor" is q and k read and
# written once at 819 GB/s; the kernel by rows x lanes a grid step; q and k
# are column blocks of one (B, S, 3·H·D) array (FLUX's single blocks: of
# linear1's 7·H·D) except Z-Image's, arrays of their own:
#
#   (batch, rows, heads, head dim)  rotary  floor   xla    256x4096 256x1024 512x1024 512x512 1024x512
#   sd35m-b1-1024   (2, 4096, 24,  64)  no  0.123  0.628   0.156   0.157   0.157   0.157   0.158
#   flux single     (1, 4352, 24, 128) yes  0.131  1.545   0.184   0.188   0.190   0.204   0.207
#   flux img stream (1, 4096, 24, 128) yes  0.123  1.170   0.174   0.174   0.173   0.183   0.176
#   zimage joint    (1, 4128, 30, 128) yes  0.155  2.211   0.220   0.236   0.236   0.256   0.265
#   flux 512² img   (1, 1024, 24, 128) yes  0.031  0.187   0.049   0.047   0.049   0.047   0.049
#   sd35m-b1-512    (2, 1024, 24,  64)  no  0.031  0.067   0.041   0.041   0.042   0.042   0.042
#   flux txt stream (1,  256, 24, 128) yes  0.008  0.038   0.016   0.016   0.015   0.015   0.015
#   sd35m context   (2,   77, 24,  64)  no  0.002  0.019   0.009   0.009   0.009   0.009   0.009
#   zimage caption  (1,   32, 30, 128) yes  0.001  0.024   0.004   0.005   0.005   0.007   0.007
#
# The kernel holds 66–79% of the HBM roofline at the cells' four classes and
# wins 4.0–9.4x there; it wins at every shorter class too, by less and less
# in ms: 0.14 and 0.026 a call at the 512² streams, 0.022 at FLUX's 256 text
# tokens, 0.010 and 0.019 at the 77- and 32-token text streams. Every call
# site is one more Mosaic kernel to compile (0.4–0.8 s each on the described
# chip at 256 x 1024; the 77-token stream has 24 sites a program), so the
# threshold stands at the smallest class measured whose win is above 0.02 ms a
# call: FLUX's 256 text tokens. 256 rows x 1024 lanes a step is within 2% of
# the fastest at SD3.5's and both FLUX classes and 7% behind 256 x 4096 at
# Z-Image's (0.16 ms a step), and compiles in a quarter of 256 x 4096's time
# (0.6 s a call site against 2.3–4.2).
QK_PROLOGUE_MIN_ELEMENTS = 256 * 24 * 128
QK_PROLOGUE_TILE = (256, 1024)  # rows, lanes a grid step


def qk_prologue_route(rows: int, heads: int, head_dim: int, rope: bool, *,
                      on_tpu: bool, pinned: str = "auto") -> bool:
    """Whether the one-pass kernel serves a q/k prologue of ``rows`` (B·S)
    rows of ``heads`` heads: the pin as :func:`route` reads it (``pallas``
    wherever the kernel is written for the shape, an XLA pin never), else on
    a TPU from QK_PROLOGUE_MIN_ELEMENTS elements a tensor up."""
    from .qk_prologue import supports

    if pinned in ("xla", "xla_chunked") or not supports(heads, head_dim, rope):
        return False
    if pinned == "pallas":
        return True
    return on_tpu and rows * heads * head_dim >= QK_PROLOGUE_MIN_ELEMENTS


class Route(NamedTuple):
    backend: str            # "xla" | "xla_chunked" | "pallas"
    block_q: int | None     # the fused kernel's blocks; None in the XLA family
    block_k: int | None
    rule: str               # the row of route() that decided


def route(seq_q: int, seq_k: int, head_dim: int, batch_heads: int, *,
          on_tpu: bool, pinned: str = "auto", chunk_threshold: int,
          itemsize: int = 2) -> Route:
    """The backend and blocks of one attention call. A pin other than
    ``auto`` is served as pinned; off a TPU the XLA family; on one the fused
    kernel where the call's row of the shape rule has blocks for it. Inside
    the XLA family the logits are written out whole up to ``chunk_threshold``
    elements (B·H·S_q·S_k) and in query chunks above. ``itemsize`` is the
    operands' (2: bfloat16, what the models compute in on the chip)."""
    # The call's row of the shape rule, and the blocks the fused kernel takes
    # there (None: the row leaves the call to the XLA family).
    if is_ragged(seq_q, seq_k):
        row, blocks = "ragged", ragged_route(seq_q, seq_k, batch_heads)
    elif head_dim % 128 != 0:
        row, blocks = "padded-dim", padded_dim_route(seq_q, seq_k, batch_heads)
    else:
        row, blocks = "lane-aligned", lane_aligned_route(seq_k, head_dim,
                                                         itemsize)

    def xla_family(rule: str) -> Route:
        chunked = batch_heads * seq_q * seq_k > chunk_threshold
        return Route("xla_chunked" if chunked else "xla", None, None, rule)

    if pinned == "pallas":
        return Route("pallas", *(blocks or DEFAULT_BLOCKS), "pinned")
    if pinned == "xla_chunked":
        return Route("xla_chunked", None, None, "pinned")
    if pinned == "xla":
        return xla_family("pinned")
    if pinned != "auto":
        raise ValueError(f"unknown attention backend {pinned!r}")
    if not on_tpu:
        return xla_family("off-tpu")
    if blocks is None:
        return xla_family(row)
    return Route("pallas", *blocks, row)
