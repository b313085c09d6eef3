"""Flash-kernel routing and block sizes.

Two sources, in this order:

- **The shape rule** for head dims that are not a multiple of 128 (the UNets'
  40 / 64 / 80-wide heads): thresholds on the key length and on B·H·S_q·S_k,
  in code, with the v5e measurements they were set from beside them
  (:func:`padded_dim_route`). No file, no environment variable.
- **A measured table** for everything else: ``scripts/bench_kernels.py``
  sweeps ``block_q``/``block_k`` at the shapes that matter (FLUX 4.6k joint
  attention, WAN 16k/32k video) and — with ``--apply`` — writes the winners to
  the JSON file ``$PA_TUNING_PATH`` names. The ``auto`` attention backend
  (ops/attention.py) then picks the measured-best blocks for the nearest
  benchmarked sequence length and falls back to XLA for sequence ranges where
  the measurement says the fused kernel LOSES (the reference's
  capability-gated backend disable, inverted: data-gated instead of
  SM-version-gated, any_device_parallel.py:126-164). A same-dim entry of such
  a table overrides the shape rule for its dim class.

Before either, a sequence length that is not a multiple of 128 (SD3's joint
text + image tokens) is decided by :func:`ragged_route`, a rule of the same
kind on the call's lengths and B·H, whatever the head dim.

There is no default file: without ``$PA_TUNING_PATH`` everything runs on the
rules and the defaults below, so a fresh clone and a checkout an earlier run
wrote into behave the same.
"""

from __future__ import annotations

import functools
import json
import os

_PATH = os.environ.get("PA_TUNING_PATH")

_DEFAULT = {
    "source": "default",       # "measured" once bench_kernels --apply ran
    "device_kind": None,
    "block_q": 256,
    "block_k": 256,
    # [{"seq": int, "head_dim": int|None, "block_q": int, "block_k": int,
    #   "pallas_ms": float, "xla_ms": float|None}, ...]
    # head_dim tags a measurement to its dim class (non-128-aligned dims run
    # the kernel zero-padded and must win their own measurements).
    "entries": [],
}


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
                     batch_heads: int | None = None) -> tuple[int, int] | None:
    """``(block_q, block_k)`` if the fused kernel serves a head dim that is
    not a multiple of 128 at these lengths and this B·H (``None``: not
    known, not tested), else ``None``: the call stays with the XLA family."""
    if seq_k < PADDED_DIM_MIN_KEYS:
        return None
    if (batch_heads is not None
            and batch_heads * seq_q * seq_k < PADDED_DIM_MIN_LOGITS):
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
                 batch_heads: int | None = None) -> tuple[int, int] | None:
    """``(block_q, block_k)`` if the fused kernel serves these lengths, of
    which one is not a multiple of 128, padded and masked; else ``None``: the
    call stays with the XLA family. Read from the call's shape alone, whatever
    the head dim."""
    if seq_k < PADDED_DIM_MIN_KEYS:
        return None
    if (batch_heads is not None
            and batch_heads * seq_q * seq_k < RAGGED_MIN_LOGITS):
        return None
    rows_q, row_k = (-(-n // 128) * 128 for n in (seq_q, seq_k))
    block_k = row_k if row_k <= RAGGED_ONE_BLOCK else PADDED_DIM_BLOCKS[1]
    return (384 if rows_q % 384 == 0 else 256), block_k


@functools.lru_cache(maxsize=1)
def kernel_tuning() -> dict:
    """The active tuning table (defaults merged under ``$PA_TUNING_PATH``).

    A measured table is generation-specific: block winners and win/lose ranges
    from a v5e do not transfer to a v6e. When the file records a
    ``device_kind`` that doesn't match the current first device, the defaults
    apply rather than foreign measurements. A table that was asked for and
    cannot be read is an error, not the defaults."""
    if not _PATH:
        return dict(_DEFAULT)
    with open(_PATH) as f:
        data = json.load(f)
    if not isinstance(data, dict):
        raise ValueError(f"{_PATH} must hold a JSON object")
    measured_kind = data.get("device_kind")
    if measured_kind:
        import jax

        if jax.devices()[0].device_kind != measured_kind:
            return dict(_DEFAULT)
    return {**_DEFAULT, **data}


def _nearest(entries: list, seq: int):
    return min(entries, key=lambda e: abs(int(e.get("seq", 0)) - seq))


def best_blocks(seq: int, head_dim: int | None = None,
                seq_k: int | None = None,
                batch_heads: int | None = None) -> tuple[int, int]:
    """(block_q, block_k) for a sequence length: the measured winner at the
    nearest benchmarked length (preferring measurements of the same head-dim
    class), else the shape rule's blocks for a head dim that is not a multiple
    of 128, else the defaults."""
    t = kernel_tuning()
    seq_k = seq if seq_k is None else seq_k
    entries = [e for e in t["entries"] if e.get("block_q") and e.get("block_k")]
    if not entries and is_ragged(seq, seq_k):
        # No measured table: a ragged length the rule routes takes the rule's
        # blocks (the padded row as one key block), whatever the head dim.
        ragged = ragged_route(seq, seq_k, batch_heads)
        if ragged is not None:
            return ragged
    if head_dim is not None:
        same_dim = [e for e in entries if e.get("head_dim") == head_dim]
        if same_dim:
            entries = same_dim
        elif head_dim % 128 != 0:
            # Never blocks tuned for another dim class: the rule's, or — a
            # forced (non-auto) pallas backend at a shape the rule leaves to
            # XLA — the defaults.
            return (padded_dim_route(seq, seq_k, batch_heads)
                    or (int(t["block_q"]), int(t["block_k"])))
        else:
            # Aligned dims must not inherit blocks tuned under the padded-FLOP
            # regime of a different dim class (mirrors pallas_wins).
            entries = [
                e for e in entries
                if e.get("head_dim") is None or e["head_dim"] % 128 == 0
            ]
    if not entries:
        return int(t["block_q"]), int(t["block_k"])
    e = _nearest(entries, seq)
    return int(e["block_q"]), int(e["block_k"])


def _fused_ms(e: dict):
    """Best measured fused-kernel time for an entry: min over the in-repo
    kernel (``pallas_ms``) and jax's upstream one (``pallas_jax_ms``)."""
    times = [e.get("pallas_ms"), e.get("pallas_jax_ms")]
    times = [t for t in times if t is not None]
    return min(times) if times else None


def fused_backend(seq: int, head_dim: int | None = None) -> str:
    """Which fused implementation serves this shape class: "pallas_jax" when
    jax's upstream kernel measured faster at the nearest benchmarked length
    (and the dim is lane-aligned — upstream has no padding logic), else the
    in-repo "pallas"."""
    if head_dim is not None and head_dim % 128 != 0:
        return "pallas"
    t = kernel_tuning()
    entries = [e for e in t["entries"] if _fused_ms(e) is not None]
    if head_dim is not None:
        same_dim = [e for e in entries if e.get("head_dim") == head_dim]
        entries = same_dim or [
            e for e in entries
            if e.get("head_dim") is None or e.get("head_dim", 0) % 128 == 0
        ]
    if not entries:
        return "pallas"
    e = _nearest(entries, seq)
    pj, pm = e.get("pallas_jax_ms"), e.get("pallas_ms")
    if pj is not None and (pm is None or pj < pm):
        return "pallas_jax"
    return "pallas"


def pallas_wins(seq: int, head_dim: int | None = None,
                seq_k: int | None = None,
                batch_heads: int | None = None) -> bool:
    """Whether the fused kernel serves this shape. Lane-aligned head dims:
    whether it beat XLA at the nearest measured length, and with no
    measurement True — the default guess (XLA's S×S logits materialization
    loses at the long lengths this path serves). Head dims that are not a
    multiple of 128 (40/64/80 UNet heads): the shape rule
    (:func:`padded_dim_route`, on the key length ``seq_k`` — ``seq`` if not
    given — and B·H), unless the table holds entries measured at that very
    ``head_dim`` (bench_kernels records it), which then gate their own dim
    class. An entry whose XLA measurement FAILED (``xla_ms`` None — S×S logits
    OOM) counts as a pallas win: that is a length where the fused kernel is
    mandatory, not absent data."""
    t = kernel_tuning()
    entries = [e for e in t["entries"] if _fused_ms(e) is not None]
    padded_dim = head_dim is not None and head_dim % 128 != 0
    by_rule = padded_dim and padded_dim_route(
        seq, seq if seq_k is None else seq_k, batch_heads) is not None
    if head_dim is not None:
        same_dim = [e for e in entries if e.get("head_dim") == head_dim]
        if same_dim:
            entries = same_dim
        elif padded_dim:
            return by_rule
        else:
            # Aligned dim: generic (dim-less or aligned-dim) entries apply.
            entries = [
                e for e in entries
                if e.get("head_dim") is None or e["head_dim"] % 128 == 0
            ]
    if not entries:
        return True
    e = _nearest(entries, seq)
    if padded_dim and not (seq / 2 <= int(e.get("seq", 0)) <= seq * 2):
        # A measured padded-dim entry speaks for at most 2x in sequence
        # length either way; beyond that the rule decides.
        return by_rule
    if e.get("xla_ms") is None:
        return True
    return float(_fused_ms(e)) <= float(e["xla_ms"])


def write_tuning(data: dict) -> str:
    """Persist a measured tuning table (bench_kernels --apply) to
    ``$PA_TUNING_PATH`` and reload."""
    if not _PATH:
        raise RuntimeError("set PA_TUNING_PATH to the file the table goes to")
    merged = {**_DEFAULT, **data, "source": "measured"}
    with open(_PATH, "w") as f:
        json.dump(merged, f, indent=2, sort_keys=True)
        f.write("\n")
    kernel_tuning.cache_clear()
    return _PATH
