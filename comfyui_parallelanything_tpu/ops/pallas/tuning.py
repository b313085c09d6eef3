"""Data-driven flash-kernel tuning.

The pallas kernel's block sizes (256/256) started as guesses; real numbers come
from ``scripts/bench_kernels.py``, which sweeps ``block_q``/``block_k`` over
{128, 256, 512} at the shapes that matter (FLUX 4.6k joint attention, WAN
16k/32k video) and — with ``--apply`` — writes the winners to the JSON file
``$PA_TUNING_PATH`` names. The ``auto`` attention backend (ops/attention.py)
then:

- picks the measured-best blocks for the nearest benchmarked sequence length,
- falls back to XLA for sequence ranges where the measurement says the fused
  kernel LOSES (the reference's capability-gated backend disable, inverted:
  data-gated instead of SM-version-gated, any_device_parallel.py:126-164).

There is no default file: without ``$PA_TUNING_PATH`` everything runs on the
defaults below, so a fresh clone and a checkout an earlier run wrote into
behave the same.
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


def best_blocks(seq: int, head_dim: int | None = None) -> tuple[int, int]:
    """(block_q, block_k) for a sequence length: the measured winner at the
    nearest benchmarked length (preferring measurements of the same head-dim
    class), else the defaults."""
    t = kernel_tuning()
    entries = [e for e in t["entries"] if e.get("block_q") and e.get("block_k")]
    if head_dim is not None:
        same_dim = [e for e in entries if e.get("head_dim") == head_dim]
        if same_dim:
            entries = same_dim
        elif head_dim % 128 != 0:
            # Padded dim with no same-dim measurement: return the defaults
            # rather than inheriting blocks tuned for a different dim class —
            # mirrors pallas_wins' filtering, which matters when a forced
            # (non-auto) pallas backend runs a padded shape the sweep never
            # measured.
            entries = []
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


def pallas_wins(seq: int, head_dim: int | None = None) -> bool:
    """Whether the fused kernel beat XLA at the nearest measured length. With
    no measurement, True for lane-aligned head dims — the default guess (XLA's
    S×S logits materialization loses at the long lengths this path serves) —
    but False for non-aligned dims (40/64 UNet heads): those run the kernel
    zero-PADDED to 128 lanes, a 2-3.2× FLOP tax that must *prove* it beats the
    chunked-XLA path before auto picks it. Entries measured at a specific
    ``head_dim`` (bench_kernels records it) gate their own dim class; an entry
    whose XLA measurement FAILED (``xla_ms`` None — S×S logits OOM) counts as
    a pallas win: that is a length where the fused kernel is mandatory, not
    absent data."""
    t = kernel_tuning()
    entries = [e for e in t["entries"] if _fused_ms(e) is not None]
    padded_dim = head_dim is not None and head_dim % 128 != 0
    if head_dim is not None:
        same_dim = [e for e in entries if e.get("head_dim") == head_dim]
        if same_dim:
            entries = same_dim
        elif padded_dim:
            return False
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
        # A padded-dim win extrapolates at most 2x in sequence length: the
        # padded FLOP tax that wins at 16k against chunked XLA was never
        # measured against the cheap plain-XLA competitor at short lengths.
        return False
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
