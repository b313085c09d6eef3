"""Attention with pluggable backends.

The reference toggles flash/xformers OFF for old GPUs (disable_flash_xformers,
any_device_parallel.py:126-164) — capability-gated attention backends are part of its
surface. The TPU equivalent is a backend registry:

- ``"xla"``    — jnp dot-product attention; XLA fuses it well for moderate
  sequence lengths and it runs everywhere (the safe fallback, like the reference's
  post-disable path). Shapes whose S×S logits would exceed ``_CHUNK_THRESHOLD``
  are automatically served by the chunked path below.
- ``"xla_chunked"`` — memory-bounded attention in plain XLA ops (lax.scan over
  query blocks; the S×S logits tensor never materializes, but every block's
  slice of it goes to HBM and back). What long sequences take off a TPU, and
  on one at a ragged length too short to pay back the kernel's padding.
- ``"pallas"`` — fused flash-attention kernel for TPU (ops/pallas/): logits and
  probabilities never leave VMEM. Serves the long sequences of the FLUX/video
  configs and, chosen from the call's shape, the UNets' long self-attention at
  40/64/80-wide heads (4096 and 1024 tokens; PERF.md §6, PR 25) and SD3's
  joint attention over 77 + 4096 tokens, padded and masked (PR 26).
- ``"pallas_jax"`` — jax's own battle-tested TPU flash kernel
  (jax.experimental.pallas.ops.tpu.flash_attention) as an alternative fused
  candidate: round-3's only hardware data point for the in-repo kernel was a
  30-minute hang at 4.6k tokens, so the kernel sweep measures BOTH fused
  implementations and the tuning table routes ``auto`` to whichever one
  actually won (128-aligned head dims only — no padding logic upstream).
- ``"auto"``   — on a TPU the fused kernel where the shape qualifies
  (:func:`_auto_backend`: a measured table for lane-aligned head dims, a
  shape rule for the others), else the xla family (plain or chunked by size).

All functions take (B, S, H, D)-shaped q/k/v ("BSHD") and return (B, S, H, D).

Long-context: inside a ``sequence_parallel(mesh, ...)`` context every ``attention``
call routes through the sequence-parallel program (ring / Ulysses over the ``seq``
mesh axis, parallel/sequence.py) — so every model family gets context parallelism
without touching model code (absent in the reference, SURVEY §5.7; first-class here).
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp


def _initial_backend() -> str:
    """Startup backend from ``PA_TPU_ATTENTION_BACKEND``
    (auto/xla/xla_chunked/pallas).

    The env override exists so a *driving process* (bench harness, a hosted
    workflow run) can force one backend for every child it spawns — without
    touching code. An
    invalid value falls back to "auto" rather than erroring at import time.
    """
    name = os.environ.get("PA_TPU_ATTENTION_BACKEND", "auto")
    return name if name in _BACKEND_NAMES else "auto"


_BACKEND_NAMES = ("auto", "xla", "xla_chunked", "pallas", "pallas_jax")

_BACKEND = _initial_backend()

_SEQ_CTX = threading.local()


@contextlib.contextmanager
def sequence_parallel(mesh, axis: str = "seq", method: str = "ring"):
    """Route all ``attention`` calls in this context over the mesh's sequence axis.

    Usable around a jitted model forward; the sharded program inlines into the trace.
    Sequence lengths must divide the axis size (ring) and heads must divide it too
    for ``method="ulysses"``.
    """
    prev = getattr(_SEQ_CTX, "cfg", None)
    _SEQ_CTX.cfg = (mesh, axis, method)
    try:
        yield
    finally:
        _SEQ_CTX.cfg = prev


def sequence_ctx_key() -> tuple | None:
    """Hashable identity of the active sequence_parallel context — the ctx is read at
    trace time, so every jit cache keyed on a model forward must include this (or a
    program traced under one context would be silently reused under another)."""
    cfg = getattr(_SEQ_CTX, "cfg", None)
    if cfg is None:
        return None
    mesh, axis, method = cfg
    return (mesh, axis, method)


_RESOLVED: set[str] = set()


def resolved_backends() -> tuple[str, ...]:
    """Backends that have actually served ``attention_local`` calls in this
    process, resolved at trace time — "auto" never appears here. Evidence
    labeling for benchmarks (a bench line must say which kernel produced the
    number), not a control surface."""
    return tuple(sorted(_RESOLVED))


def set_attention_backend(name: str) -> None:
    global _BACKEND
    if name not in _BACKEND_NAMES:
        raise ValueError(f"unknown attention backend {name!r}")
    _BACKEND = name


def get_attention_backend() -> str:
    return _BACKEND


def _xla_attention(q, k, v, scale, logits_dtype=jnp.float32):
    # (B, S, H, D) -> einsum over D; stable softmax (jax.nn.softmax subtracts
    # the row max) in ``logits_dtype`` — f32 everywhere EXCEPT the chunked
    # scan under the measured chunk tuning (see _xla_chunked_attention): the
    # sweep only measures that path, so the bf16 knob must not leak into
    # other models' plain-XLA softmax.
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(logits_dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# Above this many f32 logits elements (B*H*S_q*S_k; 2**27 ≈ 512 MB) the
# materializing XLA path is routed to the chunked one. SD-class UNets at 1024²
# (16k tokens, batch 16) would need 137 GB of logits — far past any HBM — so
# where the fused kernel does not serve them (off a TPU, a forced "xla"),
# chunking is the only way those workloads fit a device at all.
_CHUNK_THRESHOLD = 2**27

# Chunk tuning: a {threshold × softmax-dtype} sweep persists its winner to the
# JSON file ``$PA_ATTN_CHUNK_TUNING`` names; env vars override per-process for
# the sweep itself. Read at trace time. There is no default file: a fresh
# clone and a checkout an earlier run wrote into behave the same.
_CHUNK_TUNING_PATH = os.environ.get("PA_ATTN_CHUNK_TUNING")


@functools.cache
def _chunk_tuning() -> dict:
    import json

    if not _CHUNK_TUNING_PATH:
        return {}
    with open(_CHUNK_TUNING_PATH) as f:
        return json.load(f)


# Degradation-ladder override (utils/degrade.py "attn-chunk-shrink" rung):
# divides the effective chunk threshold for the REST of the process — a
# serving dispatch that OOMed at lane width 1 sheds logits memory next. The
# floor keeps block_q useful (2^20 elements ≈ 4 MB of f32 logits per block).
_CHUNK_SHRINK = 1
_CHUNK_FLOOR = 2**20


def _chunk_threshold() -> int:
    env = os.environ.get("PA_ATTN_CHUNK_ELEMS")
    base = int(env) if env else int(
        _chunk_tuning().get("chunk_elems", _CHUNK_THRESHOLD)
    )
    # The floor bounds LADDER shrinks only — a configured value (env var /
    # measured tuning) below the floor is served verbatim: the sweep and
    # tests deliberately force tiny thresholds.
    return max(min(base, _CHUNK_FLOOR), base // _CHUNK_SHRINK)


def shrink_chunk_threshold() -> int | None:
    """Halve the effective chunked-attention threshold (the ladder's
    "attn-chunk-shrink" rung); returns the new threshold, or None when
    already at the floor (the rung is spent — callers move to the next one).
    Programs traced before the shrink keep their old blocks — the caller
    must rebuild (clear_compiled_loops) for the shrink to take effect.
    Shapes ``auto`` sends to the fused kernel (on a TPU, the UNets' long
    self-attention since PR 25) hold no logits slice to shrink — the kernel
    keeps less in VMEM than any chunk keeps in HBM — so for them the rung
    sheds nothing and is simply spent."""
    global _CHUNK_SHRINK
    if _chunk_threshold() <= _CHUNK_FLOOR:
        return None
    _CHUNK_SHRINK *= 2
    return _chunk_threshold()


def reset_chunk_shrink() -> None:
    """Undo ladder shrinks (tests / operator reset after the pressure ends)."""
    global _CHUNK_SHRINK
    _CHUNK_SHRINK = 1


def _softmax_dtype():
    env = os.environ.get("PA_ATTN_BF16_SOFTMAX")
    if env is not None:
        return jnp.bfloat16 if env == "1" else jnp.float32
    return jnp.bfloat16 if _chunk_tuning().get("bf16_softmax") else jnp.float32


def chunk_config() -> dict:
    """The chunk settings serving this process (evidence labeling: a bench
    record must say which configuration produced the number). ``sources``
    attributes each value separately — one env var being set must not
    mislabel the other value's provenance."""
    def src(env_name: str, table_key: str) -> str:
        if os.environ.get(env_name) is not None:
            return "env"
        if table_key in _chunk_tuning():
            return _chunk_tuning().get("source", "measured")
        return "default"

    return {
        "chunk_elems": _chunk_threshold(),
        "bf16_softmax": _softmax_dtype() == jnp.bfloat16,
        # True while the degradation ladder's attn-chunk-shrink rung is in
        # effect — evidence labeling: a degraded process must not bank its
        # numbers as the configured chunk setting.
        "degraded": _CHUNK_SHRINK > 1,
        "sources": {
            "chunk_elems": src("PA_ATTN_CHUNK_ELEMS", "chunk_elems"),
            "bf16_softmax": src("PA_ATTN_BF16_SOFTMAX", "bf16_softmax"),
        },
    }

# Block size of jax's upstream TPU flash kernel
# (pallas.ops.tpu.flash_attention.BlockSizes.get_default — 128 on every axis in
# the pinned jaxlib). The upstream kernel asserts seq_len % block == 0 and has
# no padding, so routing to "pallas_jax" must gate on this.
_UPSTREAM_BLOCK = 128


def _xla_chunked_attention(q, k, v, scale):
    """Memory-bounded attention without a fused kernel: a ``lax.scan`` over
    query blocks, each computing an ordinary softmax against the full K/V — the
    (B, H, S_q, S_k) logits tensor never materializes, only
    (B, H, block_q, S_k) slices do. The flash kernel's memory story with plain
    XLA ops: works for any head dim and any platform, trading one fused pass
    for nq sequential block passes (each still an MXU-shaped matmul pair)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    per_row = B * H * Sk
    block_q = max(16, min(Sq, _chunk_threshold() // max(per_row, 1)) // 16 * 16)
    if block_q >= Sq:
        return _xla_attention(q, k, v, scale)
    nq = -(-Sq // block_q)
    pad = nq * block_q - Sq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # (nq, B, block_q, H, D): scan over leading block axis; padded query rows
    # attend normally and are sliced away after.
    qb = qp.reshape(B, nq, block_q, H, D).transpose(1, 0, 2, 3, 4)
    # The measured softmax dtype applies to THIS path only — it's what the
    # chunk sweep benches (the scan's per-block logits round-trips are the
    # sd15_16 MFU budget's dominant traffic); plain-XLA softmax stays f32.
    logits_dtype = _softmax_dtype()

    def body(_, qblk):
        return None, _xla_attention(qblk, k, v, scale, logits_dtype=logits_dtype)

    _, out = jax.lax.scan(body, None, qb)
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, nq * block_q, H, D)
    return out[:, :Sq]


def _pallas_jax_attention(q, k, v, scale):
    """jax's upstream fused TPU flash kernel, adapted from this module's BSHD
    layout to its BHSD one. TPU-only (no interpret path is wired); head dim
    must be 128-aligned (the upstream kernel has no lane-padding logic). Block
    sizes are left to the upstream defaults — its own heuristics are part of
    what makes it the battle-tested candidate."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as jax_flash,
    )

    from .pallas.flash_attention import over_data_axis

    def bshd(q, k, v):
        qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        return jax_flash(qt, kt, vt, sm_scale=float(scale)).transpose(0, 2, 1, 3)

    # Like the in-repo kernel: a Mosaic call must be shard_mapped to live in
    # a program partitioned over the data axis.
    return over_data_axis(bshd, q, k, v)


@functools.cache
def _pallas_available() -> bool:
    """Whether the default backend is a TPU — the only place the fused
    kernels are compiled rather than interpreted."""
    return jax.default_backend() == "tpu"


@functools.cache
def _log_interpreted_once() -> None:
    from ..utils.logging import get_logger

    get_logger().warning(
        "attention backend 'pallas' forced on the %s backend: the flash "
        "kernel runs in the Pallas INTERPRETER (test-only; orders of "
        "magnitude slower than any compiled path)", jax.default_backend(),
    )


def _require_upstream_shape(head_dim, seq_q: int, seq_k: int) -> None:
    """A FORCED ``pallas_jax`` must be able to serve the shape: the upstream
    kernel has no lane padding and asserts seq_len % block == 0. Only
    ``auto`` may choose another backend; a forced one that cannot serve
    raises instead of quietly giving way."""
    if ((head_dim is not None and head_dim % 128 != 0)
            or seq_q % _UPSTREAM_BLOCK != 0 or seq_k % _UPSTREAM_BLOCK != 0):
        raise ValueError(
            f"attention backend 'pallas_jax' cannot serve head_dim={head_dim} "
            f"seq_q={seq_q} seq_k={seq_k}: it needs a 128-multiple head dim "
            f"and {_UPSTREAM_BLOCK}-multiple sequence lengths; set the "
            f"backend to 'auto' to let the dispatch choose"
        )


def _auto_backend(seq_q: int, seq_k: int, head_dim: int | None,
                  batch_heads: int) -> str:
    """What ``auto`` resolves to BEFORE the xla→chunked size fallback — read
    from the call's shape and the backend, nothing else; ``attention_local``
    and ``backend_plan`` both decide here.

    The fused kernel needs a TPU. A sequence length that is not a multiple
    of 128 (SD3's joint 77 + 4096 tokens) goes to it padded and masked where
    ``ragged_route`` of ops/pallas/tuning.py says the padding is paid back,
    else stays with XLA. At 128-multiple lengths (the UNets' 64² and 32²
    token grids, FLUX's joint sequence):
    lane-aligned head dims (VAE 512, FLUX / WAN 128) go to it unless a
    measured table (``$PA_TUNING_PATH``) says XLA won at the nearest length;
    the others (UNet 40 / 64 / 80) by the shape rule of ops/pallas/tuning.py —
    ``seq_k`` at or above 1024, which leaves cross-attention's 77 keys and
    the short inner levels on XLA, and B·H·S_q·S_k at or above 2^27, the
    smallest count of logits at which the kernel was measured to win."""
    from .pallas.tuning import (
        fused_backend,
        is_ragged,
        pallas_wins,
        ragged_route,
    )

    if not _pallas_available():
        return "xla"
    if is_ragged(seq_q, seq_k):
        # The in-repo kernel pads the row to its blocks and masks the padded
        # keys; the upstream one cannot.
        return "pallas" if ragged_route(seq_q, seq_k, batch_heads) else "xla"
    if pallas_wins(seq_q, head_dim, seq_k=seq_k, batch_heads=batch_heads):
        # Which fused implementation won the measurement at this shape
        # class (in-repo streamed-KV kernel vs jax's upstream one).
        return fused_backend(seq_q, head_dim)
    return "xla"


def attention_local(q, k, v, scale: float | None = None) -> jnp.ndarray:
    """Backend-dispatched attention WITHOUT sequence-parallel routing — the local
    compute kernel, also safe to call from inside a shard_map body (where re-entering
    the seq-parallel path would recurse)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    backend = _BACKEND
    batch_heads = q.shape[0] * q.shape[2]
    logit_elems = batch_heads * q.shape[1] * k.shape[1]
    if backend == "auto":
        backend = _auto_backend(q.shape[1], k.shape[1], q.shape[-1],
                                batch_heads)
    if backend == "pallas_jax":
        _require_upstream_shape(q.shape[-1], q.shape[1], k.shape[1])
    if backend == "xla" and logit_elems > _chunk_threshold():
        # "xla" means the XLA family: shapes whose S×S logits would blow HBM
        # (long sequences off a TPU or at ragged lengths, or a forced
        # non-pallas run) go through the chunked path instead of OOMing.
        backend = "xla_chunked"
    _RESOLVED.add(backend)
    # Once a trace, not once a forward: attention_local runs while a program
    # is traced, so the count says which routes the compiled programs hold.
    from ..utils.metrics import registry

    registry.counter(
        "pa_attention_route_total", labels={"backend": backend},
        help="attention calls resolved to this backend while a program was "
             "traced (ops/attention.attention_local)",
    )
    if backend == "pallas":
        from .pallas.flash_attention import flash_attention
        from .pallas.tuning import best_blocks, is_ragged

        if is_ragged(q.shape[1], k.shape[1]):
            registry.counter(
                "pa_attention_padded_total", labels={"backend": backend},
                help="attention calls, counted like pa_attention_route_total, "
                     "whose sequence lengths were padded to reach the kernel",
            )

        block_q, block_k = best_blocks(
            q.shape[1], q.shape[-1], seq_k=k.shape[1], batch_heads=batch_heads,
        )
        # Compiled on a TPU; a pallas backend FORCED elsewhere (tests) runs
        # the interpreter, and says so once.
        interpret = not _pallas_available()
        if interpret:
            _log_interpreted_once()
        return flash_attention(
            q, k, v, scale=scale, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
    if backend == "pallas_jax":
        return _pallas_jax_attention(q, k, v, scale)
    if backend == "xla_chunked":
        return _xla_chunked_attention(q, k, v, scale)
    return _xla_attention(q, k, v, scale)


def backend_plan(seq_q: int, seq_k: int | None = None,
                 head_dim: int | None = None, batch: int = 1,
                 heads: int = 1) -> dict:
    """The ``attention_local`` routing ladder as a side-effect-free,
    inspectable decision — what the auto-parallel planner's attention axis
    reads (parallel/planner.py): which backend WOULD serve this shape, the
    chunk configuration it would run under, and the measurements
    (``$PA_ATTN_CHUNK_TUNING`` threshold sweep + ``$PA_TUNING_PATH``
    pallas-vs-xla wins) that decided it. ``auto`` is resolved by the same
    ``_auto_backend`` that ``attention_local`` calls and the steps around it
    mirror ``attention_local`` rule for rule; a drift test pins the two
    against each other (tests/test_planner.py)."""
    from .pallas.tuning import kernel_tuning

    seq_k = seq_q if seq_k is None else int(seq_k)
    logit_elems = int(batch) * int(heads) * int(seq_q) * int(seq_k)
    threshold = _chunk_threshold()
    candidates: list[dict] = []

    def cand(name, eligible, why, **extra):
        candidates.append(
            {"backend": name, "eligible": bool(eligible), "why": why, **extra}
        )

    tuning = kernel_tuning()
    nearest = None
    measured = [e for e in tuning["entries"]
                if e.get("pallas_ms") is not None
                or e.get("pallas_jax_ms") is not None]
    if measured:
        nearest = min(
            measured, key=lambda e: abs(int(e.get("seq", 0)) - int(seq_q))
        )
    auto = _auto_backend(seq_q, seq_k, head_dim, int(batch) * int(heads))
    fused_ok = auto != "xla"
    cand(
        "pallas", auto == "pallas",
        "fused in-repo kernel (shape rule / tuning table winner)" if fused_ok
        else "ineligible: not TPU / ragged length the rule leaves to XLA / "
             "rule or tuning says XLA",
        measured_ms=(nearest or {}).get("pallas_ms"),
    )
    cand(
        "pallas_jax", auto == "pallas_jax",
        "jax upstream fused kernel (tuning table winner)" if fused_ok
        else "ineligible: not TPU / non-aligned / tuning says XLA",
        measured_ms=(nearest or {}).get("pallas_jax_ms"),
    )
    cand(
        "xla", not fused_ok and logit_elems <= threshold,
        f"materializing logits fit ({logit_elems} <= {threshold} elems)"
        if logit_elems <= threshold
        else f"logits would materialize {logit_elems} > {threshold} elems",
        measured_ms=(nearest or {}).get("xla_ms"),
    )
    cand(
        "xla_chunked", not fused_ok and logit_elems > threshold,
        "memory-bounded scan over query blocks (logits exceed threshold)",
        measured_ms=None,
    )
    # The exact attention_local resolution order: configured pin first, the
    # auto ladder only for "auto", then the forced-pallas_jax shape check
    # and the xla→chunked size fallback — so a process-pinned backend plans the same
    # way it executes.
    backend = _BACKEND
    if backend == "auto":
        backend = auto
    if backend == "pallas_jax":
        _require_upstream_shape(head_dim, seq_q, seq_k)
    if backend == "xla" and logit_elems > threshold:
        backend = "xla_chunked"
    cfg = chunk_config()
    return {
        "backend": backend,
        "configured": _BACKEND,
        "logit_elems": logit_elems,
        "chunk_elems": cfg["chunk_elems"],
        "bf16_softmax": cfg["bf16_softmax"],
        "sources": cfg["sources"],
        "tuning_source": tuning.get("source", "default"),
        "candidates": candidates,
    }


def attention(q, k, v, scale: float | None = None) -> jnp.ndarray:
    """Scaled dot-product attention on (B, S, H, D) inputs."""
    seq_cfg = getattr(_SEQ_CTX, "cfg", None)
    if seq_cfg is not None:
        if scale is None:
            scale = q.shape[-1] ** -0.5
        from ..parallel.sequence import sharded_attention_inline

        mesh, axis, method = seq_cfg
        return sharded_attention_inline(q, k, v, mesh, axis, method, scale)
    return attention_local(q, k, v, scale)
