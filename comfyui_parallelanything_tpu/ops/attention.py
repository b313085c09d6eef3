"""Attention with pluggable backends.

The reference toggles flash/xformers OFF for old GPUs (disable_flash_xformers,
any_device_parallel.py:126-164) — capability-gated attention backends are part of its
surface. Here there are four names:

- ``"xla"``    — jnp dot-product attention, the XLA family: the logits are
  written out whole up to ``_CHUNK_THRESHOLD`` elements, and calls above it are
  served by the chunked path below. Runs everywhere.
- ``"xla_chunked"`` — memory-bounded attention in plain XLA ops (lax.scan over
  query blocks; the S×S logits tensor never materializes, but every block's
  slice of it goes to HBM and back). What long sequences take off a TPU.
- ``"pallas"`` — the fused flash-attention kernel for TPU (ops/pallas/): logits
  and probabilities never leave VMEM.
- ``"auto"``   — the default: the backend is read from the call's shape.

Which backend a call takes, and with which blocks, is decided in one place:
``route()`` of ops/pallas/tuning.py, whose thresholds stand beside the v5e
measurements they were set from. :func:`attention_local` executes its answer.

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


_BACKEND_NAMES = ("auto", "xla", "xla_chunked", "pallas")

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


def _xla_attention(q, k, v, scale):
    # (B, S, H, D) -> einsum over D; stable softmax (jax.nn.softmax subtracts
    # the row max) in float32.
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def grouped_causal_attention(q, k, v) -> jnp.ndarray:
    """Grouped-query attention on the XLA path: ``q`` (B, S, H, D) against
    ``k`` / ``v`` (B, S_k, H_kv, D) with H a multiple of H_kv — query head
    ``h`` reads key/value head ``h // (H / H_kv)``, never repeated in memory —
    under a causal mask (query ``i`` sees keys ``<= i``), scaled by 1/√D.
    What a decoder-only text tower takes at its few dozen tokens (Qwen3-4B:
    32 query heads on 8 key/value heads); the fused kernel has neither the
    mask nor the grouping, so this never routes to it. Softmax in float32."""
    B, Sq, H, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    if H % Hk:
        raise ValueError(f"{H} query heads do not group over {Hk} key/value heads")
    qg = q.reshape(B, Sq, Hk, H // Hk, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * D ** -0.5
    keep = jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None] + (Sk - Sq)
    logits = jnp.where(keep, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    _count_route("xla")
    return out.reshape(B, Sq, H, D)


def _count_route(backend: str) -> None:
    """Once a trace, not once a forward: the attention functions run while a
    program is traced, so the count says which routes the compiled programs
    hold."""
    from ..utils.metrics import registry

    _RESOLVED.add(backend)
    registry.counter(
        "pa_attention_route_total", labels={"backend": backend},
        help="attention calls resolved to this backend while a program was "
             "traced (ops/attention.attention_local)",
    )


# Above this many f32 logits elements (B*H*S_q*S_k; 2**27 ≈ 512 MB) the
# materializing XLA path is routed to the chunked one. SD-class UNets at 1024²
# (16k tokens, batch 16) would need 137 GB of logits — far past any HBM — so
# where the fused kernel does not serve them (off a TPU, a forced "xla"),
# chunking is the only way those workloads fit a device at all.
_CHUNK_THRESHOLD = 2**27

# Degradation-ladder override (utils/degrade.py "attn-chunk-shrink" rung):
# divides the effective chunk threshold for the REST of the process — a
# serving dispatch that OOMed at lane width 1 sheds logits memory next. The
# floor keeps block_q useful (2^20 elements ≈ 4 MB of f32 logits per block).
_CHUNK_SHRINK = 1
_CHUNK_FLOOR = 2**20


def _chunk_threshold() -> int:
    # The floor bounds LADDER shrinks only: a threshold already below it
    # (tests patch _CHUNK_THRESHOLD to force the chunked path at tiny
    # shapes) is served as it is.
    return max(min(_CHUNK_THRESHOLD, _CHUNK_FLOOR),
               _CHUNK_THRESHOLD // _CHUNK_SHRINK)


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


def chunk_config() -> dict:
    """The chunk threshold serving this process (evidence labeling: a bench
    record must say which configuration produced the number). ``degraded`` is
    True while the degradation ladder's attn-chunk-shrink rung is in effect:
    a degraded process must not bank its numbers as the configured setting."""
    return {"chunk_elems": _chunk_threshold(), "degraded": _CHUNK_SHRINK > 1}


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

    def body(_, qblk):
        return None, _xla_attention(qblk, k, v, scale)

    _, out = jax.lax.scan(body, None, qb)
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, nq * block_q, H, D)
    return out[:, :Sq]


@functools.cache
def _pallas_available() -> bool:
    """Whether the default backend is a TPU — the only place the fused
    kernel is compiled rather than interpreted."""
    return jax.default_backend() == "tpu"


@functools.cache
def _log_interpreted_once() -> None:
    from ..utils.logging import get_logger

    get_logger().warning(
        "attention backend 'pallas' forced on the %s backend: the flash "
        "kernel runs in the Pallas INTERPRETER (test-only; orders of "
        "magnitude slower than any compiled path)", jax.default_backend(),
    )


def resolve_route(seq_q: int, seq_k: int, head_dim: int, batch_heads: int,
                  itemsize: int = 2, on_tpu: bool | None = None):
    """``route()`` of ops/pallas/tuning.py for a call in this process: on the
    default backend, under the process's pin and its chunk threshold. What
    :func:`attention_local` executes and the planner records (the planner at
    bfloat16's item size, what the models compute in on the chip)."""
    from .pallas.tuning import route

    return route(
        seq_q, seq_k, head_dim, batch_heads,
        on_tpu=_pallas_available() if on_tpu is None else on_tpu,
        pinned=_BACKEND, chunk_threshold=_chunk_threshold(), itemsize=itemsize,
    )


def attention_local(q, k, v, scale: float | None = None) -> jnp.ndarray:
    """Backend-dispatched attention WITHOUT sequence-parallel routing — the local
    compute kernel, also safe to call from inside a shard_map body (where re-entering
    the seq-parallel path would recurse)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    shape = (q.shape[1], k.shape[1], q.shape[-1], q.shape[0] * q.shape[2],
             k.dtype.itemsize)
    chosen = resolve_route(*shape)
    if (chosen.backend == "pallas" and chosen.rule != "pinned"
            and not _mosaic_reach(q.shape[0])):
        # A partitioned program that a Mosaic call cannot live in: the XLA
        # family, as off a TPU.
        chosen = resolve_route(*shape, on_tpu=False)
    _count_route(chosen.backend)
    if chosen.backend == "pallas":
        from ..utils.metrics import registry
        from .pallas.flash_attention import flash_attention, key_split
        from .pallas.tuning import is_ragged

        # The row of route() that decided, and whether the key axis of the
        # kernel's grid is a single step (a head group's whole K and V in
        # VMEM, no carried softmax state) or streams.
        keys_a_block, _ = key_split(k.shape[1], chosen.block_k)
        registry.counter(
            "pa_attention_key_blocks_total",
            labels={"rule": chosen.rule,
                    "keys": "one" if keys_a_block >= k.shape[1] else "streamed"},
            help="fused-kernel attention calls, counted like "
                 "pa_attention_route_total, by the row of route() that named "
                 "their blocks and whether the row of keys is one key block",
        )
        if is_ragged(q.shape[1], k.shape[1]):
            registry.counter(
                "pa_attention_padded_total", labels={"backend": chosen.backend},
                help="attention calls, counted like pa_attention_route_total, "
                     "whose sequence lengths were padded to reach the kernel",
            )
        # Compiled on a TPU; a pallas backend FORCED elsewhere (tests) runs
        # the interpreter, and says so once.
        interpret = not _pallas_available()
        if interpret:
            _log_interpreted_once()
        return flash_attention(
            q, k, v, scale=scale, block_q=chosen.block_q,
            block_k=chosen.block_k, interpret=interpret,
        )
    if chosen.backend == "xla_chunked":
        return _xla_chunked_attention(q, k, v, scale)
    return _xla_attention(q, k, v, scale)


def _mosaic_reach(batch: int) -> bool:
    """Whether a Mosaic call traced here, on operands of ``batch`` rows, can
    live in the program around it: under a context mesh only where
    ``flash_attention.over_data_axis`` can shard_map it — the one partitioned
    axis is ``data`` and divides the batch. A weight-sharded program's batch
    may not divide, a tensor-parallel one's axis is not ``data``: the
    partitioner refuses a bare Mosaic call there. The one rule for the flash
    kernel's calls (:func:`attention_local`) and the q/k prologue's."""
    from ..parallel.mesh import AXIS_DATA

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return True
    return all(
        n == 1 or name in mesh.manual_axes
        or (name == AXIS_DATA and batch % n == 0)
        for name, n in dict(mesh.shape).items()
    )


def count_qk_prologue(fused: bool, rope: bool) -> None:
    """``pa_qk_prologue_total{path, rope}``, once a trace: here for every
    prologue that passes :func:`qk_prologue`, and from a model whose norm the
    entry point does not serve (WAN's runs over the full width), as ``xla``."""
    from ..utils.metrics import registry

    registry.counter(
        "pa_qk_prologue_total",
        labels={"path": "fused" if fused else "xla",
                "rope": "interleaved" if rope else "none"},
        help="q/k prologues (per-head RMS norm, and the rotary where the "
             "model has one) by the path they took, counted like "
             "pa_attention_route_total: once a trace "
             "(ops/attention.qk_prologue)",
    )


def qk_prologue(qkv, q_scale, k_scale, eps: float = 1e-6, rope=None):
    """What stands between a block's qkv projection and its attention: the
    per-head RMS norm of q and of k with their learned ``(D,)`` scales
    (``ops/basic.rms_normalize``) and, where ``rope = (cos, sin)`` is given,
    the interleaved-pair rotary (``ops/rope.apply_rope``). Returns ``(q, k)``,
    each (B, S, H, D) in the operands' dtype.

    ``qkv`` is a pair ``(q, k)`` of (B, S, H, D) arrays, or ONE array
    (B, S, N, H, D), N >= 2, whose ``[:, :, 0]`` is q and ``[:, :, 1]`` k — a
    fused projection's output as it was written, so that the kernel reads q
    and k where they lie and no slice is copied first.

    One entry point for every family; the path is read from the call, as
    ``tuning.route`` reads attention's: where a Mosaic call can live
    (:func:`_mosaic_reach`, and not under ``sequence_parallel``), on a TPU (or under a pinned ``pallas``), at
    ``tuning.qk_prologue_route``'s measured size or over, the one-pass kernel
    (ops/pallas/qk_prologue.py); otherwise — off a TPU, a text tower's or a
    context stream's few dozen rows, a sharded program — the jnp functions,
    untouched. Counted once a trace: ``pa_qk_prologue_total{path, rope}``."""
    from .basic import rms_normalize
    from .pallas.tuning import qk_prologue_route

    fused_source = not isinstance(qkv, (tuple, list))
    like = qkv if fused_source else qkv[0]
    batch, seq = like.shape[:2]
    heads, head_dim = like.shape[-2:]
    # Inside a sequence_parallel context attention() hands its call to the
    # sequence-parallel program, whose operands are sharded over ``seq``.
    in_reach = getattr(_SEQ_CTX, "cfg", None) is None and _mosaic_reach(batch)
    fused = in_reach and qk_prologue_route(
        batch * seq, heads, head_dim, rope is not None,
        on_tpu=_pallas_available(), pinned=_BACKEND,
    )
    count_qk_prologue(fused, rope is not None)
    if not fused:
        q, k = (qkv[:, :, 0], qkv[:, :, 1]) if fused_source else qkv
        q, k = rms_normalize(q, q_scale, eps), rms_normalize(k, k_scale, eps)
        if rope is not None:
            from .rope import apply_rope

            q, k = apply_rope(q, *rope), apply_rope(k, *rope)
        return q, k

    from .pallas.qk_prologue import qk_prologue_call, rope_tables

    interpret = not _pallas_available()
    if interpret:
        _log_interpreted_once()
    # (B, S, ..., H, D) -> (B, S, ...·H·D): the layout the projection wrote.
    flat = lambda x: x.reshape(batch, seq, -1)  # noqa: E731
    q_src, k_src = (flat(qkv), None) if fused_source else map(flat, qkv)
    q, k = qk_prologue_call(
        q_src, k_src, q_scale, k_scale,
        None if rope is None else rope_tables(*rope),
        heads=heads, eps=float(eps), interpret=interpret,
    )
    return (q.reshape(batch, seq, heads, head_dim),
            k.reshape(batch, seq, heads, head_dim))


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
