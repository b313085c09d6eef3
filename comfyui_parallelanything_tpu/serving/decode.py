"""Batched tail decode: VAE decodes leave the prompt workers' inline path
and batch into shared compiled decode dispatches.

The serving tier co-batches the denoise loop (scheduler/bucket), but until
round 17 every prompt's VAE decode ran inline on its own worker thread —
serializing on the device behind the next prompt's denoise dispatches, one
compiled decode per prompt even when four prompts finish the same lockstep
step and decode the same latent shape. This module is the scheduler-tail
analogue of the step bucket for the decode stage:

- **submit/ticket**: ``TPUVAEDecode`` routes eligible work (untiled image
  latents) here when a queue is installed (the server installs one alongside
  the scheduler); the worker blocks on its ticket exactly as a sampler run
  blocks on its serving ticket. Ineligible work (tiled decode, video VAE,
  odd ranks) returns ``None`` and the caller decodes inline unchanged — the
  queue can only ADD batching, never change results.
- **width-bucketed batching**: compatible latents — same VAE object, same
  per-request latent shape/dtype — concatenate on the batch axis, padded to
  the fixed bucket width (``PA_DECODE_WIDTH``), so ANY 1..W group runs ONE
  compiled program per (vae, shape) and traffic mix can't recompile (the
  step bucket's key discipline). Results are sliced back per ticket;
  per-sample independence of the decoder makes a padded row inert.
- **linger window**: a group dispatches when it reaches the width OR when
  its oldest ticket has waited ``PA_DECODE_LINGER_S`` — decodes from prompts
  retiring off the same lockstep dispatch arrive within milliseconds, which
  is the batching opportunity; a solo prompt pays at most the linger.
- **metered**: ``pa_decode_dispatch_total`` / ``pa_decode_requests_total``
  counters, ``pa_decode_batched_fraction`` gauge (requests served in
  shared dispatches / total — the loadgen ``decode_batched_fraction``
  field), ``pa_decode_queue_depth`` gauge, wait/step histograms, and a
  ``decode-dispatch`` span per dispatch.

Correctness: batched-vs-solo decode is allclose at bf16 tolerances (the
batch dim changes the XLA program, same as any width change — CLAUDE.md's
matmul-precision note), pinned by ``tests/test_reuse.py``.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import uuid
from typing import Any

from ..utils import slo, tracing
from ..utils.metrics import registry

_installed: "DecodeQueue | None" = None
_install_lock = threading.Lock()

# Process-wide batched-decode accounting (the bucket.py _batch_stats twin):
# requests decoded in dispatches carrying >1 request, over all requests.
_stats = {"total": 0, "shared": 0}
_stats_lock = threading.Lock()


def get_decode_queue() -> "DecodeQueue | None":
    """The process-wide decode queue TPUVAEDecode consults, or None
    (inline decode)."""
    return _installed


def record_decode_occupancy(occupancy: int) -> None:
    with _stats_lock:
        _stats["total"] += occupancy
        if occupancy > 1:
            _stats["shared"] += occupancy
        frac = _stats["shared"] / max(1, _stats["total"])
    registry.gauge(
        "pa_decode_batched_fraction", frac,
        help="decode requests served via shared dispatch / total",
    )


def batched_fraction() -> float:
    with _stats_lock:
        return _stats["shared"] / max(1, _stats["total"])


def _vae_token(vae) -> str:
    """Lifetime-unique token per VAE object — the group key's model
    component (one shared idiom: models/embed_cache.lifetime_token)."""
    from ..models.embed_cache import lifetime_token

    return lifetime_token(vae, "_pa_decode_token")


@dataclasses.dataclass
class DecodeTicket:
    """One latent handed to the decode tail; the submitting worker blocks in
    ``result()`` for exactly the queue wait + shared dispatch."""

    vae: Any
    z: Any
    submit_ts: float = dataclasses.field(default_factory=time.monotonic)
    prompt_id: Any = None
    trace_tid: Any = None
    trace_id: Any = None  # distributed trace identity (see ServeRequest)
    trace_parent: Any = None  # span open on the submitter (see ServeRequest)
    rid: str = dataclasses.field(default_factory=lambda: uuid.uuid4().hex)

    def __post_init__(self):
        self._done = threading.Event()
        self._result = None
        self._error: BaseException | None = None

    def resolve(self, result=None, error: BaseException | None = None) -> None:
        self._result, self._error = result, error
        self._done.set()

    def result(self, timeout: float | None = 300.0):
        if not self._done.wait(timeout):
            raise TimeoutError(f"decode ticket {self.rid} still in flight")
        if self._error is not None:
            raise self._error
        return self._result


class DecodeQueue:
    """Width-bucketed batching of tail decodes with a linger window.

    ``auto=True`` runs a dispatcher thread; ``auto=False`` exposes the same
    round as a manual ``pump()`` for deterministic tests (the scheduler's
    discipline)."""

    def __init__(self, width: int | None = None, linger_s: float | None = None,
                 auto: bool = True, max_waiting: int = 256):
        self.width = max(1, int(
            width if width is not None
            else os.environ.get("PA_DECODE_WIDTH", "4")
        ))
        self.linger_s = float(
            linger_s if linger_s is not None
            else os.environ.get("PA_DECODE_LINGER_S", "0.01")
        )
        self.max_waiting = max_waiting
        # group key -> [DecodeTicket] in arrival order.
        self._groups: dict[tuple, list] = {}  # guarded-by: _lock
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._stop = False
        self._thread = None
        if auto:
            self._thread = threading.Thread(
                target=self._loop, name="pa-decode-dispatcher", daemon=True
            )
            self._thread.start()

    # -- lifecycle ----------------------------------------------------------

    def install(self) -> "DecodeQueue":
        global _installed
        with _install_lock:
            _installed = self
        return self

    def uninstall(self) -> None:
        global _installed
        with _install_lock:
            if _installed is self:
                _installed = None

    def shutdown(self, timeout: float = 10.0) -> None:
        """Stop the dispatcher and resolve every waiting ticket with an
        error — no submitter may be left blocked on a dead queue."""
        self.uninstall()
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
        with self._lock:
            groups = list(self._groups.values())
            self._groups.clear()
        for tickets in groups:
            for t in tickets:
                t.resolve(error=RuntimeError("decode queue shutdown"))

    # -- submission ---------------------------------------------------------

    def submit(self, vae, z, tile: int = 0) -> DecodeTicket | None:
        """Admit one decode, or None when it cannot share a program (caller
        decodes inline): tiled decodes host-accumulate their own schedule,
        and only rank-4 image latents through a jit-decode VAE batch on
        dim 0."""
        if self._stop or tile:
            return None
        if getattr(z, "ndim", 0) != 4:
            return None
        if not hasattr(vae, "decode") or not hasattr(vae, "params"):
            return None
        # decode_tiled would have been chosen by decode_maybe_tiled only via
        # `tile`, but a large latent through vae.decode is the caller's
        # existing behavior — eligibility mirrors it exactly.
        key = (_vae_token(vae), tuple(z.shape), str(z.dtype))
        ticket = DecodeTicket(
            vae=vae, z=z,
            prompt_id=tracing.current_prompt_id() if tracing.on() else None,
            trace_tid=threading.get_ident() if tracing.on() else None,
            trace_id=tracing.current_trace_id() if tracing.on() else None,
            trace_parent=tracing.current_span_id() if tracing.on() else None,
        )
        with self._lock:
            if self._stop:
                # Re-checked under the lock: a shutdown() that completed
                # between the entry check and here has already resolved and
                # dropped every ticket — appending now would strand this
                # one's waiter for its full result() timeout. Inline decode
                # instead.
                return None
            waiting = sum(len(v) for v in self._groups.values())
            if waiting >= self.max_waiting:
                return None  # backpressure: shed to the inline path
            self._groups.setdefault(key, []).append(ticket)
            registry.gauge("pa_decode_queue_depth", waiting + 1,
                           help="latents waiting for a shared decode")
            self._cond.notify_all()
        return ticket

    # -- dispatch -----------------------------------------------------------

    def _ready(self, now: float) -> list[tuple]:  # palint: holds _lock
        """Group keys ripe for dispatch: width reached, or oldest ticket
        past the linger window."""
        out = []
        for key, tickets in self._groups.items():
            if not tickets:
                continue
            if len(tickets) >= self.width \
                    or now - tickets[0].submit_ts >= self.linger_s:
                out.append(key)
        return out

    def pump(self, force: bool = False) -> bool:
        """One dispatch round: run every ripe group (``force`` dispatches
        everything waiting — the manual-test / drain path). Returns whether
        anything dispatched."""
        did = False
        while True:
            with self._lock:
                now = time.monotonic()
                keys = list(self._groups) if force else self._ready(now)
                batch = None
                for key in keys:
                    tickets = self._groups.get(key) or []
                    take, rest = tickets[:self.width], tickets[self.width:]
                    if rest:
                        self._groups[key] = rest
                    else:
                        self._groups.pop(key, None)
                    if take:
                        batch = (key, take)
                        break
                if batch is None:
                    registry.gauge(
                        "pa_decode_queue_depth",
                        sum(len(v) for v in self._groups.values()),
                    )
                    return did
            self._dispatch(*batch)
            did = True

    def _dispatch(self, key: tuple, tickets: list) -> None:
        import jax
        import jax.numpy as jnp

        now = time.monotonic()
        for t in tickets:
            wait = now - t.submit_ts
            registry.histogram("pa_decode_wait_seconds", wait,
                               help="submit-to-dispatch decode queue wait")
            slo.observe_stage("decode_wait", wait)
        vae = tickets[0].vae
        k = len(tickets)
        t0_us = tracing.now_us() if tracing.on() else 0.0
        t0 = time.perf_counter()
        try:
            # Pad to the fixed width bucket with inert rows (the decoder is
            # per-sample independent), so 1..W requests share ONE compiled
            # program per (vae, per-request shape) — no recompiles from mix.
            zs = [t.z for t in tickets]
            pad = self.width - k
            if pad:
                zs = zs + [jnp.zeros_like(zs[0])] * pad
            stacked = jnp.concatenate(zs, axis=0)
            out = vae.decode(stacked)
            # palint: allow[host-sync] the completion boundary: the decode
            # histogram must include device time (the StepTimer discipline)
            jax.block_until_ready(out)
        except Exception as e:  # noqa: BLE001 — no waiter may hang
            for t in tickets:
                t.resolve(error=e)
            return
        dt = time.perf_counter() - t0
        b = tickets[0].z.shape[0]
        registry.counter("pa_decode_dispatch_total",
                         help="shared compiled decode dispatches")
        registry.counter("pa_decode_requests_total", inc=k,
                         help="decode requests served — batching numerator")
        registry.histogram("pa_decode_step_seconds", dt,
                           help="wall time of one shared decode dispatch")
        record_decode_occupancy(k)
        if tracing.on() and t0_us:
            dur_us = tracing.now_us() - t0_us
            tracing.record(
                "decode-dispatch", t0_us, dur_us, cat="serving",
                occupancy=k, masked=self.width - k, width=self.width,
            )
            for t in tickets:
                tracing.record(
                    "decode", t0_us, dur_us, cat="serving",
                    tid=t.trace_tid, prompt_id=t.prompt_id, rid=t.rid,
                    parent_span_id=t.trace_parent,
                    occupancy=k,
                    **({"trace_id": t.trace_id} if t.trace_id else {}),
                )
        for i, t in enumerate(tickets):
            t.resolve(result=out[i * b:(i + 1) * b])

    def drain(self, timeout: float = 60.0) -> None:
        """Pump until nothing is waiting (manual mode helper)."""
        t0 = time.monotonic()
        while True:
            with self._lock:
                if not any(self._groups.values()):
                    return
            self.pump(force=True)
            if time.monotonic() - t0 > timeout:
                raise TimeoutError("decode drain timed out")

    def stats(self) -> dict:
        """The /health ``reuse.decode`` section."""
        with self._lock:
            waiting = sum(len(v) for v in self._groups.values())
        return {
            "width": self.width,
            "linger_s": self.linger_s,
            "waiting": waiting,
            "batched_fraction": batched_fraction(),
        }

    def _loop(self) -> None:
        while True:
            with self._cond:
                if self._stop:
                    return
                if not any(self._groups.values()):
                    self._cond.wait(timeout=0.2)
                    continue
                now = time.monotonic()
                if not self._ready(now):
                    # Sleep until the oldest group's linger lapses (bounded
                    # below so a clock hiccup can't busy-spin).
                    oldest = min(
                        t[0].submit_ts for t in self._groups.values() if t
                    )
                    delay = max(0.001, self.linger_s - (now - oldest))
                    self._cond.wait(timeout=delay)
                    continue
            try:
                self.pump()
            except Exception:  # noqa: BLE001 — the dispatcher must survive
                time.sleep(0.05)
