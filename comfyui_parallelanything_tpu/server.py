"""Minimal ComfyUI-compatible HTTP API over the workflow host.

The reference pack's graphs are driven through ComfyUI's HTTP server (the
frontend and every scripting client POST API-format JSON to ``/prompt``).
This module is that surface for the standalone host: stdlib-only
(``http.server``), a configurable pool of worker threads executing prompts
(default ONE — the reference's serial schedule; ``workers>1`` or
``PA_SERVER_WORKERS`` turns on concurrent execution and installs the
continuous-batching scheduler, serving/, so concurrent prompts' sampler runs
share compiled step dispatches), and a persistent ``host.WorkflowCache``
shared across prompts so a model loaded by one prompt stays resident for the
next (the reference's keep-loaded behavior, which its
``cleanup_parallel_model``/finalizer pair defends, any_device_parallel.py
211-282).

Endpoints (the ComfyUI client-protocol subset that makes scripts work):

- ``POST /prompt``            ``{"prompt": {...graph...}}`` → ``{"prompt_id"}``;
                              ``extra_data.priority`` / ``extra_data.deadline_s``
                              feed the serving policy layer; 429 when the
                              bounded queue (``max_pending`` /
                              $PA_MAX_PENDING) is full — explicit
                              backpressure instead of silent latency
- ``GET  /history``           all completed prompts
- ``GET  /history/{id}``      one prompt's status + outputs
- ``GET  /view?filename=``    serve a saved image (``subfolder=`` honored)
- ``GET  /queue``             running + pending prompt ids
- ``POST /queue``             stock per-prompt cancel:
                              ``{"delete": [prompt_id, ...]}`` drops queued
                              prompts and stops running ones at their next
                              step boundary (per-lane cancel — co-batched
                              neighbors keep running); ``{"clear": true}``
                              drops every pending prompt
- ``GET  /metrics``           Prometheus text: serving per-bucket occupancy,
                              lane-wait/step-time histograms (server-side
                              p50/p95), dispatch counts (utils/metrics.py
                              registry) + queue gauges + per-device
                              ``pa_hbm_*`` memory gauges (refreshed per
                              scrape and by the periodic memory monitor)
- ``GET  /health``            one JSON health document
                              (utils/telemetry.health_snapshot,
                              ``pa-health/v3``): devices, per-device HBM +
                              utilization (deterministic pseudo-accounting
                              off-hardware), peak watermark, compile/cache
                              accounting, queue depth/workers, 1-minute
                              load average, a ``numerics`` section
                              (utils/numerics.py: sentinel flag, last
                              non-finite event, quarantined-lane total,
                              fingerprint-gate verdict; enable with
                              $PA_NUMERICS=1), and the fleet identity/
                              admission fields a router's scoreboard reads
                              (``host_id``, ``accepting``,
                              ``inflight_prompts`` — fleet/scoreboard.py
                              needs no extra endpoint)
- ``POST /drain``             fleet drain: stop seating new prompts
                              (``POST /prompt`` → 503 while draining),
                              finish running lanes; body
                              ``{"resume": true}`` re-opens admission
                              (elastic rejoin). A router mirrors the state
                              from /health's ``accepting``
- ``GET  /trace``             Chrome/Perfetto trace-event JSON of the span
                              tracer (utils/tracing.py) — per-prompt
                              timelines from HTTP ingress to device step;
                              ``?prompt_id=`` filters to one prompt. Enable
                              with ``--trace`` / $PA_TRACE=1 (off by
                              default: the tracer's disabled path is a
                              single flag check)
- ``POST /interrupt``         drop all *pending* prompts and stop every
                              *running* one at its next sampler-step boundary
                              (per-prompt cooperative scope,
                              utils/progress.py; a single compiled step
                              cannot be preempted mid-dispatch)
- ``POST /upload/image``      multipart input upload into $PA_INPUT_DIR
                              (stock dedupe suffixing; ``overwrite`` honored)
- ``GET  /object_info[/cls]`` node-registry introspection (INPUT_TYPES etc.)
- ``GET  /system_stats``      devices from devices.discovery
- ``GET  /ws``                WebSocket progress events (RFC 6455, stdlib):
                              ``status`` on queue changes,
                              ``execution_start`` when a prompt begins,
                              ``execution_cached`` with the cache-served node
                              ids, ``executing`` per node as it runs,
                              ``progress`` per sampler step (what frontends
                              render progress bars from), ``executed`` per
                              output node with its images,
                              ``execution_interrupted`` on Cancel, and the
                              canonical completion signal API clients wait
                              for — ``executing`` with ``node: null`` and the
                              ``prompt_id``. Opt-in (``extra_data.preview``
                              on POST /prompt): per-step latent previews as
                              stock binary frames (>II event-type 1 + format
                              2 (PNG) + PNG bytes; utils/latent_preview.py).

Run:  ``python -m comfyui_parallelanything_tpu.server [--port 8188]``
"""

from __future__ import annotations

import base64
import contextlib
import hashlib
import json
import os
import queue
import struct
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from .host import WorkflowCache, run_workflow
from .utils import faults, slo, tracing
from .utils.progress import Interrupted, progress_scope

_WS_GUID = "258EAFA5-E914-47DA-95CA-C5AB0DC85B11"  # RFC 6455 §1.3


def _ws_frame(payload: bytes, opcode: int = 0x1) -> bytes:
    """One server→client frame (FIN set, unmasked — RFC 6455 §5.2)."""
    head = bytes([0x80 | opcode])
    n = len(payload)
    if n < 126:
        head += bytes([n])
    elif n < 1 << 16:
        head += bytes([126]) + struct.pack(">H", n)
    else:
        head += bytes([127]) + struct.pack(">Q", n)
    return head + payload


def _ws_read_frame(rfile) -> tuple[int, bytes] | None:
    """(opcode, payload) of one client frame, or None on EOF — including an
    abrupt disconnect mid-header (a truncated read must not raise out of the
    handler as struct.error). Client frames are masked (RFC 6455 §5.3)."""

    def need(k: int) -> bytes | None:
        data = rfile.read(k)
        return data if len(data) == k else None

    hdr = need(2)
    if hdr is None:
        return None
    opcode = hdr[0] & 0x0F
    masked, n = hdr[1] & 0x80, hdr[1] & 0x7F
    if n == 126:
        ext = need(2)
        if ext is None:
            return None
        n = struct.unpack(">H", ext)[0]
    elif n == 127:
        ext = need(8)
        if ext is None:
            return None
        n = struct.unpack(">Q", ext)[0]
    mask = need(4) if masked else b"\x00" * 4
    if mask is None:
        return None
    data = need(n)
    if data is None:
        return None
    if masked:
        data = bytes(b ^ mask[i % 4] for i, b in enumerate(data))
    return opcode, data


def _jsonable(v):
    """INPUT_TYPES trees hold tuples/dicts/strings and the odd non-JSON leaf
    (a type, a float('inf') bound) — degrade those to strings."""
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, (str, int, bool)) or v is None:
        return v
    if isinstance(v, float):
        return v if v == v and abs(v) != float("inf") else str(v)
    return str(v)


class _WsListener:
    """One /ws client: a dedicated writer thread drains a bounded frame
    queue. All writes (events AND pongs) go through the single writer, so
    frames can never interleave mid-stream; ``send`` never blocks, and a
    stalled client simply fills its queue and is evicted — the socket close
    then unblocks any in-flight ``sendall``."""

    def __init__(self, sock):
        self.sock = sock
        self.frames: "queue.Queue[bytes | None]" = queue.Queue(maxsize=64)
        self._writer = threading.Thread(target=self._write_loop, daemon=True)
        self._writer.start()

    def _write_loop(self) -> None:
        while True:
            frame = self.frames.get()
            if frame is None:
                return
            try:
                self.sock.sendall(frame)
            except OSError:
                return

    def send(self, frame: bytes) -> bool:
        """False → the queue is full (stalled client): caller should evict."""
        try:
            self.frames.put_nowait(frame)
            return True
        except queue.Full:
            return False

    def close(self) -> None:
        try:
            self.frames.put_nowait(None)
        except queue.Full:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


class QueueFullError(RuntimeError):
    """Bounded prompt queue is full — surfaced as HTTP 429 (backpressure)."""


class DrainingError(RuntimeError):
    """Host is draining (POST /drain): no new prompts are seated — surfaced
    as HTTP 503 so a fleet router places the prompt elsewhere."""


def default_host_id() -> str:
    """Stable-ish per-process host identity for the fleet tier: explicit
    $PA_HOST_ID wins (operators name their hosts); otherwise hostname+pid —
    unique across a fleet of processes, including several on one machine."""
    hid = os.environ.get("PA_HOST_ID")
    if hid:
        return hid
    import socket

    try:
        name = socket.gethostname()
    except OSError:
        name = "host"
    return f"{name}-{os.getpid()}"


class PromptQueue:
    """Prompt executor with ComfyUI-shaped bookkeeping.

    Default is the reference's schedule: ONE worker thread, prompts strictly
    serial. ``workers > 1`` runs that many prompt workers concurrently and
    installs a ``serving.ContinuousBatchingScheduler`` so the overlapping
    sampler runs share compiled step dispatches (per-bucket batching); each
    prompt executes under its own ``progress_scope`` — per-prompt progress
    hooks and a per-prompt cooperative Cancel event that doubles as the
    serving layer's per-lane cancel."""

    def __init__(self, class_mappings=None, output_dir: str | None = None,
                 workers: int | None = None, max_pending: int | None = None,
                 serving: bool | None = None, trace: bool | None = None,
                 host_id: str | None = None, role: str | None = None):
        if trace is None:
            trace = os.environ.get("PA_TRACE", "") not in ("", "0", "false")
        if trace:
            tracing.enable()
        if os.environ.get("PA_NUMERICS", "") not in ("", "0", "false"):
            # Numerics sentinel (utils/numerics.py): per-lane non-finite
            # quarantine + latent fingerprints on the serving path; off by
            # default (single flag check, zero overhead).
            from .utils import numerics

            numerics.enable()
        self.class_mappings = class_mappings
        self.output_dir = output_dir or os.environ.get("PA_OUTPUT_DIR", "output")
        # Fleet identity + drain state (pa-health/v3): host_id names this
        # process on a router's scoreboard; accepting=False (POST /drain)
        # stops seating new prompts while running lanes finish.
        self.host_id = host_id or default_host_id()
        # Role-pool membership (fleet/roles.py): which stage tier this host
        # serves — "all" (the default) keeps the pre-role single-pool
        # behavior bitwise; a specific role rides the registration
        # heartbeat and /health so the router pools it.
        from .fleet.roles import normalize_role

        self.role = normalize_role(role or os.environ.get("PA_ROLE"))
        self.accepting = True
        self._drain_source = None
        # Residency advertisement (pa-health/v3): model keys this host has
        # served — its warm compiled programs / pinned weights, in the same
        # fleet/router.model_key space the ring places on. A router replaying
        # a dead sibling's prompts prefers a host whose warm set covers the
        # key over a cold primary. LRU-bounded: insertion-ordered dict,
        # oldest evicted past the cap.
        self.warm_keys: dict[str, float] = {}  # guarded-by: _lock
        self._warm_cap = 64
        self.cache = WorkflowCache()
        self.pending: "queue.Queue[tuple | None]" = queue.Queue()
        self.pending_ids: list[str] = []  # guarded-by: _lock
        # pid → its per-prompt cooperative Cancel event (progress_scope).
        self.running: dict[str, threading.Event] = {}  # guarded-by: _lock
        self.history: dict[str, dict] = {}  # guarded-by: _lock
        # Output file (as GET /view resolves it) → the prompt that wrote it;
        # filled beside the history entry while the tracer is on, read by the
        # `http-view` span.
        self.output_owner: dict[str, str] = {}  # guarded-by: _lock
        self.counter = 0
        self._lock = threading.Lock()
        self._listeners: dict = {}  # socket → _WsListener — guarded-by: _lock
        self.workers = max(
            1, int(workers if workers is not None
                   else os.environ.get("PA_SERVER_WORKERS", "1"))
        )
        if max_pending is None:
            max_pending = int(os.environ.get("PA_MAX_PENDING", "0"))
        # 0 means unbounded on BOTH spellings (param/CLI and env var).
        self.max_pending = max_pending or None
        self.scheduler = None
        self.decode_queue = None
        enable_serving = self.workers > 1 if serving is None else serving
        if enable_serving:
            from .serving import ContinuousBatchingScheduler, DecodeQueue

            self.scheduler = ContinuousBatchingScheduler().install()
            # Batched tail decode (serving/decode.py): concurrent prompts'
            # VAE decodes batch into shared compiled dispatches instead of
            # serializing inline behind each other's denoise.
            self.decode_queue = DecodeQueue().install()
        elif self.role == "decode":
            # A dedicated DECODE-tier host is the width-bucketed batching
            # target even single-worker: the router funnels every pool
            # member's decode stages here, so cross-prompt batching is the
            # point of the role (serving/decode.py lingers for siblings).
            from .serving import DecodeQueue

            self.decode_queue = DecodeQueue().install()
        # Periodic HBM sampling (utils/telemetry.py): keeps the pa_hbm_*
        # gauges and the peak watermark fresh between /metrics scrapes so
        # GET /health reflects memory state even while a prompt is wedged.
        self._mem_monitor = None
        try:
            from .utils.telemetry import MemoryMonitor, watch_compiles

            watch_compiles()  # /health's compile section needs the listeners
            self._mem_monitor = MemoryMonitor(
                float(os.environ.get("PA_MEM_SAMPLE_S", "60"))
            ).start()
        except Exception:
            pass
        # Continuous telemetry (utils/timeseries.py + utils/anomaly.py):
        # the seeded-cadence history sampler snapshots every pa_* family
        # into the bounded ring and ticks the anomaly sentinel — a daemon
        # thread entirely off the hot step path. PA_HISTORY_BYTES=0
        # disables the whole layer (bitwise no-op).
        self._history_sampler = None
        try:
            from .utils import timeseries

            if timeseries.enabled():
                self._history_sampler = timeseries.HistorySampler(
                    host=self.host_id
                ).start()
        except Exception:
            pass
        # unguarded: written once here before the threads start, only
        # iterated afterwards (shutdown joins a snapshot-stable list)
        self._workers = [
            threading.Thread(target=self._run, daemon=True)
            for _ in range(self.workers)
        ]
        for t in self._workers:
            t.start()

    def add_listener(self, sock) -> "_WsListener":
        listener = _WsListener(sock)
        with self._lock:
            self._listeners[sock] = listener
        return listener

    def remove_listener(self, sock) -> None:
        with self._lock:
            listener = self._listeners.pop(sock, None)
        if listener is not None:
            listener.close()

    def _emit(self, event: dict) -> None:
        """Queue one JSON event to every /ws client — never blocks the
        caller (the worker thread must not wedge on a stalled client); a
        client whose bounded queue fills is evicted."""
        frame = _ws_frame(json.dumps(event).encode())
        with self._lock:
            listeners = list(self._listeners.items())
        for sock, listener in listeners:
            if not listener.send(frame):
                self.remove_listener(sock)

    def _emit_binary(self, payload: bytes) -> None:
        """Queue one binary event (the stock preview-frame channel: a 4-byte
        big-endian event type + event payload, sent as a binary WS frame)."""
        frame = _ws_frame(payload, opcode=0x2)
        with self._lock:
            listeners = list(self._listeners.items())
        for sock, listener in listeners:
            if not listener.send(frame):
                self.remove_listener(sock)

    def _emit_status(self) -> None:
        with self._lock:
            remaining = len(self.pending_ids)
        self._emit({
            "type": "status",
            "data": {"status": {"exec_info": {"queue_remaining": remaining}}},
        })

    def submit(self, prompt: dict, preview: bool = False,
               priority: int = 0, deadline_s: float | None = None,
               fleet: dict | None = None,
               stage: dict | None = None) -> tuple[str, int]:
        pid = uuid.uuid4().hex
        # Bookkeeping AND enqueue under one lock: interrupt() drains under the
        # same lock, so a submit racing an interrupt either lands wholly
        # before (and is dropped with a history entry) or wholly after (and
        # survives) — never half-registered.
        with self._lock:
            if not self.accepting:
                raise DrainingError(
                    f"host {self.host_id} is draining (no new prompts)"
                )
            if (self.max_pending is not None
                    and len(self.pending_ids) - len(self.running)
                    >= self.max_pending):
                from .utils.metrics import registry

                registry.counter("pa_server_rejected_total",
                                 help="prompts refused with 429 (queue full)")
                raise QueueFullError(
                    f"queue full ({self.max_pending} pending)"
                )
            self.counter += 1
            number = self.counter
            self.pending_ids.append(pid)
            # The enqueue clock rides the item: the worker's pickup delta is
            # the ADMISSION stage of the SLO latency decomposition.
            self.pending.put((pid, prompt, bool(preview), int(priority),
                              deadline_s, fleet, stage, time.monotonic()))
        self._emit_status()
        return pid, number

    def inflight_prompts(self) -> int:
        """Queued + running — the pa-health/v3 field a fleet scoreboard
        reads for saturation decisions (caller need not hold the lock)."""
        with self._lock:
            return len(self.pending_ids)

    def _mark_warm(self, prompt: dict) -> None:
        """Record the executed prompt's model key as warm (pa-health/v3).
        Best-effort: residency advertisement must never fail a prompt."""
        try:
            from .fleet.router import model_key

            key = model_key(prompt)
            with self._lock:
                self.warm_keys.pop(key, None)
                # palint: allow[observability] epoch STAMP on an advertised
                # surface (pa-health/v3 warm-key recency), not a duration
                self.warm_keys[key] = time.time()
                while len(self.warm_keys) > self._warm_cap:
                    self.warm_keys.pop(next(iter(self.warm_keys)))
        except Exception:  # noqa: BLE001
            pass

    def drain(self, source: str = "operator") -> dict:
        """Stop seating new prompts (POST /prompt → 503); running prompts
        and their serving lanes finish normally — the fleet drain state a
        router observes via /health ``accepting``. ``source`` records WHO
        drained (operator via POST /drain vs an automatic policy): only
        non-operator drains may be auto-resumed by the rejoin hook below.
        Returns the drain view."""
        with self._lock:
            self.accepting = False
            self._drain_source = source
            state = {"host_id": self.host_id, "accepting": False,
                     "pending": len(self.pending_ids) - len(self.running),
                     "running": len(self.running)}
        return state

    def resume(self) -> dict:
        """Re-open admission after a drain (elastic rejoin)."""
        with self._lock:
            self.accepting = True
            self._drain_source = None
            return {"host_id": self.host_id, "accepting": True}

    def resume_if_auto_drained(self) -> None:
        """The heartbeat rejoin hook: re-open admission ONLY when the drain
        was not operator-initiated — a router restart mid-maintenance must
        not silently cancel the operator's POST /drain (chaos-review
        finding, round 14). A host that fell off the ring while serving has
        accepting=True already, so this is a no-op for it."""
        with self._lock:
            if self.accepting or getattr(self, "_drain_source", None) == "operator":
                return
            self.accepting = True
            self._drain_source = None

    def _drop_pending(self, pid: str) -> None:  # palint: holds _lock
        """history + bookkeeping for a prompt cancelled before it ran
        (caller holds the lock)."""
        self.pending_ids.remove(pid)
        self.history[pid] = {
            "status": {"status_str": "interrupted", "completed": False,
                       "host_id": self.host_id},
            "outputs": {},
        }

    def interrupt(self) -> int:
        """Drop every pending prompt AND ask every running one to stop at its
        next boundary (per-prompt cooperative scope events — the ComfyUI
        Cancel semantics; a single compiled step still cannot be preempted
        mid-dispatch). Anything a worker popped before this drain counts as
        running."""
        dropped = 0
        with self._lock:
            while True:
                try:
                    item = self.pending.get_nowait()
                except queue.Empty:
                    break
                if item is None:  # preserve the shutdown sentinel
                    self.pending.put(None)
                    break
                if item[0] in self.pending_ids:  # not already cancel()ed
                    dropped += 1
                    self._drop_pending(item[0])
            # An id still pending but not running is an in-flight pop (the
            # worker took it off the queue but hasn't published running yet):
            # removing it here makes the worker's pending_ids check drop it —
            # the Cancel wins the race instead of losing it.
            for pid in [p for p in self.pending_ids if p not in self.running]:
                dropped += 1
                self._drop_pending(pid)
            # Each running prompt's own scope event: set under the SAME lock
            # the worker registers it under, so a Cancel can never land in
            # the window between pop and registration. Fresh event per prompt
            # — no stale-flag choreography needed.
            for evt in self.running.values():
                evt.set()
        if self.scheduler is not None:
            self.scheduler.kick()  # lanes notice the events at this boundary
        if dropped:
            self._emit_status()  # ws clients must see the queue shrink
        return dropped

    def clear_pending(self) -> int:
        """Drop every PENDING prompt atomically (running ones finish) — the
        stock ``POST /queue {"clear": true}`` semantics. One lock hold, so a
        prompt a worker picks up concurrently is never misclassified as
        pending-then-cancelled-running."""
        dropped = 0
        with self._lock:
            for pid in [p for p in self.pending_ids if p not in self.running]:
                self._drop_pending(pid)
                dropped += 1
        if dropped:
            self._emit_status()
        return dropped

    def cancel(self, pids) -> int:
        """Per-prompt Cancel (stock ``POST /queue {"delete": [...]}``):
        pending prompts drop with an interrupted history entry; running ones
        get their scope event set — the cooperative boundary check stops the
        graph, and the serving scheduler frees the prompt's lane at the next
        step boundary without perturbing co-batched neighbors."""
        acted = 0
        with self._lock:
            targets = set(str(p) for p in pids)
            running_hits = [p for p in targets if p in self.running]
            pending_hits = [
                p for p in targets
                if p in self.pending_ids and p not in self.running
            ]
            for pid in pending_hits:
                self._drop_pending(pid)
                acted += 1
            for pid in running_hits:
                self.running[pid].set()
                acted += 1
        if running_hits and self.scheduler is not None:
            self.scheduler.kick()
        if pending_hits:
            self._emit_status()
        return acted

    def shutdown(self) -> None:
        self.pending.put(None)  # workers cascade the sentinel to siblings
        for t in self._workers:
            t.join(timeout=30)
        if self._mem_monitor is not None:
            self._mem_monitor.stop()
        if self._history_sampler is not None:
            self._history_sampler.stop()
        if self.scheduler is not None:
            self.scheduler.uninstall()
            self.scheduler.shutdown()
        if self.decode_queue is not None:
            self.decode_queue.shutdown()

    def _run(self) -> None:
        turn_end = None
        while True:
            # From the end of one turn to the next item in hand, under the id
            # of the prompt whose start the wait delayed: with one closed-loop
            # client this is the client's turn (poll, /history, /view, POST)
            # seen from the chip's side. admission-wait ends inside it.
            with tracing.span("worker-idle", cat="server",
                              start_us=turn_end) as idle:
                item = self.pending.get()
                if item is not None:
                    idle.set(prompt_id=item[0])
            if item is None:
                self.pending.put(None)  # cascade to sibling workers
                return
            turn_end = self._turn(item)

    def _turn(self, item: tuple) -> float | None:
        """One turn of a worker: run the prompt, write its history entry.
        Returns the tracer's clock where the turn ended (None with the
        tracer off), which is where the worker's next wait starts."""
        pid, prompt, preview, priority, deadline_s, fleet, stage, enq_ts = item
        cancel_evt = threading.Event()
        with self._lock:
            if pid not in self.pending_ids:
                return None  # interrupted while queued
            # Publish under the same lock interrupt()/cancel() set events
            # under; the event is fresh per prompt, so a stale Cancel
            # aimed at a previous prompt cannot exist by construction.
            self.running[pid] = cancel_evt
        self._emit({"type": "execution_start", "data": {"prompt_id": pid}})
        t0 = time.monotonic()
        # SLO admission stage: ingress → worker pickup — the queue wait
        # a closed-loop client never inflates and an open-loop one does.
        admission_s = max(0.0, t0 - enq_ts)
        slo.observe_stage("admission", admission_s)
        picked_us = None
        if tracing.on():
            picked_us = tracing.now_us()
            tracing.record("admission-wait", picked_us - admission_s * 1e6,
                           admission_s * 1e6, cat="server",
                           prompt_id=pid)
        # Per-node `executing` + per-step `progress` events — the pair a
        # stock ComfyUI frontend renders its progress bars from. The node
        # id rides a cell so the progress hook can tag its events with
        # whichever node is currently executing.
        current: dict = {"node": None}

        def on_node(nid, _pid=pid, _cur=current):
            _cur["node"] = nid
            self._emit({
                "type": "executing",
                "data": {"node": nid, "prompt_id": _pid},
            })

        def hook(value, max_value, _pid=pid, _cur=current):
            self._emit({
                "type": "progress",
                "data": {"value": value, "max": max_value,
                         "prompt_id": _pid, "node": _cur["node"]},
            })

        def on_cached(nids, _pid=pid):
            self._emit({
                "type": "execution_cached",
                "data": {"nodes": list(nids), "prompt_id": _pid},
            })

        def preview_hook(latent):
            # Stock preview frame: >II event-type 1 (PREVIEW_IMAGE) +
            # image format 2 (PNG), then the PNG bytes. Never let a
            # preview failure (odd latent rank, PIL hiccup) kill the
            # prompt — previews are best-effort by contract.
            import struct

            try:
                from .utils.latent_preview import preview_png

                png = preview_png(latent)
            except Exception:  # noqa: BLE001 — preview is best-effort
                return
            self._emit_binary(struct.pack(">II", 1, 2) + png)

        from .serving.scheduler import serving_hints

        # Fault site (utils/faults.py): the straggler rehearsal — an
        # injected slow-host stalls the prompt worker, not the HTTP
        # surface, so health polls stay green while latency inflates
        # (exactly the failure the router's saturation spill must absorb).
        _slow = faults.check("slow-host", key=pid)
        if _slow is not None:
            _slow.sleep()
        # Role-pool staged dispatch (fleet/roles.py): a router hop
        # carrying extra_data.pa_stage executes ONE carved stage — the
        # stage's upstream-closure subgraph with the previous stage's
        # content-addressed outputs preseeded. A failed carve or handle
        # resolution degrades to executing the closure (or the whole
        # graph) locally — bitwise by the fold_in contract, never an
        # error.
        exec_graph, preseed, stage_entry = self._stage_setup(prompt, stage)
        # Inbound distributed-trace context (W3C traceparent shape,
        # injected by the fleet router into extra_data.fleet): parsed
        # here so this host's whole span subtree — prompt, node, lane,
        # step, decode — joins the router's cross-host trace under one
        # trace_id. Malformed/absent context degrades to local-only.
        tp = (tracing.parse_traceparent(fleet.get("traceparent"))
              if fleet and tracing.on() else None)
        # The rest of the turn lies under one of two spans: `prompt` from the
        # pickup (where admission-wait ends and exec_s starts) while the
        # graph runs, `prompt-finish` from where that closes, however it
        # closed, to the turn's end — outputs listed, history written,
        # events sent. The stack closes it even when that work raises. Each
        # starts on the clock reading its neighbour ended on, so no instant
        # of a worker's time lies under no span.
        root = finish = tracing._NULL
        with contextlib.ExitStack() as turn:
            try:
                try:
                    # The prompt span is the root of this prompt's trace
                    # timeline; prompt_id on the scope correlates log records
                    # and spans recorded anywhere on (or on behalf of) this
                    # thread.
                    with progress_scope(
                        hook=hook,
                        preview_hook=preview_hook if preview else None,
                        interrupt_event=cancel_evt,
                        prompt_id=pid,
                    ), serving_hints(priority=priority, deadline_s=deadline_s), \
                            tracing.trace_context(tp), \
                            tracing.span(
                                "prompt", cat="server", prompt_id=pid,
                                start_us=picked_us,
                                # Every span names its host + role: the stitched
                                # fleet timeline's per-tier filter keys.
                                host_id=self.host_id, role=self.role,
                                # Cross-hop correlation: a fleet router stamps
                                # its own prompt id into extra_data.fleet, so
                                # this backend-side timeline joins the router's
                                # fleet-prompt/fleet-hop spans in one export.
                                **({"origin_prompt_id": fleet.get("origin"),
                                    "router": fleet.get("router")}
                                   if fleet else {}),
                                **({"trace_id": tp["trace_id"],
                                    "parent_span_id": tp["parent_span_id"]}
                                   if tp else {}),
                                **({"stage": stage_entry["stage"]}
                                   if stage_entry is not None else {}),
                            ) as root:
                        if stage_entry is not None:
                            # Denoise hosts may pull conds straight off the
                            # encode tier (models/embed_cache.py remote tier).
                            from .models.embed_cache import set_remote_sources

                            set_remote_sources(
                                (stage or {}).get("sources") or ())
                        try:
                            results = run_workflow(
                                exec_graph, class_mappings=self.class_mappings,
                                outputs=self.cache, on_node=on_node,
                                on_cached=on_cached, preseed=preseed,
                            )
                        finally:
                            if stage_entry is not None:
                                from .models.embed_cache import set_remote_sources

                                set_remote_sources(None)
                finally:
                    finish = turn.enter_context(tracing.span(
                        "prompt-finish", cat="server", prompt_id=pid,
                        start_us=root.end))
                entry = {
                    "status": {"status_str": "success", "completed": True,
                               "exec_s": round(time.monotonic() - t0, 3)},
                    "outputs": self._image_outputs(prompt, results),
                }
                if stage_entry is not None:
                    # The stage hand-off: exported boundary outputs banked
                    # content-addressed; the router journals these handles
                    # as the prompt's stage lineage and preseeds them into
                    # the NEXT stage's dispatch.
                    entry["status"]["pa_stage"] = {
                        "stage": stage_entry["stage"],
                        "handles": self._stage_export(stage_entry, results),
                    }
                    from .utils.metrics import registry as _metrics

                    _metrics.histogram(
                        "pa_role_stage_seconds",
                        time.monotonic() - t0,
                        labels={"role": stage_entry["stage"]},
                        help="wall seconds of one carved stage execution "
                             "on a role-pool host")
                # This host now holds the prompt's model warm (compiled
                # programs + pinned weights) — advertise it (pa-health/v3).
                self._mark_warm(prompt)
                # Per-output-node `executed` events (what API clients collect
                # result images from without polling /history).
                for nid, out in entry["outputs"].items():
                    self._emit({
                        "type": "executed",
                        "data": {"node": nid, "output": out,
                                 "prompt_id": pid},
                    })
            except Interrupted:
                entry = {
                    "status": {"status_str": "interrupted", "completed": False},
                    "outputs": {},
                }
                self._emit({
                    "type": "execution_interrupted",
                    "data": {"prompt_id": pid, "node_id": current["node"]},
                })
            except Exception as e:  # noqa: BLE001 — failures land in history
                entry = {
                    "status": {"status_str": "error", "completed": False,
                               "message": f"{type(e).__name__}: {e}"},
                    "outputs": {},
                }
                # Flight recorder: an OOM (or any error under
                # PA_POSTMORTEM=always) dumps a forensics bundle and hands
                # the client its path in the history entry — the next
                # serving-on-hardware failure is diagnosable after the fact.
                try:
                    from .utils.telemetry import (
                        looks_like_oom,
                        write_postmortem,
                    )

                    if (looks_like_oom(e)
                            or os.environ.get("PA_POSTMORTEM") == "always"):
                        bundle = write_postmortem(f"prompt-{pid}", error=e)
                        if bundle:
                            entry["status"]["postmortem"] = bundle
                except Exception:  # noqa: BLE001 — forensics is best-effort
                    pass
            # Every history entry names the host that produced it — the
            # fleet tier's per-host latency attribution rides this field
            # (scripts/loadgen.py groups client latencies by it).
            entry["status"]["host_id"] = self.host_id
            # SLO request residency: admission wait + execution — the
            # server-observable part of the client's end-to-end latency
            # (the client-side remainder is loadgen's "collect" residual).
            slo.observe_request(admission_s + (time.monotonic() - t0))
            if tracing.on():
                # Completed-prompt retention: the fleet stitcher may collect
                # this prompt's spans long after the live rings wrapped.
                tracing.retain_prompt(pid)
            with self._lock:
                self.history[pid] = entry
                if tracing.on():
                    # Whose output each file is, for GET /view's span.
                    for out in entry["outputs"].values():
                        for img in out["images"]:
                            self.output_owner[self._output_path(
                                img["subfolder"], img["filename"])] = pid
                if pid in self.pending_ids:
                    self.pending_ids.remove(pid)
                # The per-prompt Cancel event retires with the prompt: a
                # Cancel that landed after the last cooperative checkpoint
                # dies with this entry instead of leaking into the next
                # prompt (the fresh-event-per-prompt discipline).
                self.running.pop(pid, None)
            # The canonical completion signal ComfyUI API clients block on.
            self._emit({
                "type": "executing", "data": {"node": None, "prompt_id": pid},
            })
            self._emit_status()
        return finish.end

    def _stage_setup(self, prompt: dict, stage) -> tuple:
        """(exec_graph, preseed, stage_entry) for one staged dispatch.

        Re-derives the carve locally (host.carve_stages is deterministic, so
        router and backend always agree on the cut) and resolves the
        dispatch's handles: local stage store first, then the peer hosts the
        router listed. An unresolvable handle is simply not preseeded — the
        stage's upstream-closure graph recomputes that prefix locally,
        bitwise by fold_in. Unstaged prompts (or a carve the backend can't
        reproduce) fall back to the whole graph."""
        if not isinstance(stage, dict) or not stage.get("stage"):
            return prompt, None, None
        try:
            from .host import carve_stages

            plan = carve_stages(prompt)
        except Exception:
            plan = None
        stage_entry = None
        for st in (plan or {}).get("stages", ()):
            if st["stage"] == stage.get("stage"):
                stage_entry = st
                break
        if stage_entry is None:
            return prompt, None, None
        from .fleet import roles as fleet_roles
        from .utils.metrics import registry as _metrics

        handles = {str(k): v for k, v in (stage.get("handles") or {}).items()}
        sources = [str(b).rstrip("/") for b in (stage.get("sources") or ())]
        preseed: dict[str, tuple] = {}
        needs = {str(n) for n in stage_entry["needs"]}
        # Every carried handle that names a node in this closure preseeds,
        # not just the declared needs: the closure includes the whole
        # upstream prefix, and any resolved boundary inside it
        # short-circuits its subtree (a decode host must not re-run the
        # encoder class because the closure names the encode node). A miss
        # only counts for a NEEDS node — those are the ones whose absence
        # forces a prefix recompute.
        for nid in sorted(set(handles) | needs):
            if nid not in stage_entry["graph"]:
                continue
            key = handles.get(nid)
            value = fleet_roles.store.get_value(key) if key else None
            if value is None and key:
                value = self._fetch_stage_value(key, sources)
            if value is None:
                if nid in needs:
                    _metrics.counter(
                        "pa_role_handle_misses",
                        help="stage hand-off handles that resolved nowhere "
                             "(prefix recomputed locally)")
                continue
            _metrics.counter(
                "pa_role_handle_hits",
                help="stage hand-off handles resolved from the local or "
                     "peer stage store")
            preseed[nid] = tuple(value)
        return stage_entry["graph"], preseed, stage_entry

    def _fetch_stage_value(self, key: str, sources):
        """One handle off a peer's ``GET /stage/{key}``; the blob is banked
        in the local store too (this host serves it onward — takeover
        re-dispatches can land anywhere in the pool). None on any failure."""
        if not sources:
            return None
        import urllib.request

        from .fleet import roles as fleet_roles

        for base in sources:
            try:
                with urllib.request.urlopen(
                    f"{base}/stage/{key}", timeout=10
                ) as r:
                    blob = r.read()
                value = fleet_roles.deserialize_value(blob)
            except Exception:
                continue
            fleet_roles.store.put(blob)
            return value
        return None

    def _stage_export(self, stage_entry: dict, results: dict) -> dict:
        """Bank this stage's boundary outputs content-addressed; returns
        ``{node_id: content_key}`` — the handles the history entry carries
        and the journal's stage lineage records. Unserializable outputs are
        skipped (the next stage recomputes them), never an error."""
        from .fleet import roles as fleet_roles

        handles: dict[str, str] = {}
        for nid in stage_entry["exports"]:
            out = results.get(nid)
            if out is None:
                continue
            key = fleet_roles.store.put_value(out)
            if key:
                handles[nid] = key
        return handles

    def _output_path(self, subfolder: str, filename: str) -> str:
        """Where GET /view looks for a history entry's image."""
        return os.path.normpath(
            os.path.join(self.output_dir, subfolder, filename))

    def _image_outputs(self, prompt: dict, results: dict) -> dict:
        """ComfyUI history shape: per save-node ``{"images": [{filename,
        subfolder, type}]}`` — detected as outputs whose first element is a
        list of existing file paths (what the SaveImage family returns)."""
        out: dict[str, dict] = {}
        for nid in prompt:
            vals = results.get(str(nid))
            if not vals or not isinstance(vals[0], (list, tuple)):
                continue
            paths = [p for p in vals[0]
                     if isinstance(p, str) and os.path.exists(p)]
            if not paths:
                continue
            images = []
            for p in paths:
                rel = os.path.relpath(p, self.output_dir)
                sub, fname = os.path.split(rel)
                if sub.startswith(".."):
                    # Saved outside output_dir: /view's escape check would 403
                    # exactly this path, so advertising it would hand clients
                    # an unfetchable record — omit it from the history.
                    continue
                images.append(
                    {"filename": fname, "subfolder": sub, "type": "output"}
                )
            if images:
                out[str(nid)] = {"images": images}
        return out


class _Handler(BaseHTTPRequestHandler):
    q: PromptQueue  # injected by make_server
    # RFC 6455 §4 handshakes require an HTTP/1.1 status line — browsers and
    # strict WS clients reject 'HTTP/1.0 101'. (Every response sets
    # Content-Length, which HTTP/1.1 keep-alive needs.)
    protocol_version = "HTTP/1.1"
    # Every response is two small writes (buffered headers, then body);
    # with Nagle on, the body write can stall ~40ms behind the peer's
    # delayed ACK — tens of ms on every /history poll and /prompt hop,
    # which the fleet router pays per prompt. TCP_NODELAY it.
    disable_nagle_algorithm = True

    # The span of the route being served, where the route has one.
    _span = tracing._NULL

    def log_message(self, fmt, *args):  # quiet by default
        pass

    def _route(self, name: str, prompt_id: str | None = None):
        """The span of one of the three routes a prompt's client calls
        (``http-prompt``, ``http-history``, ``http-view``): live on this
        handler's thread from here to the response written, stamped by
        ``_send`` with ``status`` and ``bytes``. A handler's thread lives for
        one connection, so it records into the tracer's shared ring. The
        harness's and an operator's scrapes (/metrics, /trace, /health,
        /queue, /ws) are no prompt's work and have none."""
        self._span = tracing.shared_span(name, cat="server",
                                         prompt_id=prompt_id)
        return self._span

    def _send(self, code: int, payload, content_type="application/json"):
        body = (json.dumps(payload).encode()
                if content_type == "application/json" else payload)
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)
        self._span.set(status=code, bytes=len(body))

    def _http_fault(self) -> bool:
        """Fault site (utils/faults.py ``backend-http``): per-request
        drop/delay/5xx keyed on ``METHOD /path``. Returns True when the
        request was consumed (the caller must not answer it) — the chaos
        rehearsal for half-dead backends whose sockets misbehave while the
        process lives. No-op (one flag read) when no plan is armed."""
        act = faults.check("backend-http", key=f"{self.command} {self.path}")
        if act is None:
            return False
        if act.mode == "delay":
            act.sleep()
            return False
        if act.mode == "drop":
            # Vanish mid-request: the peer sees a reset/EOF, exactly like a
            # crashed host — the router's OSError handling must absorb it.
            import socket as _socket

            self.close_connection = True
            try:
                self.connection.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
            return True
        act.sleep()  # 5xx (default): alive but failing
        self._send(500, {"error": f"injected fault (site=backend-http, "
                                  f"hit={act.hit})"})
        return True

    def do_GET(self):  # noqa: N802 — http.server API
        self._span = tracing._NULL  # a kept-alive connection's last route's
        if self._http_fault():
            return
        url = urlparse(self.path)
        parts = [p for p in url.path.split("/") if p]
        if url.path == "/ws":
            return self._serve_websocket()
        if url.path == "/queue":
            with self.q._lock:
                running = list(self.q.running)
                pend = [p for p in self.q.pending_ids if p not in self.q.running]
            return self._send(
                200, {"queue_running": running, "queue_pending": pend}
            )
        if url.path == "/metrics":
            from .utils.metrics import registry

            with self.q._lock:
                registry.gauge("pa_server_queue_pending",
                               len(self.q.pending_ids) - len(self.q.running),
                               help="prompts queued, not yet running")
                registry.gauge("pa_server_running", len(self.q.running),
                               help="prompts executing right now")
            try:
                # Scrape-time refresh of the pa_hbm_* device gauges (the
                # periodic monitor keeps them warm between scrapes; a dead
                # device backend degrades to the last published values).
                from .devices.memory import publish_memory_gauges

                publish_memory_gauges()
            except Exception:
                pass
            try:
                # pa_numerics_* gauges (utils/numerics.py): published at
                # scrape time so a healthy server exposes explicit zeros,
                # not absent series.
                from .utils import numerics

                numerics.sentinel.publish_gauges()
            except Exception:
                pass
            try:
                # pa_roofline_* gauges (utils/roofline.py): per-program
                # calibrated predictions, plus the live trace window's
                # attribution fractions (comms / host-gap / compute /
                # exposed-transfer) when tracing is on — what
                # scripts/loadgen.py surfaces in its summary.
                from .utils import roofline

                roofline.publish_gauges()
            except Exception:
                pass
            try:
                # pa_slo_* burn-rate/budget gauges (utils/slo.py): windowed
                # objective verdicts published at scrape time — the
                # histograms carry lifetime counts, the gauges the window.
                slo.registry.publish_gauges()
            except Exception:
                pass
            try:
                # pa_embed_cache_* gauges (models/embed_cache.py): published
                # at scrape time so a fresh server exposes explicit zeros —
                # loadgen diffs them into embed_cache_hit_rate.
                from .models.embed_cache import cache as _embed_cache

                _embed_cache.publish_gauges()
            except Exception:
                pass
            try:
                # pa_role_stage_store_* gauges (fleet/roles.py): the
                # content-addressed stage hand-off store's residency.
                from .fleet.roles import store as _stage_store

                _stage_store.publish_gauges()
            except Exception:
                pass
            try:
                # pa_anomaly_* gauges (utils/anomaly.py): explicit zeros
                # for every quiet watched signal, 1 while firing — the
                # other families' scrape-time publish discipline.
                from .utils import anomaly

                anomaly.sentinel.publish_gauges()
            except Exception:
                pass
            return self._send(
                200, registry.render().encode(),
                content_type="text/plain; version=0.0.4; charset=utf-8",
            )
        if url.path == "/metrics/history":
            # The continuous-telemetry window (pa-history/v1): the bounded
            # ring's per-family points, readable while an incident is
            # happening — ?window= (seconds) and ?family= (comma name
            # prefixes) subset it. Disabled (PA_HISTORY_BYTES=0) serves an
            # empty, explicitly-disabled document rather than 404ing.
            from .utils import timeseries

            qs = parse_qs(url.query)
            try:
                window = qs.get("window", [None])[0]
                window = None if window in (None, "") else float(window)
            except ValueError:
                return self._send(400, {"error": "window must be seconds"})
            doc = timeseries.ring.window(
                window_s=window, families=qs.get("family", [None])[0]
            )
            doc["host"] = self.q.host_id
            return self._send(200, doc)
        if url.path == "/health":
            from .serving.bucket import batched_fraction
            from .utils.telemetry import health_snapshot

            with self.q._lock:
                queue = {
                    "pending": len(self.q.pending_ids) - len(self.q.running),
                    "running": len(self.q.running),
                    "workers": self.q.workers,
                    "max_pending": self.q.max_pending,
                    "completed": len(self.q.history),
                    "serving": self.q.scheduler is not None,
                    # Lane-steps served via shared dispatch / total — how
                    # much of the step traffic actually co-batched.
                    "serving_batched_fraction": round(batched_fraction(), 4),
                }
                # pa-health/v3 (fleet tier): identity + admission state a
                # router's scoreboard reads straight off this document — no
                # extra endpoint. v3 adds ``warm_keys`` (model residency:
                # which placement keys this host serves warm — the router's
                # failover re-dispatch prefers a warm sibling over a cold
                # primary); every v2 field is unchanged.
                host = {
                    "host_id": self.q.host_id,
                    "accepting": self.q.accepting,
                    "inflight_prompts": len(self.q.pending_ids),
                    "warm_keys": list(self.q.warm_keys),
                    # Role-pool membership (fleet/roles.py) — the scoreboard
                    # reads it so statically configured --backends hosts
                    # pool correctly without ever heartbeating.
                    "role": self.q.role,
                }
            return self._send(200, health_snapshot(queue=queue, host=host))
        if url.path == "/trace":
            # Chrome/Perfetto trace-event JSON (open at ui.perfetto.dev).
            # With tracing disabled the export is empty — the body says so
            # instead of 404ing, so a client can tell "off" from "no spans".
            qs = parse_qs(url.query)
            prompt_id = qs.get("prompt_id", [None])[0]
            trace = tracing.export(prompt_id=prompt_id)
            trace["enabled"] = tracing.on()
            # Stitch metadata (round 21): who this export belongs to — the
            # fleet collector labels the track and aligns the clock domain
            # off these (epoch_wall_s rides tracing.export itself).
            trace["host_id"] = self.q.host_id
            trace["role"] = self.q.role
            return self._send(200, trace)
        if parts and parts[0] == "history":
            # Read under the queue lock: the worker thread inserts entries
            # under it, and json.dumps over a dict mutated mid-iteration raises
            # RuntimeError and aborts the connection. (Entries are written once
            # at insert, so a shallow copy is a consistent view.)
            if len(parts) == 2:
                with self.q._lock:
                    entry = self.q.history.get(parts[1])
                if not entry:
                    # A poll that misses records nothing: at a poll every
                    # 20 ms the misses would be nearly all the spans there
                    # are, and they fall while the device is busy. So the
                    # span of the poll that hits opens after this lookup.
                    return self._send(200, {})
                with self._route("http-history", prompt_id=parts[1]):
                    return self._send(200, {parts[1]: entry})
            with self.q._lock:
                snap = dict(self.q.history)
            return self._send(200, snap)
        if url.path == "/view":
            with self._route("http-view") as sp:
                qs = parse_qs(url.query)
                path = self.q._output_path(qs.get("subfolder", [""])[0],
                                           qs.get("filename", [""])[0])
                if tracing.on():
                    with self.q._lock:
                        owner = self.q.output_owner.get(path)
                    if owner is not None:
                        sp.set(prompt_id=owner)
                base = os.path.abspath(self.q.output_dir)
                if not os.path.abspath(path).startswith(base + os.sep):
                    return self._send(403,
                                      {"error": "path escapes output dir"})
                if not os.path.exists(path):
                    return self._send(404, {"error": "not found"})
                with open(path, "rb") as f:
                    return self._send(200, f.read(),
                                      content_type="image/png")
        if parts and parts[0] == "object_info":
            from .nodes import NODE_CLASS_MAPPINGS, NODE_DISPLAY_NAME_MAPPINGS

            classes = dict(NODE_CLASS_MAPPINGS)
            classes.update(self.q.class_mappings or {})
            names = [parts[1]] if len(parts) == 2 else list(classes)
            info = {}
            for name in names:
                cls = classes.get(name)
                if cls is None:
                    continue
                info[name] = {
                    "input": _jsonable(cls.INPUT_TYPES()),
                    "output": _jsonable(list(cls.RETURN_TYPES)),
                    "output_name": _jsonable(
                        list(getattr(cls, "RETURN_NAMES", None)
                             or cls.RETURN_TYPES)
                    ),
                    "name": name,
                    "display_name": NODE_DISPLAY_NAME_MAPPINGS.get(name, name),
                    "description": getattr(cls, "DESCRIPTION", ""),
                    "category": getattr(cls, "CATEGORY", ""),
                }
            if len(parts) == 2 and not info:
                return self._send(404, {"error": f"unknown node {parts[1]!r}"})
            return self._send(200, info)
        if url.path == "/system_stats":
            from .devices.discovery import available_devices

            return self._send(200, {"devices": available_devices()})
        if parts and parts[0] == "embed" and len(parts) == 2:
            # Remote embed tier (models/embed_cache.py): an encode host
            # serves its content-addressed encoder outputs to denoise-pool
            # peers. 404 is a MISS, not an error — the peer encodes locally.
            from .models.embed_cache import export_blob

            blob = export_blob(parts[1])
            if blob is None:
                return self._send(404, {"error": "no such embed key"})
            return self._send(200, blob,
                              content_type="application/octet-stream")
        if parts and parts[0] == "stage" and len(parts) == 2:
            # Stage hand-off store (fleet/roles.py): serve one boundary
            # value (conds out of encode, latents out of denoise) to the
            # host running the next stage. 404 = miss = peer recomputes.
            from .fleet.roles import store as _stage_store

            blob = _stage_store.get(parts[1])
            if blob is None:
                return self._send(404, {"error": "no such stage key"})
            return self._send(200, blob,
                              content_type="application/octet-stream")
        return self._send(404, {"error": f"no route {url.path}"})

    def _serve_websocket(self):
        """RFC 6455 upgrade + event push. The thread parks reading client
        frames (ping → pong, close → exit) while PromptQueue._emit writes
        events to the raw socket from the worker thread."""
        key = self.headers.get("Sec-WebSocket-Key")
        if self.headers.get("Upgrade", "").lower() != "websocket" or not key:
            return self._send(400, {"error": "expected a WebSocket upgrade"})
        accept = base64.b64encode(
            hashlib.sha1((key + _WS_GUID).encode()).digest()
        ).decode()
        sock = self.connection
        # Register BEFORE the 101 goes out: a client that POSTs /prompt the
        # instant its handshake completes must not race past an unregistered
        # listener and miss the prompt's events (TCP buffers anything queued
        # before the client starts reading).
        listener = self.q.add_listener(sock)
        self.send_response(101, "Switching Protocols")
        self.send_header("Upgrade", "websocket")
        self.send_header("Connection", "Upgrade")
        self.send_header("Sec-WebSocket-Accept", accept)
        self.end_headers()
        self.wfile.flush()
        self.close_connection = True
        try:
            while True:
                frame = _ws_read_frame(self.rfile)
                if frame is None or frame[0] == 0x8:  # EOF / close
                    return
                if frame[0] == 0x9:  # ping → pong, via the single writer
                    listener.send(_ws_frame(frame[1], opcode=0xA))
        except OSError:
            return
        finally:
            self.q.remove_listener(sock)

    def do_POST(self):  # noqa: N802 — http.server API
        self._span = tracing._NULL  # a kept-alive connection's last route's
        if self._http_fault():
            return
        url = urlparse(self.path)
        if url.path == "/interrupt":
            return self._send(200, {"dropped": self.q.interrupt()})
        if url.path == "/drain":
            # Fleet drain: stop seating (POST /prompt → 503), finish running
            # lanes; {"resume": true} re-opens admission (elastic rejoin).
            try:
                length = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(length) or b"{}")
            except (ValueError, json.JSONDecodeError) as e:
                return self._send(400, {"error": f"bad JSON: {e}"})
            if payload.get("resume"):
                return self._send(200, self.q.resume())
            return self._send(200, self.q.drain())
        if url.path == "/queue":
            # Stock per-prompt cancel: {"delete": [prompt_id, ...]} — routed
            # through the per-prompt scope event, which the serving layer's
            # lanes also watch ({"clear": true} drops every pending prompt).
            try:
                length = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(length) or b"{}")
            except (ValueError, json.JSONDecodeError) as e:
                return self._send(400, {"error": f"bad JSON: {e}"})
            deleted = 0
            if payload.get("clear"):
                # Stock clear: every PENDING prompt drops; running ones finish.
                deleted += self.q.clear_pending()
            targets = payload.get("delete")
            if targets is not None:
                if not isinstance(targets, (list, tuple)):
                    return self._send(
                        400, {"error": '"delete" must be a list of prompt ids'}
                    )
                deleted += self.q.cancel(targets)
            return self._send(200, {"deleted": deleted})
        if url.path == "/prompt":
            with self._route("http-prompt"):
                return self._post_prompt()
        if url.path == "/history/phase":
            # Declared load-phase stamp (utils/timeseries.py): loadgen's
            # open-loop rungs announce themselves so the anomaly sentinel
            # attributes the rate ramp instead of paging on it.
            try:
                length = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(length) or b"{}")
            except (ValueError, json.JSONDecodeError) as e:
                return self._send(400, {"error": f"bad JSON: {e}"})
            label = payload.get("label")
            if not label:
                return self._send(400, {"error": "label required"})
            from .utils import timeseries

            timeseries.ring.mark_phase(
                str(label), state=str(payload.get("state") or "begin"),
                detail=payload.get("detail"),
            )
            return self._send(200, {"ok": True})
        if url.path == "/upload/image":
            return self._upload_image()
        return self._send(404, {"error": f"no route {url.path}"})

    def _post_prompt(self):
        """``POST /prompt``: the body read and parsed, the prompt submitted,
        the reply written — under the handler's ``http-prompt`` span, which
        takes the prompt's id once ``submit`` has made it."""
        try:
            length = int(self.headers.get("Content-Length", 0))
            payload = json.loads(self.rfile.read(length) or b"{}")
            prompt = payload.get("prompt")
            if not isinstance(prompt, dict) or not prompt:
                return self._send(
                    400, {"error": "body must carry a non-empty "
                                   '{"prompt": {...}} graph'}
                )
        except (ValueError, json.JSONDecodeError) as e:
            return self._send(400, {"error": f"bad JSON: {e}"})
        extra = payload.get("extra_data") or {}
        preview = bool(extra.get("preview") or payload.get("preview"))
        try:
            deadline_s = extra.get("deadline_s")
            fleet = extra.get("fleet")
            stage = extra.get("pa_stage")
            pid, number = self.q.submit(
                prompt, preview=preview,
                priority=int(extra.get("priority") or 0),
                deadline_s=None if deadline_s is None else float(deadline_s),
                fleet=fleet if isinstance(fleet, dict) else None,
                stage=stage if isinstance(stage, dict) else None,
            )
        except DrainingError as e:
            return self._send(503, {"error": str(e)})
        except QueueFullError as e:
            return self._send(429, {"error": str(e)})
        except (TypeError, ValueError) as e:
            return self._send(400, {"error": f"bad extra_data: {e}"})
        self._span.set(prompt_id=pid)
        return self._send(200, {"prompt_id": pid, "number": number})

    def _upload_image(self):
        """Stock ``POST /upload/image``: multipart form with an ``image``
        file part (+ optional ``overwrite``) saved into the input directory
        ($PA_INPUT_DIR — the folder LoadImage resolves against), response
        ``{"name", "subfolder", "type"}`` exactly as API clients expect."""
        import email
        import email.policy
        import os
        import re

        ctype = self.headers.get("Content-Type", "")
        if "multipart/form-data" not in ctype:
            return self._send(400, {"error": "multipart/form-data required"})
        try:
            length = int(self.headers.get("Content-Length", 0))
        except ValueError:
            length = -1
        # Stock image uploads are MBs; a tight cap bounds the per-thread
        # buffering (body + parsed copy) on a host that also serves models.
        if length <= 0 or length > 64 * 1024 * 1024:
            return self._send(400, {"error": "bad Content-Length"})
        body = self.rfile.read(length)
        msg = email.message_from_bytes(
            b"Content-Type: " + ctype.encode() + b"\r\n\r\n" + body,
            policy=email.policy.HTTP,
        )
        image_part = None
        overwrite = False
        for part in msg.iter_parts():
            name = part.get_param("name", header="content-disposition")
            if name == "image":
                image_part = part
            elif name == "overwrite":
                overwrite = (part.get_content() or "").strip().lower() in (
                    "1", "true", "yes")
        if image_part is None:
            return self._send(400, {"error": "no 'image' file part"})
        filename = image_part.get_filename() or "upload.png"
        # Flatten any path the client sent; keep a safe basename only, and
        # never a dot-name/empty result (open("input/..") would explode).
        filename = re.sub(r"[^A-Za-z0-9._-]", "_", os.path.basename(filename))
        if filename.strip("._") == "":
            filename = "upload.png"
        payload = image_part.get_payload(decode=True)
        if not payload:
            return self._send(400, {"error": "empty image payload"})
        in_dir = os.environ.get("PA_INPUT_DIR", "input")
        os.makedirs(in_dir, exist_ok=True)
        stem, ext = os.path.splitext(filename)
        path = os.path.join(in_dir, filename)
        if overwrite:
            with open(path, "wb") as f:
                f.write(payload)
        else:
            # Stock dedupe: suffix (1), (2), …; O_EXCL ("xb") makes the
            # pick-and-write atomic under the threaded server.
            i = 0
            while True:
                try:
                    with open(path, "xb") as f:
                        f.write(payload)
                    break
                except FileExistsError:
                    i += 1
                    filename = f"{stem} ({i}){ext}"
                    path = os.path.join(in_dir, filename)
        return self._send(200, {"name": filename, "subfolder": "",
                                "type": "input"})


class _HTTPServer(ThreadingHTTPServer):
    # http.server's default listen backlog is 5 — a fleet router's poll
    # traffic (history proxies + health polls + heartbeats, each a fresh
    # connection) overflows that in bursts and dispatch POSTs get
    # connection-reset, costing spurious failover retries.
    request_queue_size = 128


def make_server(
    host: str = "127.0.0.1",
    port: int = 8188,
    class_mappings=None,
    output_dir: str | None = None,
    workers: int | None = None,
    max_pending: int | None = None,
    serving: bool | None = None,
    trace: bool | None = None,
    host_id: str | None = None,
    role: str | None = None,
) -> tuple[ThreadingHTTPServer, PromptQueue]:
    """Build (but don't start) the HTTP server + its prompt queue. Port 0
    picks an ephemeral port (tests); ``server.server_address`` has the real
    one. ``workers > 1`` (or $PA_SERVER_WORKERS) executes prompts
    concurrently and installs the continuous-batching scheduler;
    ``max_pending`` (or $PA_MAX_PENDING) bounds the queue (429 beyond it);
    ``trace`` (or $PA_TRACE=1) turns the span tracer on so ``GET /trace``
    serves per-prompt timelines; ``host_id`` (or $PA_HOST_ID) names this
    process on a fleet router's scoreboard (pa-health/v3)."""
    q = PromptQueue(class_mappings=class_mappings, output_dir=output_dir,
                    workers=workers, max_pending=max_pending, serving=serving,
                    trace=trace, host_id=host_id, role=role)
    handler = type("Handler", (_Handler,), {"q": q})
    srv = _HTTPServer((host, port), handler)
    return srv, q


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8188)
    ap.add_argument("--output-dir", default=None)
    ap.add_argument("--workers", type=int, default=None,
                    help="concurrent prompt workers (>1 enables continuous "
                         "batching; default $PA_SERVER_WORKERS or 1)")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="bounded queue depth — 429 beyond it "
                         "(default $PA_MAX_PENDING or unbounded)")
    ap.add_argument("--trace", action="store_true", default=None,
                    help="enable span tracing (GET /trace serves "
                         "Chrome/Perfetto trace JSON; default $PA_TRACE)")
    ap.add_argument("--host-id", default=None,
                    help="fleet identity on a router's scoreboard "
                         "(default $PA_HOST_ID or hostname-pid)")
    ap.add_argument("--role", default=None,
                    choices=["all", "encode", "denoise", "decode"],
                    help="role-pool membership (fleet/roles.py): which "
                         "stage tier this host serves — rides the "
                         "registration heartbeat and /health (default "
                         "$PA_ROLE or 'all', every pool)")
    ap.add_argument("--fleet-router", default=None,
                    help="router base URL(s), comma-separated (or "
                         "$PA_FLEET_ROUTER): register this host via "
                         "heartbeats so it joins the ring elastically and "
                         "drops out when it dies. List EVERY router of an "
                         "HA pair (primary + standby): a standby that takes "
                         "over must already know the fleet's membership")
    ap.add_argument("--advertise", default=None,
                    help="base URL the ROUTER should reach this host at "
                         "(default http://<host>:<port>)")
    args = ap.parse_args()
    if args.output_dir:
        # The save nodes resolve their root from the environment; without
        # this the images land outside the directory /view serves.
        os.environ["PA_OUTPUT_DIR"] = args.output_dir
    from .devices.discovery import default_device
    from .utils.compile_cache import enable_compilation_cache
    from .utils.logging import get_logger

    cache_dir = enable_compilation_cache()
    dev = default_device()
    get_logger().info("compute device %s (%s); compile cache at %s",
                      dev, dev.device_kind, cache_dir)
    srv, q = make_server(args.host, args.port, output_dir=args.output_dir,
                         workers=args.workers, max_pending=args.max_pending,
                         trace=args.trace, host_id=args.host_id,
                         role=args.role)
    heartbeats = []
    router_base = args.fleet_router or os.environ.get("PA_FLEET_ROUTER")
    if router_base:
        from .fleet.registry import HeartbeatClient

        # A wildcard bind is not a reachable address — advertise the host's
        # name instead (or let --advertise override for NAT/containers).
        reach = args.host
        if reach in ("0.0.0.0", "::", ""):
            import socket

            try:
                reach = socket.gethostname()
            except OSError:
                reach = "127.0.0.1"
        advertise = args.advertise or (
            f"http://{reach}:{srv.server_address[1]}"
        )
        # One heartbeat client PER router: an HA pair's standby must hold
        # live membership BEFORE its takeover (round-14 chaos finding: a
        # promoted standby that only ever heard of backends through the dead
        # primary has an empty ring and 503s everything).
        for rb in (b for b in router_base.split(",") if b):
            heartbeats.append(HeartbeatClient(
                rb, q.host_id, advertise,
                interval_s=float(os.environ.get("PA_FLEET_HEARTBEAT_S", "2")),
                # Rejoin after falling off the ring (router restart /
                # standby takeover / our own heartbeats lost): re-open
                # admission so the returning host takes traffic again — a
                # host that expired off the ring mid-drain would otherwise
                # rejoin refusing forever.
                on_rejoin=q.resume_if_auto_drained,
                role=q.role,
            ).start())
    # palint: allow[observability] server startup banner (CLI surface)
    print(f"ParallelAnything workflow server on http://{args.host}:{args.port}")
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        for hb in heartbeats:
            hb.stop()
        q.shutdown()


if __name__ == "__main__":
    main()
