"""End-to-end span tracing: per-prompt timelines from HTTP ingress to TPU step.

The reference's observability is ~40 ``[ParallelAnything]`` print sites and
"read s/it off the progress bar" (SURVEY §5.1, §5.5). This reproduction has
far more moving parts — weight-streaming prefetch rings, continuous-batching
lane lifecycles, per-thread progress scopes — and every open ROADMAP item
("measure flux_stream on hardware", "measure serving latency on hardware")
is blocked on being able to *see* where time goes. This module is that layer:
a process-wide :class:`Tracer` producing per-prompt traces of nested spans

    prompt → workflow-node → sampler-run → lane-wait → step → denoise
                                              → stream-stage-{prefetch,compute}

exported in Chrome/Perfetto trace-event JSON (``GET /trace?prompt_id=...`` on
the server, ``--trace-out`` on bench.py, ``scripts/trace_summary.py`` offline).

Design rules (the near-zero-overhead contract):

- **disabled is a single flag check**: :func:`span` returns one shared
  ``_NULL`` singleton when tracing is off — no Span object, no clock read, no
  buffer touch. Instrumentation sites that must *compute* attributes guard on
  :func:`on` first.
- **recording is lock-free per thread**: every recording thread owns its own
  ring buffer (a bounded ``deque`` — old spans fall off instead of growing
  without bound); the tracer's lock is taken only once per thread, at
  registration, and at export (which snapshots the per-thread deques).
- **prompt correlation rides the progress scopes**: a span opened with
  ``prompt_id=...`` establishes the thread's current prompt; nested spans
  inherit it, and threads that carry no span context fall back to the
  per-thread ``utils.progress`` scope (the serving scheduler captures the
  submitting thread's identity at admission, so lane-wait/step spans recorded
  from the dispatcher thread land on the *prompt's* timeline).
- **cross-thread spans carry an explicit tid**: :func:`record` writes a
  completed span into the *recording* thread's buffer but may stamp it with
  the submitting thread's tid — per-tid interval nesting is preserved because
  the submitting thread is blocked in ``ticket.result()`` for exactly that
  interval.
- **threads that live for one call share one ring**: ``http.server`` makes a
  thread a connection, and a ring (and the tracer's lock) a thread would be
  a 16,384-slot deque for one span and, past :data:`RETIRED_RING_BUDGET`
  dead threads, evicted spans. :func:`shared_span` has such a thread record
  into the tracer's one shared ring instead: nothing is registered, no lock
  is taken, every row still carries its own tid.
- **one tree**: every span carries ``parent_span_id`` — the innermost span
  open on its thread when it opened, or, for a span recorded on behalf of
  another thread (``record(..., tid=)``), the id the caller captured at
  submission (:func:`current_span_id`). Root spans have none; the prompt
  span keeps the router's id from ``traceparent`` under the same key.
- **one clock with the profiler**: while tracing is on, every live span
  (:func:`span`) also holds a ``jax.profiler.TraceAnnotation`` of its name
  (the sampler's ``step`` a ``StepTraceAnnotation`` of its ``step``) carrying
  ``span_id`` and ``prompt_id``, entered and left on the span's own thread.
  Under any profiler session the span tree then lies on the xplane's host
  plane, on the trace's clock, beside the PJRT events. Spans written after
  the fact with :func:`record` have no annotation (their interval is over,
  often on another thread). ``jax`` is imported on the first live span,
  never at module import.

``block_until_ready`` discipline: instrumentation only ever *reads the clock*
at boundaries that already synchronize (the serving bucket's post-dispatch
block, the streaming runner's backpressure block, the save node's wait before
its fetch) or brackets the host's side of a dispatch (the eager loops' step
boundaries, ``denoise``) — tracing never adds a device sync
of its own.
"""

from __future__ import annotations

import contextlib
import hashlib
import itertools
import os
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Optional

# Per-thread span buffer capacity: at ~150 bytes/span this bounds a thread's
# trace memory at a few MiB while holding minutes of step-granularity spans.
DEFAULT_CAPACITY = 16384

# Explicit budgets for the two secondary retention tiers. Both tiers COUNT
# their evictions (``pa_trace_dropped_total{reason=}`` + ``Tracer.dropped``)
# instead of dropping silently — a full ring is an observability failure the
# operator must be able to see.
#
# - retired ring: dead threads whose pthread ident was recycled (one entry
#   per dead thread's whole buffer).
# - prompt retention: completed prompts snapshotted by :meth:`retain_prompt`
#   so a fleet collector can stitch a prompt's timeline after its recording
#   threads' rings have wrapped (one entry per prompt). Its entries are
#   copies, so a tier smaller than what the rings hold counts evictions of
#   prompts whose every row is still there: sized over the 104 prompts a
#   45 s window of the fastest benchmark cell serves.
RETIRED_RING_BUDGET = 256
PROMPT_RETENTION = 256

_span_ids = itertools.count(1)

_HEX = set("0123456789abcdef")


def format_traceparent(trace_id: str, span_id: int | None = None,
                       sampled: bool = True) -> str:
    """W3C-traceparent-style context header: ``00-<32hex trace_id>-<16hex
    span_id>-<01|00>``. The fleet router uses the prompt_id lineage as the
    trace_id (``uuid4().hex`` is already 32 lowercase hex chars); any other
    string is md5-hashed into shape so callers never need to care.
    ``span_id`` defaults to a fresh id from the process-wide counter."""
    tid = str(trace_id).lower()
    if len(tid) != 32 or not set(tid) <= _HEX:
        tid = hashlib.md5(str(trace_id).encode()).hexdigest()
    if span_id is None:
        span_id = next(_span_ids)
    sid = format((int(span_id) & ((1 << 64) - 1)) or 1, "016x")
    return f"00-{tid}-{sid}-{'01' if sampled else '00'}"


def parse_traceparent(header) -> dict | None:
    """Inverse of :func:`format_traceparent`: ``{"trace_id", "parent_span_id",
    "sampled"}``, or ``None`` for anything malformed (unknown version,
    all-zero ids, wrong field widths) — a bad inbound context must degrade to
    an untraced hop, never to an exception on the serving path."""
    if not isinstance(header, str):
        return None
    parts = header.strip().lower().split("-")
    if len(parts) != 4:
        return None
    ver, tid, sid, flags = parts
    if ver != "00" or len(tid) != 32 or len(sid) != 16 or len(flags) != 2:
        return None
    if not (set(tid) <= _HEX and set(sid) <= _HEX and set(flags) <= _HEX):
        return None
    parent = int(sid, 16)
    if int(tid, 16) == 0 or parent == 0:
        return None
    return {
        "trace_id": tid,
        "parent_span_id": parent,
        "sampled": bool(int(flags, 16) & 1),
    }


def now_us() -> float:
    """Monotonic microseconds — the trace-event clock (Chrome ``ts`` unit)."""
    return time.perf_counter_ns() / 1e3


_profiler = None


def _annotate(name: str, attrs: dict, span_id: int):
    """Enter the span's twin on the profiler's host plane (a no-op object
    unless a profiler session is open). Without jax there is no profiler to
    annotate for."""
    global _profiler
    if _profiler is None:
        try:
            from jax import profiler as _profiler
        except ImportError:
            _profiler = False
    if not _profiler:
        return None
    kw = {"span_id": span_id}
    if attrs.get("prompt_id") is not None:
        kw["prompt_id"] = attrs["prompt_id"]
    if name == "step" and "step" in attrs:
        ann = _profiler.StepTraceAnnotation(name, step_num=attrs["step"], **kw)
    else:
        ann = _profiler.TraceAnnotation(name, **kw)
    ann.__enter__()
    return ann


class _NullSpan:
    """The disabled-path singleton: a context manager that does nothing and
    allocates nothing. ``set()`` (attribute attach) is a no-op too, so call
    sites never need a second enabled-check."""

    __slots__ = ()
    end = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NULL = _NullSpan()


class _OpenSpan:
    """One live span on the opening thread's stack; closing (context exit)
    records a completed ``X`` event into that thread's ring buffer."""

    __slots__ = ("_tracer", "_local", "name", "cat", "ts", "end", "attrs",
                 "span_id", "_ann", "_closed")

    def __init__(self, tracer, local, name, cat, attrs, start_us=None):
        self._tracer = tracer
        self._local = local
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.span_id = next(_span_ids)
        self.ts = start_us  # None: the clock is read on entry
        self.end = None  # the tracer's clock where the span closed
        self._ann = None
        self._closed = False

    def set(self, **attrs):
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        stack = self._local.stack
        if stack:
            self.attrs.setdefault("parent_span_id", stack[-1].span_id)
        stack.append(self)
        self._ann = _annotate(self.name, self.attrs, self.span_id)
        if self.ts is None:
            self.ts = now_us()
        return self

    def _close(self):
        self._closed = True
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __exit__(self, *exc):
        if self._closed:
            # Left once already, or abandoned below: one event per span.
            return False
        self.end = now_us()
        dur = self.end - self.ts
        stack = self._local.stack
        if self in stack:
            # A span still open above this one was abandoned by the code it
            # traced (a sampler that raised between two step boundaries):
            # its annotation is closed and it is counted as dropped, not
            # recorded.
            abandoned = 0
            while stack[-1] is not self:
                stack.pop()._close()
                abandoned += 1
            stack.pop()
            if abandoned:
                self._tracer._drop("abandoned", abandoned)
        self._close()
        self._tracer._emit(
            self._local, self.name, self.ts, dur, self.cat,
            threading.get_ident(), self.attrs, self.span_id,
        )
        return False


class _Local(threading.local):
    """Per-thread recording state: the open-span stack, the ring buffer, and
    the active distributed-trace context (parsed traceparent or None)."""

    def __init__(self):
        self.stack: list[_OpenSpan] = []
        self.events: deque | None = None
        self.ctx: dict | None = None


class Tracer:
    """Process-wide span recorder. ``enabled`` is the hot-path flag; all other
    state is touched only while tracing is on."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.enabled = False
        self.capacity = capacity
        self._local = _Local()
        self._lock = threading.Lock()
        # thread ident -> (thread name, events deque) — registration happens
        # once per recording thread; export snapshots under the lock.
        self._buffers: dict[int, tuple[str, deque]] = {}  # guarded-by: _lock
        # Thread IDENTS ARE REUSED after a thread dies (pthread ids recycle
        # aggressively under http.server's thread-per-request churn): when a
        # new thread claims a dead recorder's ident, the dead thread's spans
        # must survive — they move to this bounded retired ring instead of
        # being silently replaced. Every event row carries its own tid, so
        # retired buffers export exactly like live ones. The ring's budget is
        # explicit and its evictions are COUNTED (``dropped`` below +
        # ``pa_trace_dropped_total{reason="retired-ring"}``), never silent.
        self._retired: deque = deque(maxlen=RETIRED_RING_BUDGET)  # guarded-by: _lock
        # Completed prompts snapshotted by retain_prompt(): prompt_id -> list
        # of event rows, LRU-bounded at PROMPT_RETENTION prompts so a fleet
        # trace collector can still stitch a finished prompt after the live
        # rings wrapped. guarded-by: _lock
        self._retained: OrderedDict[str, list] = OrderedDict()
        # Eviction accounting per reason — the local mirror of the
        # pa_trace_dropped_total counter (readable without a metrics scrape).
        self.dropped: dict[str, int] = {}  # guarded-by: _lock
        # The one ring of the threads that live for one call (shared_span);
        # export and retention snapshot it under _lock like every other ring.
        # unguarded: its writers only append, which a deque does atomically
        self._shared: deque = deque(maxlen=capacity)
        # perf_counter_ns of ts == 0: the trace-event clock's origin.
        self._epoch_ns = time.perf_counter_ns()

    # -- lifecycle ----------------------------------------------------------

    def enable(self, capacity: int | None = None) -> None:
        """Turn tracing on (clearing any previous trace). ``capacity`` is
        per-call, not sticky: omitting it restores the default — a tiny
        capacity chosen for one capture must not silently truncate the
        next."""
        with self._lock:
            self.capacity = DEFAULT_CAPACITY if capacity is None else capacity
            self._buffers.clear()
            self._retired.clear()
            self._retained.clear()
            self._shared = deque(maxlen=self.capacity)
            self.dropped = {}
            self._epoch_ns = time.perf_counter_ns()
        self._local = _Local()
        self.enabled = True

    def disable(self) -> None:
        """Stop recording; the captured trace stays exportable until the next
        ``enable()``."""
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._buffers.clear()
            self._retired.clear()
            self._retained.clear()
            self._shared.clear()
            self.dropped = {}

    # -- recording ----------------------------------------------------------

    def _events(self, local) -> deque:
        ev = local.events
        if ev is None:
            ev = local.events = deque(maxlen=self.capacity)
            t = threading.current_thread()
            evicted = 0
            with self._lock:
                prev = self._buffers.get(threading.get_ident())
                if prev is not None and prev[1]:
                    # Recycled ident: retire the dead thread's spans rather
                    # than dropping them (short-lived HTTP handler threads
                    # record real spans — fleet dispatch hops among them).
                    if len(self._retired) == self._retired.maxlen:
                        evicted = len(self._retired[0][1])
                    self._retired.append(prev)
                self._buffers[threading.get_ident()] = (t.name, ev)
            if evicted:
                self._drop("retired-ring", evicted)
        return ev

    def _drop(self, reason: str, n: int) -> None:
        """Count ``n`` spans lost for ``reason``. Called outside ``_lock``:
        the counter is emitted with the tracer lock released (the metrics
        registry has its own lock; keep the order acyclic)."""
        with self._lock:
            self.dropped[reason] = self.dropped.get(reason, 0) + n
        # Lazy import: tracing must stay importable without jax (metrics.py
        # imports jax); a metrics hiccup must never break the traced path.
        try:
            from .metrics import registry

            registry.counter(
                "pa_trace_dropped_total", float(n),
                labels={"reason": reason},
                help="spans evicted from tracer retention tiers "
                     "(retired-thread ring, completed-prompt retention) or "
                     "abandoned open by the code they traced — nonzero "
                     "means the stitched-timeline view is incomplete",
            )
        except Exception:
            pass

    def _emit(self, local, name, ts, dur, cat, tid, attrs, span_id) -> None:
        self._events(local).append((name, ts, dur, cat, tid, attrs, span_id))

    def span(self, name: str, cat: str = "host",
             prompt_id: str | None = None, start_us: float | None = None,
             **attrs):
        """Open a nested span on the calling thread (context manager). When
        tracing is disabled this is the single flag check returning the
        shared null singleton. ``start_us`` starts the span at a reading of
        :func:`now_us` the caller already holds — the ``end`` of the span it
        follows by definition — so that the two abut exactly and no instant
        between them is left to no span (None reads the clock on entry)."""
        if not self.enabled:
            return _NULL
        local = self._local
        if prompt_id is None:
            prompt_id = self._current_prompt_id(local)
        if prompt_id is not None:
            attrs["prompt_id"] = prompt_id
        if local.ctx is not None:
            attrs.setdefault("trace_id", local.ctx["trace_id"])
        return _OpenSpan(self, local, name, cat, attrs, start_us)

    def shared_span(self, name: str, cat: str = "host",
                    prompt_id: str | None = None, **attrs):
        """:meth:`span` for a thread that lives for one call (an HTTP
        handler's): a thread that has no ring yet records into the tracer's
        one shared ring from here on, so it registers nothing, takes no lock
        and can never push a dead thread's spans off the retired ring."""
        if not self.enabled:
            return _NULL
        local = self._local
        if local.events is None:
            local.events = self._shared
        return self.span(name, cat=cat, prompt_id=prompt_id, **attrs)

    def record(self, name: str, ts: float, dur: float, cat: str = "host",
               tid: int | None = None, prompt_id: str | None = None,
               **attrs) -> None:
        """Record an already-measured span (explicit interval). ``tid``
        attributes the span to another thread's timeline (the serving
        dispatcher recording on behalf of a blocked submitter); the write
        still goes to the *calling* thread's lock-free buffer. Its parent is
        the innermost span open on the calling thread; with ``tid`` the
        caller passes ``parent_span_id=`` as captured on the submitter."""
        if not self.enabled:
            return
        local = self._local
        if attrs.get("parent_span_id") is None:
            attrs.pop("parent_span_id", None)
            if tid is None and local.stack:
                attrs["parent_span_id"] = local.stack[-1].span_id
        if prompt_id is None:
            prompt_id = self._current_prompt_id(local)
        if prompt_id is not None:
            attrs["prompt_id"] = prompt_id
        if local.ctx is not None:
            attrs.setdefault("trace_id", local.ctx["trace_id"])
        self._emit(
            local, name, ts, max(0.0, dur), cat,
            tid if tid is not None else threading.get_ident(),
            attrs, next(_span_ids),
        )

    # -- context ------------------------------------------------------------

    def _current_prompt_id(self, local=None) -> Optional[str]:
        local = local if local is not None else self._local
        for s in reversed(local.stack):
            pid = s.attrs.get("prompt_id")
            if pid is not None:
                return pid
        # No span context on this thread: fall back to the per-thread
        # progress scope (the per-prompt correlation the server installs).
        try:
            from .progress import current_scope

            scope = current_scope()
            return getattr(scope, "prompt_id", None)
        except Exception:
            return None

    def current_prompt_id(self) -> Optional[str]:
        """The prompt the calling thread is working for right now, or None."""
        return self._current_prompt_id()

    def current_span_id(self) -> Optional[int]:
        stack = self._local.stack
        return stack[-1].span_id if stack else None

    def current_trace_id(self) -> Optional[str]:
        """The distributed trace_id active on the calling thread (from
        :meth:`trace_context`, or inherited off the span stack), or None.
        The serving scheduler captures this at admission — lane-wait/step
        spans recorded later from the dispatcher thread carry the
        SUBMITTER's trace identity, same rule as the captured tid."""
        ctx = self._local.ctx
        if ctx is not None:
            return ctx["trace_id"]
        for s in reversed(self._local.stack):
            tid = s.attrs.get("trace_id")
            if tid:
                return tid
        return None

    @contextlib.contextmanager
    def trace_context(self, traceparent):
        """Activate a distributed-trace context (a traceparent header string
        or an already-parsed dict) on the calling thread: every span/record
        opened inside is stamped with the context's ``trace_id`` attr, so a
        backend's local spans join the router's cross-host trace. Malformed
        or absent context degrades to an untraced (but still locally
        recorded) scope — never an error on the serving path."""
        ctx = (parse_traceparent(traceparent)
               if not isinstance(traceparent, dict) else traceparent)
        if not self.enabled or not ctx:
            yield None
            return
        local = self._local
        prev = local.ctx
        local.ctx = ctx
        try:
            yield ctx
        finally:
            local.ctx = prev

    def _rings(self):  # caller holds _lock
        """(tid, thread name, rows) of every ring, each copied in one step:
        a ring's own thread (and, for the shared one, any handler's) appends
        without the lock. The retired rings (dead threads whose ident was
        recycled) and the shared one belong to no one thread, tid 0: their
        rows carry their own tids, so they render like the live rings'."""
        for tid, (name, ev) in self._buffers.items():
            yield tid, name, list(ev)
        for name, ev in self._retired:
            yield 0, name, list(ev)
        yield 0, "shared", list(self._shared)

    # -- completed-prompt retention -----------------------------------------

    def retain_prompt(self, prompt_id: str | None) -> int:
        """Snapshot every event stamped with ``prompt_id`` into the bounded
        completed-prompt retention ring, so the fleet trace collector can
        stitch a finished prompt's timeline even after its recording
        threads' ring buffers have wrapped (high-throughput hosts wrap in
        seconds). LRU-bounded at :data:`PROMPT_RETENTION` prompts; evictions
        are counted (reason ``"prompt-retention"``). Returns the number of
        rows retained."""
        if not self.enabled or not prompt_id:
            return 0
        evicted = 0
        with self._lock:
            rows = [r for _tid, _name, ring in self._rings()
                    for r in ring if r[5].get("prompt_id") == prompt_id]
            if not rows:
                return 0
            self._retained[prompt_id] = rows
            self._retained.move_to_end(prompt_id)
            while len(self._retained) > PROMPT_RETENTION:
                _pid, old = self._retained.popitem(last=False)
                evicted += len(old)
        if evicted:
            self._drop("prompt-retention", evicted)
        return len(rows)

    # -- export -------------------------------------------------------------

    def export(self, prompt_id: str | None = None) -> dict:
        """Chrome/Perfetto trace-event JSON (the ``chrome://tracing`` /
        ui.perfetto.dev format): complete ``X`` events with ``ts``/``dur`` in
        microseconds, plus thread-name metadata. ``prompt_id`` filters to one
        prompt's timeline (spans stamped with that prompt_id)."""
        pid = os.getpid()
        with self._lock:
            snap = list(self._rings())
            # Completed-prompt retention: rows may duplicate live-buffer rows
            # (retention snapshots, it does not move) — deduped by span_id
            # below, since span ids are process-unique.
            if prompt_id is not None:
                retained = list(self._retained.get(prompt_id, ()))
            else:
                retained = [r for rows in self._retained.values()
                            for r in rows]
        epoch_us = self._epoch_ns / 1e3
        snap.append((0, "retained", retained))
        events: list[dict] = []
        tids_seen: set[int] = set()
        span_ids_seen: set[int] = set()
        for _rec_tid, _tname, recs in snap:
            for name, ts, dur, cat, tid, attrs, span_id in recs:
                if prompt_id is not None and attrs.get("prompt_id") != prompt_id:
                    continue
                if span_id in span_ids_seen:
                    continue
                span_ids_seen.add(span_id)
                args = dict(attrs)
                args["span_id"] = span_id
                events.append({
                    "ph": "X", "name": name, "cat": cat,
                    "ts": round(ts - epoch_us, 3),
                    "dur": round(dur, 3),
                    "pid": pid, "tid": tid, "args": args,
                })
                tids_seen.add(tid)
        events.sort(key=lambda e: (e["tid"], e["ts"], -e["dur"]))
        thread_names = {tid: tname for tid, tname, _ in snap}
        meta = [{
            "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": thread_names.get(tid, f"thread-{tid}")},
        } for tid in sorted(tids_seen)]
        meta.insert(0, {
            "ph": "M", "name": "process_name", "pid": pid,
            "args": {"name": "parallel_anything_tpu"},
        })
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            # Wall-clock anchor of ts == 0, taken NOW (the wall clock less
            # the monotonic time since the epoch): whoever aligns this trace
            # with another clock domain — the cross-host stitcher, a profiler
            # bracket's own wall stamp — pairs two wall readings taken moments
            # apart, so wall/monotonic drift since enable() stays out of the
            # alignment. NTP-level skew (ms) between hosts is the accepted
            # error bar.
            # palint: allow[observability] clock-alignment epoch STAMP
            "epoch_wall_s": time.time()
            - (time.perf_counter_ns() - self._epoch_ns) / 1e9,
        }


# The process-wide tracer every instrumentation site records into and the
# server's GET /trace renders. Tests may enable()/disable() it.
tracer = Tracer()


def on() -> bool:
    """The hot-path enabled check — guard attribute computation with this."""
    return tracer.enabled


def enable(capacity: int | None = None) -> None:
    tracer.enable(capacity)


def disable() -> None:
    tracer.disable()


def span(name: str, cat: str = "host", prompt_id: str | None = None,
         start_us: float | None = None, **attrs):
    return tracer.span(name, cat=cat, prompt_id=prompt_id, start_us=start_us,
                       **attrs)


def shared_span(name: str, cat: str = "host", prompt_id: str | None = None,
                **attrs):
    return tracer.shared_span(name, cat=cat, prompt_id=prompt_id, **attrs)


def record(name: str, ts: float, dur: float, cat: str = "host",
           tid: int | None = None, prompt_id: str | None = None, **attrs):
    tracer.record(name, ts, dur, cat=cat, tid=tid, prompt_id=prompt_id,
                  **attrs)


def export(prompt_id: str | None = None) -> dict:
    return tracer.export(prompt_id)


def current_prompt_id() -> Optional[str]:
    return tracer.current_prompt_id()


def current_span_id() -> Optional[int]:
    return tracer.current_span_id()


def trace_context(traceparent):
    return tracer.trace_context(traceparent)


def current_trace_id() -> Optional[str]:
    return tracer.current_trace_id()


def retain_prompt(prompt_id: str | None) -> int:
    return tracer.retain_prompt(prompt_id)


# -- trace-derived aggregates ------------------------------------------------
#
# Shared by bench.py (every JSON line), __graft_entry__.dryrun_multichip, and
# scripts/trace_summary.py (which re-implements the same math stdlib-only; a
# tier-1 test pins the two against each other on the same fixture).


def _percentile(samples: list[float], q: float) -> float:
    """Nearest-rank percentile (scripts/loadgen.py convention)."""
    if not samples:
        return 0.0
    s = sorted(samples)
    k = max(0, min(len(s) - 1, round(q / 100.0 * (len(s) - 1))))
    return s[k]


def _x_events(events) -> list[dict]:
    if isinstance(events, dict):
        events = events.get("traceEvents", [])
    return [e for e in events if e.get("ph") == "X"]


def stream_overlap_efficiency(events) -> float | None:
    """Fraction of each ``stream-run`` span's wall time occupied by
    ``stream-stage-compute`` spans, averaged over runs; in (0, 1] by
    construction (compute spans are non-overlapping and contained in their
    run). Exposed transfer/backpressure time — the part double-buffering
    exists to hide — is exactly what pushes this below 1; it is the
    overlap-efficiency number the flux_stream live-window measurement needs.
    None when the trace holds no streamed runs."""
    xs = _x_events(events)
    runs = [e for e in xs if e["name"] == "stream-run" and e.get("dur", 0) > 0]
    if not runs:
        return None
    comps = [e for e in xs if e["name"] == "stream-stage-compute"]
    effs = []
    for r in runs:
        r0, r1 = r["ts"], r["ts"] + r["dur"]
        busy = sum(
            c["dur"] for c in comps
            if c["tid"] == r["tid"] and c["ts"] >= r0
            and c["ts"] + c["dur"] <= r1 + 1.0  # float-rounding slack (µs)
        )
        effs.append(min(1.0, busy / r["dur"]))
    return sum(effs) / len(effs)


def lane_wait_p95_s(events) -> float | None:
    """p95 of serving ``lane-wait`` spans (submit → seated), seconds."""
    waits = [e["dur"] / 1e6 for e in _x_events(events)
             if e["name"] == "lane-wait"]
    return _percentile(waits, 95) if waits else None


def host_gap_ms(events) -> float | None:
    """Mean host-side gap between consecutive ``step`` spans on each thread —
    the per-step scheduling overhead the device cannot see. None with fewer
    than two steps anywhere."""
    steps: dict[int, list[dict]] = {}
    for e in _x_events(events):
        if e["name"] == "step":
            steps.setdefault(e["tid"], []).append(e)
    gaps = []
    for evs in steps.values():
        evs.sort(key=lambda e: e["ts"])
        for a, b in zip(evs, evs[1:]):
            gaps.append(max(0.0, b["ts"] - (a["ts"] + a["dur"])) / 1e3)
    return sum(gaps) / len(gaps) if gaps else None


def fleet_hop_p95_ms(events) -> float | None:
    """p95 of the router's ``fleet-hop`` spans (place → backend accepted),
    milliseconds — the fleet tier's own overhead per dispatch, distinct from
    the backend-side prompt time. The hop span and the backend's prompt span
    share ``origin_prompt_id``/``prompt_id``, so one Perfetto export shows
    the prompt's timeline across the hop. None when no fleet routing ran
    inside the traced window (kept out of :func:`trace_aggregates`, whose
    key set is pinned against scripts/trace_summary.py)."""
    hops = [e["dur"] / 1e3 for e in _x_events(events)
            if e["name"] == "fleet-hop"]
    return round(_percentile(hops, 95), 4) if hops else None


def trace_aggregates(events) -> dict:
    """The trace-derived aggregate fields every bench.py JSON line carries."""
    eff = stream_overlap_efficiency(events)
    p95 = lane_wait_p95_s(events)
    gap = host_gap_ms(events)
    return {
        "stream_overlap_efficiency": None if eff is None else round(eff, 4),
        "lane_wait_p95": None if p95 is None else round(p95, 6),
        "host_gap_ms": None if gap is None else round(gap, 4),
    }
