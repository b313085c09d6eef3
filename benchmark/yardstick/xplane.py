"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the benchmark
reports: per chip, the seconds in which an operation ran (the union of the
device-operation intervals inside the traced window), the time of each XLA
module and operation by name, and the idle gaps. Read with
``jax.profiler.ProfileData`` and nothing else.

What a trace of this JAX on a TPU looks like (looked at by hand, PR 23): one
plane per chip named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one
event per executed program, named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops``
(one event per HLO operation, nested operations included); host threads live
in ``/host:CPU``. Nested operations (a ``while`` and its body) overlap, so
busy time is a union of intervals, never a sum of durations."""

from __future__ import annotations

import dataclasses
import glob
import os
import re

MARK = "yardstick-window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


@dataclasses.dataclass
class Chip:
    ops: list        # (name, start_ns, end_ns)
    modules: list    # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    chips: dict      # chip id -> Chip
    window: tuple | None  # (start_ns, end_ns) of the harness's marker, trace clock


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips: dict[int, Chip] = {}
    window = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = Chip([], [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dest = chip.ops
                elif line.name == MODULES_LINE:
                    dest = chip.modules
                else:
                    continue
                for ev in line.events:
                    dest.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
            chips[int(m.group(1))] = chip
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARK:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    return Trace(chips, window)


def clip(intervals, window):
    if window is None:
        return [(a, b) for _, a, b in intervals]
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for _, a, b in intervals
            if b > lo and a < hi]


def union(intervals) -> list[tuple]:
    """Sorted, merged (start, end) pairs."""
    out: list[list] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(chip: Chip, window) -> int:
    return sum(b - a for a, b in union(clip(chip.ops, window)))


def gaps(chip: Chip, window) -> list[tuple]:
    """Idle (start, end) pairs between busy intervals inside the window."""
    merged = union(clip(chip.ops, window))
    if window is None:
        if not merged:
            return []
        window = (merged[0][0], merged[-1][1])
    out, at = [], window[0]
    for a, b in merged:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def time_by_name(events, window, pattern: str | None = None) -> dict[str, int]:
    """Name → summed ns inside the window (module fingerprints and operation
    suffixes kept: callers group by pattern)."""
    rx = re.compile(pattern) if pattern else None
    out: dict[str, int] = {}
    lo, hi = window if window else (None, None)
    for name, a, b in events:
        if rx is not None and not rx.search(name):
            continue
        if window is not None:
            a, b = max(a, lo), min(b, hi)
        if b > a:
            out[name] = out.get(name, 0) + (b - a)
    return out


def count_by_pattern(events, window, pattern: str) -> int:
    """Events matching ``pattern`` that START inside the window."""
    rx = re.compile(pattern)
    lo, hi = window if window else (float("-inf"), float("inf"))
    return sum(1 for name, a, _ in events if lo <= a < hi and rx.search(name))


def last_ns(chip: Chip) -> int | None:
    """Where the chip's device trace stops: the latest end of any event."""
    return max((b for events in (chip.ops, chip.modules) for _, _, b in events),
               default=None)


def last_start_ns(chip: Chip) -> int | None:
    """When the last module run the chip's trace holds began. The profiler is
    stopped while the device is busy, so that run is the one the stop cut: a
    run is whole only if it had ended by then (``whole_runs``' ``ended_by``)."""
    return max((a for _, a, _ in chip.modules), default=None)


def whole_runs(events, window, pattern: str, ended_by=None) -> list[tuple]:
    """(start, end) of the events matching ``pattern`` that lie wholly
    inside the window: what a mean time per event is taken over. With
    ``ended_by`` (``last_start_ns`` of the chip) a run counts only if it had
    ended when the chip's last module run began. That last run was cut short
    by the profiler's stop, not by the program; the device's clock runs a
    millisecond behind the host's, and so its stump can lie inside the marked
    window: 23.5 ms of a 115 ms step pulled the mean down by 1% (PR 29), 207
    ms of a 232 ms one by 0.27% (PR 30). Whether the stump ends at the trace's
    last device event is not asked: it did in the nine traces looked at, and
    in a tenth that rule still counted a short run (PERF.md, PR 30). Where the
    stop fell between two programs the run left out was whole: one sample of
    some eighty."""
    rx = re.compile(pattern)
    lo, hi = window if window else (float("-inf"), float("inf"))
    if ended_by is not None:
        hi = min(hi, ended_by)
    return [(a, b) for name, a, b in events
            if lo <= a and b <= hi and rx.search(name)]


def whole_events(events, window, pattern: str, ended_by=None) -> list[int]:
    """The durations (ns) of ``whole_runs``."""
    return [b - a for a, b in whole_runs(events, window, pattern, ended_by)]


def self_time_by_name(chip: Chip, window) -> dict[str, int]:
    """Operation name → ns in which it was the innermost running operation
    (a ``while`` is not charged for its body)."""
    evs = sorted(((a, b, n) for n, a, b in chip.ops), key=lambda e: (e[0], -e[1]))
    lo, hi = window if window else (float("-inf"), float("inf"))
    out: dict[str, int] = {}
    stack: list = []  # (end, name, last_resume)

    def charge(name, a, b):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out[name] = out.get(name, 0) + (b - a)

    def pop_until(t):
        while stack and stack[-1][0] <= t:
            end, name, since = stack.pop()
            charge(name, since, end)
            if stack:
                stack[-1][2] = end

    for a, b, name in evs:
        pop_until(a)
        if stack:
            charge(stack[-1][1], stack[-1][2], a)
        stack.append([b, name, a])
    pop_until(float("inf"))
    return out
