"""The fixed set of per-layer metric readers, the profiler bracket they read
from, and the breakdown. A metric is one ``layer_metrics/<name>.json`` that
names a reader of this set and its arguments; a reader that finds nothing to
read returns ``None`` and the harness leaves the metric out of the line.

Readers: ``compile_delta``, ``client_minus_exec_ms``, ``span_sum_ms``,
``span_count``, ``trace_module_ms``, ``trace_op_ms``, ``trace_idle_share``,
``roofline_share``."""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import re
import shutil
import threading
import time

from . import stats, xplane


class ProfilerBracket(threading.Thread):
    """Brackets ``seconds`` of the steady window with ``jax.profiler``,
    ``start_after_s`` after the window opens. The harness's own marker (a
    ``TraceAnnotation`` held for the bracket) gives the window on the trace's
    clock; both clocks are stamped when it is opened."""

    def __init__(self, log_dir: str, spec: dict, window):
        super().__init__(daemon=True)
        self.log_dir, self.window = log_dir, window
        self.start_after = float(spec.get("start_after_s", 5))
        self.seconds = float(spec.get("seconds", 8))
        self.mark_perf_ns = self.mark_wall_s = None
        self.error = None
        self.xplane_path = None

    def run(self):
        import jax

        try:
            while self.window.start is None:
                time.sleep(0.01)
            wait = self.window.start + self.start_after - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            shutil.rmtree(self.log_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            try:
                self.mark_wall_s = time.time()
                self.mark_perf_ns = time.perf_counter_ns()
                with jax.profiler.TraceAnnotation(xplane.MARK):
                    time.sleep(self.seconds)
            finally:
                jax.profiler.stop_trace()
            self.xplane_path = xplane.find_xplane(self.log_dir)
        except Exception as e:  # noqa: BLE001 — reported, the run goes on
            self.error = f"{type(e).__name__}: {e}"

    def reduce(self):
        if self.xplane_path is None:
            return None
        return xplane.load(self.xplane_path)


@dataclasses.dataclass
class Context:
    cell: dict
    window: object
    results: list
    spans: dict | None
    metrics0: dict
    metrics1: dict
    comp0: dict
    comp1: dict
    trace: object
    bracket: ProfilerBracket
    chips: int
    device_kind: str
    here: str
    batch: int

    @property
    def config(self):
        return self.cell["config_data"]

    def win(self):
        return self.trace.window if self.trace is not None else None

    def requests_in_trace(self) -> float:
        """Requests' worth of work inside the traced window, from the client's
        record: each request's overlap with the bracket over its own length."""
        if self.bracket.mark_perf_ns is None:
            return 0.0
        lo = self.bracket.mark_perf_ns / 1e9
        hi = lo + self.bracket.seconds
        n = 0.0
        for r in self.results:
            if r.ok and r.done > r.sent:
                n += max(0.0, min(r.done, hi) - max(r.sent, lo)) / (r.done - r.sent)
        return n

    def patterns(self, group: str) -> str | None:
        pats = (self.config.get("trace_modules") or {}).get(group)
        return "|".join(f"(?:{p})" for p in pats) if pats else None


# -- readers -------------------------------------------------------------------


def _compile_delta(a, ctx):
    return ctx.comp1[a["key"]] - ctx.comp0[a["key"]]


def _client_minus_exec_ms(a, ctx):
    ok = [r for r in ctx.results if r.ok and r.exec_s is not None]
    if not ok:
        return None
    return 1e3 * (stats.median([r.latency for r in ok])
                  - stats.median([r.exec_s for r in ok]))


def _span_events(ctx):
    return _complete_events(ctx.spans)


def _complete_events(spans):
    return [e for e in (spans or {}).get("traceEvents", ())
            if e.get("ph") == "X"]


def _span_sum_ms(a, ctx):
    """Per prompt, the summed duration of the spans named ``name`` whose
    ``class_type`` matches ``class_include`` and not ``class_exclude``; the
    median over the window's prompts. Host-clock intervals: a stage that
    returns before the device has finished is measured at its enqueue."""
    inc = re.compile(a["class_include"]) if a.get("class_include") else None
    exc = re.compile(a["class_exclude"]) if a.get("class_exclude") else None
    pids = {r.prompt_id for r in ctx.results if r.ok}
    per: dict[str, float] = {}
    for e in _span_events(ctx):
        if e["name"] != a["name"]:
            continue
        args = e.get("args", {})
        pid, ct = args.get("prompt_id"), str(args.get("class_type") or "")
        if pid not in pids:
            continue
        if (inc and not inc.search(ct)) or (exc and exc.search(ct)):
            continue
        per[pid] = per.get(pid, 0.0) + e["dur"] / 1e3
    return stats.median(per.values()) if per else None


def spans_per_prompt(results, spans, name: str) -> dict[str, int]:
    """For every succeeded prompt of the window, how many spans named
    ``name`` the program recorded under its id (0 if none)."""
    per = {r.prompt_id: 0 for r in results if r.ok}
    for e in _complete_events(spans):
        pid = e.get("args", {}).get("prompt_id")
        if e["name"] == name and pid in per:
            per[pid] += 1
    return per


def _span_count(a, ctx):
    """Per prompt, how many spans named ``name`` the program recorded; the
    median over the window's prompts (the eager sampler records one ``step``
    span per denoiser step)."""
    per = [n for n in spans_per_prompt(ctx.results, ctx.spans, a["name"]).values() if n]
    return stats.median(per) if per else None


def _module_events(ctx, group):
    """Durations (ns) of the group's module runs wholly inside the traced
    window (the chip's last run, which the profiler's stop cut, is not), on
    the chip that spent most time in them."""
    pat = ctx.patterns(group)
    if ctx.trace is None or not ctx.trace.chips or pat is None:
        return []
    per_chip = [xplane.whole_events(c.modules, ctx.win(), pat, xplane.last_start_ns(c))
                for c in ctx.trace.chips.values()]
    return max(per_chip, key=sum)


def _trace_module_ms(a, ctx):
    durs = _module_events(ctx, a["group"])
    if not durs:
        return None
    per = a.get("per", "event")
    if per == "event":
        return sum(durs) / len(durs) / 1e6
    if per == "request":
        n = ctx.requests_in_trace()
        return sum(durs) / 1e6 / n if n else None
    return sum(durs) / 1e6


def _trace_op_ms(a, ctx):
    """Device time of the operations whose name matches ``pattern``. With
    ``within_group``, only inside that group's module runs that lie wholly in
    the traced window, per such run (a decode runs once a request, so that is
    per request without guessing how many requests the window held)."""
    if ctx.trace is None or not ctx.trace.chips:
        return None
    group = a.get("within_group")
    best = None
    for chip in ctx.trace.chips.values():
        if group:
            pat = ctx.patterns(group)
            if pat is None:
                return None
            runs = xplane.whole_runs(chip.modules, ctx.win(), pat,
                                     xplane.last_start_ns(chip)) if ctx.win() else []
            if not runs:
                continue
            total = sum(sum(xplane.time_by_name(chip.ops, w, a["pattern"]).values())
                        for w in runs)
            value = total / len(runs)
        else:
            value = sum(xplane.time_by_name(chip.ops, ctx.win(), a["pattern"]).values())
        best = value if best is None else max(best, value)
    return None if not best else best / 1e6


def _busy_shares(ctx):
    if ctx.trace is None or not ctx.trace.chips or ctx.win() is None:
        return None
    span = ctx.win()[1] - ctx.win()[0]
    return {c: xplane.busy_ns(chip, ctx.win()) / span
            for c, chip in ctx.trace.chips.items()}


def _trace_idle_share(a, ctx):
    shares = _busy_shares(ctx)
    return None if not shares else 100.0 * (1.0 - min(shares.values()))


def peaks(ctx) -> dict:
    with open(os.path.join(ctx.here, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if ctx.device_kind not in table:
        raise KeyError(f"device_kind {ctx.device_kind!r} is not in benchmark/"
                       "peaks.json; add it with its source")
    return table[ctx.device_kind]


def _cost(a, ctx) -> dict:
    """``flops`` and ``bytes`` of one run of what ``cost`` names, at the cell's
    shapes, by the configuration's shape functions."""
    mod = importlib.import_module(f"yardstick.{ctx.config['shape_functions']}")
    return getattr(mod, a["cost"])(ctx.config, ctx.cell["mix"], ctx.chips)


def _roofline_share(a, ctx):
    """Least time for one forward of the group's module at the cell's shapes
    (operations over peak FLOP/s against bytes over peak B/s, the larger) over
    its mean device time in the trace. No clamp: over 100% is a fault."""
    durs = _module_events(ctx, a["group"])
    if not durs:
        return None
    cost, pk = _cost(a, ctx), peaks(ctx)
    least = max(cost["flops"] / pk["flops_per_s_bf16"],
                cost["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / (sum(durs) / len(durs) / 1e9)


def _window_mfu(a, ctx):
    """The traced window's share of the chip's peak FLOP/s: ``cost`` operations
    for every run of the group's module in the window (a run cut by an edge
    counts by the share of it that lies inside), over the window's length —
    up to where the device trace stops — times the peak; the worst chip. All
    else the window holds (the decode, the sampler's own programs, the gaps
    between programs and between prompts) is time and no operations, so it
    bounds every kernel's roofline from the whole step's side. No clamp."""
    pat = ctx.patterns(a["group"])
    if ctx.trace is None or not ctx.trace.chips or pat is None or ctx.win() is None:
        return None
    shares = []
    for chip in ctx.trace.chips.values():
        end = xplane.last_ns(chip)
        durs = xplane.whole_events(chip.modules, ctx.win(), pat,
                                   xplane.last_start_ns(chip))
        if not durs:
            continue
        win = (ctx.win()[0], min(ctx.win()[1], end))
        inside = sum(xplane.time_by_name(chip.modules, win, pat).values())
        runs = inside / (sum(durs) / len(durs))
        shares.append(runs / ((win[1] - win[0]) / 1e9))
    if not shares:
        return None
    return 100.0 * min(shares) * _cost(a, ctx)["flops"] / peaks(ctx)["flops_per_s_bf16"]


READERS = {
    "compile_delta": _compile_delta,
    "client_minus_exec_ms": _client_minus_exec_ms,
    "span_sum_ms": _span_sum_ms,
    "span_count": _span_count,
    "trace_module_ms": _trace_module_ms,
    "trace_op_ms": _trace_op_ms,
    "trace_idle_share": _trace_idle_share,
    "roofline_share": _roofline_share,
    "window_mfu": _window_mfu,
}


def read(metric: dict, ctx: Context):
    value = READERS[metric["reader"]](metric.get("args", {}), ctx)
    return None if value is None else float(value)


# -- device busy time and the breakdown --------------------------------------------


def device_busy(ctx: Context) -> dict:
    """``busy_s`` (seconds in which an operation ran, averaged over the chips
    used) and ``window_s`` (the traced window), from the profiler's trace."""
    if ctx.trace is None or not ctx.trace.chips or ctx.win() is None:
        return {"trace_error": ctx.bracket.error or "no device plane in the trace"}
    busy = [xplane.busy_ns(c, ctx.win()) for c in ctx.trace.chips.values()]
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (ctx.win()[1] - ctx.win()[0]) / 1e9}


def _short(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def op_label(name: str) -> str:
    """An operation event is named by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...), kind=kOutput, ...``): keep the
    instruction's name without its number, and a custom call's target."""
    head = re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{head}:{target.group(1)}" if target else head


def _host_spans_on_trace_clock(ctx):
    """Program spans as (start_ns, end_ns, label) on the trace's clock. The
    span tracer's clock is perf_counter; its export gives the wall time of its
    origin; the bracket stamped wall and perf_counter together and the marker
    gives that instant on the trace's clock."""
    if not ctx.spans or ctx.win() is None or ctx.bracket.mark_perf_ns is None:
        return []
    origin_wall = ctx.spans.get("epoch_wall_s")
    if origin_wall is None:
        return []
    # perf_counter ns of the tracer's ts == 0
    origin_perf = ctx.bracket.mark_perf_ns + (origin_wall - ctx.bracket.mark_wall_s) * 1e9
    to_trace = ctx.win()[0] - ctx.bracket.mark_perf_ns
    out = []
    for e in _span_events(ctx):
        label = e["name"]
        if label == "workflow-node":
            label = f"workflow-node:{e.get('args', {}).get('class_type')}"
        a = origin_perf + e["ts"] * 1e3 + to_trace
        out.append((a, a + e["dur"] * 1e3, label))
    return out


def breakdown(ctx: Context) -> dict:
    """Top device operations by self time and XLA modules by time on the
    busiest chip, and the longest idle gaps named for the innermost program
    span that covers their middle on the host (``between-prompts`` when only
    the window does, ``unattributed`` when the clocks cannot be aligned)."""
    if ctx.trace is None or not ctx.trace.chips:
        return {"device_ops": [], "idle_gaps": []}
    chip = max(ctx.trace.chips.values(), key=lambda c: xplane.busy_ns(c, ctx.win()))
    ops: dict[str, int] = {}
    for name, ns in xplane.self_time_by_name(chip, ctx.win()).items():
        ops[op_label(name)] = ops.get(op_label(name), 0) + ns
    mods: dict[str, int] = {}
    for name, ns in xplane.time_by_name(chip.modules, ctx.win()).items():
        mods["module:" + _short(name)] = mods.get("module:" + _short(name), 0) + ns
    top = sorted({**ops, **mods}.items(), key=lambda kv: -kv[1])[:10]
    spans = _host_spans_on_trace_clock(ctx)
    named: dict[str, int] = {}
    for a, b in xplane.gaps(chip, ctx.win()):
        mid = (a + b) / 2
        if not spans:
            label = "unattributed"
        else:
            cover = [s for s in spans if s[0] <= mid < s[1]]
            label = (min(cover, key=lambda s: s[1] - s[0])[2] if cover
                     else "between-prompts")
        named[label] = named.get(label, 0) + (b - a)
    idle = sorted(named.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v / 1e9] for k, v in top],
            "idle_gaps": [[k, v / 1e9] for k, v in idle]}


def dump_structure(xplane_path: str, out_path: str) -> None:
    """Planes, lines, event counts and the commonest names of a trace, for
    looking at it by hand before writing patterns against it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    doc = []
    for plane in data.planes:
        p = {"plane": plane.name, "lines": []}
        for line in plane.lines:
            names: dict[str, list] = {}
            n = 0
            first = None
            for ev in line.events:
                n += 1
                rec = names.setdefault(ev.name, [0, 0])
                rec[0] += 1
                rec[1] += ev.duration_ns
                if first is None:
                    first = {"name": ev.name, "start_ns": ev.start_ns,
                             "duration_ns": ev.duration_ns,
                             "stats": {str(k): str(v)[:200] for k, v in ev.stats}}
            topn = sorted(names.items(), key=lambda kv: -kv[1][1])[:40]
            p["lines"].append({"line": line.name, "events": n, "first": first,
                               "top": [[k, c, ns] for k, (c, ns) in topn]})
        doc.append(p)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
