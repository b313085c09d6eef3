"""Per-layer time breakdown of a captured trace — stdlib-only.

Reads a Chrome/Perfetto trace-event JSON (``bench.py --trace-out``, the
server's ``GET /trace``, or a ``utils/tracing.py`` export written to disk)
and prints where the time went: total/mean span time per layer (the ``cat``
field: server / graph / sampling / serving / stream / bench), the busiest
span names, the trace-derived aggregates — stream overlap efficiency,
lane-wait p95, host gap — and the numerics sentinel's counters (non-finite
events by site, quarantines) recorded as instant ``numerics``-cat spans.

Stdlib-only by contract (it must run on a laptop holding just the trace
file, no jax): the aggregate math re-implements
``utils/tracing.trace_aggregates``; ``tests/test_observability.py`` pins the
two against each other on the same fixture so they cannot drift.

Usage:
    python scripts/trace_summary.py trace.json [--json] [--prompt-id ID]
"""

from __future__ import annotations

import argparse
import json
from collections import defaultdict

# The span-category vocabulary (the ``cat`` field of every emitted span) —
# this tuple is the OWNING REGISTRY: palint's registry-consistency pass
# fails CI on any span site whose category is missing here, and on any
# entry no span site uses, so the per-layer table above can never grow a
# silent `?` row. One entry per layer:
SPAN_CATEGORIES = (
    "host",       # utils/tracing.py default — uncategorized host work
    "server",     # server.py prompt / admission-wait spans
    "graph",      # host.py workflow-node + nodes.py save-stage spans
    "sampling",   # sampling/runner.py sampler-run + eager step + denoise spans
    "serving",    # serving/bucket.py dispatch/lane/step spans
    "stream",     # parallel/streaming.py run/prefetch/wait/compute spans
    "bench",      # bench.py timed-iteration step spans
    "compile",    # utils/telemetry.py instrument_jit compile spans
    "fleet",      # fleet/router.py fleet-prompt / fleet-hop spans
    "numerics",   # utils/numerics.py nonfinite-event / quarantine instants
    "faults",     # utils/faults.py fault-injected instants
    "anomaly",    # utils/anomaly.py sentinel-firing instants
    "degrade",    # utils/degrade.py degradation-rung instants
)


def load_events(path: str) -> list[dict]:
    with open(path) as f:
        data = json.load(f)
    events = data.get("traceEvents", data) if isinstance(data, dict) else data
    return [e for e in events if e.get("ph") == "X"]


def percentile(samples: list[float], q: float) -> float:
    """Nearest-rank percentile (the scripts/loadgen.py convention)."""
    if not samples:
        return 0.0
    s = sorted(samples)
    k = max(0, min(len(s) - 1, round(q / 100.0 * (len(s) - 1))))
    return s[k]


def stream_overlap_efficiency(events: list[dict]) -> float | None:
    """Mirror of utils/tracing.stream_overlap_efficiency (drift-pinned by
    test): Σ stream-stage-compute / stream-run wall time, mean over runs."""
    runs = [e for e in events
            if e["name"] == "stream-run" and e.get("dur", 0) > 0]
    if not runs:
        return None
    comps = [e for e in events if e["name"] == "stream-stage-compute"]
    effs = []
    for r in runs:
        r0, r1 = r["ts"], r["ts"] + r["dur"]
        busy = sum(c["dur"] for c in comps
                   if c["tid"] == r["tid"] and c["ts"] >= r0
                   and c["ts"] + c["dur"] <= r1 + 1.0)
        effs.append(min(1.0, busy / r["dur"]))
    return sum(effs) / len(effs)


def lane_wait_p95_s(events: list[dict]) -> float | None:
    waits = [e["dur"] / 1e6 for e in events if e["name"] == "lane-wait"]
    return percentile(waits, 95) if waits else None


def host_gap_ms(events: list[dict]) -> float | None:
    steps: dict[int, list[dict]] = defaultdict(list)
    for e in events:
        if e["name"] == "step":
            steps[e["tid"]].append(e)
    gaps = []
    for evs in steps.values():
        evs.sort(key=lambda e: e["ts"])
        for a, b in zip(evs, evs[1:]):
            gaps.append(max(0.0, b["ts"] - (a["ts"] + a["dur"])) / 1e3)
    return sum(gaps) / len(gaps) if gaps else None


def _load_roofline():
    """utils/roofline.py loaded standalone by file path — its module level
    is stdlib-only and free of package-relative imports by contract (the
    scripts/roofline_report.py loader), so the bucket-decomposition math
    has ONE implementation instead of a hand-maintained mirror. The
    trace-aggregate functions above predate that contract and stay mirrored
    (drift-pinned by tests/test_observability.py)."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "comfyui_parallelanything_tpu", "utils", "roofline.py",
    )
    spec = importlib.util.spec_from_file_location("pa_roofline_ts", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_roofline = _load_roofline()


def attribution(events: list[dict]) -> dict | None:
    """utils/roofline.attribution_from_trace over the whole trace window,
    plus the two headline fractions (comms / host-gap — where the
    non-compute time went). Streamed windows measure compute directly and
    leave host-gap residual; async dispatch windows measure the host gaps
    and leave compute residual — see the roofline module for the bucket
    contract."""
    attr = _roofline.attribution_from_trace(events)
    if attr is None:
        return None
    fr = _roofline.attribution_fractions(attr)
    return {
        **attr,
        "comms_fraction": fr["comms_fraction"],
        "host_gap_fraction": fr["host_gap_fraction"],
    }


def chaos_counts(events: list[dict]) -> dict:
    """Chaos-tier spans (round 14): ``fault-injected`` instants from the
    injection registry (utils/faults.py) and ``degradation`` instants from
    the ladder (utils/degrade.py) — a captured trace proves what was
    injected and what gracefully degraded, by site and by rung."""
    faults = [e for e in events if e["name"] == "fault-injected"]
    rungs = [e for e in events if e["name"] == "degradation"]
    by_site: dict[str, int] = defaultdict(int)
    for e in faults:
        by_site[str(e.get("args", {}).get("site", "?"))] += 1
    by_rung: dict[str, int] = defaultdict(int)
    for e in rungs:
        by_rung[str(e.get("args", {}).get("rung", "?"))] += 1
    return {
        "faults_injected": len(faults),
        "faults_by_site": dict(sorted(by_site.items())),
        "degradations": len(rungs),
        "degradations_by_rung": dict(sorted(by_rung.items())),
    }


def numerics_counts(events: list[dict]) -> dict:
    """Numerics sentinel spans (utils/numerics.py records an instant span
    per non-finite observation / quarantine when tracing is on) — so a
    captured trace carries its own numeric-health verdict offline."""
    nonfinite = [e for e in events if e["name"] == "nonfinite-event"]
    quarantines = [e for e in events if e["name"] == "quarantine"]
    by_where: dict[str, int] = defaultdict(int)
    for e in nonfinite:
        by_where[str(e.get("args", {}).get("where", "?"))] += 1
    return {
        "nonfinite_events": len(nonfinite),
        "quarantines": len(quarantines),
        "nonfinite_by_where": dict(sorted(by_where.items())),
    }


def forensics_counts(events: list[dict]) -> dict:
    """Request-forensics span attrs (round 21): the tracer stamps every
    span that runs under an inbound traceparent with ``trace_id``, and the
    router's fleet-hop / stage-dispatch spans carry ``role`` + ``pool``
    labels.  Reported as NEW keys only — the pinned aggregate keys above
    (stream overlap, lane-wait p95, host gap) are untouched."""
    trace_ids = set()
    by_role: dict[str, int] = defaultdict(int)
    by_pool: dict[str, int] = defaultdict(int)
    by_host: dict[str, int] = defaultdict(int)
    for e in events:
        args = e.get("args", {})
        tid = args.get("trace_id")
        if tid is not None:
            trace_ids.add(str(tid))
        if args.get("role") is not None:
            by_role[str(args["role"])] += 1
        if args.get("pool") is not None:
            by_pool[str(args["pool"])] += 1
        if args.get("host") is not None:
            by_host[str(args["host"])] += 1
    return {
        "trace_ids": len(trace_ids),
        "spans_by_role": dict(sorted(by_role.items())),
        "spans_by_pool": dict(sorted(by_pool.items())),
        "spans_by_host": dict(sorted(by_host.items())),
    }


def summarize(events: list[dict]) -> dict:
    by_cat: dict[str, list[float]] = defaultdict(list)
    by_name: dict[str, list[float]] = defaultdict(list)
    for e in events:
        by_cat[e.get("cat", "?")].append(e.get("dur", 0.0))
        by_name[e["name"]].append(e.get("dur", 0.0))
    eff = stream_overlap_efficiency(events)
    p95 = lane_wait_p95_s(events)
    gap = host_gap_ms(events)
    return {
        "numerics": numerics_counts(events),
        "chaos": chaos_counts(events),
        "forensics": forensics_counts(events),
        "spans": len(events),
        "layers": {
            cat: {
                "spans": len(durs),
                "total_ms": round(sum(durs) / 1e3, 3),
                "mean_ms": round(sum(durs) / len(durs) / 1e3, 3),
                "max_ms": round(max(durs) / 1e3, 3),
            }
            for cat, durs in sorted(
                by_cat.items(), key=lambda kv: -sum(kv[1])
            )
        },
        "top_spans": {
            name: {
                "count": len(durs),
                "total_ms": round(sum(durs) / 1e3, 3),
                "p95_ms": round(percentile(durs, 95) / 1e3, 3),
            }
            for name, durs in sorted(
                by_name.items(), key=lambda kv: -sum(kv[1])
            )[:12]
        },
        "stream_overlap_efficiency": None if eff is None else round(eff, 4),
        "lane_wait_p95": None if p95 is None else round(p95, 6),
        "host_gap_ms": None if gap is None else round(gap, 4),
        # Roofline bucket decomposition of the traced window (comms and
        # host-gap fractions included — where the non-compute time went).
        "attribution": attribution(events),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace", help="trace-event JSON file")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable summary (one JSON object)")
    ap.add_argument("--prompt-id", default=None,
                    help="restrict to one prompt's spans")
    args = ap.parse_args()
    events = load_events(args.trace)
    if args.prompt_id is not None:
        events = [e for e in events
                  if e.get("args", {}).get("prompt_id") == args.prompt_id]
    s = summarize(events)
    if args.json:
        print(json.dumps(s))
        return
    print(f"{s['spans']} spans")
    print(f"{'layer':<10} {'spans':>6} {'total ms':>10} {'mean ms':>9} "
          f"{'max ms':>9}")
    for cat, row in s["layers"].items():
        print(f"{cat:<10} {row['spans']:>6} {row['total_ms']:>10.3f} "
              f"{row['mean_ms']:>9.3f} {row['max_ms']:>9.3f}")
    print()
    print(f"{'span':<24} {'count':>6} {'total ms':>10} {'p95 ms':>9}")
    for name, row in s["top_spans"].items():
        print(f"{name:<24} {row['count']:>6} {row['total_ms']:>10.3f} "
              f"{row['p95_ms']:>9.3f}")
    print()
    print(f"stream_overlap_efficiency: {s['stream_overlap_efficiency']}")
    print(f"lane_wait_p95: {s['lane_wait_p95']}")
    print(f"host_gap_ms: {s['host_gap_ms']}")
    attr = s["attribution"]
    if attr is not None:
        print(f"attribution: compute {attr['compute_s']}s, exposed transfer "
              f"{attr['exposed_transfer_s']}s, comms {attr['comms_s']}s "
              f"({attr['comms_fraction']:.1%}), host gap "
              f"{attr['host_gap_s']}s ({attr['host_gap_fraction']:.1%}) "
              f"of {attr['wall_s']}s wall")
    n = s["numerics"]
    print(f"numerics: {n['nonfinite_events']} non-finite event(s), "
          f"{n['quarantines']} quarantine(s)"
          + (f" — by site {n['nonfinite_by_where']}"
             if n["nonfinite_by_where"] else ""))
    fx = s["forensics"]
    if fx["trace_ids"] or fx["spans_by_role"]:
        print(f"forensics: {fx['trace_ids']} trace id(s)"
              + (f", spans by role {fx['spans_by_role']}"
                 if fx["spans_by_role"] else "")
              + (f", by host {fx['spans_by_host']}"
                 if fx["spans_by_host"] else ""))
    c = s["chaos"]
    print(f"chaos: {c['faults_injected']} injected fault(s)"
          + (f" by site {c['faults_by_site']}" if c["faults_by_site"] else "")
          + f", {c['degradations']} degradation rung(s)"
          + (f" by rung {c['degradations_by_rung']}"
             if c["degradations_by_rung"] else ""))


if __name__ == "__main__":
    try:
        main()
    except BrokenPipeError:
        pass  # `trace_summary.py t.json | head` is a normal way to use this
