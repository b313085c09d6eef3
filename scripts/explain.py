#!/usr/bin/env python3
"""Price every wall-second of one prompt: the request-forensics CLI.

``explain.py <prompt_id> --base http://router:8187`` fetches the stitched
cross-host timeline (``GET /fleet/trace?prompt_id=`` — every host the prompt
touched, one trace_id, clock-aligned tracks; see fleet/router.py
``stitch_trace``) and reconstructs where the client-observed wall went,
priced with the roofline bucket vocabulary (utils/roofline.py):

- ``compute``          — device/program execution (workflow-node span union)
- ``exposed_transfer`` — weight-streaming prefetch the overlap didn't hide
- ``comms``            — cross-host hops: dispatch POSTs, stage hand-offs,
                         remote handle/cond fetches
- ``queue_wait``       — admission + lane-seat waits (every ``*-wait`` span)
- ``host_gap``         — the residual: wall time no span accounts for
                         (scheduler gaps, history polling, HTTP overhead)

Bucket precedence is queue > transfer > comms > compute (a lane-wait inside
a workflow-node span is queue time, not compute), and ``host_gap`` is the
residual against the wall — so the buckets are non-negative and sum to the
wall BY CONSTRUCTION whenever the wall covers the trace window. The
``--check`` gate (CI: scripts/ci_tier1.sh) enforces the conservation rule:
every bucket >= 0 and |sum - wall| <= 10% of wall.

Stdlib-only and jax-free (the scripts/ standalone contract — same as
trace_summary.py): runs anywhere the trace JSON can be carried.

The reference answers "why was prompt X slow" with per-thread progress
prints read off a terminal (any_device_parallel.py progress lines); this
CLI answers it from one stitched document covering every host.

Usage:
  explain.py <prompt_id> [--base URL]      # fetch + explain one prompt
  explain.py --trace-file doc.json         # explain an already-saved stitch
  explain.py ... --wall-s 3.2              # price against the CLIENT wall
  explain.py ... --check [--min-hosts 3]   # CI gate (exit 1 on violation)
  explain.py ... --json                    # machine-readable report
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import urllib.request

SCHEMA = "pa-explain/v1"

# Bucket classification by span name, applied in precedence order (first
# match wins): queue > exposed_transfer > comms > compute. Substring rules
# keep the map robust to per-subsystem naming (lane-wait, admission-wait,
# decode-wait... are all queue).
QUEUE_SUFFIX = "-wait"
TRANSFER_MARKS = ("prefetch", "transfer", "h2d", "d2h")
COMMS_NAMES = ("fleet-hop", "stage-dispatch")
COMMS_MARKS = ("fetch", "comms", "collective", "all-gather", "all-reduce")
COMPUTE_NAMES = ("workflow-node",)
BUCKETS = ("compute", "exposed_transfer", "comms", "queue_wait", "host_gap")


def classify(name: str) -> str | None:
    n = str(name)
    if n.endswith(QUEUE_SUFFIX):
        return "queue_wait"
    if any(m in n for m in TRANSFER_MARKS):
        return "exposed_transfer"
    if n in COMMS_NAMES or any(m in n for m in COMMS_MARKS):
        return "comms"
    if n in COMPUTE_NAMES:
        return "compute"
    return None


# -- interval algebra (seconds) ----------------------------------------------


def _merge(ivals):
    """Union of [s, e) intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(i for i in ivals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _subtract(ivals, cuts):
    """``ivals`` minus ``cuts`` (both disjoint sorted)."""
    out = []
    for s, e in ivals:
        cur = s
        for cs, ce in cuts:
            if ce <= cur or cs >= e:
                continue
            if cs > cur:
                out.append([cur, cs])
            cur = max(cur, ce)
            if cur >= e:
                break
        if cur < e:
            out.append([cur, e])
    return out


def _total(ivals) -> float:
    return sum(e - s for s, e in ivals)


# -- the explanation ---------------------------------------------------------


def _x_events(doc):
    return [e for e in doc.get("traceEvents", ()) if e.get("ph") == "X"]


def _span_interval(e):
    s = e.get("ts", 0.0) / 1e6
    return [s, s + max(0.0, e.get("dur", 0.0)) / 1e6]


def _bucketize(events):
    """The five-bucket pricing of one event set against its own window.
    Returns (window_s, by_bucket_intervals) — ``host_gap`` is priced by the
    caller against whichever wall it answers for."""
    pools = {"queue_wait": [], "exposed_transfer": [], "comms": [],
             "compute": []}
    for e in events:
        b = classify(e.get("name", ""))
        if b is not None:
            pools[b].append(_span_interval(e))
    covered = []
    out = {}
    # Precedence by subtraction: a second already priced as queue is never
    # double-billed as compute.
    for b in ("queue_wait", "exposed_transfer", "comms", "compute"):
        u = _subtract(_merge(pools[b]), covered)
        out[b] = u
        covered = _merge(covered + u)
    return out


def explain_doc(doc: dict, wall_s: float | None = None) -> dict:
    """Turn one stitched fleet trace (``pa-fleet-trace/v1``) into the priced
    forensics report. ``wall_s`` is the CLIENT-observed end-to-end latency
    when the caller has it; absent, the router's ``fleet-prompt`` span
    (submit -> entry collected) stands in, then the raw trace extent."""
    xs = _x_events(doc)
    if not xs:
        return {"schema": SCHEMA, "error": "trace holds no spans",
                "trace_id": doc.get("trace_id")}
    t0 = min(e.get("ts", 0.0) for e in xs) / 1e6
    t1 = max(e.get("ts", 0.0) + e.get("dur", 0.0) for e in xs) / 1e6
    window_s = max(0.0, t1 - t0)
    fleet_prompt = next((e for e in xs if e.get("name") == "fleet-prompt"),
                        None)
    if wall_s is None and fleet_prompt is not None:
        wall_s = fleet_prompt.get("dur", 0.0) / 1e6
    if wall_s is None:
        wall_s = window_s

    pools = _bucketize(xs)
    buckets = {b: round(_total(u), 6) for b, u in pools.items()}
    accounted = sum(buckets.values())
    buckets["host_gap"] = round(max(0.0, wall_s - accounted), 6)
    total = sum(buckets.values())
    rel_err = abs(total - wall_s) / wall_s if wall_s > 0 else 0.0
    dominant = max(BUCKETS, key=lambda b: buckets[b])

    # Per-stage rows: one per backend prompt span (a mid-stage failover
    # shows the same stage twice, on two hosts — both priced).
    stages = []
    for e in xs:
        if e.get("name") != "prompt":
            continue
        args = e.get("args") or {}
        lo, hi = _span_interval(e)
        inside = [x for x in xs
                  if x.get("pid") == e.get("pid")
                  and _span_interval(x)[0] >= lo - 1e-6
                  and _span_interval(x)[1] <= hi + 1e-6]
        sp = _bucketize(inside)
        row = {
            "host": args.get("host_id") or args.get("host"),
            "role": args.get("role"),
            "stage": args.get("stage"),
            "start_s": round(lo - t0, 6),
            "wall_s": round(hi - lo, 6),
        }
        for b in ("compute", "exposed_transfer", "comms", "queue_wait"):
            row[b + "_s"] = round(_total(sp[b]), 6)
        row["host_gap_s"] = round(
            max(0.0, row["wall_s"] - sum(
                row[b + "_s"]
                for b in ("compute", "exposed_transfer", "comms",
                          "queue_wait"))), 6)
        stages.append(row)
    stages.sort(key=lambda r: r["start_s"])

    # The cross-host critical path: stage executions in time order with the
    # inter-stage gaps (dispatch + collect + hand-off) called out — the gap
    # seconds are where the router/journal story (instant events) points.
    path = []
    cursor = t0
    for row in stages:
        gap = row["start_s"] - (cursor - t0)
        if gap > 1e-6:
            path.append({"kind": "gap", "wall_s": round(gap, 6)})
        path.append({"kind": "stage", **{k: row[k] for k in
                                         ("host", "role", "stage", "wall_s")}})
        cursor = max(cursor, t0 + row["start_s"] + row["wall_s"])
    tail = t1 - cursor
    if tail > 1e-6:
        path.append({"kind": "gap", "wall_s": round(tail, 6)})

    trace_ids = {str((e.get("args") or {}).get("trace_id"))
                 for e in xs if (e.get("args") or {}).get("trace_id")}
    hosts = doc.get("hosts") or []
    journal = sorted({e.get("name") for e in doc.get("traceEvents", ())
                      if e.get("ph") == "i"})

    report = {
        "schema": SCHEMA,
        "trace_id": doc.get("trace_id"),
        "trace_ids_seen": sorted(trace_ids),
        "hosts": hosts,
        "host_tracks": sum(1 for h in hosts if h.get("role") != "router"),
        "fetch_ok": [h.get("host") for h in hosts if h.get("ok")],
        "fetch_failed": [h.get("host") for h in hosts if not h.get("ok")],
        "spans": len(xs),
        "journal_events": journal,
        "wall_s": round(wall_s, 6),
        "trace_window_s": round(window_s, 6),
        "buckets_s": buckets,
        "bucket_fractions": {
            b: round(v / wall_s, 4) if wall_s > 0 else 0.0
            for b, v in buckets.items()
        },
        "dominant_bucket": dominant,
        "conservation": {
            "sum_s": round(total, 6),
            "wall_s": round(wall_s, 6),
            "rel_err": round(rel_err, 4),
        },
        "stages": stages,
        "critical_path": path,
    }
    # SLO stage deltas when objectives are declared (same env contract as
    # utils/slo.py, parsed stdlib-side): how far the wall sits from each
    # latency objective's threshold.
    objectives = _objectives_from_env()
    if objectives:
        report["slo"] = [
            {"objective": name, "threshold_s": thr,
             "delta_s": round(wall_s - thr, 6),
             "met": wall_s <= thr}
            for name, thr in objectives
        ]
    return report


def _objectives_from_env() -> list:
    """(name, threshold_s) pairs from PA_SLO_OBJECTIVES (the utils/slo.py
    JSON contract), without importing the package (jax-free)."""
    raw = os.environ.get("PA_SLO_OBJECTIVES")
    if not raw:
        return []
    try:
        objs = json.loads(raw)
        return [(str(o["name"]), float(o["threshold_s"]))
                for o in objs if "name" in o and "threshold_s" in o]
    except (ValueError, TypeError, KeyError):
        return []


def check(report: dict, *, tolerance: float = 0.10,
          min_hosts: int = 1) -> list:
    """The conservation gate: every violated rule as a message (empty =
    pass). CI runs this on the fleet smoke's slowest prompt."""
    errs = []
    if report.get("error"):
        return [f"no explanation: {report['error']}"]
    if report.get("host_tracks", 0) < min_hosts:
        errs.append(
            f"stitched timeline covers {report.get('host_tracks', 0)} host "
            f"track(s), need >= {min_hosts}"
        )
    if len(report.get("trace_ids_seen") or ()) > 1:
        errs.append(
            f"spans carry {len(report['trace_ids_seen'])} trace_ids, "
            f"expected one lineage: {report['trace_ids_seen']}"
        )
    for b, v in (report.get("buckets_s") or {}).items():
        if v < 0:
            errs.append(f"bucket {b} is negative ({v}s)")
    cons = report.get("conservation") or {}
    if cons.get("rel_err", 1.0) > tolerance:
        errs.append(
            f"buckets sum to {cons.get('sum_s')}s vs wall "
            f"{cons.get('wall_s')}s — rel err {cons.get('rel_err')} > "
            f"{tolerance} (the 10% conservation rule)"
        )
    return errs


def _fetch(base: str, prompt_id: str, timeout: float = 30.0) -> dict:
    url = f"{base.rstrip('/')}/fleet/trace?prompt_id={prompt_id}"
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _render(report: dict) -> str:
    if report.get("error"):
        return f"explain: {report['error']}"
    lines = [
        f"prompt {report['trace_id']} — wall {report['wall_s']:.3f}s over "
        f"{report['host_tracks']} host track(s), {report['spans']} spans",
    ]
    if report.get("fetch_failed"):
        lines.append(f"  (missing hops: {', '.join(map(str, report['fetch_failed']))})")
    w = report["wall_s"] or 1.0
    for b in BUCKETS:
        v = report["buckets_s"].get(b, 0.0)
        bar = "#" * int(round(40 * v / w))
        flag = "  <= dominant" if b == report["dominant_bucket"] else ""
        lines.append(f"  {b:<17} {v:>8.3f}s {v / w:>6.1%} {bar}{flag}")
    cons = report["conservation"]
    lines.append(
        f"  conservation: buckets sum {cons['sum_s']:.3f}s vs wall "
        f"{cons['wall_s']:.3f}s (rel err {cons['rel_err']:.1%})"
    )
    if report.get("stages"):
        lines.append("  critical path:")
        for seg in report["critical_path"]:
            if seg["kind"] == "gap":
                lines.append(f"    .. {seg['wall_s']:.3f}s hand-off/queue gap")
            else:
                lines.append(
                    f"    [{seg.get('role') or '-'}] {seg.get('host')}: "
                    f"{seg['wall_s']:.3f}s"
                )
    for o in report.get("slo") or ():
        verdict = "met" if o["met"] else "MISSED"
        lines.append(
            f"  slo {o['objective']}: {verdict} "
            f"(delta {o['delta_s']:+.3f}s vs {o['threshold_s']}s)"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("prompt_id", nargs="?", help="router-scoped prompt id")
    ap.add_argument("--base", default="http://127.0.0.1:8187",
                    help="fleet router base URL")
    ap.add_argument("--trace-file", help="explain a saved stitched trace "
                    "instead of fetching")
    ap.add_argument("--wall-s", type=float, default=None,
                    help="client-observed wall to price against")
    ap.add_argument("--check", action="store_true",
                    help="gate mode: exit 1 unless buckets are non-negative "
                         "and conserve the wall within --tolerance")
    ap.add_argument("--tolerance", type=float, default=0.10)
    ap.add_argument("--min-hosts", type=int, default=1,
                    help="--check: minimum stitched host tracks")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    if args.trace_file:
        with open(args.trace_file) as f:
            doc = json.load(f)
        # The CI forensics dump wraps the stitched doc with the
        # client-observed wall it was measured against
        # (tests/test_roles.py::TestRequestForensics writes it under
        # PA_FORENSICS_DUMP) — unwrap, and let the recorded wall stand in
        # unless --wall-s overrides.
        if isinstance(doc, dict) and isinstance(doc.get("doc"), dict):
            if args.wall_s is None and doc.get("wall_s") is not None:
                args.wall_s = float(doc["wall_s"])
            doc = doc["doc"]
    elif args.prompt_id:
        try:
            doc = _fetch(args.base, args.prompt_id)
        except OSError as e:
            print(f"explain: cannot fetch stitched trace: {e}",
                  file=sys.stderr)
            return 2
    else:
        ap.error("need a prompt_id (or --trace-file)")

    report = explain_doc(doc, wall_s=args.wall_s)
    if args.as_json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        print(_render(report))
    if args.check:
        errs = check(report, tolerance=args.tolerance,
                     min_hosts=args.min_hosts)
        for e in errs:
            print(f"explain --check: {e}", file=sys.stderr)
        return 1 if errs else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
