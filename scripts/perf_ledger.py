"""Perf-ledger queries and the regression gate.

The ledger (``ledger/perf_ledger.jsonl``, schema ``pa-perf-ledger/v1``) holds
one JSON record per bench/dryrun/loadgen run, appended by bench.py (kinds
``bench``/``error``), ``__graft_entry__.dryrun_multichip`` (``dryrun``), and
``scripts/loadgen.py`` (``loadgen``) — see
``comfyui_parallelanything_tpu/utils/telemetry.py`` for the writer.

Modes:

- default            one summary line per ledger kind + the latest bench
                     record per (rung, platform) group
- ``--check``        the REGRESSION GATE: for every (rung, platform) group,
                     compare the group's latest bench record against its
                     baseline and exit 1 when step time regressed by more
                     than ``--step-pct`` (default 25%) or peak HBM by more
                     than ``--hbm-pct`` (default 15%). Groups with no
                     baseline are reported as SKIP, never failed — a fresh
                     checkout with an empty ledger must pass CI.

Baseline resolution per (rung, platform) group, in order:

1. the banked evidence, when ``--baseline FILE`` names one: its records for
   the same rung AND platform that are not stale, invalid or dryrun-marked.
   Median when several.
2. the group's own PRIOR ledger records (everything before the latest).
   Median again — a one-off fast outlier must not turn every later honest
   run into a "regression".

Stale re-emits, dryrun-marked records, and ``error`` records are never
compared in either direction. Stays jax-free (imports bench.py, whose module
level is stdlib-only), so it never takes the chip from a process that has it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

LEDGER_SCHEMA = "pa-perf-ledger/v1"


def _load_jsonl(path: str) -> list[dict]:
    out: list[dict] = []
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                out.append(rec)
    return out


def _comparable(rec: dict) -> bool:
    """A bench record the gate may compare: measured (not a stale re-emit or
    a mocked dry-run), with a positive numeric step time."""
    if rec.get("kind") != "bench" or rec.get("schema") != LEDGER_SCHEMA:
        return False
    if rec.get("stale") or rec.get("dryrun") or rec.get("invalid"):
        return False
    v = rec.get("value")
    return isinstance(v, (int, float)) and v > 0


def _group_key(rec: dict) -> tuple:
    return (rec.get("rung") or rec.get("metric") or "?",
            rec.get("platform") or "?")


def _banked_baseline(rung: str, platform: str, baseline_path: str | None
                     ) -> tuple[float | None, float | None]:
    """(median step time, median peak HBM) of the banked evidence records for
    this rung+platform, or (None, None)."""
    vals: list[float] = []
    hbm: list[float] = []
    for rec in _load_jsonl(baseline_path) if baseline_path else ():
        if rec.get("rung") != rung or rec.get("platform") != platform:
            continue
        if rec.get("stale") or rec.get("invalid") or rec.get("dryrun"):
            continue
        v = rec.get("value")
        if isinstance(v, (int, float)) and v > 0:
            vals.append(float(v))
        p = rec.get("peak_hbm_bytes")
        if isinstance(p, (int, float)) and p > 0:
            hbm.append(float(p))
    return (statistics.median(vals) if vals else None,
            statistics.median(hbm) if hbm else None)


def _prior_baseline(prior: list[dict]) -> tuple[float | None, float | None]:
    vals = [float(r["value"]) for r in prior]
    hbm = [float(r["peak_hbm_bytes"]) for r in prior
           if isinstance(r.get("peak_hbm_bytes"), (int, float))
           and r["peak_hbm_bytes"] > 0]
    return (statistics.median(vals) if vals else None,
            statistics.median(hbm) if hbm else None)


def check(records: list[dict], baseline_path: str | None, step_pct: float,
          hbm_pct: float) -> int:
    """The gate. Prints one verdict line per group; returns the exit code."""
    groups: dict[tuple, list[dict]] = {}
    for rec in records:
        if _comparable(rec):
            groups.setdefault(_group_key(rec), []).append(rec)
    if not groups:
        print("perf_ledger: no comparable bench records in the ledger — OK "
              "(nothing to gate)")
        return 0
    failures = 0
    for (rung, platform), recs in sorted(groups.items()):
        latest, prior = recs[-1], recs[:-1]
        base_v, base_hbm = _banked_baseline(rung, platform, baseline_path)
        prior_v, prior_hbm = _prior_baseline(prior)
        source = "banked"
        if base_v is None:
            base_v = prior_v
            source = f"ledger[{len(prior)}]"
        if base_hbm is None:
            # Resolved independently of the step-time source: records banked
            # before round 9 carry no peak_hbm_bytes, and the HBM half of the
            # gate must not go inert just because a step-time baseline exists.
            base_hbm = prior_hbm
        if base_v is None:
            print(f"SKIP  {rung}/{platform}: no baseline "
                  f"(latest {latest['value']} s/it)")
            continue
        v = float(latest["value"])
        ratio = v / base_v
        verdict = []
        if ratio > 1.0 + step_pct / 100.0:
            verdict.append(
                f"step time {v:.4g} s/it vs baseline {base_v:.4g} "
                f"({ratio:.2f}x > +{step_pct:g}%)"
            )
        p = latest.get("peak_hbm_bytes")
        if (base_hbm and isinstance(p, (int, float)) and p > 0
                and p / base_hbm > 1.0 + hbm_pct / 100.0):
            verdict.append(
                f"peak HBM {p / 2**30:.2f} GiB vs baseline "
                f"{base_hbm / 2**30:.2f} GiB "
                f"({p / base_hbm:.2f}x > +{hbm_pct:g}%)"
            )
        if verdict:
            failures += 1
            print(f"REGRESSION  {rung}/{platform} [{source}]: "
                  + "; ".join(verdict))
        else:
            print(f"OK    {rung}/{platform} [{source}]: {v:.4g} s/it "
                  f"({ratio:.2f}x baseline)")
    if failures:
        print(f"perf_ledger: {failures} regressed group(s)")
        return 1
    print("perf_ledger: no regressions")
    return 0


def summarize(records: list[dict]) -> None:
    kinds: dict[str, int] = {}
    for rec in records:
        kinds[rec.get("kind", "?")] = kinds.get(rec.get("kind", "?"), 0) + 1
    print(f"{len(records)} ledger record(s): "
          + (", ".join(f"{k}={n}" for k, n in sorted(kinds.items()))
             or "none"))
    latest: dict[tuple, dict] = {}
    for rec in records:
        if _comparable(rec):
            latest[_group_key(rec)] = rec
    for (rung, platform), rec in sorted(latest.items()):
        extras = []
        if rec.get("compile_time_s") is not None:
            extras.append(f"compile {rec['compile_time_s']}s "
                          f"(hits {rec.get('compile_cache_hits')}, "
                          f"misses {rec.get('compile_cache_misses')})")
        if isinstance(rec.get("peak_hbm_bytes"), (int, float)):
            extras.append(f"peak {rec['peak_hbm_bytes'] / 2**30:.2f} GiB")
        print(f"  {rung}/{platform}: {rec.get('value')} {rec.get('unit', '')}"
              + (" — " + ", ".join(extras) if extras else ""))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ledger", default=None,
                    help="ledger file or directory (default: $PA_LEDGER_DIR "
                         "or <evidence dir>/ledger)")
    ap.add_argument("--baseline", default=None,
                    help="banked evidence file (JSON lines); without it "
                         "a group's own prior records are its baseline")
    ap.add_argument("--check", action="store_true",
                    help="run the regression gate (exit 1 on regression)")
    ap.add_argument("--step-pct", type=float, default=25.0,
                    help="max tolerated step-time growth vs baseline (%%)")
    ap.add_argument("--hbm-pct", type=float, default=15.0,
                    help="max tolerated peak-HBM growth vs baseline (%%)")
    args = ap.parse_args()

    from bench import evidence_dir

    ledger = (args.ledger or os.environ.get("PA_LEDGER_DIR")
              or os.path.join(evidence_dir(), "ledger"))
    if os.path.isdir(ledger):
        ledger = os.path.join(ledger, "perf_ledger.jsonl")
    records = _load_jsonl(ledger)
    if args.check:
        sys.exit(check(records, args.baseline, args.step_pct, args.hbm_pct))
    summarize(records)


if __name__ == "__main__":
    main()
