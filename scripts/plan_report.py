"""Auto-parallel plan report and gate (stdlib-only, jax-free).

The planner (``comfyui_parallelanything_tpu/parallel/planner.py``) records
every routing decision it takes; bench.py and the dryrun append the
measured ones as ``kind="plan"`` perf-ledger records carrying the chosen
plan, the shadow hand-rule plan it was scored against, the per-candidate
table, and — when a measurement followed — predicted-vs-actual. This
script is the offline consumer, the same audit/gate shape as
scripts/perf_ledger.py / numerics_audit.py / roofline_report.py:

- default      one line per (rung, platform) group: chosen vs hand plan,
               predicted scores, divergence, and the measured ratio.
- ``--check``  the PLAN GATE (wired into scripts/ci_tier1.sh after the
               roofline gate): for the latest plan record per group,
               the chosen plan must MATCH-OR-BEAT the shadow hand rules
               by predicted score (``plan_predicted_s <=
               plan_hand_predicted_s`` — the planner must never pick a
               plan its own model says is worse than the ladder it
               replaced), and when an actual was measured the
               predicted-vs-actual ratio must sit in the same (0, 1.2]
               calibration band the roofline gate holds rung predictions
               to. A plan-free ledger is SKIP, never a failure.

Stays jax-free: reads only the ledger JSONL (``PA_LEDGER_DIR`` redirects,
the perf-ledger rule), so it runs without a backend.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LEDGER_SCHEMA = "pa-perf-ledger/v1"
# The roofline gate's sane band, shared verbatim: a plan prediction more
# than 1.2x the measured step means the planner's cost model (or its
# calibration) is lying about the plans it ranks.
RATIO_BAND = (0.0, 1.2)


def ledger_path() -> str:
    ledger_dir = os.environ.get("PA_LEDGER_DIR")
    if not ledger_dir:
        evidence = os.environ.get("PA_EVIDENCE_DIR")
        ledger_dir = (
            os.path.join(evidence, "ledger") if evidence
            else os.path.join(_REPO, "ledger")
        )
    return os.path.join(ledger_dir, "perf_ledger.jsonl")


def load_records(path: str | None = None) -> list[dict]:
    path = path or ledger_path()
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


def _is_plan(rec: dict) -> bool:
    return (
        rec.get("schema") == LEDGER_SCHEMA
        and rec.get("kind") == "plan"
        and not rec.get("stale")
        and not rec.get("invalid")
        and isinstance(rec.get("plan_predicted_s"), (int, float))
        and isinstance(rec.get("plan_hand_predicted_s"), (int, float))
    )


def _group(rec: dict) -> str:
    return f"{rec.get('rung') or '?'}/{rec.get('platform') or '?'}"


def latest_per_group(records: list[dict]) -> dict[str, dict]:
    groups: dict[str, dict] = {}
    for rec in records:
        if _is_plan(rec):
            groups[_group(rec)] = rec  # latest wins (file order)
    return groups


def _fmt_plan(rec: dict) -> str:
    mode = rec.get("plan_mode")
    bits = [str(mode)]
    if mode in ("replicate", "tp", "fsdp"):
        bits.append(f"dp={rec.get('plan_dp')}x tp={rec.get('plan_tp')}")
    if rec.get("plan_stages"):
        bits.append(f"{rec.get('plan_stages')} stage(s)")
    return " ".join(bits)


def report(records: list[dict]) -> int:
    groups = latest_per_group(records)
    if not groups:
        print("plan_report: no kind=plan records in the ledger")
        return 0
    for key in sorted(groups):
        rec = groups[key]
        ratio = rec.get("plan_ratio")
        print(
            f"{key:28s} chosen {_fmt_plan(rec):26s} "
            f"predicted {rec.get('plan_predicted_s'):.4g}s vs hand "
            f"{rec.get('plan_hand_mode')} "
            f"{rec.get('plan_hand_predicted_s'):.4g}s  "
            f"divergent={bool(rec.get('plan_divergent'))}  "
            f"actual={rec.get('plan_actual_s') or '-'}  "
            f"ratio={ratio if ratio is not None else '-'}"
            f"{'  [dryrun]' if rec.get('dryrun') else ''}"
        )
    return 0


def check(records: list[dict]) -> int:
    """The gate: latest plan record per (rung, platform) group must
    match-or-beat the shadow hand rules and keep predicted-vs-actual in
    the calibration band."""
    groups = latest_per_group(records)
    if not groups:
        print("plan_report: no kind=plan records in the ledger — SKIP "
              "(nothing to gate)")
        return 0
    problems: list[str] = []
    for key in sorted(groups):
        rec = groups[key]
        chosen = float(rec["plan_predicted_s"])
        hand = float(rec["plan_hand_predicted_s"])
        if chosen > hand * (1 + 1e-9):
            problems.append(
                f"{key}: chosen plan predicts {chosen:.6g}s, WORSE than the "
                f"shadow hand rules' {hand:.6g}s — the planner must "
                "match-or-beat the ladder it replaced"
            )
        actual = rec.get("plan_actual_s")
        if isinstance(actual, (int, float)) and actual > 0:
            ratio = chosen / float(actual)
            lo, hi = RATIO_BAND
            if not lo < ratio <= hi:
                problems.append(
                    f"{key}: predicted-vs-actual ratio {ratio:.4g} outside "
                    f"({lo}, {hi}] (predicted {chosen:.6g}s vs measured "
                    f"{actual:.6g}s) — the plan cost model is lying"
                )
    if problems:
        print("plan_report --check: FAIL")
        for p in problems:
            print(f"  - {p}")
        return 1
    print(
        f"plan_report --check: OK — {len(groups)} plan group(s), every "
        "chosen plan matches-or-beats the hand rules"
        + (", ratios in band" if any(
            g.get("plan_actual_s") for g in groups.values()) else "")
    )
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="gate mode (ci_tier1.sh): nonzero exit on a plan "
                         "that loses to the hand rules or an out-of-band "
                         "predicted-vs-actual ratio")
    ap.add_argument("--ledger", default=None,
                    help="explicit perf_ledger.jsonl path")
    args = ap.parse_args()
    records = load_records(args.ledger)
    return check(records) if args.check else report(records)


if __name__ == "__main__":
    sys.exit(main())
