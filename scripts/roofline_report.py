"""Roofline attribution report, schema gate, and calibration bank.

The measured side of every run lives in the perf ledger
(``ledger/perf_ledger.jsonl``); the predicted side rides the same records as
``predicted_step_s`` / ``predicted_step_raw_s`` / ``roofline_ratio`` /
``attribution`` / ``roofline_programs`` (bench.py + dryrun, written via
``utils/roofline.py``). This script is the offline consumer — the exact
audit/gate/bank trio scripts/perf_ledger.py and scripts/numerics_audit.py
established:

- default      one line per (rung, platform) group: predicted vs actual,
               ratio, attribution fractions, FLOPs source — plus the
               calibration store's current key count.
- ``--check``  the SCHEMA GATE (wired into scripts/ci_tier1.sh after the
               perf and numerics gates): for the latest roofline-carrying
               record per group, ``roofline_ratio`` must sit in (0, 1.2]
               (a prediction more than 1.2x the measured time means the
               model or its calibration is lying), every attribution bucket
               must be non-negative, and the buckets must sum to within 10%
               of the recorded wall. Records without roofline fields (the
               pre-round-13 history) are skipped; an empty/unroofed ledger
               is SKIP, never a failure.
- ``--bank``   fit per-(program, platform, shape-bucket) calibration scales
               from the FULL ledger history (scale = conservative p25 of
               actual / predicted_raw — always against the raw prediction
               so re-banking converges, and below-median so an honest
               speedup doesn't trip the fixed (0, 1.2] band) and persist to
               ``ledger/roofline_calib.json``. Run after banking new
               hardware evidence — the next run's predictions are then
               self-corrected.

Stays jax-free: ``utils/roofline.py`` is loaded standalone by file path (its
module level is stdlib-only and free of package-relative imports by
contract), so this runs on a laptop with just the ledger.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

LEDGER_SCHEMA = "pa-perf-ledger/v1"


def _load_roofline():
    """utils/roofline.py loaded standalone — no package import, no jax."""
    path = os.path.join(_REPO, "comfyui_parallelanything_tpu", "utils",
                        "roofline.py")
    spec = importlib.util.spec_from_file_location("pa_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


roofline = _load_roofline()

ATTR_BUCKETS = ("compute_s", "exposed_transfer_s", "comms_s", "host_gap_s")


def _carries_roofline(rec: dict) -> bool:
    """A record this gate may judge: a measured bench/dryrun line (never a
    stale re-emit or error record) that actually carries a roofline ratio —
    the pre-roofline history and null-filled stale lines are out of scope."""
    if rec.get("schema") != LEDGER_SCHEMA:
        return False
    if rec.get("kind") not in ("bench", "dryrun"):
        return False
    if rec.get("stale") or rec.get("invalid"):
        return False
    return isinstance(rec.get("roofline_ratio"), (int, float))


def _group_key(rec: dict) -> str:
    return (f"{rec.get('rung') or rec.get('metric') or '?'}/"
            f"{rec.get('platform') or '?'}")


def _check_attribution(attr) -> list[str]:
    """Bucket sanity: non-negative, and Σ buckets within 10% of the wall."""
    problems: list[str] = []
    if attr is None:
        return problems  # an untraced run legitimately carries null
    if not isinstance(attr, dict):
        return [f"attribution is not an object: {attr!r}"]
    for b in ATTR_BUCKETS:
        v = attr.get(b)
        if not isinstance(v, (int, float)) or v < 0:
            problems.append(f"attribution bucket {b} not non-negative: {v!r}")
    wall = attr.get("wall_s")
    if isinstance(wall, (int, float)) and wall > 0:
        total = sum(
            attr.get(b) for b in ATTR_BUCKETS
            if isinstance(attr.get(b), (int, float))
        )
        if not 0.9 * wall <= total <= 1.1 * wall:
            problems.append(
                f"attribution buckets sum {total:.4g}s vs wall "
                f"{wall:.4g}s (outside the 10% band)"
            )
    return problems


def check(records: list[dict]) -> int:
    """The gate: latest roofline-carrying record per group; exit 1 on any
    out-of-band ratio or malformed attribution."""
    groups: dict[str, dict] = {}
    for rec in records:
        if _carries_roofline(rec):
            groups[_group_key(rec)] = rec  # latest wins (file order)
    if not groups:
        print("roofline_report: no roofline-carrying records in the ledger "
              "— SKIP (nothing to gate)")
        return 0
    failures = 0
    for key, rec in sorted(groups.items()):
        ratio = rec["roofline_ratio"]
        problems = []
        if not 0.0 < ratio <= 1.2:
            problems.append(
                f"roofline_ratio {ratio} outside (0, 1.2] — the analytic "
                "model (or its calibration) disagrees with the clock"
            )
        problems += _check_attribution(rec.get("attribution"))
        if problems:
            failures += 1
            print(f"FAIL  {key}: " + "; ".join(problems))
        else:
            print(f"OK    {key}: ratio {ratio} "
                  f"(predicted {rec.get('predicted_step_s')}s, "
                  f"measured {rec.get('value')}{rec.get('unit', '')})")
    if failures:
        print(f"roofline_report: {failures} failed group(s)")
        return 1
    print("roofline_report: roofline schema sane")
    return 0


def bank(records: list[dict], calib_file: str) -> int:
    """Fit + persist the calibration store from the full ledger history."""
    scales = roofline.fit_calibration(records)
    if not scales:
        print("roofline_report: nothing to bank (no records carry both a "
              "raw prediction and a measurement)")
        return 1
    path = roofline.save_calibration(scales, calib_file)
    if path is None:
        print(f"roofline_report: could not write {calib_file}")
        return 1
    for key, entry in sorted(scales.items()):
        print(f"BANK  {key}: scale {entry['scale']} (n={entry['n']})")
    print(f"calibration written to {path} ({len(scales)} key(s))")
    return 0


def summarize(records: list[dict], calib_file: str) -> None:
    latest: dict[str, dict] = {}
    total = 0
    for rec in records:
        if _carries_roofline(rec):
            total += 1
            latest[_group_key(rec)] = rec
    calib = roofline.load_calibration(calib_file)
    print(f"{total} roofline-carrying record(s) across {len(latest)} "
          f"group(s); {len(calib)} calibration key(s) banked")
    for key, rec in sorted(latest.items()):
        fr = roofline.attribution_fractions(rec.get("attribution"))
        attr_txt = (
            "untraced" if fr is None else
            f"compute {fr['compute_fraction']:.0%} / transfer "
            f"{fr['exposed_transfer_fraction']:.0%} / comms "
            f"{fr['comms_fraction']:.0%} / host-gap "
            f"{fr['host_gap_fraction']:.0%}"
        )
        progs = rec.get("roofline_programs")
        print(f"  {key}: predicted {rec.get('predicted_step_s')}s vs "
              f"measured {rec.get('value')} (ratio "
              f"{rec.get('roofline_ratio')}, flops_source "
              f"{rec.get('flops_source')}); {attr_txt}"
              + (f"; {len(progs)} program row(s)"
                 if isinstance(progs, dict) else ""))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ledger", default=None,
                    help="ledger file or directory (default: $PA_LEDGER_DIR "
                         "or <evidence dir>/ledger)")
    ap.add_argument("--calib", default=None,
                    help="calibration store (default: <ledger dir>/"
                         f"{roofline.CALIB_FILENAME})")
    ap.add_argument("--check", action="store_true",
                    help="run the schema gate (exit 1 on an out-of-band "
                         "ratio or malformed attribution)")
    ap.add_argument("--bank", action="store_true",
                    help="fit calibration scales from ledger history and "
                         "persist them")
    args = ap.parse_args()

    from bench import evidence_dir

    ledger = (args.ledger or os.environ.get("PA_LEDGER_DIR")
              or os.path.join(evidence_dir(), "ledger"))
    if ledger.endswith(".jsonl"):
        ledger_dir = os.path.dirname(ledger) or "."
    else:
        ledger_dir = ledger
        ledger = os.path.join(ledger, "perf_ledger.jsonl")
    calib_file = args.calib or os.path.join(ledger_dir,
                                            roofline.CALIB_FILENAME)
    records = roofline.load_jsonl(ledger)
    if args.bank:
        sys.exit(bank(records, calib_file))
    if args.check:
        sys.exit(check(records))
    summarize(records, calib_file)


if __name__ == "__main__":
    try:
        main()
    except BrokenPipeError:
        pass
