"""Traffic-twin accuracy report, gate, and bank (fleet/twin.py's consumer).

Every open-loop loadgen run appends a ``kind="openloop"`` record to the perf
ledger: the seeded arrival schedule (kind/seed/rps/duration per rung — or
verbatim offsets for trace replay), the measured latency-under-load curve,
per-host service evidence, and the declared twin error band. This script
replays those records through the discrete-event twin and compares predicted
vs measured p95 — the exact audit/gate/bank trio scripts/perf_ledger.py,
numerics_audit.py, and roofline_report.py established:

- default      one line per rung of the latest openloop record per group
               (base URL): twin p95 vs measured p95, relative error, the
               capacity source (roofline / measured / mean).
- ``--check``  the TWIN GATE (wired into scripts/ci_tier1.sh after the
               roofline gate): for the latest openloop record per group,
               every rung with enough arrivals must keep
               ``|twin p95 − measured p95| / measured`` within the record's
               declared ``twin_band`` (``--band`` overrides). A ledger with
               no openloop records is SKIP, never a failure — the gate
               activates the moment open-loop evidence banks.
- ``--bank``   persist the latest comparison per group to
               ``ledger/twin_bank.json`` (``pa-twin-bank/v1``) — the banked
               predicted-vs-measured accuracy the ROADMAP autoscaling item
               builds on.

Stays jax-free: fleet/twin.py (and, inside it, utils/roofline.py) is loaded
standalone by file path — module levels stdlib-only by contract — so this
runs on a laptop with just the ledger.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

LEDGER_SCHEMA = "pa-perf-ledger/v1"
BANK_SCHEMA = "pa-twin-bank/v1"
BANK_FILENAME = "twin_bank.json"

# Rungs with fewer arrivals than this are statistically meaningless for a
# p95 comparison (nearest-rank p95 of 4 samples is just the max) — reported
# but never gated.
MIN_ARRIVALS = 8

DEFAULT_BAND = 0.5


def _load_std(relpath: str, alias: str):
    path = os.path.join(_REPO, "comfyui_parallelanything_tpu",
                        *relpath.split("/"))
    spec = importlib.util.spec_from_file_location(alias, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


twin = _load_std("fleet/twin.py", "pa_twin_report")
roofline = _load_std("utils/roofline.py", "pa_roofline_twin_report")


def _is_openloop(rec: dict) -> bool:
    return (rec.get("schema") == LEDGER_SCHEMA
            and rec.get("kind") == "openloop"
            and not rec.get("stale") and not rec.get("invalid")
            and isinstance(rec.get("openloop"), dict))


def _group_key(rec: dict) -> str:
    return str(rec.get("base") or "?")


def latest_by_group(records: list[dict]) -> dict[str, dict]:
    groups: dict[str, dict] = {}
    for rec in records:
        if _is_openloop(rec):
            groups[_group_key(rec)] = rec  # latest wins (file order)
    return groups


def _declared_band(rec: dict) -> float:
    """The record's declared twin error band — explicit None-checks, not
    truthiness: a declared band of 0 (zero tolerance) must gate at 0, not
    silently loosen to the default."""
    for band in (rec.get("twin_band"),
                 (rec.get("openloop") or {}).get("twin_band")):
        if band is not None:
            return float(band)
    return DEFAULT_BAND


def _gateable(rung: dict) -> bool:
    return (isinstance(rung.get("measured_p95_s"), (int, float))
            and rung["measured_p95_s"] > 0
            and int(rung.get("arrivals") or 0) >= MIN_ARRIVALS
            and rung.get("p95_err") is not None)


def check(records: list[dict], band_override: float | None = None,
          calib: dict | None = None) -> int:
    groups = latest_by_group(records)
    if not groups:
        print("twin_report: no openloop records in the ledger — SKIP "
              "(nothing to gate)")
        return 0
    failures = 0
    for key, rec in sorted(groups.items()):
        band = band_override if band_override is not None \
            else _declared_band(rec)
        rep = twin.replay_record(rec, calib)
        if rep is None:
            print(f"SKIP  {key}: record carries no replayable rungs/hosts")
            continue
        gated = [r for r in rep["rungs"] if _gateable(r)]
        if not gated:
            print(f"SKIP  {key}: no rung with ≥{MIN_ARRIVALS} arrivals and "
                  f"a measured p95")
            continue
        worst = max(r["p95_err"] for r in gated)
        sources = sorted({h["source"] for h in rep["hosts"]})
        if worst > band:
            failures += 1
            print(f"FAIL  {key}: twin p95 error {worst} outside the "
                  f"declared band {band} ({len(gated)} gated rung(s), "
                  f"capacity: {','.join(sources)}) — the capacity model "
                  f"disagrees with the measured queue")
        else:
            print(f"OK    {key}: twin p95 error {worst} within band {band} "
                  f"({len(gated)} gated rung(s), capacity: "
                  f"{','.join(sources)})")
    if failures:
        print(f"twin_report: {failures} failed group(s)")
        return 1
    print("twin_report: twin predictions within the declared band")
    return 0


def bank(records: list[dict], bank_file: str,
         calib: dict | None = None) -> int:
    import time

    groups = latest_by_group(records)
    if not groups:
        print("twin_report: nothing to bank (no openloop records)")
        return 1
    entries: dict[str, dict] = {}
    for key, rec in sorted(groups.items()):
        rep = twin.replay_record(rec, calib)
        if rep is None:
            continue
        gated = [r for r in rep["rungs"] if _gateable(r)]
        entries[key] = {
            "kind": rep["kind"],
            "seed": rep["seed"],
            "client_overhead_s": rep["client_overhead_s"],
            "hosts": rep["hosts"],
            "rungs": rep["rungs"],
            "p95_err_max": (
                round(max(r["p95_err"] for r in gated), 4) if gated else None
            ),
            "band": _declared_band(rec),
            "record_ts": rec.get("ts"),
        }
        print(f"BANK  {key}: p95 err max {entries[key]['p95_err_max']} "
              f"over {len(rep['rungs'])} rung(s)")
    if not entries:
        print("twin_report: nothing replayable to bank")
        return 1
    try:
        os.makedirs(os.path.dirname(bank_file) or ".", exist_ok=True)
        with open(bank_file, "w") as f:
            json.dump({"schema": BANK_SCHEMA, "ts": time.time(),
                       "groups": entries}, f, indent=1, sort_keys=True)
    except OSError as e:
        print(f"twin_report: could not write {bank_file}: {e}")
        return 1
    print(f"twin bank written to {bank_file} ({len(entries)} group(s))")
    return 0


def summarize(records: list[dict], calib: dict | None = None) -> None:
    groups = latest_by_group(records)
    total = sum(1 for rec in records if _is_openloop(rec))
    print(f"{total} openloop record(s) across {len(groups)} group(s)")
    for key, rec in sorted(groups.items()):
        rep = twin.replay_record(rec, calib)
        if rep is None:
            print(f"  {key}: not replayable (no hosts/rungs)")
            continue
        sources = sorted({h["source"] for h in rep["hosts"]})
        print(f"  {key}: kind={rep['kind']} seed={rep['seed']} "
              f"overhead={rep['client_overhead_s']}s "
              f"capacity={','.join(sources)}")
        for r in rep["rungs"]:
            print(f"    {r.get('rps_offered')} rps: twin p95 "
                  f"{r['twin_p95_s']}s vs measured {r['measured_p95_s']}s "
                  f"(err {r['p95_err']}, {r['arrivals']} arrivals)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ledger", default=None,
                    help="ledger file or directory (default: $PA_LEDGER_DIR "
                         "or <evidence dir>/ledger)")
    ap.add_argument("--calib", default=None,
                    help="roofline calibration store for the roofline "
                         "capacity tier (default: <ledger dir>/"
                         f"{roofline.CALIB_FILENAME})")
    ap.add_argument("--band", type=float, default=None,
                    help="override the records' declared twin error band")
    ap.add_argument("--check", action="store_true",
                    help="run the twin gate (exit 1 when predicted p95 "
                         "leaves the band; SKIP on an openloop-free ledger)")
    ap.add_argument("--bank", action="store_true",
                    help="persist the latest twin-vs-measured comparison "
                         "per group to the twin bank")
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
    calib = roofline.load_calibration(calib_file)
    records = roofline.load_jsonl(ledger)
    if args.bank:
        sys.exit(bank(records, os.path.join(ledger_dir, BANK_FILENAME),
                      calib))
    if args.check:
        sys.exit(check(records, band_override=args.band, calib=calib))
    summarize(records, calib)


if __name__ == "__main__":
    try:
        main()
    except BrokenPipeError:
        pass
