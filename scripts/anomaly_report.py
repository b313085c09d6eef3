"""Anomaly attribution gate: every sentinel firing must have a declared cause.

The online anomaly sentinel (``utils/anomaly.py``) appends a
``kind="anomaly"`` record to the perf ledger for every firing — signal,
observed vs baseline, z-score, and ``attributed_to`` (the fault sites and
load phase overlapping the firing window). This script is the audit over
those records, the same shape as ``scripts/numerics_audit.py`` over
fingerprints:

- default      one line per firing (signal, observed/baseline, cause)
- ``--check``  the ATTRIBUTION GATE: exit 1 if any firing has
               ``attributed == False`` — an anomaly nobody declared a
               fault plan or load phase for is either a real regression
               or a broken detector, and both block. Ledgers with no
               anomaly records at all are SKIP, never failed (a fresh
               checkout — and any clean run — must pass CI).

Stays jax-free (imports bench.py, whose module level is stdlib-only) so it
runs on a laptop holding just the ledger.
"""

from __future__ import annotations

import argparse
import json
import os
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


def anomaly_records(records: list[dict]) -> list[dict]:
    return [r for r in records
            if r.get("kind") == "anomaly"
            and r.get("schema") == LEDGER_SCHEMA]


def _cause(rec: dict) -> str:
    at = rec.get("attributed_to") or {}
    parts = []
    if at.get("faults"):
        parts.append("faults=" + ",".join(at["faults"]))
    if at.get("phase"):
        parts.append(f"phase={at['phase']}")
    return " ".join(parts) or "UNATTRIBUTED"


def summarize(records: list[dict]) -> None:
    events = anomaly_records(records)
    if not events:
        print("anomaly_report: no anomaly records in the ledger")
        return
    print(f"{len(events)} anomaly firing(s):")
    for rec in events:
        print(f"  {rec.get('signal')}: observed {rec.get('observed')} "
              f"vs baseline {rec.get('baseline')} (z={rec.get('z')}) "
              f"on {rec.get('host') or '?'} — {_cause(rec)}"
              + (f" [postmortem {rec['postmortem']}]"
                 if rec.get("postmortem") else ""))


def check(records: list[dict]) -> int:
    events = anomaly_records(records)
    if not events:
        print("anomaly_report: SKIP — no anomaly records in the ledger "
              "(clean run or sentinel off)")
        return 0
    bad = [r for r in events if not r.get("attributed")]
    for rec in events:
        status = "FAIL " if not rec.get("attributed") else "ok   "
        print(f"{status}{rec.get('signal')}: observed {rec.get('observed')} "
              f"vs baseline {rec.get('baseline')} — {_cause(rec)}")
    if bad:
        print(f"anomaly_report: FAILED — {len(bad)}/{len(events)} "
              f"firing(s) with no declared fault/phase cause")
        return 1
    print(f"anomaly_report: ok — {len(events)} firing(s), all attributed")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ledger", default=None,
                    help="ledger file or directory (default: $PA_LEDGER_DIR "
                         "or <evidence dir>/ledger)")
    ap.add_argument("--check", action="store_true",
                    help="run the attribution gate (exit 1 on any "
                         "unattributed firing)")
    args = ap.parse_args()

    from bench import evidence_dir

    ledger = (args.ledger or os.environ.get("PA_LEDGER_DIR")
              or os.path.join(evidence_dir(), "ledger"))
    if not ledger.endswith(".jsonl"):
        ledger = os.path.join(ledger, "perf_ledger.jsonl")
    records = _load_jsonl(ledger)
    if args.check:
        sys.exit(check(records))
    summarize(records)


if __name__ == "__main__":
    main()
