"""Numerics drift gate: golden latent fingerprints per rung, banked beside
the perf ledger.

Every fresh bench line (bench.py) carries a ``latent_fingerprint`` — a
deterministic bf16-quantized digest of the rung's final latent
(``utils/numerics.py``; invariant to occupancy, bucket width, and dp
sharding by construction) — and a ``nonfinite_events`` count. This script is
the audit over the ledger those lines append to, exactly like the perf gate
(``scripts/perf_ledger.py``) is for step time and peak HBM:

- default      one coverage line per (rung, platform) group
- ``--check``  the DRIFT GATE: for every group, compare the latest bench
               record's fingerprint against the banked golden — or, with no
               golden banked yet, against the group's own most recent prior
               record — and exit 1 on a mismatch OR on
               ``nonfinite_events > 0`` in the latest record. Groups with no
               fingerprint anywhere are SKIP, never failed (a fresh checkout
               with an empty ledger must pass CI).
- ``--bank``   bank the latest fingerprint per group as the golden
               (``<ledger>/numerics_golden.json``) — run after an INTENDED
               numeric change (new kernel, precision policy), the same
               handshake as re-banking a perf baseline.

Stale re-emits, dryrun-marked records, and ``error`` records are never
compared. The verdict is also written to ``<ledger>/numerics_gate.json``
(best-effort) — the ``numerics.fingerprint_gate`` field of ``GET /health``.
Stays jax-free (imports bench.py, whose module level is stdlib-only) so it
runs without a backend.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

LEDGER_SCHEMA = "pa-perf-ledger/v1"
GOLDEN_FILENAME = "numerics_golden.json"
GATE_FILENAME = "numerics_gate.json"


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
    """A record the gate may compare: a measured bench line (never a stale
    re-emit, dry-run, or error record) carrying a fingerprint string."""
    if rec.get("kind") != "bench" or rec.get("schema") != LEDGER_SCHEMA:
        return False
    if rec.get("stale") or rec.get("dryrun") or rec.get("invalid"):
        return False
    return isinstance(rec.get("latent_fingerprint"), str)


def _group_key(rec: dict) -> str:
    return f"{rec.get('rung') or '?'}/{rec.get('platform') or '?'}"


def _load_golden(path: str) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, json.JSONDecodeError):
        return {}


def _write_gate(ledger_dir: str, verdict: dict) -> None:
    try:
        with open(os.path.join(ledger_dir, GATE_FILENAME), "w") as f:
            json.dump(verdict, f, indent=1)
    except OSError:
        pass  # best-effort: a read-only checkout must not fail the gate


def check(records: list[dict], golden: dict, *, ledger_dir: str,
          write_gate: bool = True) -> int:
    """The gate. One verdict line per group; returns the exit code and
    writes the ``numerics_gate.json`` status for ``GET /health``."""
    groups: dict[str, list[dict]] = {}
    for rec in records:
        if _comparable(rec):
            groups.setdefault(_group_key(rec), []).append(rec)
    results: dict[str, dict] = {}
    failures = 0
    if not groups:
        print("numerics_audit: no fingerprinted bench records in the ledger "
              "— OK (nothing to gate)")
    for key, recs in sorted(groups.items()):
        latest, prior = recs[-1], recs[:-1]
        fp = latest["latent_fingerprint"]
        nfe = latest.get("nonfinite_events")
        base = (golden.get(key) or {}).get("fingerprint")
        source = "golden"
        if base is None and prior:
            base = prior[-1]["latent_fingerprint"]
            source = f"ledger[{len(prior)}]"
        problems = []
        if isinstance(nfe, (int, float)) and nfe > 0:
            problems.append(f"nonfinite_events={int(nfe)}")
        if base is None:
            status = "SKIP " if not problems else "FAIL "
            print(f"{status} {key}: no golden or prior fingerprint "
                  f"(latest {fp})" + ("; " + "; ".join(problems)
                                      if problems else ""))
            results[key] = {"status": status.strip().lower(),
                            "fingerprint": fp}
            failures += bool(problems)
            continue
        if fp != base:
            problems.append(f"fingerprint drift: {fp} != {base} [{source}]")
        if problems:
            failures += 1
            print(f"DRIFT {key}: " + "; ".join(problems))
            results[key] = {"status": "drift", "fingerprint": fp,
                            "baseline": base, "source": source}
        else:
            print(f"OK    {key}: {fp} [{source}]")
            results[key] = {"status": "ok", "fingerprint": fp,
                            "source": source}
    if write_gate:
        _write_gate(ledger_dir, {
            "status": "drift" if failures else ("ok" if groups else "skip"),
            "ts": time.time(),
            "groups": results,
        })
    if failures:
        print(f"numerics_audit: {failures} drifted/poisoned group(s)")
        return 1
    print("numerics_audit: no fingerprint drift")
    return 0


def bank(records: list[dict], golden_path: str) -> int:
    """Bank the latest fingerprint per group as the golden."""
    golden = _load_golden(golden_path)
    latest: dict[str, dict] = {}
    for rec in records:
        if _comparable(rec):
            latest[_group_key(rec)] = rec
    if not latest:
        print("numerics_audit: nothing to bank (no fingerprinted bench "
              "records)")
        return 1
    for key, rec in sorted(latest.items()):
        golden[key] = {
            "fingerprint": rec["latent_fingerprint"],
            "ts": rec.get("ts"),
            "banked_ts": time.time(),
        }
        print(f"BANK  {key}: {rec['latent_fingerprint']}")
    os.makedirs(os.path.dirname(golden_path) or ".", exist_ok=True)
    with open(golden_path, "w") as f:
        json.dump(golden, f, indent=1, sort_keys=True)
    return 0


def summarize(records: list[dict], golden: dict) -> None:
    latest: dict[str, dict] = {}
    total = 0
    for rec in records:
        if _comparable(rec):
            total += 1
            latest[_group_key(rec)] = rec
    print(f"{total} fingerprinted bench record(s) across "
          f"{len(latest)} group(s); {len(golden)} golden(s) banked")
    for key, rec in sorted(latest.items()):
        g = (golden.get(key) or {}).get("fingerprint")
        mark = "=" if g == rec["latent_fingerprint"] else (
            "?" if g is None else "!")
        print(f"  {key}: {rec['latent_fingerprint']} "
              f"(nonfinite_events={rec.get('nonfinite_events')}) "
              f"golden{mark}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ledger", default=None,
                    help="ledger file or directory (default: $PA_LEDGER_DIR "
                         "or <evidence dir>/ledger)")
    ap.add_argument("--golden", default=None,
                    help="golden fingerprint bank (default: "
                         f"<ledger dir>/{GOLDEN_FILENAME})")
    ap.add_argument("--check", action="store_true",
                    help="run the drift gate (exit 1 on drift or non-finite "
                         "events)")
    ap.add_argument("--bank", action="store_true",
                    help="bank the latest fingerprint per (rung, platform) "
                         "as the golden")
    args = ap.parse_args()

    from bench import evidence_dir

    ledger = (args.ledger or os.environ.get("PA_LEDGER_DIR")
              or os.path.join(evidence_dir(), "ledger"))
    if ledger.endswith(".jsonl"):
        ledger_dir = os.path.dirname(ledger) or "."
    else:  # a directory (existing or not — fresh checkouts have none yet)
        ledger_dir = ledger
        ledger = os.path.join(ledger, "perf_ledger.jsonl")
    golden_path = args.golden or os.path.join(ledger_dir, GOLDEN_FILENAME)
    records = _load_jsonl(ledger)
    if args.bank:
        sys.exit(bank(records, golden_path))
    if args.check:
        sys.exit(check(records, _load_golden(golden_path),
                       ledger_dir=ledger_dir))
    summarize(records, _load_golden(golden_path))


if __name__ == "__main__":
    main()
