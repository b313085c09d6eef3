"""Traffic twin (fleet/twin.py + scripts/twin_report.py, round 15): seeded
arrival processes, the discrete-event queueing simulation, the tiered
per-host capacity model (roofline prediction → measured service p50 → mean),
record replay, and the twin gate's SKIP/OK/FAIL/bank discipline."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from comfyui_parallelanything_tpu.fleet import twin

REPO = Path(__file__).resolve().parents[1]


class TestArrivals:
    def test_poisson_deterministic_and_rate(self):
        a = twin.gen_arrivals("poisson", rps=10, duration_s=50, seed=7)
        b = twin.gen_arrivals("poisson", rps=10, duration_s=50, seed=7)
        assert a == b and a == sorted(a)
        assert all(0 <= t < 50 for t in a)
        assert len(a) / 50 == pytest.approx(10, rel=0.15)
        c = twin.gen_arrivals("poisson", rps=10, duration_s=50, seed=8)
        assert c != a  # a different seed is a different schedule

    def test_onoff_bursty_but_same_offered_load(self):
        a = twin.gen_arrivals("onoff", rps=10, duration_s=60, seed=3,
                              on_s=1.0, off_s=1.0)
        assert len(a) / 60 == pytest.approx(10, rel=0.2)
        # every arrival lands in an ON window ([2k, 2k+1))
        assert all((t % 2.0) < 1.0 for t in a)

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError):
            twin.gen_arrivals("diurnal", rps=1, duration_s=1)

    def test_journal_replay_and_arrivals_doc_roundtrip(self, tmp_path):
        jpath = tmp_path / "journal.jsonl"
        with open(jpath, "w") as f:
            for i, ts in enumerate((100.0, 100.5, 102.25)):
                f.write(json.dumps({"ev": "submit", "pid": f"p{i}",
                                    "ts": ts}) + "\n")
            f.write(json.dumps({"ev": "dispatch", "pid": "p0",
                                "ts": 103.0}) + "\n")
            f.write("torn{garbage\n")
        offsets = twin.arrivals_from_journal(str(jpath))
        assert offsets == [0.0, 0.5, 2.25]  # submits only, rebased
        doc = twin.load_arrivals(str(jpath))
        assert doc["kind"] == "replay"
        assert doc["rungs"][0]["offsets"] == offsets
        # save/load of a generated schedule
        out = tmp_path / "arrivals.json"
        twin.save_arrivals(str(out), [{"rps": 5, "duration_s": 2,
                                       "offsets": [0.1, 0.4]}],
                           kind="poisson", seed=7)
        doc2 = twin.load_arrivals(str(out))
        assert doc2["schema"] == twin.ARRIVALS_SCHEMA
        assert doc2["rungs"][0]["offsets"] == [0.1, 0.4]


class TestSimulation:
    def _hosts(self, n=2, service=0.1, workers=1):
        return [{"host_id": f"h{i}", "service_s": service,
                 "workers": workers} for i in range(n)]

    def test_queueing_grows_with_load(self):
        """The open-loop point: past saturation, p95 blows up — the twin
        must reproduce the knee the closed loop can never see."""
        hosts = self._hosts(n=2, service=0.1)  # capacity ≈ 20 rps
        low = twin.simulate(
            twin.gen_arrivals("poisson", rps=5, duration_s=30, seed=1), hosts)
        high = twin.simulate(
            twin.gen_arrivals("poisson", rps=40, duration_s=30, seed=1),
            hosts)
        assert low["latency_p95_s"] < 0.3
        assert high["latency_p95_s"] > 5 * low["latency_p95_s"]
        assert high["queue_wait_mean_s"] > low["queue_wait_mean_s"]

    def test_more_workers_absorb_more(self):
        arrivals = twin.gen_arrivals("poisson", rps=30, duration_s=20, seed=2)
        one = twin.simulate(arrivals, self._hosts(n=2, workers=1))
        four = twin.simulate(arrivals, self._hosts(n=2, workers=4))
        assert four["latency_p95_s"] < one["latency_p95_s"]

    def test_deterministic_and_balanced(self):
        arrivals = twin.gen_arrivals("poisson", rps=20, duration_s=10, seed=4)
        s1 = twin.simulate(arrivals, self._hosts())
        s2 = twin.simulate(arrivals, self._hosts())
        assert s1 == s2
        assert s1["requests"] == len(arrivals) == sum(s1["hosts"].values())
        # both hosts served (least-start placement spreads a saturating load)
        assert all(v > 0 for v in s1["hosts"].values())

    def test_overhead_shifts_latency_only(self):
        arrivals = twin.gen_arrivals("poisson", rps=5, duration_s=10, seed=5)
        base = twin.simulate(arrivals, self._hosts())
        off = twin.simulate(arrivals, self._hosts(), overhead_s=0.25)
        assert off["latency_p50_s"] == pytest.approx(
            base["latency_p50_s"] + 0.25)
        assert off["queue_wait_mean_s"] == base["queue_wait_mean_s"]


class TestRoleTandem:
    """Round 20: host rows carrying ``role`` turn the simulation into the
    disaggregated encode→denoise→decode tandem (fleet/roles.py's pools with
    stage hand-off edges); an all-``all`` fleet stays on the single-queue
    path bit-for-bit."""

    def _role_hosts(self, n_denoise=2):
        return (
            [{"host_id": "enc", "service_s": 0.01, "workers": 1,
              "role": "encode"}]
            + [{"host_id": f"den{i}", "service_s": 0.10, "workers": 1,
                "role": "denoise"} for i in range(n_denoise)]
            + [{"host_id": "dec", "service_s": 0.02, "workers": 1,
                "role": "decode"}]
        )

    def test_all_role_rows_match_roleless_rows_bitwise(self):
        arrivals = twin.gen_arrivals("poisson", rps=10, duration_s=10, seed=6)
        plain = [{"host_id": f"h{i}", "service_s": 0.05, "workers": 2}
                 for i in range(3)]
        tagged = [dict(h, role="all") for h in plain]
        assert twin.simulate(arrivals, plain) == twin.simulate(
            arrivals, tagged)

    def test_tandem_latency_is_the_stage_sum_at_low_load(self):
        arrivals = twin.gen_arrivals("poisson", rps=2, duration_s=20, seed=7)
        s = twin.simulate(arrivals, self._role_hosts())
        assert s["requests"] == len(arrivals)
        # Unqueued request = one visit per stage pool: 0.01 + 0.10 + 0.02.
        assert s["latency_p50_s"] == pytest.approx(0.13, abs=0.02)
        # Every stage pool served; each request denoises exactly once.
        assert s["hosts"]["enc"] == len(arrivals)
        assert s["hosts"]["dec"] == len(arrivals)
        assert s["hosts"]["den0"] + s["hosts"]["den1"] == len(arrivals)

    def test_generalist_covers_stages_with_no_dedicated_host(self):
        arrivals = twin.gen_arrivals("poisson", rps=2, duration_s=10, seed=8)
        hosts = [
            {"host_id": "den", "service_s": 0.05, "workers": 1,
             "role": "denoise"},
            {"host_id": "gen", "service_s": 0.05, "workers": 1,
             "role": "all"},
        ]
        s = twin.simulate(arrivals, hosts)
        assert s["requests"] == len(arrivals)
        # encode + decode have only the generalist — it serves every
        # request at least twice.
        assert s["hosts"]["gen"] >= 2 * len(arrivals)

    def test_widening_the_bottleneck_pool_absorbs_the_load(self):
        """The twin-level readout of suggest_pool_split: denoise saturates
        first (capacity 10 rps at 0.1 s service) — one more denoise host is
        the fix, the per-role scaling knob."""
        arrivals = twin.gen_arrivals("poisson", rps=15, duration_s=20, seed=9)
        narrow = twin.simulate(arrivals, self._role_hosts(n_denoise=1))
        wide = twin.simulate(arrivals, self._role_hosts(n_denoise=2))
        assert wide["latency_p95_s"] < narrow["latency_p95_s"] / 2


class TestCapacityTiers:
    def test_measured_and_mean_tiers(self):
        rec = {
            "service_p50_s": 0.2,
            "hosts": {
                "h0": {"service_p50_s": 0.1, "workers": 2},
                "h1": {"workers": 1},              # falls back to the mean
                "h2": "not-a-row",                 # ignored
            },
        }
        rows = {h["host_id"]: h for h in twin.host_service_times(rec)}
        assert rows["h0"]["service_s"] == 0.1
        assert rows["h0"]["source"] == "measured"
        assert rows["h0"]["workers"] == 2
        assert rows["h1"]["service_s"] == 0.2
        assert rows["h1"]["source"] == "mean"
        assert "h2" not in rows

    def test_roofline_tier_with_calibration(self):
        rec = {"hosts": {"h0": {
            "flops": 1e12, "bytes_accessed": 1e9, "workers": 1,
            "platform": "cpu",
        }}}
        [row] = twin.host_service_times(rec, calib={})
        assert row["source"] == "roofline"
        # CPU pseudo-spec: compute-bound at 1e12 / 2e12 = 0.5 s
        assert row["service_s"] == pytest.approx(0.5, rel=0.05)
        [scaled] = twin.host_service_times(rec, calib={
            "rung:openloop|cpu|*": {"scale": 2.0, "n": 4},
        })
        assert scaled["service_s"] == pytest.approx(2 * row["service_s"])

    def test_no_capacity_evidence_is_empty(self):
        assert twin.host_service_times({"hosts": {"h0": {}}}) == []


def _openloop_record(measured_from_twin=True, band=0.25):
    """A synthetic openloop ledger record whose measured curve either
    matches the twin's own prediction (OK) or wildly disagrees (FAIL)."""
    hosts = [{"host_id": "h0", "service_s": 0.1, "workers": 1},
             {"host_id": "h1", "service_s": 0.1, "workers": 1}]
    curve = []
    for rps in (5.0, 15.0):
        arrivals = twin.gen_arrivals("poisson", rps=rps, duration_s=10,
                                     seed=7)
        sim = twin.simulate(arrivals, hosts, overhead_s=0.05)
        measured = (sim["latency_p95_s"] if measured_from_twin else
                    sim["latency_p95_s"] * 10 + 5)
        curve.append({
            "rps": rps, "rps_offered": round(len(arrivals) / 10, 4),
            "duration_s": 10, "arrivals": len(arrivals),
            "completed": len(arrivals),
            "latency_p50_s": sim["latency_p50_s"],
            "latency_p95_s": round(measured, 6),
            "latency_p99_s": sim["latency_p99_s"],
        })
    return {
        "schema": "pa-perf-ledger/v1", "kind": "openloop",
        "base": "http://test:1", "ts": 1.0,
        "openloop": {"kind": "poisson", "seed": 7, "curve": curve,
                     "client_overhead_s": 0.05, "twin_band": band},
        "twin_band": band,
        "hosts": {"h0": {"service_p50_s": 0.1, "workers": 1},
                  "h1": {"service_p50_s": 0.1, "workers": 1}},
        "service_p50_s": 0.1,
    }


class TestReplayRecord:
    def test_replay_matches_itself(self):
        rep = twin.replay_record(_openloop_record())
        assert rep is not None
        assert rep["p95_err_max"] == pytest.approx(0.0, abs=1e-6)
        assert len(rep["rungs"]) == 2
        assert {h["source"] for h in rep["hosts"]} == {"measured"}

    def test_unreplayable_records(self):
        assert twin.replay_record({}) is None
        assert twin.replay_record({"openloop": {"curve": []}}) is None
        rec = _openloop_record()
        rec.pop("hosts")
        rec.pop("service_p50_s")
        assert twin.replay_record(rec) is None


class TestTwinReportScript:
    def _run(self, ledger_dir, *args):
        return subprocess.run(
            [sys.executable, str(REPO / "scripts" / "twin_report.py"),
             "--ledger", str(ledger_dir), *args],
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ),
        )

    def _write_ledger(self, tmp_path, records):
        d = tmp_path / "ledger"
        d.mkdir(parents=True, exist_ok=True)
        with open(d / "perf_ledger.jsonl", "w") as f:
            for rec in records:
                f.write(json.dumps(rec) + "\n")
        return d

    def test_skip_on_empty_ledger(self, tmp_path):
        d = self._write_ledger(tmp_path, [
            {"schema": "pa-perf-ledger/v1", "kind": "bench", "value": 1.0},
        ])
        proc = self._run(d, "--check")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "SKIP" in proc.stdout

    def test_check_ok_and_fail(self, tmp_path):
        d = self._write_ledger(tmp_path, [_openloop_record()])
        proc = self._run(d, "--check")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "OK" in proc.stdout
        d2 = self._write_ledger(
            tmp_path / "bad", [_openloop_record(measured_from_twin=False)])
        proc = self._run(d2, "--check")
        assert proc.returncode == 1
        assert "FAIL" in proc.stdout

    def test_latest_record_wins(self, tmp_path):
        # An old out-of-band record is superseded by a newer in-band one.
        d = self._write_ledger(tmp_path, [
            _openloop_record(measured_from_twin=False),
            _openloop_record(),
        ])
        proc = self._run(d, "--check")
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_bank_writes_twin_bank(self, tmp_path):
        d = self._write_ledger(tmp_path, [_openloop_record()])
        proc = self._run(d, "--bank")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        bank = json.loads((d / "twin_bank.json").read_text())
        assert bank["schema"] == "pa-twin-bank/v1"
        [group] = bank["groups"].values()
        assert group["p95_err_max"] == pytest.approx(0.0, abs=1e-6)
        assert group["band"] == 0.25
