"""Traffic twin: replay an arrival trace against predicted per-host capacity.

The open-loop loadgen (scripts/loadgen.py) measures latency-under-load by
actually driving a fleet; this module predicts the same curves in SECONDS,
with NO devices — a discrete-event simulation of the router's
placement/queueing over per-host service times, so placement/admission/
scaling policies can be evaluated offline (the capacity-prediction story of
PAPERS.md arxiv 2412.14374; ROADMAP round-15 "open-loop traffic twin").
Twin-predicted p95 vs measured p95 is a checkable, bankable number
(``scripts/twin_report.py --check/--bank`` — ci_tier1-gated within the
declared error band).

Three pieces:

- **arrival processes** (:func:`gen_arrivals`): seeded, deterministic —
  Poisson (exponential inter-arrivals at ``rps``), bursty ON-OFF (Poisson
  at ``rps`` during ON windows, silent during OFF — the diurnal-burst
  rehearsal), and trace replay (:func:`arrivals_from_journal` lifts submit
  timestamps out of a recorded fleet journal). scripts/loadgen.py loads this
  file standalone and fires REAL requests on the same schedule the twin
  replays — one generator, two consumers, so "the same seeded arrival
  trace" is true by construction.
- **the simulation** (:func:`simulate`): per-host pools of ``workers``
  servers with deterministic service times; each arrival is placed on the
  host that can START it earliest (ring affinity collapses to this under
  one model key: the primary while free, spill-to-least-loaded when
  saturated — the router's admission shape without its HTTP). Latency =
  queue wait + service; the output is the same p50/p95/p99 curve shape the
  open-loop loadgen emits.
- **per-host capacity** (:func:`host_service_times`): tiered like every
  calibration consumer — (1) the roofline prediction
  (``utils/roofline.predict_time_s`` × the calibration store) when the
  record carries per-host FLOPs/bytes rows; (2) the record's own measured
  per-host service p50 (the ledger-calibrated fallback — what the CPU smoke
  exercises, where no compiled-program roofline rows exist for the toy
  graphs); (3) the record-wide mean. Sources are named in the output so a
  twin report says WHAT predicted, not just how well.

Import discipline: module level is stdlib-only and free of package-relative
imports (the utils/roofline.py contract) — scripts/loadgen.py and
scripts/twin_report.py load this file standalone by path; utils/roofline.py
is itself loaded lazily by path for the prediction tier.
"""

from __future__ import annotations

import heapq
import json
import os
import random

ARRIVALS_SCHEMA = "pa-arrivals/v1"

ARRIVAL_KINDS = ("poisson", "onoff", "replay")


def _percentile(samples, q: float) -> float:
    """Nearest-rank percentile (the scripts/loadgen.py convention — the twin
    and the measurement must rank identically or the error band lies)."""
    if not samples:
        return 0.0
    s = sorted(samples)
    k = max(0, min(len(s) - 1, round(q / 100.0 * (len(s) - 1))))
    return s[k]


# ---------------------------------------------------------------------------
# arrival processes (seeded, deterministic)
# ---------------------------------------------------------------------------


def gen_arrivals(kind: str, *, rps: float, duration_s: float, seed: int = 0,
                 on_s: float = 1.0, off_s: float = 1.0) -> list[float]:
    """Arrival offsets (seconds from the rung's start), sorted ascending.

    ``poisson``: exponential inter-arrival gaps at ``rps`` — the open-loop
    memoryless baseline. ``onoff``: the same process gated by an ON/OFF
    square wave (``on_s`` busy, ``off_s`` silent) with the ON rate scaled so
    the OFFERED average stays ``rps`` — burstiness changes the queue, not
    the load, which is exactly the comparison the twin exists to predict.
    Deterministic in (kind, rps, duration, seed, on_s, off_s): two calls
    yield the identical schedule."""
    if kind not in ("poisson", "onoff"):
        raise ValueError(f"unknown arrival kind {kind!r} "
                         f"(have: poisson, onoff; replay loads a file)")
    rps = float(rps)
    duration_s = float(duration_s)
    if rps <= 0 or duration_s <= 0:
        return []
    rng = random.Random(int(seed))
    out: list[float] = []
    if kind == "poisson":
        t = rng.expovariate(rps)
        while t < duration_s:
            out.append(round(t, 6))
            t += rng.expovariate(rps)
        return out
    # onoff: ON windows carry the whole offered load.
    on_s = max(1e-3, float(on_s))
    off_s = max(0.0, float(off_s))
    duty = on_s / (on_s + off_s)
    rate_on = rps / max(1e-9, duty)
    t = 0.0
    while t < duration_s:
        # one ON window
        w = rng.expovariate(rate_on)
        while w < on_s and t + w < duration_s:
            out.append(round(t + w, 6))
            w += rng.expovariate(rate_on)
        t += on_s + off_s
    out.sort()
    return out


def arrivals_from_journal(path: str) -> list[float]:
    """Trace replay: submit-record timestamps from a recorded fleet journal
    (``pa-fleet-journal/v1`` JSONL), as offsets from the first submit —
    yesterday's real traffic becomes today's load schedule. Torn/garbage
    lines are skipped (the journal's own replay discipline)."""
    stamps: list[float] = []
    try:
        with open(path, "rb") as f:
            for raw in f:
                try:
                    rec = json.loads(raw)
                except ValueError:
                    continue
                if (isinstance(rec, dict) and rec.get("ev") == "submit"
                        and isinstance(rec.get("ts"), (int, float))):
                    stamps.append(float(rec["ts"]))
    except OSError:
        return []
    if not stamps:
        return []
    t0 = min(stamps)
    return sorted(round(t - t0, 6) for t in stamps)


def save_arrivals(path: str, rungs: list[dict], *, kind: str,
                  seed: int | None = None) -> str:
    """Persist an arrival schedule (``--arrivals-out``): one JSON document
    ``{"schema", "kind", "seed", "rungs": [{"rps", "duration_s",
    "offsets"}]}`` — the twin (and a later replay run) reads it back."""
    doc = {"schema": ARRIVALS_SCHEMA, "kind": kind, "seed": seed,
           "rungs": rungs}
    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def load_arrivals(path: str) -> dict:
    """An ``--arrivals-in`` file: either a saved arrivals document (schema
    pa-arrivals/v1) or a raw fleet journal (detected by its records) —
    normalized to the arrivals-document shape with one rung."""
    try:
        with open(path) as f:
            head = f.read(4096)
    except OSError as e:
        raise ValueError(f"cannot read arrivals file {path!r}: {e}") from e
    if '"pa-arrivals/v1"' in head:
        with open(path) as f:
            doc = json.load(f)
        if not isinstance(doc.get("rungs"), list):
            raise ValueError(f"{path!r}: arrivals document has no rungs")
        return doc
    offsets = arrivals_from_journal(path)
    if not offsets:
        raise ValueError(
            f"{path!r} is neither a pa-arrivals/v1 document nor a journal "
            f"with submit records"
        )
    dur = max(offsets) or 1.0
    return {"schema": ARRIVALS_SCHEMA, "kind": "replay", "seed": None,
            "rungs": [{"rps": round(len(offsets) / dur, 4),
                       "duration_s": round(dur, 3), "offsets": offsets}]}


# ---------------------------------------------------------------------------
# the discrete-event simulation
# ---------------------------------------------------------------------------


ROLE_STAGES = ("encode", "denoise", "decode")


def simulate(arrivals: list[float], hosts: list[dict],
             percentiles=(50, 95, 99), overhead_s: float = 0.0) -> dict:
    """Replay ``arrivals`` over per-host worker pools.

    ``hosts``: ``[{"host_id", "service_s", "workers"}]`` — ``service_s`` is
    the deterministic per-request service time, ``workers`` the host's
    concurrent servers (the backend's prompt-worker pool). Placement is the
    router's admission shape under one model key: every arrival goes to the
    host that can START it earliest (primary affinity while free ≡ earliest
    start; saturation spill ≡ least-loaded) — FIFO per worker, no preemption.

    Host rows may also carry ``"role"`` (fleet/roles.py): when any host
    declares a role other than ``all``, the simulation becomes the
    DISAGGREGATED tandem — each request flows encode → denoise → decode,
    each stage placed earliest-start within that stage's pool (role match
    plus ``all`` generalists, who share one worker heap across every stage
    they serve), and a stage's completion time is the next stage's arrival
    — the hand-off edge. A host's ``service_s`` is its per-STAGE service
    time there (an encode host's measured p50 is encode work by
    construction). An all-``all`` fleet takes the single-queue path
    unchanged, bit-for-bit.

    ``overhead_s`` is a constant per-request client-side term (HTTP +
    history-poll cadence — what loadgen's ``collect`` residual measures),
    added to every latency but occupying no server: the twin predicts the
    CLIENT's end-to-end curve, which is what the measured record carries.

    Returns the measured-curve shape: latency percentiles, achieved rps,
    mean queue wait, and per-host request counts — directly comparable to
    one open-loop loadgen rung."""
    pools: dict[str, list[float]] = {}
    service: dict[str, float] = {}
    role_of: dict[str, str] = {}
    for h in hosts:
        hid = str(h.get("host_id"))
        workers = max(1, int(h.get("workers") or 1))
        pools[hid] = [0.0] * workers  # heap of worker-free times
        service[hid] = max(1e-6, float(h.get("service_s") or 0.0))
        role_of[hid] = str(h.get("role") or "all")
    if not pools:
        raise ValueError("simulate() needs at least one host")
    for heap in pools.values():
        heapq.heapify(heap)
    disaggregated = any(r != "all" for r in role_of.values())
    # Stage hand-off edges: per-stage candidate pools, empty stages elided
    # (a fleet with no encode specialists and no generalists has no encode
    # hop to model).
    stage_pools = [
        [hid for hid in pools if role_of[hid] in (stage, "all")]
        for stage in ROLE_STAGES
    ] if disaggregated else [list(pools)]
    stage_pools = [p for p in stage_pools if p]
    lat: list[float] = []
    waits: list[float] = []
    served: dict[str, int] = {hid: 0 for hid in pools}
    end = 0.0
    for t in arrivals:
        t_stage = t
        wait = 0.0
        for pool in stage_pools:
            # Earliest possible START across the stage's hosts; service
            # time breaks ties (a faster host that starts at the same
            # instant finishes first).
            best_hid = min(
                pool,
                key=lambda hid: (max(pools[hid][0], t_stage), service[hid]),
            )
            heap = pools[best_hid]
            free = heapq.heappop(heap)
            start = max(free, t_stage)
            done = start + service[best_hid]
            heapq.heappush(heap, done)
            wait += start - t_stage
            served[best_hid] += 1
            t_stage = done  # the hand-off: next stage arrives at completion
        lat.append(t_stage - t + max(0.0, float(overhead_s)))
        waits.append(wait)
        end = max(end, t_stage)
    out = {
        "requests": len(arrivals),
        "wall_s": round(end, 6),
        "achieved_rps": round(len(arrivals) / end, 4) if end > 0 else None,
        "queue_wait_mean_s": (
            round(sum(waits) / len(waits), 6) if waits else 0.0
        ),
        "hosts": served,
    }
    for q in percentiles:
        out[f"latency_p{q}_s"] = round(_percentile(lat, q), 6)
    return out


# ---------------------------------------------------------------------------
# per-host capacity (the roofline/calibration tier)
# ---------------------------------------------------------------------------


def _load_roofline():
    """utils/roofline.py loaded standalone by file path (its module level is
    stdlib-only and free of package-relative imports by contract) — the twin
    must predict without jax, from just the ledger."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "utils", "roofline.py",
    )
    spec = importlib.util.spec_from_file_location("pa_roofline_twin", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def host_service_times(record: dict, calib: dict | None = None) -> list[dict]:
    """Per-host ``[{"host_id", "service_s", "workers", "source"}]`` from an
    openloop ledger record's ``hosts`` section. Tiered:

    1. ``roofline``: the host row carries ``flops``/``bytes_accessed`` (+
       optional ``device_kind``/``platform``/``n_devices``) — priced through
       ``roofline.predict_time_s`` × the calibration store's scale for
       (``program`` = the row's ``roofline_program`` or ``rung:openloop``,
       platform, shape bucket);
    2. ``measured``: the row's ``service_p50_s`` (per-request exec p50 the
       loadgen clients collected off the history entries — the fleet's own
       same-workload measurement, the ledger-calibration analog);
    3. ``mean``: the record-wide ``service_p50_s``.

    Hosts with none of the three are dropped (a host that served nothing
    has no capacity evidence)."""
    rows = record.get("hosts") or {}
    fallback = record.get("service_p50_s")
    roofline = None
    out: list[dict] = []
    for hid, row in rows.items():
        if not isinstance(row, dict):
            continue
        workers = int(row.get("workers") or 1)
        flops = row.get("flops")
        if isinstance(flops, (int, float)) and flops > 0:
            if roofline is None:
                roofline = _load_roofline()
            spec = roofline.platform_spec(
                str(row.get("device_kind") or ""),
                str(row.get("platform") or "cpu"),
            )
            pred = roofline.predict_time_s(
                flops, row.get("bytes_accessed"), spec,
                n_devices=int(row.get("n_devices") or 1),
            )
            program = str(row.get("roofline_program") or "rung:openloop")
            scale = roofline.calibration_scale(
                calib if calib is not None else roofline.load_calibration(),
                program, spec.get("platform") or "cpu",
                roofline.shape_bucket(flops),
            )
            out.append({"host_id": hid,
                        "service_s": pred["predicted_s"] * scale,
                        "workers": workers, "source": "roofline",
                        "role": str(row.get("role") or "all")})
            continue
        svc = row.get("service_p50_s")
        if isinstance(svc, (int, float)) and svc > 0:
            out.append({"host_id": hid, "service_s": float(svc),
                        "workers": workers, "source": "measured",
                        "role": str(row.get("role") or "all")})
            continue
        if isinstance(fallback, (int, float)) and fallback > 0:
            out.append({"host_id": hid, "service_s": float(fallback),
                        "workers": workers, "source": "mean",
                        "role": str(row.get("role") or "all")})
    return out


# ---------------------------------------------------------------------------
# record replay (the twin_report.py engine)
# ---------------------------------------------------------------------------


def rung_arrivals(rung: dict, *, kind: str, seed: int | None) -> list[float]:
    """One curve rung's arrival schedule: verbatim offsets when the record
    carries them, else regenerated from the stored (kind, seed, rps,
    duration) — bit-identical to the loadgen run's by the seeded-generator
    contract."""
    offsets = rung.get("offsets")
    if isinstance(offsets, list) and offsets:
        return [float(t) for t in offsets]
    if kind == "replay":
        # A replay rung IS its offsets — nothing to regenerate. Empty means
        # unreplayable (the caller skips the rung), never a generator call
        # (gen_arrivals rejects the kind, and the CI gate must SKIP, not
        # crash, on a degenerate banked record).
        return []
    # The REQUESTED rate seeds the generator (rps_offered is the realized
    # arrivals/duration — close, but regeneration must use the same input).
    return gen_arrivals(
        kind, rps=float(rung.get("rps") or rung.get("rps_offered") or 0.0),
        duration_s=float(rung.get("duration_s") or 0.0),
        seed=int(seed or 0),
        on_s=float(rung.get("on_s") or 1.0),
        off_s=float(rung.get("off_s") or 1.0),
    )


def replay_record(record: dict, calib: dict | None = None) -> dict | None:
    """Replay one ``kind="openloop"`` ledger record through the twin:
    regenerate each rung's arrivals, price the hosts, simulate, and compare
    predicted vs measured p95 per rung. None when the record carries no
    usable hosts or rungs (nothing to predict against)."""
    ol = record.get("openloop") or {}
    rungs = ol.get("curve") or []
    hosts = host_service_times(record, calib)
    if not hosts or not rungs:
        return None
    kind = str(ol.get("kind") or "poisson")
    seed = ol.get("seed")
    # The record's calibrated client-side constant (loadgen computes it at
    # the lowest offered rate, where queueing is ~0 and the residual is
    # pure transport + poll cadence).
    overhead = float(ol.get("client_overhead_s") or 0.0)
    out_rungs: list[dict] = []
    for rung in rungs:
        arrivals = rung_arrivals(rung, kind=kind, seed=seed)
        if not arrivals:
            continue
        sim = simulate(arrivals, hosts, overhead_s=overhead)
        measured = rung.get("latency_p95_s")
        err = None
        if isinstance(measured, (int, float)) and measured > 0:
            err = abs(sim["latency_p95_s"] - measured) / measured
        out_rungs.append({
            "rps_offered": rung.get("rps_offered") or rung.get("rps"),
            "arrivals": len(arrivals),
            "twin_p50_s": sim["latency_p50_s"],
            "twin_p95_s": sim["latency_p95_s"],
            "twin_p99_s": sim.get("latency_p99_s"),
            "measured_p50_s": rung.get("latency_p50_s"),
            "measured_p95_s": measured,
            "measured_p99_s": rung.get("latency_p99_s"),
            "p95_err": None if err is None else round(err, 4),
        })
    if not out_rungs:
        return None
    errs = [r["p95_err"] for r in out_rungs if r["p95_err"] is not None]
    return {
        "kind": kind,
        "seed": seed,
        "client_overhead_s": overhead,
        "hosts": hosts,
        "rungs": out_rungs,
        "p95_err_max": round(max(errs), 4) if errs else None,
        "band": record.get("twin_band"),
    }
