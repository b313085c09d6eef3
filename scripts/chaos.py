"""Chaos matrix runner: a seeded fault schedule against a live fleet, gated.

Builds an in-process fleet — primary router (durable prompt journal,
fleet/journal.py) + standby router tailing the same journal + N ``server.py``
backends — runs a BASELINE closed loop (scripts/loadgen.py, seeded prompt
schedule), then the SAME schedule as a CHAOS run while:

- the seeded fault plan fires (``utils/faults.py``: backend-http 5xx on
  POST /prompt, a slow-host stall — deterministic in ``--seed``),
- the primary ROUTER is killed mid-run (the standby detects the stale lease,
  replays every unresolved prompt from the journal through normal placement;
  clients fail over via loadgen's ``fallback_bases``),
- one BACKEND is killed mid-denoise (ordinary PR 7 failover, now
  warm-preferring).

A separate NETWORK-PARTITION leg (round 20) arms the ``network-partition``
fault site mid-run against one denoise host in BOTH directions — the
router's ``_post``/``_get`` and health polls to it raise refused-socket
errors while the host's own heartbeats silently vanish, each side staying
alive — and gates the same zero-lost + bitwise contract: the partitioned
host's in-flight prompts must fail over, and at least one failover plus
both direction's fault fires must be attributable.

Gates (exit 1 on any failure; one JSON verdict line on stdout, human table
on stderr — the bench.py/loadgen contract):

- ``prompts_lost == 0`` and every prompt completed;
- every completed latent BITWISE-equal to the fault-free baseline (the
  prompt nodes emit deterministic latents tagged by producing host — a
  replayed/failed-over prompt must deliver the identical result);
- bounded p95 inflation: chaos p95 ≤ ``--p95-factor`` × baseline p95 plus a
  takeover allowance (2 × lease TTL + the injected delays) — degradation
  must be graceful, not unbounded;
- each fired fault attributable: ``pa_fault_injected_total`` grew by the
  plan's firing count;
- a STREAM-OOM phase: a real weight-streamed model (tiny FLUX topology)
  forwards through an injected prefetch OOM — the re-carve ladder
  (``pa_degradation_total{rung="stream-recarve"}``) absorbs it and the
  output matches the unfaulted forward (the fleet phase's latents stay
  bitwise because they never cross a program rebuild; a re-carve recomposes
  XLA stages, so this phase gates allclose at the repo's bf16 tolerances).

The REAL-model bitwise replay contract (fold_in RNG) is dryrun §18's job on
the virtual mesh; this runner is the operational rehearsal CI can afford.

CPU-only: the run pins ``JAX_PLATFORMS=cpu`` — its virtual fleet is several
servers in one process rehearsing failover, which says nothing about a chip
and must never take one (N real backends on one host need one chip each).

Requires PA_EVIDENCE_DIR (the one arming rule — chaos artifacts must never
land in the repo's real evidence); sets it to a temp dir when absent.

Usage:
    python scripts/chaos.py [--backends 2] [--clients 3] [--requests 3]
        [--seed 7] [--work-s 0.5] [--p95-factor 25] [--skip-stream] [--keep]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
_SCRIPTS = os.path.dirname(os.path.abspath(__file__))
if _SCRIPTS not in sys.path:
    sys.path.insert(0, _SCRIPTS)


def _chaos_node(tag: str, out_dir: str):
    """Per-backend prompt node: sleeps ``work_s`` (the GIL-free device-time
    stand-in), computes a DETERMINISTIC latent from (seed, steps) — a pure
    function, so the bitwise gate isolates delivery integrity (half-runs,
    mixed replays) from numerics — and dumps it tagged with the producing
    host."""
    import numpy as np

    class ChaosDenoise:
        CATEGORY = "chaos"
        RETURN_TYPES = ("INT",)
        FUNCTION = "run"

        @classmethod
        def INPUT_TYPES(cls):
            return {"required": {"seed": ("INT", {"default": 0}),
                                 "steps": ("INT", {"default": 4}),
                                 "work_s": ("FLOAT", {"default": 0.0})}}

        def run(self, seed, steps, work_s):
            if work_s:
                time.sleep(float(work_s))
            arr = np.random.default_rng(int(seed)).standard_normal(
                (4, 8, 8)
            ).astype(np.float32)
            for _ in range(int(steps)):
                arr = np.tanh(arr * 1.1, dtype=np.float32)
            os.makedirs(out_dir, exist_ok=True)
            np.save(os.path.join(out_dir, f"{int(seed)}-{tag}.npy"), arr)
            return (int(seed),)

    return ChaosDenoise


def _graph(work_s: float):
    return {"1": {"class_type": "ChaosDenoise",
                  "inputs": {"seed": 0, "steps": 4, "work_s": float(work_s)}}}


class _Fleet:
    """Primary router (+ optional standby on the same journal) over N
    backends, all in-process."""

    def __init__(self, root: str, n_backends: int, out_dir: str,
                 journal: bool, lease_ttl_s: float = 1.0):
        from comfyui_parallelanything_tpu.fleet import (
            FleetRegistry,
            PromptJournal,
            Scoreboard,
            make_router,
        )
        from comfyui_parallelanything_tpu.server import make_server

        self.backends = []
        for i in range(n_backends):
            tag = f"chaos-host-{i}"
            srv, q = make_server(
                port=0, output_dir=os.path.join(root, tag),
                class_mappings={"ChaosDenoise": _chaos_node(tag, out_dir)},
                host_id=tag,
            )
            threading.Thread(target=srv.serve_forever, daemon=True).start()
            self.backends.append(
                (tag, f"http://127.0.0.1:{srv.server_address[1]}", srv, q)
            )
        seeds = [(t, b) for t, b, _, _ in self.backends]
        self.journal_path = os.path.join(root, "fleet-journal.jsonl")
        mk = dict(
            backends=seeds,
            saturation_depth=1, monitor_s=0.05, max_attempts=6,
        )
        self.srv, self.router = make_router(
            port=0,
            fleet_registry=FleetRegistry(ttl_s=5.0),
            scoreboard=Scoreboard(poll_s=0.1, stale_after_s=5.0,
                                  fail_after=2, timeout_s=2.0),
            journal=(PromptJournal(self.journal_path) if journal else None),
            lease_ttl_s=lease_ttl_s,
            **mk,
        )
        threading.Thread(target=self.srv.serve_forever, daemon=True).start()
        self.base = f"http://127.0.0.1:{self.srv.server_address[1]}"
        self.standby = self.standby_srv = None
        if journal:
            self.standby_srv, self.standby = make_router(
                port=0,
                fleet_registry=FleetRegistry(ttl_s=5.0),
                scoreboard=Scoreboard(poll_s=0.1, stale_after_s=5.0,
                                      fail_after=2, timeout_s=2.0),
                journal=PromptJournal(self.journal_path),
                standby=True, lease_ttl_s=lease_ttl_s,
                **mk,
            )
            threading.Thread(target=self.standby_srv.serve_forever,
                             daemon=True).start()
            self.standby_base = (
                f"http://127.0.0.1:{self.standby_srv.server_address[1]}"
            )
        t0 = time.monotonic()
        while not all(self.router.scoreboard.healthy(t) for t, *_ in seeds):
            if time.monotonic() - t0 > 60:
                raise TimeoutError("backends never turned healthy")
            time.sleep(0.02)

    def kill_router(self) -> None:
        """Crash the primary front door (HTTP gone, monitor stops, lease
        goes stale) — the standby's takeover trigger."""
        self.srv.shutdown()
        self.srv.server_close()
        self.router.shutdown()

    def kill_backend(self, idx: int) -> None:
        tag, base, srv, q = self.backends[idx]
        srv.shutdown()
        srv.server_close()
        q.interrupt()

    def stop(self) -> None:
        for srv in (self.srv, self.standby_srv):
            if srv is not None:
                try:
                    srv.shutdown()
                    srv.server_close()
                except OSError:
                    pass
        for r in (self.router, self.standby):
            if r is not None:
                r.shutdown()
        for _, _, srv, q in self.backends:
            try:
                srv.shutdown()
                srv.server_close()
            except OSError:
                pass
            q.shutdown()


class _Armed(threading.Thread):
    """A fault armed on STATE, not on a timer: polls ``condition`` and calls
    ``action`` once, the first time it holds — so the fault lands where the
    phase says it does (a prompt in flight on the victim) however fast or
    loaded the machine is. ``fired`` says that it has landed; ``cancel`` disarms
    it (the poll also gives up at ``DEADLINE_S``, and the phase then fails its
    gate: a fault that never landed proves nothing)."""

    DEADLINE_S, POLL_S = 120.0, 0.005

    def __init__(self, condition, action):
        super().__init__(daemon=True)
        self._condition, self._action = condition, action
        self._cancelled = threading.Event()
        self.fired = False

    def run(self) -> None:
        t0 = time.monotonic()
        while not self._cancelled.is_set():
            if time.monotonic() - t0 > self.DEADLINE_S:
                return
            if self._condition():
                self._action()
                self.fired = True  # only now: the next fault waits for this one
                return
            self._cancelled.wait(self.POLL_S)

    def cancel(self) -> None:
        self._cancelled.set()
        self.join(timeout=30)


def _mid_denoise(router, host_id: str | None = None) -> bool:
    """A prompt is on a backend right now (on ``host_id``, where one is
    named): dispatched, not yet collected."""
    stats = router.stats()
    if host_id is not None:
        return stats["router_inflight"].get(host_id, 0) >= 1
    return stats["prompts"].get("inflight", 0) >= 1


def default_plan(seed: int) -> dict:
    """The seeded chaos schedule: one 5xx on a prompt dispatch (the router
    must walk on / retry, never count it lost), one slow-host stall (the
    spill/latency rehearsal), and one garbled journal record on a dispatch
    append (round 15: crash-mid-write rehearsal — the standby's fold must
    skip the damage and its takeover replay the prompt from its surviving
    submit record; garble, not truncate, so neighboring records stay
    parseable and the damage is exactly one record wide). nth values derive
    from the seed inside the registry, so two runs of one seed fire
    identically. Round 22 adds a slow-disk stall on journal dispatch
    appends (the fsync-stall rehearsal): the injected latency must land in
    ``pa_disk_append_seconds`` and the anomaly sentinel's
    ``disk_append_p95`` watch must fire ATTRIBUTED to it — the
    telemetry-plane leg of the chaos gate."""
    return {"seed": int(seed), "faults": [
        {"site": "backend-http", "match": "POST /prompt", "mode": "5xx",
         "count": 1},
        {"site": "slow-host", "mode": "stall", "delay_s": 0.5, "count": 1},
        {"site": "journal-corrupt", "match": "dispatch", "mode": "garble",
         "count": 1},
        {"site": "slow-disk", "match": "dispatch", "delay_s": 1.5,
         "count": 2},
    ]}


def _fired_total() -> float:
    from comfyui_parallelanything_tpu.utils.faults import registry as freg

    return float(sum(freg.fired().values()))


def _bitwise_check(base_dir: str, chaos_dir: str, seed: int,
                   total: int) -> tuple[int, int]:
    """(missing, mismatched) latent counts between the two runs: the
    deterministic latent per seed value must be identical for EVERY
    submitted seed, and every chaos seed must have produced one at all
    (at-least-once delivery: every dumped copy must match)."""
    import random as _random

    import numpy as np

    # ONE sequential RNG — the exact schedule loadgen submitted (a fresh
    # Random(seed) per element would repeat the first value and the gate
    # would only ever check prompt 1).
    _rng = _random.Random(seed)
    sched = [_rng.randrange(1 << 31) for _ in range(total)]
    mismatched = missing = 0
    for s in sched:
        b_files = sorted(glob.glob(os.path.join(base_dir, f"{s}-*.npy")))
        c_files = sorted(glob.glob(os.path.join(chaos_dir, f"{s}-*.npy")))
        if not b_files or not c_files:
            missing += 1
            continue
        b = np.load(b_files[0])
        for cf in c_files:
            if not (np.load(cf) == b).all():
                mismatched += 1
    return missing, mismatched


def run_fleet_chaos(**kw) -> dict:
    """The fleet phase (importable — tests/test_chaos.py drives this exact
    path). Returns the verdict dict; ``ok`` is the gate. Under
    ``PA_LOCKCHECK=1`` (ci_tier1.sh sets it for the chaos smoke) the
    lock-acquisition-order graph recorded across the whole
    router+standby+backends run must stay ACYCLIC — the verdict carries
    ``lock_cycles`` and a cycle fails the phase (a potential deadlock under
    fault injection is a chaos failure even if this run never hung).

    Round 22: the telemetry plane rides along. Wall-clock sampler cadence
    is not assertable in CI, so the phase pins PA_HISTORY_INTERVAL_S high
    (background samplers never tick mid-run) and drives the history ring +
    anomaly sentinel with EXPLICIT ticks — the injected slow-disk stall
    must fire the ``disk_append_p95`` watch ATTRIBUTED to the armed plan,
    and every firing must be attributed (an unattributed anomaly under a
    known fault plan is a telemetry failure)."""
    interval_before = os.environ.get("PA_HISTORY_INTERVAL_S")
    os.environ["PA_HISTORY_INTERVAL_S"] = "3600"  # manual ticks only
    try:
        return _fleet_chaos(**kw)
    finally:
        if interval_before is None:
            os.environ.pop("PA_HISTORY_INTERVAL_S", None)
        else:
            os.environ["PA_HISTORY_INTERVAL_S"] = interval_before


def _fleet_chaos(*, n_backends: int = 2, clients: int = 3,
                 requests: int = 3, seed: int = 7, work_s: float = 0.5,
                 p95_factor: float = 25.0, lease_ttl_s: float = 1.0,
                 root: str | None = None,
                 plan: dict | None = None) -> dict:
    from loadgen import run_load

    from comfyui_parallelanything_tpu.utils import faults

    lockcheck = None
    if os.environ.get("PA_LOCKCHECK") == "1":
        from comfyui_parallelanything_tpu.utils import lockcheck

        # Installed here when the harness (tests/conftest.py) hasn't
        # already: locks created from this point on — every per-instance
        # router/scoreboard/journal/server lock below — are tracked.
        lockcheck.install()

    root = root or tempfile.mkdtemp(prefix="pa-chaos-")
    total = clients * requests
    g = _graph(work_s)

    # -- baseline: same topology, no faults, no kills -----------------------
    os.environ.pop("PA_FAULT_PLAN", None)
    faults.reload()
    base_dir = os.path.join(root, "baseline")
    fleet = _Fleet(os.path.join(root, "b"), n_backends, base_dir,
                   journal=False)
    try:
        baseline = run_load(
            fleet.base, g, clients=clients, requests=requests, timeout=120,
            seed_key="1:inputs:seed", seed=seed,
            hosts=[b for _, b, _, _ in fleet.backends],
        )
    finally:
        fleet.stop()

    # -- chaos: seeded plan + router kill + backend kill --------------------
    os.environ["PA_FAULT_PLAN"] = json.dumps(plan or default_plan(seed))
    faults.reload()
    fired_before = _fired_total()
    from comfyui_parallelanything_tpu.utils.faults import registry as _freg

    by_site_before = dict(_freg.fired())

    # -- telemetry plane: deterministic sentinel warmup ---------------------
    # Scratch-journal appends between explicit ticks establish the
    # disk-append baseline the injected stall is judged against (the plan's
    # slow-disk spec matches "dispatch", so warm "resolve" appends never
    # fire it, and the scratch path keeps warm records out of the fleet
    # journal the standby replays).
    from comfyui_parallelanything_tpu.utils import anomaly, timeseries

    sentinel_on = timeseries.enabled() and anomaly.enabled()
    anomaly_events: list[dict] = []
    if sentinel_on:
        from comfyui_parallelanything_tpu.fleet.journal import PromptJournal

        timeseries.ring.reset()
        anomaly.sentinel.reset(seed=seed)
        timeseries.ring.mark_phase("chaos-fleet", state="begin")
        warm = PromptJournal(os.path.join(root, "warm-journal.jsonl"))
        for i in range(8):
            warm.append("resolve", f"warm-{i}")
            timeseries.ring.snapshot()
            anomaly_events += anomaly.sentinel.observe(timeseries.ring)
        warm.close()

    chaos_dir = os.path.join(root, "chaos")
    fleet = _Fleet(os.path.join(root, "c"), n_backends, chaos_dir,
                   journal=True, lease_ttl_s=lease_ttl_s)
    victim_q = fleet.backends[0][3]
    # The primary dies with a prompt mid-denoise on a backend, and backend 0
    # after it with a prompt of its own running — or, if none reaches it
    # while no router is up, once the standby has taken over.
    router_kill = _Armed(lambda: _mid_denoise(fleet.router), fleet.kill_router)
    backend_kill = _Armed(
        lambda: router_kill.fired and (victim_q.running
                                       or fleet.standby.active),
        lambda: fleet.kill_backend(0))
    arms = [router_kill, backend_kill]
    try:
        for t in arms:
            t.start()
        chaos = run_load(
            fleet.base, g, clients=clients, requests=requests, timeout=240,
            seed_key="1:inputs:seed", seed=seed,
            hosts=[b for _, b, _, _ in fleet.backends],
            fallback_bases=[fleet.standby_base],
        )
    finally:
        for t in arms:
            t.cancel()
        fleet.stop()
        os.environ.pop("PA_FAULT_PLAN", None)
    fired = _fired_total() - fired_before
    # Per-site DELTAS over this run (not lifetime counts — another phase in
    # the same process, e.g. the stream-OOM rehearsal, fires too), the same
    # discipline as `fired` above. reload() swaps the registry object, so
    # re-import the module-level name rather than holding a stale reference.
    from comfyui_parallelanything_tpu.utils.faults import registry as _freg2

    fired_by_site = {
        site: n - by_site_before.get(site, 0)
        for site, n in _freg2.fired().items()
        if n - by_site_before.get(site, 0) > 0
    }

    # Post-run sentinel ticks: the stall samples are in the histogram now;
    # the snapshot's window delta carries both the latency spike and the
    # pa_fault_injected_total growth the attributor reads. The phase mark
    # closes AFTER the ticks so phase attribution still sees it open.
    if sentinel_on:
        for _ in range(2):
            timeseries.ring.snapshot()
            anomaly_events += anomaly.sentinel.observe(timeseries.ring)
        timeseries.ring.mark_phase("chaos-fleet", state="end")

    # -- gates ---------------------------------------------------------------
    failures: list[str] = []
    for arm, what in zip(arms, ("router", "backend")):
        if not arm.fired:
            failures.append(f"the {what} was never killed (no prompt was "
                            "in flight to kill it under)")
    if chaos.get("prompts_lost"):
        failures.append(f"prompts_lost={chaos['prompts_lost']} (must be 0)")
    if chaos["completed"] != total:
        failures.append(
            f"completed {chaos['completed']}/{total} (errors: "
            f"{chaos.get('errors')})"
        )
    # Bitwise survivors: the deterministic latent per seed value must be
    # identical between the baseline and chaos runs, for every submitted
    # seed — and every chaos seed must have produced one at all.
    missing, mismatched = _bitwise_check(base_dir, chaos_dir, seed, total)
    if missing:
        failures.append(f"{missing} seed(s) missing a latent dump")
    if mismatched:
        failures.append(f"{mismatched} latent(s) diverged from baseline")
    # Bounded p95 inflation: takeover costs ~lease TTL + detection sweeps;
    # anything beyond the allowance means degradation wasn't graceful.
    allowance = 2.0 * lease_ttl_s + 2.0 + work_s
    p95_bound = p95_factor * max(baseline["latency_p95_s"], 0.05) + allowance
    if chaos["latency_p95_s"] > p95_bound:
        failures.append(
            f"p95 {chaos['latency_p95_s']}s exceeds bound {p95_bound:.2f}s "
            f"(baseline {baseline['latency_p95_s']}s)"
        )
    if fired <= 0:
        failures.append("fault plan never fired (injection unproven)")
    # Telemetry-plane gates (round 22): the armed slow-disk stall must be
    # (a) counted at its site, (b) seen by the sentinel as an ATTRIBUTED
    # anomaly carrying a postmortem — and nothing may fire unattributed
    # under a known fault plan.
    planned_sites = {f["site"] for f in (plan or default_plan(seed))["faults"]}
    if "slow-disk" in planned_sites and \
            fired_by_site.get("slow-disk", 0) <= 0:
        failures.append("slow-disk never fired (injection unproven)")
    anomalies_block = None
    if sentinel_on:
        attributed = [e for e in anomaly_events if e.get("attributed")]
        unattributed = [e for e in anomaly_events
                        if not e.get("attributed")]
        if "slow-disk" in planned_sites and not attributed:
            failures.append(
                "no attributed anomaly fired (sentinel unproven — the "
                "slow-disk stall should trip disk_append_p95)"
            )
        if unattributed:
            failures.append(
                f"{len(unattributed)} unattributed anomaly firing(s): "
                + ", ".join(e["signal"] for e in unattributed)
            )
        anomalies_block = {
            "fired": len(anomaly_events),
            "attributed": len(attributed),
            "unattributed": len(unattributed),
            "signals": sorted({e["signal"] for e in anomaly_events}),
            "postmortems": [e["postmortem"] for e in anomaly_events
                            if e.get("postmortem")],
        }
    lock_cycles = None
    if lockcheck is not None:
        cycles = lockcheck.cycles()
        lock_cycles = len(cycles)
        if cycles:
            failures.append(
                "lock-order cycle(s) recorded (potential deadlock): "
                + "; ".join(" -> ".join(c) for c in cycles)
            )
    return {
        "phase": "fleet",
        "ok": not failures,
        "failures": failures,
        "lock_cycles": lock_cycles,
        "total_prompts": total,
        "prompts_lost": chaos.get("prompts_lost"),
        "completed": chaos["completed"],
        "faults_fired": fired,
        "faults_by_site": fired_by_site,
        "faults_injected_counter": chaos.get("faults_injected"),
        "anomalies": anomalies_block,
        "baseline_p95_s": baseline["latency_p95_s"],
        "chaos_p95_s": chaos["latency_p95_s"],
        "p95_bound_s": round(p95_bound, 3),
        "fleet": chaos.get("fleet"),
        "root": root,
    }


def run_partition_chaos(*, n_backends: int = 3, clients: int = 3,
                        requests: int = 3, seed: int = 11,
                        work_s: float = 0.5, p95_factor: float = 25.0,
                        root: str | None = None) -> dict:
    """The network-partition leg (round 20, importable — tests/test_chaos.py
    drives this exact path): mid-run, BOTH directions of one denoise host's
    traffic drop while each side stays alive — the ``network-partition``
    fault site cuts the router's dispatch/collect/health-poll calls to the
    victim (refused-socket OSError) and swallows the victim's own heartbeats
    — and the victim's in-flight prompts must fail over with zero lost and
    bitwise survivors. The victim runs a real ``HeartbeatClient`` beating
    ``role="denoise"`` into ``/fleet/register``, so the backend→router half
    exercises the same code path a ``server.py --role denoise`` process
    runs, and the fleet is DISAGGREGATED for the router (role pools live)."""
    from loadgen import run_load

    from comfyui_parallelanything_tpu.fleet import HeartbeatClient
    from comfyui_parallelanything_tpu.utils import faults

    root = root or tempfile.mkdtemp(prefix="pa-partition-")
    total = clients * requests
    g = _graph(work_s)

    # -- baseline: same topology, no partition ------------------------------
    os.environ.pop("PA_FAULT_PLAN", None)
    faults.reload()
    base_dir = os.path.join(root, "baseline")
    fleet = _Fleet(os.path.join(root, "b"), n_backends, base_dir,
                   journal=False)
    try:
        baseline = run_load(
            fleet.base, g, clients=clients, requests=requests, timeout=120,
            seed_key="1:inputs:seed", seed=seed,
            hosts=[b for _, b, _, _ in fleet.backends],
        )
    finally:
        fleet.stop()

    # -- partition: arm BOTH directions against host 0 mid-run --------------
    chaos_dir = os.path.join(root, "chaos")
    fleet = _Fleet(os.path.join(root, "c"), n_backends, chaos_dir,
                   journal=False)
    victim_id, victim_base = fleet.backends[0][0], fleet.backends[0][1]
    hb = HeartbeatClient(fleet.base, victim_id, victim_base,
                         interval_s=0.1, role="denoise").start()

    def arm():
        # count=None: every hit from the 1st on — a partition persists
        # until healed, unlike the one-shot faults in the default plan.
        os.environ["PA_FAULT_PLAN"] = json.dumps({"seed": int(seed), "faults": [
            {"site": "network-partition", "nth": 1, "count": None,
             "match": f"router->{victim_base}"},
            {"site": "network-partition", "nth": 1, "count": None,
             "match": f"{victim_id}->router"},
        ]})
        faults.reload()

    # Armed when the victim has a prompt in flight: what fails over is that
    # prompt, not whatever a timer happened to catch.
    timer = _Armed(lambda: _mid_denoise(fleet.router, victim_id), arm)
    fired = 0.0
    try:
        timer.start()
        chaos = run_load(
            fleet.base, g, clients=clients, requests=requests, timeout=240,
            seed_key="1:inputs:seed", seed=seed,
            hosts=[b for _, b, _, _ in fleet.backends],
        )
    finally:
        timer.cancel()
        hb.stop()
        fleet.stop()
        # arm()'s reload zeroed the registry, so its lifetime total IS this
        # leg's count — read it before the disarm reload resets it again.
        fired = _fired_total()
        os.environ.pop("PA_FAULT_PLAN", None)
        faults.reload()
    beat_drops = hb._failures

    # -- gates ---------------------------------------------------------------
    failures: list[str] = []
    if chaos.get("prompts_lost"):
        failures.append(f"prompts_lost={chaos['prompts_lost']} (must be 0)")
    if chaos["completed"] != total:
        failures.append(
            f"completed {chaos['completed']}/{total} (errors: "
            f"{chaos.get('errors')})"
        )
    missing, mismatched = _bitwise_check(base_dir, chaos_dir, seed, total)
    if missing:
        failures.append(f"{missing} seed(s) missing a latent dump")
    if mismatched:
        failures.append(f"{mismatched} latent(s) diverged from baseline")
    # Detection is scoreboard polls (0.1 s cadence, fail_after 2, 2 s
    # timeout) + one dispatch walking onto the cut link — no lease TTL in
    # this leg (single router), so the allowance is poll-detection-shaped.
    allowance = 6.0 + work_s
    p95_bound = p95_factor * max(baseline["latency_p95_s"], 0.05) + allowance
    if chaos["latency_p95_s"] > p95_bound:
        failures.append(
            f"p95 {chaos['latency_p95_s']}s exceeds bound {p95_bound:.2f}s "
            f"(baseline {baseline['latency_p95_s']}s)"
        )
    if not timer.fired:
        failures.append("the partition was never armed (the victim never "
                        "had a prompt in flight)")
    if fired <= 0:
        failures.append("network-partition never fired (injection unproven)")
    if beat_drops <= 0:
        failures.append(
            "backend->router direction never cut (no heartbeat dropped)"
        )
    failovers = (chaos.get("fleet") or {}).get("failovers")
    if not failovers:
        failures.append(
            "no failover recorded — the victim's in-flight prompt was "
            "never failed over"
        )
    return {
        "phase": "partition",
        "ok": not failures,
        "failures": failures,
        "total_prompts": total,
        "prompts_lost": chaos.get("prompts_lost"),
        "completed": chaos["completed"],
        "victim": victim_id,
        "faults_fired": fired,
        "heartbeats_dropped": beat_drops,
        "failovers": failovers,
        "baseline_p95_s": baseline["latency_p95_s"],
        "chaos_p95_s": chaos["latency_p95_s"],
        "p95_bound_s": round(p95_bound, 3),
        "fleet": chaos.get("fleet"),
        "root": root,
    }


def run_stream_oom_chaos(*, nth: int = 2) -> dict:
    """The stream-OOM phase: a REAL weight-streamed model (tiny FLUX
    topology on CPU) forwards through an injected prefetch OOM; the
    orchestrator's re-carve ladder must absorb it — completion + allclose to
    the unfaulted forward + the ``stream-recarve`` rung counted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_parallelanything_tpu import (
        DeviceChain,
        ParallelConfig,
        parallelize,
    )
    from comfyui_parallelanything_tpu.models.flux import FluxConfig, build_flux
    from comfyui_parallelanything_tpu.models.loader import params_nbytes
    from comfyui_parallelanything_tpu.utils import faults
    from comfyui_parallelanything_tpu.utils.metrics import registry as metrics

    cfg = FluxConfig(
        in_channels=16, hidden_size=64, num_heads=4, depth=2,
        depth_single_blocks=4, context_in_dim=32, vec_in_dim=16,
        axes_dim=(4, 6, 6), guidance_embed=False, dtype=jnp.float32,
    )
    model = build_flux(cfg, jax.random.key(0), sample_shape=(1, 8, 8, 4),
                       txt_len=16)
    x = jax.random.normal(jax.random.key(1), (2, 8, 8, 4))
    t = jnp.linspace(900.0, 1.0, 2)
    ctx = jax.random.normal(jax.random.key(2), (2, 16, cfg.context_in_dim))
    y = jax.random.normal(jax.random.key(3), (2, cfg.vec_in_dim))
    want = model.apply(model.params, x, t, ctx, y=y)

    os.environ["PA_FAULT_PLAN"] = json.dumps({"faults": [
        {"site": "stream-prefetch-oom", "nth": int(nth), "count": 1},
    ]})
    faults.reload()
    rung0 = metrics.get("pa_degradation_total",
                        {"rung": "stream-recarve"}) or 0.0
    failures: list[str] = []
    try:
        # Budget = full param bytes → max stage 2/5 of the weights → a
        # ~3-stage carve with a strictly finer carve available (the
        # re-carve rung must have somewhere to go; a 1-segment-per-stage
        # carve would be the exhaustion case, tested elsewhere).
        pm = parallelize(
            model, DeviceChain.even(["cpu:0"]),
            ParallelConfig(weight_sharding="stream",
                           hbm_budget_bytes=params_nbytes(model.params)),
        )
        n0 = pm._get_streaming_runner().n_stages
        got = pm(x, t, ctx, y=y)
        n1 = pm._stream_runner.n_stages
        if not np.allclose(np.asarray(got), np.asarray(want),
                           rtol=2e-3, atol=1e-4):
            failures.append("re-carved streamed output diverged")
        if n1 <= n0:
            failures.append(f"no re-carve happened ({n0} → {n1} stages)")
    except Exception as e:  # noqa: BLE001 — the gate IS "it must not raise"
        failures.append(f"streamed forward died: {type(e).__name__}: {e}")
        n0 = n1 = None
    finally:
        os.environ.pop("PA_FAULT_PLAN", None)
        faults.reload()
    rung = (metrics.get("pa_degradation_total",
                        {"rung": "stream-recarve"}) or 0.0) - rung0
    if rung <= 0:
        failures.append("stream-recarve rung not counted")
    return {
        "phase": "stream-oom",
        "ok": not failures,
        "failures": failures,
        "stages_before": n0,
        "stages_after": n1,
        "recarve_rungs": rung,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backends", type=int, default=2)
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--requests", type=int, default=3,
                    help="prompts per client (closed loop)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--work-s", type=float, default=0.5)
    ap.add_argument("--p95-factor", type=float, default=25.0)
    ap.add_argument("--lease-ttl-s", type=float, default=1.0)
    ap.add_argument("--skip-stream", action="store_true",
                    help="skip the stream-OOM phase (no jax model build)")
    ap.add_argument("--skip-partition", action="store_true",
                    help="skip the network-partition leg")
    ap.add_argument("--plan", default=None,
                    help="override the fleet phase's PA_FAULT_PLAN JSON")
    args = ap.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax
    if not os.environ.get("PA_EVIDENCE_DIR"):
        # The one arming rule (utils/faults.py): chaos artifacts — ledgers,
        # postmortems, journals — must never land in the repo's evidence.
        os.environ["PA_EVIDENCE_DIR"] = tempfile.mkdtemp(prefix="pa-chaos-ev-")
    phases = [run_fleet_chaos(
        n_backends=args.backends, clients=args.clients,
        requests=args.requests, seed=args.seed, work_s=args.work_s,
        p95_factor=args.p95_factor, lease_ttl_s=args.lease_ttl_s,
        plan=json.loads(args.plan) if args.plan else None,
    )]
    if not args.skip_partition:
        phases.append(run_partition_chaos(
            n_backends=max(3, args.backends), clients=args.clients,
            requests=args.requests, seed=args.seed + 4, work_s=args.work_s,
            p95_factor=args.p95_factor,
        ))
    if not args.skip_stream:
        phases.append(run_stream_oom_chaos())
    verdict = {
        "chaos": "ok" if all(p["ok"] for p in phases) else "FAILED",
        "seed": args.seed,
        "phases": phases,
    }
    for p in phases:
        sys.stderr.write(
            f"chaos[{p['phase']}]: {'ok' if p['ok'] else 'FAILED'}"
            + (f" — {'; '.join(p['failures'])}" if p["failures"] else "")
            + "\n"
        )
    print(json.dumps(verdict))
    return 0 if verdict["chaos"] == "ok" else 1


if __name__ == "__main__":
    sys.exit(main())
