"""Fleet tier (fleet/): consistent-hash placement, health-driven admission,
drain, elastic join/leave, and lossless failover — router + real server.py
backends in-process (toy sleep nodes keep the unit/e2e tests fast; the
CI fleet smoke drives scripts/loadgen.py's fleet mode end to end and gates
on prompts_lost == 0)."""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from comfyui_parallelanything_tpu.fleet import (
    FleetRegistry,
    HashRing,
    HeartbeatClient,
    Scoreboard,
    make_router,
    model_key,
)
from comfyui_parallelanything_tpu.server import make_server

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))


class _SleepWork:
    """Toy graph node: sleeps ``work_s`` (stands in for device-bound sampler
    time — releases the GIL like a real dispatch) and echoes the seed."""

    CATEGORY = "test"
    RETURN_TYPES = ("INT",)
    FUNCTION = "run"

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"seed": ("INT", {"default": 0}),
                             "work_s": ("FLOAT", {"default": 0.0})}}

    def run(self, seed, work_s):
        time.sleep(float(work_s))
        return (int(seed),)


def _graph(seed, work_s=0.0):
    return {"1": {"class_type": "SleepWork",
                  "inputs": {"seed": seed, "work_s": work_s}}}


def _get(base, path, timeout=15):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return json.loads(r.read())


def _post(base, path, payload=None, timeout=15):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload or {}).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _wait(pred, timeout=20, interval=0.02, what="condition"):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if pred():
            return
        time.sleep(interval)
    raise TimeoutError(f"never saw: {what}")


def _wait_entry(base, pid, timeout=30):
    out = {}

    def have():
        hist = _get(base, f"/history/{pid}")
        if pid in hist:
            out["entry"] = hist[pid]
            return True
        return False

    _wait(have, timeout=timeout, what=f"history entry for {pid}")
    return out["entry"]


class _Backend:
    def __init__(self, tmp_path, host_id):
        self.srv, self.q = make_server(
            port=0, output_dir=str(tmp_path / host_id),
            class_mappings={"SleepWork": _SleepWork}, host_id=host_id,
        )
        threading.Thread(target=self.srv.serve_forever, daemon=True).start()
        self.base = f"http://127.0.0.1:{self.srv.server_address[1]}"
        self.host_id = host_id
        self.alive = True

    def kill(self):
        """Emulate a crash: the HTTP surface vanishes, then in-flight work
        dies (order matters — the router must never be able to fetch a
        post-kill history entry)."""
        self.srv.shutdown()
        self.srv.server_close()
        self.q.interrupt()
        self.alive = False

    def stop(self):
        if self.alive:
            self.srv.shutdown()
            self.srv.server_close()
        self.q.shutdown()


@pytest.fixture
def fleet(tmp_path):
    """Two backends + a fast-polling router (static ring seeds)."""
    backends = [_Backend(tmp_path, f"host-{i}") for i in range(2)]
    srv, router = make_router(
        port=0, backends=[(b.host_id, b.base) for b in backends],
        fleet_registry=FleetRegistry(ttl_s=3.0),
        scoreboard=Scoreboard(poll_s=0.1, stale_after_s=5.0, fail_after=2,
                              timeout_s=2.0),
        saturation_depth=1, monitor_s=0.05, max_attempts=4,
    )
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    _wait(lambda: all(router.scoreboard.healthy(b.host_id) for b in backends),
          what="both backends healthy on the scoreboard")
    yield base, router, backends
    srv.shutdown()
    srv.server_close()
    router.shutdown()
    for b in backends:
        b.stop()


class TestHashRing:
    def test_deterministic_and_covering(self):
        r = HashRing(vnodes=32)
        r.rebuild(["a", "b", "c"])
        seq = r.sequence("model-x")
        assert sorted(seq) == ["a", "b", "c"]
        assert r.sequence("model-x") == seq  # deterministic
        r2 = HashRing(vnodes=32)
        r2.rebuild(["c", "a", "b"])  # order-independent construction
        assert r2.sequence("model-x") == seq

    def test_join_moves_only_some_keys(self):
        """Consistent hashing's point: adding a host remaps a fraction of
        keys, not the whole map — warm compiled programs mostly stay put."""
        r = HashRing(vnodes=64)
        r.rebuild(["a", "b", "c"])
        keys = [f"model-{i}" for i in range(200)]
        before = {k: r.sequence(k)[0] for k in keys}
        r.rebuild(["a", "b", "c", "d"])
        after = {k: r.sequence(k)[0] for k in keys}
        moved = sum(1 for k in keys if before[k] != after[k])
        assert 0 < moved < len(keys) // 2, moved  # ~1/4 expected
        # Every key that moved, moved TO the new host — never shuffled
        # between the survivors.
        assert all(after[k] == "d" for k in keys if before[k] != after[k])

    def test_model_key_ignores_volatile_inputs(self):
        g1 = {"1": {"class_type": "CheckpointLoaderSimple",
                    "inputs": {"ckpt_name": "a.safetensors"}},
              "2": {"class_type": "KSampler",
                    "inputs": {"seed": 1, "steps": 4}}}
        g2 = json.loads(json.dumps(g1))
        g2["2"]["inputs"].update(seed=99, steps=30)
        assert model_key(g1) == model_key(g2)  # same model → same primary
        g3 = json.loads(json.dumps(g1))
        g3["1"]["inputs"]["ckpt_name"] = "b.safetensors"
        assert model_key(g1) != model_key(g3)  # different model → may move
        # Loaderless graphs key on structure, not inputs.
        assert model_key(_graph(1)) == model_key(_graph(2))


class TestHealthV2:
    def test_health_carries_fleet_fields(self, fleet):
        _, _, backends = fleet
        doc = _get(backends[0].base, "/health")
        assert doc["schema"] == "pa-health/v3"
        assert doc["host_id"] == "host-0"
        assert doc["accepting"] is True
        assert doc["inflight_prompts"] == 0
        assert "queue" in doc and "compile" in doc  # v1 fields intact

    def test_drain_stops_seating_and_resume_reopens(self, fleet):
        _, _, backends = fleet
        b = backends[0]
        state = _post(b.base, "/drain")
        assert state == {"host_id": "host-0", "accepting": False,
                         "pending": 0, "running": 0}
        assert _get(b.base, "/health")["accepting"] is False
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(b.base, "/prompt", {"prompt": _graph(1)})
        assert err.value.code == 503
        assert _post(b.base, "/drain", {"resume": True})["accepting"] is True
        pid = _post(b.base, "/prompt", {"prompt": _graph(2)})["prompt_id"]
        entry = _wait_entry(b.base, pid)
        assert entry["status"]["status_str"] == "success"
        assert entry["status"]["host_id"] == "host-0"


class TestScoreboard:
    def test_poll_reads_health_document(self, fleet):
        _, router, backends = fleet
        snap = router.scoreboard.snapshot()
        for b in backends:
            s = snap[b.host_id]
            assert s["healthy"] and s["accepting"]
            assert s["schema"] == "pa-health/v3"
            assert s["inflight_prompts"] == 0
            assert s["numerics_ok"] is True
            assert s["health_age_s"] is not None

    def test_failure_backoff_and_staleness(self):
        sb = Scoreboard(poll_s=0.1, stale_after_s=0.5, fail_after=3,
                        timeout_s=0.5)
        # Unreachable host: each failure doubles the backoff window.
        assert not sb.poll_host("ghost", "http://127.0.0.1:9")
        e = sb._entries["ghost"]
        assert e.consecutive_failures == 1
        first_backoff = e.next_poll - time.monotonic()
        assert not sb.poll_host("ghost", "http://127.0.0.1:9")
        assert e.consecutive_failures == 2
        assert e.next_poll - time.monotonic() > first_backoff
        assert not sb.healthy("ghost")
        assert not sb.dead("ghost")
        sb.record_failure("ghost")
        assert sb.dead("ghost")
        # Staleness: a host with a FINE last document but an old poll stops
        # counting as healthy — decisions are only as good as their data age.
        sb2 = Scoreboard(poll_s=0.1, stale_after_s=0.05)
        sb2._entry("h", "http://x").last_ok = time.monotonic() - 1.0
        assert not sb2.healthy("h")


class TestRouterPlacement:
    def test_warm_affinity_unsaturated(self, fleet):
        """Sequential prompts for one model land on ONE host — its compiled
        programs stay warm; the other host sees nothing."""
        base, router, backends = fleet
        served = set()
        for i in range(4):
            pid = _post(base, "/prompt", {"prompt": _graph(i)})["prompt_id"]
            entry = _wait_entry(base, pid)
            assert entry["status"]["status_str"] == "success"
            served.add(entry["status"]["fleet"]["host_id"])
            assert entry["status"]["fleet"]["failovers"] == 0
        assert len(served) == 1, served

    def test_spill_when_primary_saturated(self, fleet):
        """depth=1: concurrent prompts spill off the busy primary to the
        next ring host instead of queueing behind it."""
        base, router, backends = fleet
        pids = [
            _post(base, "/prompt",
                  {"prompt": _graph(100 + i, work_s=0.8)})["prompt_id"]
            for i in range(2)
        ]
        served = set()
        for pid in pids:
            entry = _wait_entry(base, pid)
            assert entry["status"]["status_str"] == "success"
            served.add(entry["status"]["fleet"]["host_id"])
        assert len(served) == 2, served  # both hosts worked

    def test_drain_via_router_redirects_traffic(self, fleet):
        base, router, backends = fleet
        # Find the model's primary, then drain it through the router.
        key = model_key(_graph(0))
        primary = router.registry.sequence(key)[0]
        resp = _post(base, "/fleet/drain", {"host_id": primary})
        assert resp["accepting"] is False
        other = next(b.host_id for b in backends if b.host_id != primary)
        for i in range(2):
            pid = _post(base, "/prompt", {"prompt": _graph(200 + i)})["prompt_id"]
            entry = _wait_entry(base, pid)
            assert entry["status"]["fleet"]["host_id"] == other
        # Rejoin: resume + one scoreboard refresh puts it back in rotation.
        primary_base = router.registry.base_of(primary)
        _post(primary_base, "/drain", {"resume": True})
        _wait(lambda: router.scoreboard.accepting(primary),
              what="drained host accepting again")

    def test_backend_client_error_passes_through(self, fleet):
        """A backend 400 (bad graph) is the REQUEST's fault: passed through
        verbatim, never retried on siblings, never counted as lost."""
        base, router, backends = fleet
        bad = {"1": {"class_type": "SleepWork",
                     "inputs": {"seed": "not-an-int", "work_s": 0.0}}}
        # SleepWork.run would TypeError → backend reports an error ENTRY,
        # not a 400 — so use a graph the backend's submit path rejects
        # outright: extra_data with a bad deadline.
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(base, "/prompt", {"prompt": _graph(1),
                                    "extra_data": {"deadline_s": "bogus"}})
        assert err.value.code == 400
        assert router.stats()["lost"] == 0
        # And the fleet keeps serving.
        pid = _post(base, "/prompt", {"prompt": _graph(2)})["prompt_id"]
        assert _wait_entry(base, pid)["status"]["status_str"] == "success"

    def test_resolved_prompts_pruned_beyond_history_budget(self, fleet):
        base, router, backends = fleet
        router.max_history = 3
        pids = []
        for i in range(6):
            pid = _post(base, "/prompt", {"prompt": _graph(300 + i)})["prompt_id"]
            _wait_entry(base, pid)
            pids.append(pid)
        _wait(lambda: len(router.prompts) <= 3, timeout=10,
              what="history pruned to budget")
        # Newest entries survive; the oldest were evicted.
        assert _get(base, f"/history/{pids[-1]}")
        assert _get(base, f"/history/{pids[0]}") == {}

    def test_no_healthy_host_is_503(self, tmp_path):
        srv, router = make_router(port=0, backends=[],
                                  monitor_s=0.05, auto=True)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(base, "/prompt", {"prompt": _graph(1)})
            assert err.value.code == 503
        finally:
            srv.shutdown()
            srv.server_close()
            router.shutdown()


class TestElasticMembership:
    def test_heartbeat_join_and_expiry(self, tmp_path, fleet):
        base, router, backends = fleet
        extra = _Backend(tmp_path, "host-late")
        try:
            hb = HeartbeatClient(base, extra.host_id, extra.base,
                                 interval_s=0.5)
            assert hb.beat_once()
            # Joined AND immediately placeable (the register handler polls
            # the joiner's health inline).
            assert "host-late" in router.registry.hosts()
            _wait(lambda: router.scoreboard.healthy("host-late"),
                  what="joiner healthy")
            # No more beats: the host expires off the ring after ttl.
            _wait(lambda: "host-late" not in router.registry.hosts(),
                  timeout=10, what="joiner expired")
        finally:
            extra.stop()

    def test_explicit_leave(self, fleet):
        base, router, backends = fleet
        assert _post(base, "/fleet/leave",
                     {"host_id": "host-1"})["removed"] is True
        assert "host-1" not in router.registry.hosts()
        # Static hosts never expire by heartbeat, so host-0 is still there.
        assert "host-0" in router.registry.hosts()


class TestFailover:
    def test_kill_host_mid_prompt_lossless(self, fleet):
        """The headline: a host dies mid-prompt; the router detects it via
        failing health polls, re-submits to the sibling, and the client's
        prompt_id resolves successfully — zero prompts lost, the failover
        visible in status.fleet."""
        base, router, backends = fleet
        key = model_key(_graph(0, work_s=3.0))
        victim_id = router.registry.sequence(key)[0]
        victim = next(b for b in backends if b.host_id == victim_id)
        survivor = next(b for b in backends if b.host_id != victim_id)

        pid = _post(base, "/prompt",
                    {"prompt": _graph(7, work_s=3.0)})["prompt_id"]
        _wait(lambda: len(victim.q.running) > 0,
              what="victim mid-prompt")  # genuinely mid-'denoise'
        victim.kill()
        entry = _wait_entry(base, pid, timeout=30)
        assert entry["status"]["status_str"] == "success", entry["status"]
        fleet_meta = entry["status"]["fleet"]
        assert fleet_meta["host_id"] == survivor.host_id
        assert fleet_meta["failovers"] == 1
        assert router.stats()["lost"] == 0
        # The dead host is off the scoreboard's healthy set; new prompts
        # keep flowing to the survivor.
        assert not router.scoreboard.healthy(victim_id)
        pid2 = _post(base, "/prompt", {"prompt": _graph(8)})["prompt_id"]
        entry2 = _wait_entry(base, pid2)
        assert entry2["status"]["fleet"]["host_id"] == survivor.host_id


class TestJournal:
    def test_append_fold_roundtrip(self, tmp_path):
        from comfyui_parallelanything_tpu.fleet import PromptJournal

        j = PromptJournal(str(tmp_path / "j.jsonl"))
        j.append("submit", "p1", graph={"1": {}}, extra=None, key="k1",
                 number=1)
        j.append("dispatch", "p1", host="h0", backend_pid="b1", attempt=1)
        j.append("submit", "p2", graph={"2": {}}, extra=None, key="k2",
                 number=2)
        j.append("resolve", "p1", status="done",
                 entry={"status": {"status_str": "success"}})
        table = j.replay()
        assert table["p1"]["phase"] == "resolve"
        assert table["p1"]["entry"]["status"]["status_str"] == "success"
        assert table["p2"]["phase"] == "submit"
        assert table["p2"]["graph"] == {"2": {}}

    def test_torn_tail_skipped(self, tmp_path):
        from comfyui_parallelanything_tpu.fleet import PromptJournal

        j = PromptJournal(str(tmp_path / "j.jsonl"))
        j.append("submit", "p1", graph={}, key="k", number=1)
        j.close()
        with open(j.path, "ab") as f:
            f.write(b'{"schema": "pa-fleet-journal/v1", "ev": "disp')  # torn
        table = j.replay()
        assert list(table) == ["p1"]

    def test_lease_lifecycle(self, tmp_path):
        from comfyui_parallelanything_tpu.fleet import PromptJournal

        j = PromptJournal(str(tmp_path / "j.jsonl"))
        assert j.lease_stale(ttl_s=1.0)          # no lease yet
        j.write_lease("router-a")
        assert not j.lease_stale(ttl_s=60.0)
        assert j.read_lease()["router_id"] == "router-a"
        # A holder never treats its OWN lease as a dead primary.
        assert not j.lease_stale(ttl_s=0.0, holder_not="router-a")
        time.sleep(0.05)
        assert j.lease_stale(ttl_s=0.01)         # aged out


class TestRouterHA:
    def _standby(self, journal_path, backends, lease_ttl=0.5):
        from comfyui_parallelanything_tpu.fleet import (
            FleetRegistry,
            PromptJournal,
            Scoreboard,
            make_router,
        )

        srv, router = make_router(
            port=0, backends=[(b.host_id, b.base) for b in backends],
            fleet_registry=FleetRegistry(ttl_s=3.0),
            scoreboard=Scoreboard(poll_s=0.1, stale_after_s=5.0,
                                  fail_after=2, timeout_s=2.0),
            saturation_depth=1, monitor_s=0.05,
            journal=PromptJournal(journal_path),
            standby=True, lease_ttl_s=lease_ttl,
        )
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, router, f"http://127.0.0.1:{srv.server_address[1]}"

    def test_standby_refuses_prompts_503(self, tmp_path, fleet):
        _, _, backends = fleet
        srv, router, base = self._standby(
            str(tmp_path / "j.jsonl"), backends, lease_ttl=3600,
        )
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(base, "/prompt", {"prompt": _graph(1)})
            assert err.value.code == 503
            assert json.loads(err.value.read())["role"] == "standby"
        finally:
            srv.shutdown()
            srv.server_close()
            router.shutdown()

    def test_router_kill_mid_denoise_standby_takeover_zero_lost(
        self, tmp_path
    ):
        """The HA headline: the PRIMARY ROUTER dies mid-denoise; the standby
        tails the shared journal, sees the lease go stale, takes over,
        re-collects/replays every unresolved prompt — zero lost, completed
        entries (including ones resolved before the kill) served by the
        standby it never saw live."""
        from comfyui_parallelanything_tpu.fleet import (
            FleetRegistry,
            PromptJournal,
            Scoreboard,
            make_router,
        )

        backends = [_Backend(tmp_path, f"ha-host-{i}") for i in range(2)]
        jpath = str(tmp_path / "journal.jsonl")
        srv1, primary = make_router(
            port=0, backends=[(b.host_id, b.base) for b in backends],
            fleet_registry=FleetRegistry(ttl_s=3.0),
            scoreboard=Scoreboard(poll_s=0.1, stale_after_s=5.0,
                                  fail_after=2, timeout_s=2.0),
            saturation_depth=2, monitor_s=0.05,
            journal=PromptJournal(jpath), lease_ttl_s=0.5,
        )
        threading.Thread(target=srv1.serve_forever, daemon=True).start()
        base1 = f"http://127.0.0.1:{srv1.server_address[1]}"
        srv2, standby, base2 = self._standby(jpath, backends, lease_ttl=0.5)
        try:
            _wait(lambda: all(primary.scoreboard.healthy(b.host_id)
                              for b in backends),
                  what="backends healthy on the primary")
            # One prompt completes BEFORE the kill (the journal-resolve
            # record the standby must serve from /history later)...
            pid_done = _post(base1, "/prompt",
                             {"prompt": _graph(70)})["prompt_id"]
            entry_done = _wait_entry(base1, pid_done)
            assert entry_done["status"]["status_str"] == "success"
            # ... and two are MID-DENOISE when the router dies.
            pids = [
                _post(base1, "/prompt",
                      {"prompt": _graph(71 + i, work_s=2.0)})["prompt_id"]
                for i in range(2)
            ]
            _wait(lambda: sum(len(b.q.running) for b in backends) >= 1,
                  what="work running mid-denoise")
            srv1.shutdown()
            srv1.server_close()
            primary.shutdown()   # lease stops refreshing → stale
            _wait(lambda: standby.active, timeout=15,
                  what="standby takeover")
            # The standby serves history it never saw live (journal replay)…
            got = _get(base2, f"/history/{pid_done}")
            assert got[pid_done]["status"]["status_str"] == "success"
            # …and the mid-denoise prompts complete through it: collected
            # from the live backends (or failed over) — zero lost.
            for pid in pids:
                entry = _wait_entry(base2, pid, timeout=60)
                assert entry["status"]["status_str"] == "success", entry
            assert standby.stats()["lost"] == 0
        finally:
            srv2.shutdown()
            srv2.server_close()
            standby.shutdown()
            for b in backends:
                b.stop()

    def test_journal_records_full_lifecycle(self, tmp_path):
        from comfyui_parallelanything_tpu.fleet import (
            FleetRegistry,
            PromptJournal,
            Scoreboard,
            make_router,
        )

        backends = [_Backend(tmp_path, "jr-host-0")]
        jpath = str(tmp_path / "jr.jsonl")
        srv, router = make_router(
            port=0, backends=[(b.host_id, b.base) for b in backends],
            fleet_registry=FleetRegistry(ttl_s=3.0),
            scoreboard=Scoreboard(poll_s=0.1, fail_after=2, timeout_s=2.0),
            monitor_s=0.05, journal=PromptJournal(jpath),
        )
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        try:
            _wait(lambda: router.scoreboard.healthy("jr-host-0"),
                  what="backend healthy")
            pid = _post(base, "/prompt", {"prompt": _graph(5)})["prompt_id"]
            _wait_entry(base, pid)
            evs = [r["ev"] for r in PromptJournal.iter_records(jpath)
                   if r["pid"] == pid]
            assert evs[:2] == ["submit", "dispatch"]
            _wait(lambda: "resolve" in [
                r["ev"] for r in PromptJournal.iter_records(jpath)
                if r["pid"] == pid
            ], what="resolve journaled")
            table = PromptJournal(jpath).replay()
            assert table[pid]["phase"] == "resolve"
            assert table[pid]["entry"]["status"]["status_str"] == "success"
        finally:
            srv.shutdown()
            srv.server_close()
            router.shutdown()
            for b in backends:
                b.stop()


class TestStageLineageReplay:
    """Round-20 satellite: a DECODE-tier host dies mid-decode while the
    primary router is also gone — the standby's journal takeover must
    re-dispatch the decode stage from the journaled denoise output handle
    (stage lineage, fleet/journal.py), never re-denoise, and the survivor
    stays bitwise. The decode pool has ONE host, so the re-dispatch also
    exercises place()'s degrade-to-global-ring path."""

    def test_decode_kill_standby_redispatches_from_denoise_handle(
        self, tmp_path
    ):
        from test_roles import _RoleBackend, _sgraph
        from test_roles import _wait as _rwait
        from comfyui_parallelanything_tpu.fleet import (
            FleetRegistry,
            PromptJournal,
            Scoreboard,
            make_router,
        )
        from comfyui_parallelanything_tpu.fleet import roles as fleet_roles

        fleet_roles.store.clear()
        specs = [("sr-enc", "encode"), ("sr-den", "denoise"),
                 ("sr-dec", "decode")]
        backends = [_RoleBackend(tmp_path, hid, role) for hid, role in specs]
        by_id = {b.host_id: b for b in backends}
        jpath = str(tmp_path / "journal.jsonl")

        def _router(standby):
            srv, router = make_router(
                port=0, backends=[(b.host_id, b.base) for b in backends],
                fleet_registry=FleetRegistry(ttl_s=5.0),
                scoreboard=Scoreboard(poll_s=0.1, stale_after_s=5.0,
                                      fail_after=2, timeout_s=2.0),
                saturation_depth=2, monitor_s=0.05, max_attempts=4,
                journal=PromptJournal(jpath), lease_ttl_s=0.5,
                standby=standby,
            )
            threading.Thread(target=srv.serve_forever, daemon=True).start()
            return srv, router, f"http://127.0.0.1:{srv.server_address[1]}"

        srv1, primary, base1 = _router(standby=False)
        srv2, standby, base2 = _router(standby=True)
        try:
            _wait(lambda: all(primary.scoreboard.healthy(b.host_id)
                              for b in backends),
                  what="role backends healthy on the primary")
            _wait(lambda: primary.roles.disaggregated(),
                  what="roles visible to the primary")
            pid = _post(base1, "/prompt",
                        {"prompt": _sgraph(21, dec_s=4.0)})["prompt_id"]
            # Decode RUNNING means encode + denoise already resolved and
            # their stage_resolve lineage (with handles) is journaled.
            _rwait(lambda: len(by_id["sr-dec"].q.running) > 0,
                   what="decode stage running")
            srv1.shutdown()
            srv1.server_close()
            primary.shutdown()          # lease stops refreshing
            by_id["sr-dec"].kill()      # ... then the decode host crashes
            _wait(lambda: standby.active, timeout=15,
                  what="standby takeover")
            entry = _wait_entry(base2, pid, timeout=60)
            assert entry["status"]["status_str"] == "success"
            assert standby.stats()["lost"] == 0
            recs = [r for r in PromptJournal.iter_records(jpath)
                    if r["pid"] == pid]
            # Denoise ran EXACTLY once across both routers' lifetimes: the
            # standby resumed from the journaled denoise handle.
            den = [r for r in recs if r["ev"] == "stage_dispatch"
                   and r.get("stage") == "denoise"]
            assert len(den) == 1, recs
            resolves = [r for r in recs if r["ev"] == "stage_resolve"]
            assert [r["stage"] for r in resolves[:2]] == [
                "encode", "denoise"]
            den_handle = resolves[1]["handles"]["2"]
            # The handle survived the decode-host crash (content-addressed
            # store on the surviving hosts) — the retry consumed it instead
            # of re-denoising.
            assert fleet_roles.store.get(den_handle) is not None
            dec = [r for r in recs if r["ev"] == "stage_dispatch"
                   and r.get("stage") == "decode"]
            assert len(dec) >= 2            # original + post-takeover retry
            assert dec[-1]["host"] != "sr-dec"   # pool empty → global ring
            # Bitwise: the failed-over decode dumped the same latent a
            # direct single-host run produces.
            survivor = by_id[dec[-1]["host"]]
            staged = np.load(os.path.join(
                survivor.out_dir, f"21-{survivor.host_id}.npy"))
            ref = by_id["sr-enc"]
            pid2 = _post(ref.base, "/prompt",
                         {"prompt": _sgraph(21)})["prompt_id"]
            assert (_wait_entry(ref.base, pid2)["status"]["status_str"]
                    == "success")
            direct = np.load(os.path.join(ref.out_dir, "21-sr-enc.npy"))
            assert staged.tobytes() == direct.tobytes()
        finally:
            srv2.shutdown()
            srv2.server_close()
            standby.shutdown()
            for b in backends:
                if b.alive:
                    b.stop()
                else:
                    b.q.shutdown()
            fleet_roles.store.clear()


class TestResidencyAwarePlacement:
    def test_health_v3_advertises_warm_keys(self, fleet):
        """A backend that served a model advertises its key (pa-health/v3);
        the scoreboard parses it into warm()."""
        base, router, backends = fleet
        pid = _post(base, "/prompt", {"prompt": _graph(1)})["prompt_id"]
        entry = _wait_entry(base, pid)
        hot = entry["status"]["fleet"]["host_id"]
        key = model_key(_graph(1))
        hot_base = next(b.base for b in backends if b.host_id == hot)
        doc = _get(hot_base, "/health")
        assert key in doc["warm_keys"]
        _wait(lambda: router.scoreboard.warm(hot, key),
              what="scoreboard sees the warm key")
        cold = next(b.host_id for b in backends if b.host_id != hot)
        assert not router.scoreboard.warm(cold, key)

    def test_failover_prefers_warm_sibling(self, fleet):
        """place(prefer_warm=True) orders warm hosts first even when ring
        order says otherwise — the replay path's preference."""
        base, router, backends = fleet
        key = model_key(_graph(1))
        seq = router.registry.sequence(key)
        primary, sibling = seq[0], seq[1]

        def _fabricate_warmth():
            # The monitor's background poll rewrites warm_keys from the real
            # health docs — re-fabricate immediately before each placement.
            with router.scoreboard._lock:
                router.scoreboard._entries[sibling].warm_keys = (
                    frozenset({key})
                )
                router.scoreboard._entries[primary].warm_keys = frozenset()

        _fabricate_warmth()
        cold_first, _, _ = router.place(key)
        assert cold_first == primary          # fresh traffic: ring order
        _fabricate_warmth()
        warm_first, _, _ = router.place(key, prefer_warm=True)
        assert warm_first == sibling          # replay: warmth wins
        # Warmth never overrides health: a draining warm host loses.
        try:
            router.scoreboard.mark_draining(sibling)
            _fabricate_warmth()
            with router.scoreboard._lock:
                router.scoreboard._entries[sibling].accepting = False
            again, _, _ = router.place(key, prefer_warm=True)
            assert again == primary
        finally:
            with router.scoreboard._lock:
                router.scoreboard._entries[sibling].accepting = True


class TestHeartbeatRejoin:
    def test_rejoin_fires_callback_and_resumes(self, tmp_path, fleet):
        """A host whose registration lapsed (router lost it) re-JOINS on its
        next beat — the on_rejoin hook fires exactly then (never on refresh
        beats), restoring admission on the returning backend."""
        from comfyui_parallelanything_tpu.fleet import HeartbeatClient

        base, router, backends = fleet
        extra = _Backend(tmp_path, "rejoin-host")
        rejoins = []
        hb = HeartbeatClient(base, extra.host_id, extra.base,
                             interval_s=0.5,
                             on_rejoin=lambda: rejoins.append(1))
        try:
            assert hb.beat_once()            # first join: NOT a rejoin
            assert rejoins == []
            assert hb.beat_once()            # refresh: not a rejoin either
            assert rejoins == []
            router.registry.remove(extra.host_id)  # expiry stand-in
            assert hb.beat_once()            # falls back ON → rejoin
            assert len(rejoins) == 1
        finally:
            extra.stop()


class TestFleetSmoke:
    """The CI gate (scripts/ci_tier1.sh): router + loadgen fleet mode,
    ~10 prompts over 2 backends on CPU, prompts_lost == 0."""

    def test_loadgen_fleet_mode_two_backends(self, fleet):
        from loadgen import print_human_summary, run_load

        base, router, backends = fleet
        summary = run_load(
            base, _graph(0, work_s=0.1), clients=3, requests=4,
            timeout=60, seed_key="1:inputs:seed", seed=7,
            hosts=[b.base for b in backends],
        )
        print_human_summary(summary)
        assert summary["completed"] == 12, summary
        assert summary["failed"] == 0 and summary["rejected_429"] == 0
        assert summary["prompts_lost"] == 0, summary
        assert summary["seed"] == 7
        # Dispatch is at-least-once by design (a POST that errors after the
        # backend accepted is retried on a sibling — same mechanism as
        # failover), so allow a transient-retry margin over the 12 prompts.
        assert 12 <= summary["fleet"]["dispatches"] <= 14, summary["fleet"]
        # Per-host sections: every completion attributed, both hosts seen
        # (depth=1 + 3 concurrent clients forces spill off the primary).
        hosts = summary["hosts"]
        assert sum(h["completed"] for h in hosts.values()) == 12
        assert all(h["reachable"] for h in hosts.values())
        assert sum(1 for h in hosts.values() if h["completed"] > 0) == 2
        for h in hosts.values():
            if h["completed"]:
                assert h["latency_p95_s"] >= h["latency_p50_s"] > 0

    def test_seeded_schedule_reproducible(self, fleet):
        """--seed contract: same seed → identical submitted prompt set."""
        import random

        sched1 = [random.Random(7).randrange(1 << 31) for _ in range(12)]
        sched2 = [random.Random(7).randrange(1 << 31) for _ in range(12)]
        assert sched1 == sched2
        assert sched1 != [random.Random(8).randrange(1 << 31)
                          for _ in range(12)]


class TestOpenLoopSmoke:
    """Round 15 acceptance: open-loop loadgen on the fleet emits a
    latency-under-load curve + SLO decomposition in one summary, the
    kind=openloop ledger record replays through the traffic twin within the
    declared band (the twin_report --check gate), and GET /fleet/metrics
    serves one merged host-labeled Prometheus view."""

    @staticmethod
    def _open_load(fleet):
        from loadgen import print_human_summary, run_open_load

        from comfyui_parallelanything_tpu.utils.metrics import registry

        registry.reset()  # lifetime histograms: this run's scrape only
        base, router, backends = fleet
        summary = run_open_load(
            base, _graph(0, work_s=0.05), kind="poisson",
            rps_list=[4.0, 10.0], duration_s=2.0, timeout=60, seed=7,
            seed_key="1:inputs:seed", hosts=[b.base for b in backends],
        )
        print_human_summary(summary)
        return summary

    def test_openloop_curve_slo_ledger_and_twin(self, fleet, tmp_path,
                                                monkeypatch):
        import re

        from comfyui_parallelanything_tpu.fleet import twin

        base, router, backends = fleet
        summary = self._open_load(fleet)
        # -- the curve: one rung per offered rate, quantiles ordered
        curve = summary["openloop"]["curve"]
        assert len(curve) == 2
        for rung in curve:
            assert rung["completed"] == rung["arrivals"] > 0, rung
            assert (0 < rung["latency_p50_s"] <= rung["latency_p95_s"]
                    <= rung["latency_p99_s"]), rung
        assert summary["failed"] == 0 and summary["prompts_lost"] == 0
        assert summary["openloop"]["kind"] == "poisson"
        assert summary["openloop"]["seed"] == 7
        # -- the SLO decomposition: server stages + the client residual
        slo_view = summary["slo"]
        assert slo_view["stages"]["admission"]["p50_s"] is not None
        assert slo_view["request_p50_s"] > 0
        assert slo_view["collect_p50_s"] >= 0
        assert slo_view["burn_rates"], slo_view
        [obj] = slo_view["objectives"]
        assert obj["ok"] is True and obj["requests"] > 0
        # -- per-host capacity evidence for the twin (hosts the spill
        #    never reached legitimately carry no service history)
        served = [h for h in summary["hosts"].values() if h["completed"]]
        assert served
        assert all(h["service_p50_s"] > 0 and h["workers"] == 1
                   for h in served)
        # -- the kind=openloop ledger record lands and the twin replays it
        #    (how close it comes is a time measured on a shared machine:
        #    ``test_openloop_twin_within_its_band`` below, outside tier-1)
        monkeypatch.setenv("PA_LEDGER_DIR", str(tmp_path / "ledger"))
        from loadgen import _append_ledger

        _append_ledger(summary, base, kind="openloop")
        [record] = [json.loads(line) for line in open(
            tmp_path / "ledger" / "perf_ledger.jsonl")]
        assert record["kind"] == "openloop"
        rep = twin.replay_record({**summary, "base": base})
        assert rep is not None and rep["p95_err_max"] is not None
        assert rep["band"] == summary["openloop"]["twin_band"]
        # -- GET /fleet/metrics: ONE merged host-labeled Prometheus view
        text = _get_text(base, "/fleet/metrics")
        for b in backends:
            assert re.search(
                rf'^pa_server_queue_pending\{{host="{b.host_id}"\}} ',
                text, re.M), b.host_id
        # the router's own series are host-labeled too
        assert re.search(r'^pa_fleet_completed_total\{host="router-', text,
                         re.M)
        # live hosts are not stale
        for b in backends:
            assert f'pa_fleet_scrape_stale{{host="{b.host_id}"}} 0' in text
        # -- GET /fleet/slo: objective verdicts over the merged view
        doc = _get(base, "/fleet/slo")
        assert doc["schema"] == "pa-fleet-slo/v1"
        assert doc["objectives"][0]["requests"] > 0
        assert doc["objectives"][0]["ok"] is True
        assert set(doc["hosts"]) == {b.host_id for b in backends}


    @pytest.mark.slow
    def test_openloop_twin_within_its_band(self, fleet, tmp_path, monkeypatch):
        """The exact ci_tier1 gate against a live run: the twin's p95 within
        the record's declared band of the MEASURED p95. A comparison of two
        times, one of them taken on whatever else the machine is running —
        under six xdist workers it misses its band now and then (0.5106
        against 0.5 in the run that moved it here), so it runs where
        ``ci_tier1.sh`` runs ``test_fleet.py`` whole, not in tier-1."""
        import subprocess

        from loadgen import _append_ledger

        from comfyui_parallelanything_tpu.fleet import twin

        base = fleet[0]
        summary = self._open_load(fleet)
        monkeypatch.setenv("PA_LEDGER_DIR", str(tmp_path / "ledger"))
        _append_ledger(summary, base, kind="openloop")
        rep = twin.replay_record({**summary, "base": base})
        assert rep["p95_err_max"] <= summary["openloop"]["twin_band"], rep
        proc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), "..", "scripts",
                          "twin_report.py"),
             "--ledger", str(tmp_path / "ledger"), "--check"],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "OK" in proc.stdout


def _get_text(base, path, timeout=15):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return r.read().decode()


class TestFleetMetricsAggregation:
    def test_dead_backend_degrades_not_stalls(self, fleet):
        """Satellite: with one backend dead, /fleet/metrics still carries
        the survivor's series, marks the dead host stale, and answers
        within the poll timeout (the scrape rides the scoreboard's failure
        backoff — no fresh fetch of a host in backoff)."""
        import re

        base, router, backends = fleet
        victim, survivor = backends[0], backends[1]
        # A warm scrape first, so the dead host has a cached section.
        text = _get_text(base, "/fleet/metrics")
        assert f'host="{victim.host_id}"' in text
        victim.kill()
        _wait(lambda: router.scoreboard.in_backoff(victim.host_id)
              or router.scoreboard.dead(victim.host_id),
              what="victim in failure backoff")
        t0 = time.time()
        text = _get_text(base, "/fleet/metrics")
        elapsed = time.time() - t0
        # never blocks past the poll timeout (fixture timeout_s=2.0) —
        # the dead host's section is served from cache, not re-fetched
        assert elapsed < 2.0 + 1.0, elapsed
        assert re.search(
            rf'^pa_server_queue_pending\{{host="{survivor.host_id}"\}} ',
            text, re.M)
        assert f'pa_fleet_scrape_stale{{host="{victim.host_id}"}} 1' in text
        assert f'pa_fleet_scrape_stale{{host="{survivor.host_id}"}} 0' \
            in text
        # the cached section still carries the dead host's last series
        assert re.search(
            rf'^pa_server_queue_pending\{{host="{victim.host_id}"\}} ',
            text, re.M)


class TestRingChangePreferWarm:
    def test_join_rehomes_to_warm_sibling_first(self, fleet):
        """Satellite (ROADMAP fleet remainder): after a ring CHANGE (join/
        leave), fresh placement runs prefer_warm for a dwell — a key whose
        primary moved (or whose primary is simply cold) goes to the host
        actually holding it warm, instead of paying compile + staging on
        the cold ring primary. Warmth here is REAL (the sibling served the
        model through its own front door), not fabricated."""
        base, router, backends = fleet
        g = _graph(1)
        key = model_key(g)
        seq = router.registry.sequence(key)
        primary, sibling = seq[0], seq[1]
        sib = next(b for b in backends if b.host_id == sibling)
        # Warm the SIBLING directly (bypassing the router): it genuinely
        # serves the model and advertises the key via pa-health/v3.
        pid = _post(sib.base, "/prompt", {"prompt": _graph(91)})["prompt_id"]
        _wait_entry(sib.base, pid)
        _wait(lambda: router.scoreboard.warm(sibling, key),
              what="sibling advertises the warm key")
        assert not router.scoreboard.warm(primary, key)
        # No ring change: ring order wins — the cold primary takes it.
        pid = _post(base, "/prompt", {"prompt": _graph(92)})["prompt_id"]
        assert _wait_entry(base, pid)["status"]["fleet"]["host_id"] \
            == primary
        # Ring change: the prefer-warm dwell re-homes the key to the warm
        # sibling. (note_ring_change is what /fleet/register's join and
        # leave/expiry call; invoked directly so the test pins the
        # placement behavior, not the membership plumbing.)
        _wait(lambda: router.scoreboard.warm(sibling, key),
              what="sibling still warm")  # health re-polls must agree
        router.note_ring_change()
        try:
            pid = _post(base, "/prompt", {"prompt": _graph(93)})["prompt_id"]
            assert _wait_entry(base, pid)["status"]["fleet"]["host_id"] \
                == sibling
        finally:
            router._ring_changed_until = 0.0
        # Dwell expired: ring order is restored.
        pid = _post(base, "/prompt", {"prompt": _graph(94)})["prompt_id"]
        assert _wait_entry(base, pid)["status"]["fleet"]["host_id"] \
            == primary

    def test_membership_events_open_the_dwell(self, tmp_path, fleet):
        base, router, backends = fleet
        assert not router._ring_recently_changed()
        extra = _Backend(tmp_path, "dwell-host")
        try:
            hb = HeartbeatClient(base, extra.host_id, extra.base,
                                 interval_s=0.5)
            assert hb.beat_once()               # join → dwell opens
            assert router._ring_recently_changed()
            router._ring_changed_until = 0.0    # reset
            assert hb.beat_once()               # refresh → NO dwell
            assert not router._ring_recently_changed()
            _post(base, "/fleet/leave", {"host_id": extra.host_id})
            assert router._ring_recently_changed()  # leave → dwell opens
        finally:
            router._ring_changed_until = 0.0
            extra.stop()
