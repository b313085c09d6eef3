"""Load generator for the workflow server (stdlib-only): closed- OR open-loop.

CLOSED loop (default): N concurrent clients, each POSTing its prompt graph,
blocking until the prompt completes (polling ``/history/{id}``), and
immediately submitting the next — offered load equals in-flight concurrency,
the regime continuous batching (serving/) is built for.

OPEN loop (``--openloop poisson|onoff|replay``, round 15): requests fire on
a seeded arrival schedule (fleet/twin.py's generator — the same one the
traffic twin replays) REGARDLESS of completions — the regime where queues
actually grow. One rung per ``--rps`` rate; the summary becomes a
latency-under-load curve (p50/p95/p99 vs offered RPS) plus the SLO stage
decomposition (admission / lane_wait / eval / decode scraped off
``pa_slo_stage_seconds``, the client-side ``collect`` residual, burn-rate
gauges, and — behind a router — ``GET /fleet/slo`` verdicts), appended to
the ledger as ``kind="openloop"`` — the record ``scripts/twin_report.py``
checks the twin's prediction against. Prints ONE JSON summary line: latency percentiles,
throughput, HTTP 429 rejections, the serving dispatch/occupancy counters,
AND server-side p50/p95 read from the ``GET /metrics`` histograms
(``server_step_*``/``server_lane_wait_*`` — what the server measured per
lockstep dispatch / lane admission, vs the client clocks which fold in
queueing + HTTP + polling) — so a run shows not just *how fast* but *how
batched* and *where the time went* (the serving metrics; on the chip: not measured).

The ONE summary line goes to **stdout** (ledger-appendable, `| jq`-able —
the same one-JSON-line contract bench.py keeps); the human-readable table
goes to **stderr**, so piping a fleet run into the ledger never has to strip
prose.

The generator itself is stdlib-only and never imports jax: it takes no chip,
whatever the servers it drives run on.

Usage:
    python scripts/loadgen.py --graph workflow.json \
        [--base http://127.0.0.1:8188] [--clients 4] [--requests 2] \
        [--timeout 300] [--seed-key 3:inputs:seed] [--seed 7] \
        [--hosts http://h1:8188,http://h2:8188]

``--seed-key`` (node:path:to:field) makes every submission unique by writing
the request counter into that graph field — defeating the workflow cache so
each prompt actually samples (the default for KSampler graphs: vary the
seed). ``--seed N`` makes that schedule REPRODUCIBLE: the written values
come from a seeded RNG instead of the live counter, so two runs with the
same seed submit the identical prompt set.

``--hosts`` (comma list of backend base URLs) turns on FLEET mode: ``--base``
points at a fleet router (fleet/router.py) and the summary adds per-host
sections — client-side p50/p95 grouped by the serving host (the router
stamps ``status.fleet.host_id`` on every entry), per-backend dispatch/
lane-step deltas scraped from each host's /metrics — plus the router's own
``pa_fleet_*`` deltas (dispatches, spills, failovers) and ``prompts_lost``
(router-lost + client-timeout), the number the fleet CI smoke gates on
staying zero.

Against a DISAGGREGATED fleet (backends launched with ``--role``,
fleet/roles.py) each per-host row carries its role, the summary adds a
``roles`` per-pool section (pool membership, served counts, worst p95) plus
the router's ``pa_role_dispatch_total{role=}`` stage-dispatch deltas, and
the closed-loop ledger record banks as ``kind="roles"`` — the record the
role-pool CI smoke gates.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import re
import sys
import threading
import time
import urllib.error
import urllib.request


def trace_sampled(n: int, fraction: float, seed: int | None = None) -> bool:
    """Seeded, PREFIX-STABLE trace-sampling decision for submission ``n``:
    whether prompt n is sampled depends only on (seed, n) — never on the
    total request count or thread interleaving — so growing a run keeps
    every earlier decision, and a re-run with one seed samples the identical
    prompt set (the reproducible-schedule discipline ``run_load`` already
    applies to seeds)."""
    if fraction <= 0:
        return False
    if fraction >= 1:
        return True
    h = hashlib.md5(
        f"pa-trace:{0 if seed is None else seed}:{n}".encode()
    ).hexdigest()
    return int(h[:8], 16) / float(0xFFFFFFFF) < fraction


def _append_ledger(summary: dict, base: str, kind: str = "loadgen") -> None:
    """Perf-ledger append (kind=loadgen, or kind=openloop for open-loop
    runs — the record the traffic twin replays) via bench.py's stdlib-only
    twin of ``utils/telemetry.append_ledger_record`` — loadgen must stay
    jax-free by contract, so it cannot import the package, but bench's
    module level is stdlib-only (scripts/perf_ledger.py imports it the same
    way). One copy of the dir-resolution/schema stamp, not three.
    Best-effort by that helper's contract: a read-only checkout must not
    fail the load run it summarizes."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from bench import _ledger_append

    _ledger_append({**summary, "base": base}, kind)


def _load_pkg_file(relpath: str, alias: str):
    """A package file loaded standalone by path — its module level must be
    stdlib-only and free of package-relative imports by contract (the
    utils/roofline.py loader pattern), so loadgen rides the SAME code the
    fleet/servers run, without importing the package (whose __init__ pulls
    jax, which a load generator has no use for)."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "comfyui_parallelanything_tpu", *relpath.split("/"),
    )
    spec = importlib.util.spec_from_file_location(alias, path)
    mod = importlib.util.module_from_spec(spec)
    # Registered BEFORE exec: dataclass processing under `from __future__
    # import annotations` resolves the module through sys.modules.
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


_retry = _load_pkg_file("utils/retry.py", "pa_retry_loadgen")
# utils/slo.py: the objective/stage vocabulary + the Prometheus-text readers
# (stage quantiles, threshold fractions) — the scraped twin of the server's
# in-process SLO registry. fleet/twin.py: the seeded arrival-process
# generator the open-loop scheduler fires and the traffic twin replays — ONE
# generator, so "the same arrival trace" is true by construction.
_slo = _load_pkg_file("utils/slo.py", "pa_slo_loadgen")
_twin = _load_pkg_file("fleet/twin.py", "pa_twin_loadgen")
# History polling: the SHARED poll shape (retry.POLL — 50 ms cadence backing
# off toward 500 ms) — a long denoise no longer costs 20 HTTP polls per
# second per client, the jitter de-synchronizes N clients' polls, and a
# future tuning of the fleet's poll policy applies here automatically.
_POLL = _retry.POLL


class _Front:
    """The client's view of the front door: an ordered list of router bases
    (primary first, standbys after). A connection failure or a standby 503
    advances to the next base — the router-HA story from the CLIENT side:
    a router kill mid-run costs a reconnect, never the prompt."""

    def __init__(self, bases):
        self.bases = [b.rstrip("/") for b in bases]
        self._i = 0
        self._lock = threading.Lock()

    @property
    def base(self) -> str:
        with self._lock:
            return self.bases[self._i]

    def _advance(self, frm: str) -> None:
        with self._lock:
            if self.bases[self._i] == frm and len(self.bases) > 1:
                self._i = (self._i + 1) % len(self.bases)

    def request(self, method, path, payload=None, timeout: float = 30):
        """One HTTP call with base failover: OSError / standby-503 walks the
        base list (once around); anything else propagates."""
        last = None
        for _ in range(max(1, len(self.bases))):
            base = self.base
            try:
                if method == "GET":
                    with urllib.request.urlopen(
                        base + path, timeout=timeout
                    ) as r:
                        body = r.read()
                    ct = r.headers.get("Content-Type", "")
                    return json.loads(body) if "json" in ct else body.decode()
                req = urllib.request.Request(
                    base + path, data=json.dumps(payload).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST",
                )
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    return json.loads(r.read())
            except urllib.error.HTTPError as e:
                if e.code == 503:
                    try:
                        detail = json.loads(e.read() or b"{}")
                    except ValueError:
                        detail = {}
                    if detail.get("role") == "standby":
                        last = e
                        self._advance(base)
                        continue
                raise
            except OSError as e:
                last = e
                self._advance(base)
                continue
        raise last if last is not None else OSError("no base reachable")


def _get(base: str, path: str, timeout: float = 30):
    if isinstance(base, _Front):
        return base.request("GET", path, timeout=timeout)
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        body = r.read()
    ct = r.headers.get("Content-Type", "")
    return json.loads(body) if "json" in ct else body.decode()


def _post(base: str, path: str, payload: dict, timeout: float = 30):
    if isinstance(base, _Front):
        return base.request("POST", path, payload, timeout=timeout)
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _mark_phase(base, label: str, state: str) -> None:
    """Best-effort phase-boundary stamp into the front door's metric
    history ring (POST /history/phase, round 22) — the anomaly sentinel
    attributes firings to the open phase, so each open-loop rung stamps
    its edges. A pre-round-22 server 404s and a dead front door refuses;
    either way the rung just runs unstamped."""
    try:
        _post(base, "/history/phase", {"label": label, "state": state},
              timeout=5)
    except Exception:
        pass


def _wait_done(base, pid: str, timeout: float):
    t0 = time.time()
    attempt = 0
    while time.time() - t0 < timeout:
        try:
            hist = _get(base, f"/history/{pid}")
        except (urllib.error.URLError, OSError):
            # The front door may be mid-failover (router kill → standby
            # takeover): keep polling on the policy's backoff — the prompt
            # survives in the journal even while no router answers.
            hist = {}
        if pid in hist:
            return hist[pid]
        time.sleep(_POLL.backoff_s(attempt, key=pid))
        attempt += 1
    raise TimeoutError(f"prompt {pid} never completed")


def _set_path(graph: dict, dotted: str, value):
    """Write ``value`` at ``node:inputs:field`` (colon-separated path)."""
    parts = dotted.split(":")
    node = graph
    for p in parts[:-1]:
        node = node[p]
    node[parts[-1]] = value


def _histogram_quantile(text: str, name: str, q: float,
                        labels: dict | None = None) -> float | None:
    """Quantile from a Prometheus histogram's ``_bucket`` exposition, merged
    across (optionally label-filtered) label sets — linear interpolation
    within the target bucket, the same estimate the server's in-process
    ``registry.quantile`` computes. The implementation is utils/slo.py's
    reader (ONE parser for loadgen, the router's /fleet/slo, and
    twin_report); the wrapper keeps the name tests pin against the
    registry."""
    return _slo.histogram_quantile(text, name, q, labels=labels)


def _serving_counters(base: str) -> dict:
    """Scrape the serving counters from the Prometheus text endpoint."""
    try:
        text = _get(base, "/metrics")
    except (urllib.error.URLError, OSError):
        return {}
    out: dict[str, float] = {}
    for metric, key in (("pa_serving_step_seconds", "step"),
                        ("pa_serving_lane_wait_seconds", "lane_wait")):
        for q in (50, 95):
            v = _histogram_quantile(text, metric, q)
            if v is not None:
                out[f"{key}_p{q}_s"] = round(v, 6)
    for name in ("pa_serving_dispatch_total", "pa_serving_completed_total",
                 "pa_serving_cancelled_total", "pa_serving_rejected_total",
                 "pa_serving_lane_steps_total",
                 # Cross-request reuse (round 17): real encoder program
                 # runs (the embed-cache miss cost) and batched tail-decode
                 # dispatch/request counters (serving/decode.py).
                 "pa_encoder_invocations_total",
                 "pa_decode_dispatch_total", "pa_decode_requests_total",
                 # Numerics sentinel (utils/numerics.py): non-finite
                 # observations and quarantined lanes (summed over labels),
                 # plus the enabled gauge (published at scrape time) that
                 # tells a clean 0 apart from an unwatched run.
                 "pa_numerics_nonfinite_total",
                 "pa_numerics_quarantined_total",
                 "pa_numerics_sentinel_enabled",
                 # Chaos tier (round 14): injected-fault and
                 # degradation-ladder counters (utils/faults.py,
                 # utils/degrade.py) — a chaos run's summary proves what was
                 # injected and what gracefully degraded, summed over their
                 # {site=}/{rung=} labels.
                 "pa_fault_injected_total", "pa_degradation_total",
                 # Anomaly sentinel (round 22, utils/anomaly.py): firings
                 # and the unattributed subset (summed over {signal=}) — a
                 # run's summary proves what the telemetry plane flagged.
                 "pa_anomaly_events_total", "pa_anomaly_unattributed_total",
                 # Universal lane batching (round 16): capability seats,
                 # inline-fallback bounces (summed over reason/sampler), and
                 # control-trunk conflicts — the mixed-workload rung's gates.
                 "pa_serving_lane_capability_total",
                 "pa_serving_inline_fallback_total",
                 "pa_serving_ctrl_conflict_total",
                 # Fleet router counters (fleet/router.py) — present when
                 # --base is a router; summed over their {host=} labels.
                 "pa_fleet_dispatch_total", "pa_fleet_spill_total",
                 "pa_fleet_failover_total", "pa_fleet_completed_total",
                 "pa_fleet_prompts_lost_total",
                 # Role pools (round 20): stage dispatches / resolves per
                 # role — the disaggregated router's attribution counters.
                 "pa_role_dispatch_total", "pa_role_stage_resolved_total"):
        total = 0.0
        found = False
        for m in re.finditer(rf"^{name}(?:\{{[^}}]*\}})? ([0-9.eE+-]+)$",
                             text, re.M):
            total += float(m.group(1))
            found = True
        if found:
            out[name] = total
    m = re.search(r"^pa_serving_batched_fraction ([0-9.eE+-]+)$", text, re.M)
    if m:
        out["pa_serving_batched_fraction"] = float(m.group(1))
    # Per-kind capability seats (round 16): the {kind=} label breakdown of
    # lane seats, stored under flat "name:kind" keys so the before/after
    # diff machinery stays float-valued.
    for m in re.finditer(
        r'^pa_serving_lane_capability_total\{[^}]*kind="([^"]+)"[^}]*\} '
        r"([0-9.eE+-]+)$",
        text, re.M,
    ):
        key = f"pa_serving_lane_capability_total:{m.group(1)}"
        out[key] = out.get(key, 0.0) + float(m.group(2))
    # Per-role stage dispatches (round 20): the {role=} breakdown of the
    # disaggregated router's dispatch counter, flat "name:role" keys so the
    # before/after diff machinery stays float-valued.
    for m in re.finditer(
        r'^pa_role_dispatch_total\{[^}]*role="([^"]+)"[^}]*\} '
        r"([0-9.eE+-]+)$",
        text, re.M,
    ):
        key = f"pa_role_dispatch_total:{m.group(1)}"
        out[key] = out.get(key, 0.0) + float(m.group(2))
    # Reuse gauges (round 17): the embed cache's monotonic hit/miss/eviction
    # totals (diffed like counters — they only grow) + current bytes, and
    # the decode tail's lifetime batched fraction.
    for name in ("pa_embed_cache_hits", "pa_embed_cache_misses",
                 "pa_embed_cache_evictions", "pa_embed_cache_bytes",
                 "pa_decode_batched_fraction"):
        m = re.search(rf"^{name} ([0-9.eE+-]+)$", text, re.M)
        if m:
            out[name] = float(m.group(1))
    # Roofline attribution fractions (utils/roofline.py, published at scrape
    # time when the server traces): where the non-compute time goes —
    # comms (fleet hops) and host-gap alongside compute/exposed-transfer.
    for name in ("pa_roofline_compute_fraction",
                 "pa_roofline_exposed_transfer_fraction",
                 "pa_roofline_comms_fraction",
                 "pa_roofline_host_gap_fraction"):
        m = re.search(rf"^{name} ([0-9.eE+-]+)$", text, re.M)
        if m:
            out[name] = float(m.group(1))
    return out


WORKLOAD_KINDS = ("txt2img", "img2img", "controlnet", "lora")


def parse_workload_mix(spec: str | None) -> dict | None:
    """``txt2img,img2img,controlnet,lora:<frac>`` → ``{kind: fraction}``.

    Each comma item is ``kind`` or ``kind:frac``; explicit fractions are
    taken as-is and the remaining probability mass splits equally over the
    fraction-less kinds (so ``txt2img,lora:0.1`` is 0.9/0.1). With every
    fraction explicit the map is normalized. Unknown kinds and infeasible
    masses fail fast."""
    if not spec:
        return None
    fixed: dict[str, float] = {}
    free: list[str] = []
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        kind, _, frac = item.partition(":")
        if kind not in WORKLOAD_KINDS:
            raise ValueError(
                f"unknown workload kind {kind!r} (want one of "
                f"{', '.join(WORKLOAD_KINDS)})"
            )
        if kind in fixed or kind in free:
            raise ValueError(f"workload kind {kind!r} given twice")
        if frac:
            f = float(frac)
            if not 0.0 <= f <= 1.0:
                raise ValueError(f"workload fraction {kind}:{f} not in [0, 1]")
            fixed[kind] = f
        else:
            free.append(kind)
    if not fixed and not free:
        return None
    rest = 1.0 - sum(fixed.values())
    if free:
        if rest <= 0.0:
            raise ValueError(
                "explicit workload fractions sum to >= 1 with "
                f"fraction-less kinds left over: {spec!r}"
            )
        fixed.update({k: rest / len(free) for k in free})
    total = sum(fixed.values())
    if total <= 0.0:
        raise ValueError(f"workload mix has zero total weight: {spec!r}")
    return {k: v / total for k, v in fixed.items()}


def workload_schedule(total: int, mix: dict, seed: int | None = 0) -> list:
    """The per-submission capability kinds: value n is a pure function of
    (seed, n) — the run_load schedule discipline, so two runs with one seed
    sample the identical kind sequence regardless of client interleaving."""
    rng = random.Random(f"workload:{seed if seed is not None else 0}")
    kinds = list(mix)
    weights = [mix[k] for k in kinds]
    return rng.choices(kinds, weights=weights, k=total)


def _capability_summary(before: dict, after: dict) -> dict:
    """The universal-lane-batching summary fields (round 16), diffed from
    the scraped counters: how many lane seats each capability kind took,
    how many sampler runs bounced to the inline eager loop, and control-
    trunk conflicts. None = the counter never existed on either scrape."""

    def delta(name):
        return (after.get(name, 0.0) - before.get(name, 0.0)
                if name in after or name in before else None)

    prefix = "pa_serving_lane_capability_total:"
    kinds = sorted(
        k[len(prefix):] for k in set(before) | set(after)
        if k.startswith(prefix)
    )
    return {
        # Lane seats by capability kind over this run ({kind=} breakdown of
        # pa_serving_lane_capability_total; None: no capability seating).
        "lane_capability": {
            k: delta(prefix + k) for k in kinds
        } or None,
        # Sampler runs that fell back to the inline eager loop with a
        # scheduler installed (reason=degraded|ineligible summed) — the
        # mixed-workload gate number: eligible traffic must keep this 0.
        "serving_inline_fallbacks": delta("pa_serving_inline_fallback_total"),
        "serving_ctrl_conflicts": delta("pa_serving_ctrl_conflict_total"),
    }


def parse_prompt_dist(spec: str | None) -> float | None:
    """``zipf:<s>`` → the exponent s (production prompt popularity is
    zipf-shaped: a few hot prompts dominate, a long tail follows)."""
    if not spec:
        return None
    kind, _, arg = spec.partition(":")
    if kind != "zipf":
        raise ValueError(f"unknown prompt distribution {spec!r} (want zipf:<s>)")
    return float(arg or "1.1")


def prompt_schedule(total: int, *, s: float | None, vocab: list[str],
                    fanout: int = 1, seed: int | None = 0) -> list[str]:
    """The per-submission prompt texts: ``ceil(total/fanout)`` GROUPS, each
    group one zipf-sampled text repeated ``fanout`` times — submissions
    within a group differ only in their --seed-key value, i.e. they are
    sibling seeds of one prompt (the serving tier's shared-cond fanout
    shape). Seeded and threading-independent: value n is a pure function of
    (seed, n), the run_load schedule discipline."""
    fanout = max(1, int(fanout))
    rng = random.Random(seed if seed is not None else 0)
    groups = (total + fanout - 1) // fanout
    if s is None:
        picks = [vocab[g % len(vocab)] for g in range(groups)]
    else:
        weights = [1.0 / (k + 1) ** s for k in range(len(vocab))]
        picks = rng.choices(vocab, weights=weights, k=groups)
    return [picks[i // fanout] for i in range(total)]


def _prompt_texts(total: int, *, prompt_key, prompt_dist, prompt_vocab,
                  seed_fanout, seed):
    """The per-submission prompt-text schedule both loops share (closed and
    open loop MUST bank records under the same schedule for the same
    flags), or None when no prompt key / no distribution is in play."""
    if not (prompt_key and (prompt_dist or seed_fanout > 1)):
        return None
    return prompt_schedule(
        total, s=parse_prompt_dist(prompt_dist),
        vocab=prompt_vocab or [f"prompt {k}" for k in range(32)],
        fanout=seed_fanout, seed=seed,
    )


def _reuse_summary(before: dict, after: dict) -> dict:
    """The cross-request-reuse summary fields, diffed from the scraped
    counters: hit rate over THIS run, real encoder invocations, and the
    decode tail's batching — the numbers the zipf/fanout CI smoke gates."""

    def delta(name):
        return (after.get(name, 0.0) - before.get(name, 0.0)
                if name in after or name in before else None)

    hits, misses = delta("pa_embed_cache_hits"), delta("pa_embed_cache_misses")
    hit_rate = None
    if hits is not None and misses is not None and hits + misses > 0:
        hit_rate = round(hits / (hits + misses), 4)
    return {
        # Fraction of encode lookups served from the content-addressed
        # cache over this run (None: cache absent or no lookups).
        "embed_cache_hit_rate": hit_rate,
        "embed_cache_evictions": delta("pa_embed_cache_evictions"),
        # Real text-encoder program runs over this run — the number the
        # zipf rung gates at <= 0.5x total prompts.
        "encoder_invocations": delta("pa_encoder_invocations_total"),
        # Decode-tail batching: requests served via shared decode dispatch
        # / total (process lifetime, the same gauge /health reports) plus
        # this run's dispatch/request deltas.
        "decode_batched_fraction": after.get("pa_decode_batched_fraction"),
        "decode_dispatches": delta("pa_decode_dispatch_total"),
        "decode_requests": delta("pa_decode_requests_total"),
    }


def percentile(samples: list[float], q: float) -> float:
    """Nearest-rank percentile (no numpy — stdlib-only by contract)."""
    if not samples:
        return 0.0
    s = sorted(samples)
    k = max(0, min(len(s) - 1, round(q / 100.0 * (len(s) - 1))))
    return s[k]


def _host_probe(hosts: list[str]) -> dict:
    """One scrape per backend: its health identity + serving counters —
    the before/after pair fleet mode diffs for per-host dispatch deltas."""
    out: dict[str, dict] = {}
    for h in hosts:
        h = h.rstrip("/")
        probe: dict = {"base": h}
        try:
            health = _get(h, "/health", timeout=10)
            probe["host_id"] = health.get("host_id")
            probe["accepting"] = health.get("accepting")
            probe["inflight_prompts"] = health.get("inflight_prompts")
            # Role pool (round 20): the backend's declared --role, "all"
            # when undeclared — threaded into the per-host summary rows so
            # role sections and the twin's stage pools can form.
            probe["role"] = health.get("role")
            # Worker-pool width: the twin's per-host concurrency
            # (fleet/twin.py simulates `workers` servers per host).
            probe["workers"] = (health.get("queue") or {}).get("workers")
        except (urllib.error.URLError, OSError, ValueError):
            probe["host_id"] = None
        probe["counters"] = _serving_counters(h)
        out[h] = probe
    return out


def _role_sections(per_host: dict | None) -> dict | None:
    """Per-role pool aggregation of the fleet per-host rows (round 20,
    fleet/roles.py): which hosts form each pool, how much each pool served,
    and the pool's worst client p95. None unless some backend declares a
    role other than ``all`` — homogeneous summaries gain nothing."""
    if not per_host:
        return None
    if not any((h.get("role") or "all") != "all" for h in per_host.values()):
        return None
    pools: dict[str, dict] = {}
    for hid, h in per_host.items():
        r = str(h.get("role") or "all")
        p = pools.setdefault(r, {"hosts": [], "completed": 0,
                                 "dispatches": 0.0, "p95s": []})
        p["hosts"].append(hid)
        p["completed"] += int(h.get("completed") or 0)
        if h.get("dispatches") is not None:
            p["dispatches"] += float(h["dispatches"])
        if h.get("completed") and h.get("latency_p95_s") is not None:
            p["p95s"].append(float(h["latency_p95_s"]))
    return {
        r: {
            "hosts": sorted(p["hosts"]),
            "completed": p["completed"],
            "dispatches": p["dispatches"],
            "latency_p95_s": max(p["p95s"]) if p["p95s"] else None,
        }
        for r, p in sorted(pools.items())
    }


def _role_dispatch_deltas(before: dict, after: dict) -> dict | None:
    """This run's stage dispatches per role, diffed from the router's
    ``pa_role_dispatch_total{role=}`` breakdown (flat "name:role" scrape
    keys). None outside a disaggregated fleet — the counter never exists."""
    prefix = "pa_role_dispatch_total:"
    roles = sorted(
        k[len(prefix):] for k in set(before) | set(after)
        if k.startswith(prefix)
    )
    return {
        r: after.get(prefix + r, 0.0) - before.get(prefix + r, 0.0)
        for r in roles
    } or None


def run_load(base: str, graph: dict, *, clients: int, requests: int,
             timeout: float, seed_key: str | None = None,
             extra_data: dict | None = None,
             samplers: list[str] | None = None,
             sampler_key: str | None = None,
             seed: int | None = None,
             hosts: list[str] | None = None,
             fallback_bases: list[str] | None = None,
             prompt_dist: str | None = None,
             prompt_key: str | None = None,
             prompt_vocab: list[str] | None = None,
             seed_fanout: int = 1,
             workload_mix: dict | None = None,
             workload_graphs: dict | None = None,
             trace_sample: float = 0.0) -> dict:
    """The closed loop; returns the summary dict (importable — the e2e and
    fleet-smoke tests drive in-process servers through this exact code path).

    ``samplers`` + ``sampler_key`` make the workload MIXED: prompt n runs
    ``samplers[n % len]`` (round-robin, written into the graph at
    ``sampler_key``) — the traffic shape the stateful-lane scheduler
    co-batches into one dispatch stream, whose amortization the summary
    reports (shared-dispatch counters scraped from /metrics).

    ``seed`` makes the prompt schedule reproducible: the per-prompt value
    written at ``seed_key`` comes from ``random.Random(seed)`` instead of
    the live counter. ``hosts`` turns on fleet mode (see module docstring).
    ``fallback_bases`` (router HA): standby router URLs tried in order when
    the primary stops answering or replies standby-503 — a router kill
    mid-run costs the clients a reconnect, never a prompt.

    Cross-request reuse shape (round 17): ``prompt_dist`` (``zipf:<s>``) +
    ``prompt_key`` sample each submission's prompt TEXT from
    ``prompt_vocab`` under a seeded zipf — the redundant production traffic
    the embed cache collapses; ``seed_fanout`` N groups submissions into
    N-seed siblings of one sampled prompt (the shared-cond fanout shape).
    The summary gains ``embed_cache_hit_rate`` / ``encoder_invocations`` /
    ``decode_batched_fraction`` scraped-delta fields either way.

    Mixed capability traffic (round 16): ``workload_mix`` ({kind: fraction}
    over txt2img/img2img/controlnet/lora, see parse_workload_mix) samples
    each submission's CAPABILITY kind seeded (value n pure in (seed, n))
    and submits the matching graph from ``workload_graphs`` ({kind: graph
    dict}; kinds without an entry — txt2img canonically — use the base
    ``graph``). Variant graphs must keep the base graph's node ids at
    ``seed_key``/``sampler_key``/``prompt_key`` so the per-prompt writes
    land. The summary gains ``workload_mix``/``workload_counts`` plus the
    ``lane_capability`` per-kind seat deltas and the
    ``serving_inline_fallbacks`` gate number either way.

    Request forensics (round 21): ``trace_sample`` tags a seeded,
    prefix-stable fraction of submissions for full distributed capture
    (``extra_data.pa_trace_sampled`` — the router injects a traceparent on
    every hop of a tagged prompt) and, after each tagged prompt completes,
    fetches its stitched timeline (``GET /fleet/trace`` behind a router,
    ``GET /trace`` on a plain server). The summary gains ``traced_prompts``
    + ``trace_fetch_rate`` (stitch fetch success)."""
    if fallback_bases:
        base = _Front([base, *fallback_bases])
    latencies: list[float] = []
    lat_by_host: dict = {}
    failures: list[str] = []
    rejected = [0]
    timeouts = [0]
    traced = [0]
    traced_ok = [0]
    lock = threading.Lock()
    counter = [0]
    # Reproducible schedule: value n is a pure function of (seed, n), so two
    # runs with one seed submit the identical prompt set regardless of how
    # the client threads interleave.
    schedule = None
    if seed is not None:
        rng = random.Random(seed)
        schedule = [rng.randrange(1 << 31) for _ in range(clients * requests)]
    texts = _prompt_texts(
        clients * requests, prompt_key=prompt_key, prompt_dist=prompt_dist,
        prompt_vocab=prompt_vocab, seed_fanout=seed_fanout, seed=seed,
    )
    kind_schedule = None
    kind_counts: dict[str, int] = {}
    if workload_mix:
        kind_schedule = workload_schedule(clients * requests, workload_mix,
                                          seed=seed)
        for k in kind_schedule:
            kind_counts[k] = kind_counts.get(k, 0) + 1
    before = _serving_counters(base)
    hosts_before = _host_probe(hosts) if hosts else None
    t_start = time.time()

    def client(ci: int) -> None:
        for _ in range(requests):
            with lock:
                counter[0] += 1
                n = counter[0]
            src = graph
            if kind_schedule is not None:
                src = (workload_graphs or {}).get(kind_schedule[n - 1], graph)
            g = json.loads(json.dumps(src))
            if seed_key:
                _set_path(g, seed_key,
                          schedule[n - 1] if schedule is not None else n)
            if samplers and sampler_key:
                _set_path(g, sampler_key, samplers[n % len(samplers)])
            if texts is not None:
                _set_path(g, prompt_key, texts[n - 1])
            payload = {"prompt": g}
            sampled = trace_sampled(n, trace_sample, seed)
            ed = dict(extra_data) if extra_data else {}
            if sampled:
                ed["pa_trace_sampled"] = True
            if ed:
                payload["extra_data"] = ed
            t0 = time.time()
            # Submit with bounded retry (utils/retry.py shape): a 503 or a
            # refused connection can be a router mid-failover (standby
            # takeover costs ~a lease TTL) — retry on backoff until the
            # window closes, then count the failure. 429 (bounded queue) and
            # 4xx (request at fault) are never retried.
            pid = None
            post_deadline = t0 + min(60.0, timeout)
            attempt = 0
            while True:
                try:
                    pid = _post(base, "/prompt", payload)["prompt_id"]
                    break
                except urllib.error.HTTPError as e:
                    if e.code == 503 and time.time() < post_deadline:
                        time.sleep(_POLL.backoff_s(attempt, key=f"s{ci}"))
                        attempt += 1
                        continue
                    with lock:
                        if e.code == 429:
                            rejected[0] += 1
                        else:
                            failures.append(f"client {ci}: HTTP {e.code}")
                    break
                except OSError as e:
                    if time.time() < post_deadline:
                        time.sleep(_POLL.backoff_s(attempt, key=f"s{ci}"))
                        attempt += 1
                        continue
                    with lock:
                        failures.append(f"client {ci}: unreachable ({e})")
                    break
            if pid is None:
                continue
            try:
                entry = _wait_done(base, pid, timeout)
            except TimeoutError:
                # A prompt that never completes is LOST from the client's
                # view — it must count (the fleet gate), not silently kill
                # this client thread.
                with lock:
                    timeouts[0] += 1
                    failures.append(f"client {ci}: timeout ({pid})")
                continue
            dt = time.time() - t0
            status = entry.get("status") or {}
            served_by = (status.get("fleet") or {}).get("host_id") \
                or status.get("host_id")
            fetched = None
            if sampled:
                # The stitched-capture round trip the sampling exists for:
                # a tagged prompt's distributed timeline must actually be
                # collectable, and the summary reports the hit rate.
                fetched = False
                path = (f"/fleet/trace?prompt_id={pid}" if hosts
                        else f"/trace?prompt_id={pid}")
                try:
                    doc = _get(base, path)
                    fetched = (not doc.get("error")
                               and any(e.get("ph") == "X"
                                       for e in doc.get("traceEvents") or ()))
                except (OSError, urllib.error.HTTPError, ValueError):
                    pass
            with lock:
                if sampled:
                    traced[0] += 1
                    if fetched:
                        traced_ok[0] += 1
                if status.get("status_str") == "success":
                    latencies.append(dt)
                    if served_by:
                        lat_by_host.setdefault(served_by, []).append(dt)
                else:
                    failures.append(
                        f"client {ci}: {status.get('status_str')}"
                    )

    threads = [threading.Thread(target=client, args=(i,)) for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.time() - t_start
    after = _serving_counters(base)
    dispatches = (
        after.get("pa_serving_dispatch_total", 0.0)
        - before.get("pa_serving_dispatch_total", 0.0)
    ) if after else None
    lane_steps = (
        after.get("pa_serving_lane_steps_total", 0.0)
        - before.get("pa_serving_lane_steps_total", 0.0)
    ) if after else None
    fleet = None
    per_host = None
    prompts_lost = None
    if hosts:
        hosts_after = _host_probe(hosts)
        per_host = {}
        for h in hosts:
            h = h.rstrip("/")
            b, a = hosts_before.get(h, {}), hosts_after.get(h, {})
            hid = a.get("host_id") or b.get("host_id") or h
            cb, ca = b.get("counters") or {}, a.get("counters") or {}
            lats = lat_by_host.get(hid, [])
            per_host[hid] = {
                "base": h,
                "role": a.get("role") or b.get("role") or "all",
                "completed": len(lats),
                "latency_p50_s": round(percentile(lats, 50), 3),
                "latency_p95_s": round(percentile(lats, 95), 3),
                "dispatches": (
                    ca.get("pa_serving_dispatch_total", 0.0)
                    - cb.get("pa_serving_dispatch_total", 0.0)
                ) if ca else None,
                "lane_steps": (
                    ca.get("pa_serving_lane_steps_total", 0.0)
                    - cb.get("pa_serving_lane_steps_total", 0.0)
                ) if ca else None,
                "server_step_p50_s": ca.get("step_p50_s"),
                "server_step_p95_s": ca.get("step_p95_s"),
                "accepting": a.get("accepting"),
                "reachable": a.get("host_id") is not None,
            }
        # Router-side deltas (--base is the fleet front door). A router-lost
        # prompt and a client-timeout are the same failure seen from two
        # ends; the gate number is their sum.
        def _delta(name):
            return (after.get(name, 0.0) - before.get(name, 0.0)
                    if name in after or name in before else None)

        fleet = {
            "dispatches": _delta("pa_fleet_dispatch_total"),
            "spills": _delta("pa_fleet_spill_total"),
            "failovers": _delta("pa_fleet_failover_total"),
            "completed": _delta("pa_fleet_completed_total"),
        }
        role_disp = _role_dispatch_deltas(before, after)
        if role_disp:
            fleet["role_dispatches"] = role_disp
        lost_router = _delta("pa_fleet_prompts_lost_total")
        prompts_lost = (lost_router or 0.0) + timeouts[0]
    elif timeouts[0]:
        prompts_lost = float(timeouts[0])
    return {
        "clients": clients,
        "requests": clients * requests,
        "seed": seed,
        "samplers": samplers or None,
        "prompt_dist": prompt_dist if texts is not None else None,
        "seed_fanout": (
            seed_fanout if texts is not None and seed_fanout > 1 else None
        ),
        "distinct_prompts": len(set(texts)) if texts is not None else None,
        "workload_mix": workload_mix or None,
        "workload_counts": kind_counts or None,
        **_capability_summary(before, after),
        **_reuse_summary(before, after),
        "completed": len(latencies),
        "failed": len(failures),
        "rejected_429": rejected[0],
        "wall_s": round(wall, 3),
        "throughput_rps": round(len(latencies) / wall, 3) if wall > 0 else None,
        "latency_p50_s": round(percentile(latencies, 50), 3),
        "latency_p95_s": round(percentile(latencies, 95), 3),
        "latency_max_s": round(max(latencies), 3) if latencies else 0.0,
        "serving_dispatches": dispatches,
        # Dispatch amortization: lane-steps served per compiled dispatch over
        # this run (1.0 = no sharing; N = every dispatch carried N lanes) —
        # the mixed-workload number the ROADMAP serving-on-hardware item banks.
        "serving_lane_steps": lane_steps,
        "dispatch_amortization": (
            round(lane_steps / dispatches, 3)
            if lane_steps and dispatches else None
        ),
        # End-state shared-dispatch fraction (process lifetime, not deltas —
        # the same gauge GET /health reports).
        "serving_batched_fraction": after.get("pa_serving_batched_fraction"),
        # Numerics sentinel deltas over this run (utils/numerics.py): lanes
        # quarantined by the non-finite watchdog and raw non-finite
        # observations. The counters only exist once an event fires, so an
        # absent counter with the sentinel ENABLED means a clean run (0) and
        # with the sentinel disabled means unwatched (None) — the gauge the
        # server publishes at scrape time disambiguates the two.
        "numerics_quarantined": (
            after.get("pa_numerics_quarantined_total", 0.0)
            - before.get("pa_numerics_quarantined_total", 0.0)
        ) if after.get("pa_numerics_sentinel_enabled") else None,
        "numerics_nonfinite": (
            after.get("pa_numerics_nonfinite_total", 0.0)
            - before.get("pa_numerics_nonfinite_total", 0.0)
        ) if after.get("pa_numerics_sentinel_enabled") else None,
        # Chaos tier (round 14): faults fired by the injection registry and
        # degradation-ladder rungs taken over this run (summed over
        # site/rung labels; None = the counters never existed — no plan
        # armed AND nothing degraded).
        "faults_injected": (
            after.get("pa_fault_injected_total", 0.0)
            - before.get("pa_fault_injected_total", 0.0)
        ) if ("pa_fault_injected_total" in after
              or "pa_fault_injected_total" in before) else None,
        "degradations": (
            after.get("pa_degradation_total", 0.0)
            - before.get("pa_degradation_total", 0.0)
        ) if ("pa_degradation_total" in after
              or "pa_degradation_total" in before) else None,
        # Anomaly sentinel deltas over this run (round 22,
        # utils/anomaly.py): signal firings and the unattributed subset
        # (None = the counters never existed — sentinel off or nothing
        # ever fired process-wide).
        "anomalies_fired": (
            after.get("pa_anomaly_events_total", 0.0)
            - before.get("pa_anomaly_events_total", 0.0)
        ) if ("pa_anomaly_events_total" in after
              or "pa_anomaly_events_total" in before) else None,
        "anomalies_unattributed": (
            after.get("pa_anomaly_unattributed_total", 0.0)
            - before.get("pa_anomaly_unattributed_total", 0.0)
        ) if ("pa_anomaly_unattributed_total" in after
              or "pa_anomaly_unattributed_total" in before) else None,
        # Server-side quantiles from the /metrics histograms (end-state
        # values — histograms are cumulative): what the SERVER measured per
        # lockstep dispatch / lane admission, vs the client-clock latencies
        # above which include queueing + HTTP + polling.
        "server_step_p50_s": after.get("step_p50_s"),
        "server_step_p95_s": after.get("step_p95_s"),
        "server_lane_wait_p95_s": after.get("lane_wait_p95_s"),
        # Roofline attribution fractions over the server's live trace window
        # (utils/roofline.py buckets, scraped from /metrics; None when the
        # server runs untraced): how much of the wall went to cross-host
        # comms and to host scheduling gaps rather than device compute.
        "roofline_comms_fraction": after.get("pa_roofline_comms_fraction"),
        "roofline_host_gap_fraction": after.get(
            "pa_roofline_host_gap_fraction"
        ),
        # Fleet mode (--hosts): per-host client latencies + dispatch deltas,
        # router-side placement/failover deltas, and the CI-gated loss count
        # (router-lost + client-timeout; None outside fleet mode unless a
        # timeout made the number real). "roles" (round 20): the per-role
        # pool aggregation — None unless some backend declared a role.
        "hosts": per_host,
        "roles": _role_sections(per_host),
        "fleet": fleet,
        "prompts_lost": prompts_lost,
        "timeouts": timeouts[0],
        # Request forensics (--trace-sample): prompts tagged for distributed
        # capture, and the fraction whose stitched timeline was actually
        # fetchable after completion (None = sampling off).
        "traced_prompts": traced[0] if trace_sample > 0 else None,
        "trace_fetch_rate": (
            round(traced_ok[0] / traced[0], 3)
            if trace_sample > 0 and traced[0] else
            (0.0 if trace_sample > 0 else None)
        ),
        "errors": failures[:5],
    }


def _scrape_slo(base, e2e_p50=None, e2e_p95=None) -> dict | None:
    """The SLO view of a run, scraped off ``GET /metrics``: per-stage
    latency decomposition quantiles (``pa_slo_stage_seconds``), server-side
    request residency, windowed burn-rate gauges, and — fleet mode — the
    router's merged ``GET /fleet/slo`` verdicts. The CLIENT-side residual,
    ``collect`` (history polling + HTTP + everything the server cannot
    see), is e2e minus server residency at matching quantiles — the fifth
    stage of the decomposition, computable only here.

    The scrape prefers ``GET /fleet/metrics`` (a router's merged
    host-labeled view — the backends' ``pa_slo_*`` series live THERE in a
    real multi-process fleet; the router's own registry never carries
    them) and falls back to ``GET /metrics`` on a plain server (404)."""
    text = None
    try:
        text = _get(base, "/fleet/metrics")
    except (urllib.error.URLError, OSError, ValueError):
        pass  # not a router (404) or unreachable — try the plain endpoint
    if not isinstance(text, str) or "# TYPE" not in text:
        try:
            text = _get(base, "/metrics")
        except (urllib.error.URLError, OSError):
            return None
    stages: dict[str, dict] = {}
    for stage in ("admission", "encode", "lane_wait", "eval",
                  "decode_wait", "decode"):
        p50 = _histogram_quantile(text, "pa_slo_stage_seconds", 50,
                                  labels={"stage": stage})
        if p50 is None:
            continue
        p95 = _histogram_quantile(text, "pa_slo_stage_seconds", 95,
                                  labels={"stage": stage})
        stages[stage] = {"p50_s": round(p50, 6),
                         "p95_s": round(p95, 6) if p95 is not None else None}
    req50 = _histogram_quantile(text, "pa_slo_request_seconds", 50)
    req95 = _histogram_quantile(text, "pa_slo_request_seconds", 95)
    burn: dict[str, float] = {}
    for m in re.finditer(
        r'^pa_slo_burn_rate\{[^}]*objective="([^"]+)"[^}]*\} '
        r"([0-9.eE+-]+)$",
        text, re.M,
    ):
        # Merged fleet views carry one host-labeled gauge per backend: the
        # fleet's burn rate for an objective is its WORST host's.
        burn[m.group(1)] = max(burn.get(m.group(1), 0.0),
                               float(m.group(2)))
    out: dict = {
        "stages": stages or None,
        "request_p50_s": round(req50, 6) if req50 is not None else None,
        "request_p95_s": round(req95, 6) if req95 is not None else None,
        "burn_rates": burn or None,
    }
    if e2e_p50 is not None and req50 is not None:
        out["collect_p50_s"] = round(max(0.0, e2e_p50 - req50), 6)
    if e2e_p95 is not None and req95 is not None:
        out["collect_p95_s"] = round(max(0.0, e2e_p95 - req95), 6)
    try:
        fleet_slo = _get(base, "/fleet/slo", timeout=10)
        if isinstance(fleet_slo, dict) and fleet_slo.get("objectives"):
            out["objectives"] = fleet_slo["objectives"]
    except (urllib.error.URLError, OSError, ValueError):
        pass  # not a router (plain server 404s) — gauges carry the verdict
    if not stages and req50 is None and not burn and "objectives" not in out:
        return None  # PA_SLO=0 everywhere: no SLO section, not zeros
    return out


def run_open_load(base: str, graph: dict, *, kind: str = "poisson",
                  rps_list=(4.0,), duration_s: float = 3.0,
                  timeout: float = 300.0, seed: int | None = 0,
                  seed_key: str | None = None,
                  extra_data: dict | None = None,
                  samplers: list[str] | None = None,
                  sampler_key: str | None = None,
                  hosts: list[str] | None = None,
                  fallback_bases: list[str] | None = None,
                  on_s: float = 1.0, off_s: float = 1.0,
                  arrivals_doc: dict | None = None,
                  arrivals_out: str | None = None,
                  twin_band: float = 0.5,
                  prompt_dist: str | None = None,
                  prompt_key: str | None = None,
                  prompt_vocab: list[str] | None = None,
                  seed_fanout: int = 1) -> dict:
    """OPEN-loop load: requests fire on a seeded arrival schedule
    (fleet/twin.py's generator — Poisson, bursty ON-OFF, or trace replay)
    regardless of completions, which is the regime where queues actually
    grow (the closed loop's offered load can never exceed its concurrency).
    One rung per offered rate in ``rps_list``; the summary's
    ``openloop.curve`` is latency-under-load (p50/p95/p99 vs offered RPS)
    and its ``slo`` section the stage decomposition + burn rates — together
    the ``kind="openloop"`` ledger record the traffic twin replays
    (``scripts/twin_report.py``)."""
    if fallback_bases:
        base = _Front([base, *fallback_bases])
    sched_rng = random.Random(seed if seed is not None else 0)
    before = _serving_counters(base)
    hosts_before = _host_probe(hosts) if hosts else None
    if arrivals_doc is not None:
        kind = str(arrivals_doc.get("kind") or "replay")
        rungs_in = [
            {"rps": r.get("rps"), "duration_s": float(r.get("duration_s") or 0.0),
             "offsets": [float(t) for t in r.get("offsets") or []],
             "replay": True}
            for r in arrivals_doc.get("rungs") or []
        ]
    else:
        rungs_in = [
            {"rps": float(r), "duration_s": float(duration_s),
             "offsets": _twin.gen_arrivals(
                 kind, rps=float(r), duration_s=float(duration_s),
                 seed=int(seed or 0), on_s=on_s, off_s=off_s,
             ),
             "replay": False}
            for r in rps_list
        ]
    texts = _prompt_texts(
        sum(len(r["offsets"]) for r in rungs_in), prompt_key=prompt_key,
        prompt_dist=prompt_dist, prompt_vocab=prompt_vocab,
        seed_fanout=seed_fanout, seed=seed,
    )
    all_lat: list[float] = []
    lat_by_host: dict = {}
    exec_by_host: dict = {}
    failures: list[str] = []
    rejected = [0]
    timeouts = [0]
    counter = [0]
    lock = threading.Lock()
    curve: list[dict] = []
    t_start = time.time()
    for rung_idx, rung in enumerate(rungs_in):
        offsets = rung["offsets"]
        rung_lat: list[float] = []
        rung_exec: list[float] = []
        rung_label = f"openloop-{kind}-r{rung_idx}-{rung['rps']}rps"
        _mark_phase(base, rung_label, "begin")
        rt0 = time.time()

        def fire(_rung_lat=rung_lat, _rung_exec=rung_exec):
            # Open-loop discipline: fired at the scheduled instant (the
            # scheduler thread below owns the clock), never "when the
            # previous one finished" — and never retry a refusal (a
            # dropped arrival is data, not an error to paper over).
            g = json.loads(json.dumps(graph))
            with lock:
                counter[0] += 1
                n = counter[0]
                val = sched_rng.randrange(1 << 31)
            if seed_key:
                _set_path(g, seed_key, val if seed is not None else n)
            if samplers and sampler_key:
                _set_path(g, sampler_key, samplers[n % len(samplers)])
            if texts is not None and n <= len(texts):
                _set_path(g, prompt_key, texts[n - 1])
            payload = {"prompt": g}
            if extra_data:
                payload["extra_data"] = extra_data
            t0 = time.time()
            try:
                pid = _post(base, "/prompt", payload)["prompt_id"]
            except urllib.error.HTTPError as e:
                with lock:
                    if e.code == 429:
                        rejected[0] += 1
                    else:
                        failures.append(f"openloop: HTTP {e.code}")
                return
            except OSError as e:
                with lock:
                    failures.append(f"openloop: unreachable ({e})")
                return
            try:
                entry = _wait_done(base, pid, timeout)
            except TimeoutError:
                with lock:
                    timeouts[0] += 1
                    failures.append(f"openloop: timeout ({pid})")
                return
            dt = time.time() - t0
            status = entry.get("status") or {}
            served_by = (status.get("fleet") or {}).get("host_id") \
                or status.get("host_id")
            with lock:
                if status.get("status_str") == "success":
                    _rung_lat.append(dt)
                    all_lat.append(dt)
                    ex = status.get("exec_s")
                    if isinstance(ex, (int, float)):
                        _rung_exec.append(float(ex))
                    if served_by:
                        lat_by_host.setdefault(served_by, []).append(dt)
                        if isinstance(ex, (int, float)):
                            exec_by_host.setdefault(
                                served_by, []
                            ).append(float(ex))
                else:
                    failures.append(
                        f"openloop: {status.get('status_str')}"
                    )

        # One scheduler thread owns the arrival clock and spawns a request
        # thread only AT each arrival's fire time — live threads stay
        # bounded by in-flight requests, not by the rung's total (a 60 s
        # 100-rps rung must not park 6000 stacks up front and let their
        # creation storm distort the very arrival fidelity being measured).
        threads: list[threading.Thread] = []
        for off in offsets:
            delay = rt0 + off - time.time()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(target=fire, daemon=True)
            threads.append(th)
            th.start()
        for th in threads:
            th.join(timeout + rung["duration_s"] + 60)
        wall = time.time() - rt0
        _mark_phase(base, rung_label, "end")
        dur = rung["duration_s"] or (max(offsets) if offsets else 0.0) or 1.0
        entry: dict = {
            "rps": rung["rps"],
            "rps_offered": round(len(offsets) / dur, 4),
            "duration_s": rung["duration_s"],
            "arrivals": len(offsets),
            "completed": len(rung_lat),
            "achieved_rps": round(len(rung_lat) / wall, 4) if wall > 0 else None,
            "latency_p50_s": round(percentile(rung_lat, 50), 6),
            "latency_p95_s": round(percentile(rung_lat, 95), 6),
            "latency_p99_s": round(percentile(rung_lat, 99), 6),
            # This rung's OWN service p50 — the overhead calibration below
            # must not subtract a contention-inflated pooled value.
            "service_p50_s": (
                round(percentile(rung_exec, 50), 6) if rung_exec else None
            ),
        }
        if kind == "onoff":
            entry["on_s"], entry["off_s"] = on_s, off_s
        if rung["replay"]:
            # Replay rungs carry their offsets verbatim — the twin cannot
            # regenerate a recorded trace from (kind, seed).
            entry["offsets"] = offsets
        curve.append(entry)
    wall = time.time() - t_start
    after = _serving_counters(base)
    if arrivals_out:
        _twin.save_arrivals(
            arrivals_out,
            [{"rps": r["rps"], "duration_s": r["duration_s"],
              "offsets": r["offsets"]} for r in rungs_in],
            kind=kind, seed=seed,
        )
    e2e_p50 = percentile(all_lat, 50) if all_lat else None
    e2e_p95 = percentile(all_lat, 95) if all_lat else None
    slo_view = _scrape_slo(base, e2e_p50=e2e_p50, e2e_p95=e2e_p95)
    all_exec = [v for vs in exec_by_host.values() for v in vs]
    # Per-host sections: fleet mode diffs the backend probes (run_load's
    # shape) + the twin's capacity fields; single-server mode synthesizes
    # one row per serving host_id from the entries alone.
    per_host: dict | None = None
    fleet = None
    prompts_lost = None
    if hosts:
        hosts_after = _host_probe(hosts)
        # Entries are attributed by the ROUTER's host id
        # (status.fleet.host_id), which for bare-URL --backends seeds is
        # URL-derived and differs from the backend's self-declared
        # /health host_id — join the two through the router's ring
        # snapshot so per-host service evidence lands either way.
        ring_map: dict[str, str] = {}
        try:
            doc = _get(base, "/fleet/hosts", timeout=10)
            for row in doc.get("ring") or []:
                if row.get("base") and row.get("host_id"):
                    ring_map[str(row["base"]).rstrip("/")] = \
                        str(row["host_id"])
        except (urllib.error.URLError, OSError, ValueError):
            pass
        per_host = {}
        for h in hosts:
            h = h.rstrip("/")
            b, a = hosts_before.get(h, {}), hosts_after.get(h, {})
            phid = a.get("host_id") or b.get("host_id")
            hid = ring_map.get(h) or phid or h
            cb, ca = b.get("counters") or {}, a.get("counters") or {}
            lats = lat_by_host.get(hid) \
                or (lat_by_host.get(phid, []) if phid else [])
            execs = exec_by_host.get(hid) \
                or (exec_by_host.get(phid, []) if phid else [])
            per_host[hid] = {
                "base": h,
                "role": a.get("role") or b.get("role") or "all",
                "completed": len(lats),
                "latency_p50_s": round(percentile(lats, 50), 3),
                "latency_p95_s": round(percentile(lats, 95), 3),
                "dispatches": (
                    ca.get("pa_serving_dispatch_total", 0.0)
                    - cb.get("pa_serving_dispatch_total", 0.0)
                ) if ca else None,
                "server_step_p50_s": ca.get("step_p50_s"),
                "server_step_p95_s": ca.get("step_p95_s"),
                # The twin's capacity inputs: per-request service p50
                # (exec_s off the history entries — same workload on every
                # host by construction) and the worker-pool width.
                "service_p50_s": (
                    round(percentile(execs, 50), 6) if execs else None
                ),
                "workers": a.get("workers") or b.get("workers"),
                "accepting": a.get("accepting"),
                "reachable": a.get("host_id") is not None,
            }

        def _delta(name):
            return (after.get(name, 0.0) - before.get(name, 0.0)
                    if name in after or name in before else None)

        fleet = {
            "dispatches": _delta("pa_fleet_dispatch_total"),
            "spills": _delta("pa_fleet_spill_total"),
            "failovers": _delta("pa_fleet_failover_total"),
            "completed": _delta("pa_fleet_completed_total"),
        }
        role_disp = _role_dispatch_deltas(before, after)
        if role_disp:
            fleet["role_dispatches"] = role_disp
        lost_router = _delta("pa_fleet_prompts_lost_total")
        prompts_lost = (lost_router or 0.0) + timeouts[0]
    elif exec_by_host:
        workers = None
        try:
            health = _get(base, "/health", timeout=10)
            workers = (health.get("queue") or {}).get("workers")
        except (urllib.error.URLError, OSError, ValueError):
            pass
        per_host = {
            hid: {
                "completed": len(lat_by_host.get(hid, [])),
                "latency_p50_s": round(
                    percentile(lat_by_host.get(hid, []), 50), 3
                ),
                "latency_p95_s": round(
                    percentile(lat_by_host.get(hid, []), 95), 3
                ),
                "service_p50_s": round(percentile(execs, 50), 6),
                "workers": workers,
            }
            for hid, execs in exec_by_host.items()
        }
    if prompts_lost is None and timeouts[0]:
        # Unconditional (not nested under any per-host branch): a run whose
        # EVERY request timed out has no exec evidence but its losses are
        # the most real of all — the closed-loop run_load discipline.
        prompts_lost = float(timeouts[0])
    total_arrivals = sum(len(r["offsets"]) for r in rungs_in)
    dispatches = (
        after.get("pa_serving_dispatch_total", 0.0)
        - before.get("pa_serving_dispatch_total", 0.0)
    ) if after else None
    lane_steps = (
        after.get("pa_serving_lane_steps_total", 0.0)
        - before.get("pa_serving_lane_steps_total", 0.0)
    ) if after else None
    # The twin's client-side constant: at the LOWEST offered rate queueing
    # is ~zero, so (client p50 − service p50) is pure transport + history
    # poll cadence — the per-request overhead the twin adds on top of its
    # queue + service model (fleet/twin.py simulate(overhead_s=...)). BOTH
    # sides of the subtraction come from the lightest rung: a pooled
    # service p50 folds in contention-inflated exec times from saturated
    # rungs and would clamp the constant toward zero.
    overall_service = (
        round(percentile(all_exec, 50), 6) if all_exec else None
    )
    client_overhead = None
    calibration_rungs = [c for c in curve if c["completed"] > 0]
    if calibration_rungs:
        lightest = min(calibration_rungs,
                       key=lambda c: c["rps_offered"] or 0.0)
        light_service = lightest.get("service_p50_s") or overall_service
        if light_service is not None:
            client_overhead = round(
                max(0.0, lightest["latency_p50_s"] - light_service), 6
            )
    return {
        "mode": "openloop",
        "openloop": {
            "kind": kind,
            "seed": seed,
            "curve": curve,
            "client_overhead_s": client_overhead,
            "twin_band": twin_band,
        },
        "twin_band": twin_band,
        "requests": total_arrivals,
        "seed": seed,
        "samplers": samplers or None,
        "prompt_dist": prompt_dist if texts is not None else None,
        "seed_fanout": (
            seed_fanout if texts is not None and seed_fanout > 1 else None
        ),
        "distinct_prompts": len(set(texts)) if texts is not None else None,
        **_reuse_summary(before, after),
        "completed": len(all_lat),
        "failed": len(failures),
        "rejected_429": rejected[0],
        "timeouts": timeouts[0],
        "wall_s": round(wall, 3),
        "throughput_rps": round(len(all_lat) / wall, 3) if wall > 0 else None,
        "latency_p50_s": round(percentile(all_lat, 50), 3),
        "latency_p95_s": round(percentile(all_lat, 95), 3),
        "latency_p99_s": round(percentile(all_lat, 99), 3),
        "latency_max_s": round(max(all_lat), 3) if all_lat else 0.0,
        "serving_dispatches": dispatches,
        "serving_lane_steps": lane_steps,
        "dispatch_amortization": (
            round(lane_steps / dispatches, 3)
            if lane_steps and dispatches else None
        ),
        "serving_batched_fraction": after.get("pa_serving_batched_fraction"),
        "service_p50_s": overall_service,
        "slo": slo_view,
        "hosts": per_host,
        "roles": _role_sections(per_host),
        "fleet": fleet,
        "prompts_lost": prompts_lost,
        "errors": failures[:5],
    }


def print_human_summary(summary: dict, stream=None) -> None:
    """The operator-facing table — stderr by contract, so stdout stays ONE
    JSON line (the same ledger-appendable discipline as bench.py)."""
    stream = stream if stream is not None else sys.stderr
    w = stream.write
    w("── loadgen summary ──────────────────────────────\n")
    w(f"  prompts   {summary['completed']}/{summary['requests']} ok"
      f"  ({summary['failed']} failed, {summary['rejected_429']} rejected,"
      f" {summary.get('timeouts', 0)} timed out)\n")
    w(f"  wall      {summary['wall_s']}s"
      f"  throughput {summary['throughput_rps']} rps\n")
    w(f"  latency   p50 {summary['latency_p50_s']}s"
      f"  p95 {summary['latency_p95_s']}s"
      f"  max {summary['latency_max_s']}s\n")
    for rung in (summary.get("openloop") or {}).get("curve") or []:
        w(f"  openloop  {rung.get('rps_offered')} rps offered"
          f" ({rung.get('completed')}/{rung.get('arrivals')} ok)"
          f"  p50 {rung.get('latency_p50_s')}s"
          f"  p95 {rung.get('latency_p95_s')}s"
          f"  p99 {rung.get('latency_p99_s')}s\n")
    slo_view = summary.get("slo") or {}
    for stage, q in (slo_view.get("stages") or {}).items():
        w(f"  slo-stage {stage:<10} p50 {q.get('p50_s')}s"
          f"  p95 {q.get('p95_s')}s\n")
    if slo_view.get("collect_p50_s") is not None:
        w(f"  slo-stage collect    p50 {slo_view['collect_p50_s']}s"
          f"  p95 {slo_view.get('collect_p95_s')}s  (client residual)\n")
    for name, burn in (slo_view.get("burn_rates") or {}).items():
        w(f"  slo-burn  {name}: {burn}"
          f"{'  [BURNING]' if burn > 1.0 else ''}\n")
    if summary.get("dispatch_amortization") is not None:
        w(f"  serving   {summary['serving_dispatches']:.0f} dispatches,"
          f" {summary['serving_lane_steps']:.0f} lane-steps"
          f" ({summary['dispatch_amortization']}x amortized)\n")
    if summary.get("workload_counts"):
        parts = ", ".join(f"{k}={v}"
                          for k, v in sorted(summary["workload_counts"].items()))
        w(f"  workload  {parts}\n")
    caps = summary.get("lane_capability")
    if caps or summary.get("serving_inline_fallbacks") is not None:
        cap_s = ", ".join(f"{k}={v:.0f}" for k, v in sorted(caps.items())) \
            if caps else "-"
        w(f"  caps      lane-steps by kind: {cap_s}\n")
        w(f"  caps      inline fallbacks "
          f"{summary.get('serving_inline_fallbacks')}"
          f"  ctrl conflicts {summary.get('serving_ctrl_conflicts')}\n")
    if summary.get("embed_cache_hit_rate") is not None or \
            summary.get("encoder_invocations") is not None:
        w(f"  reuse     embed-cache hit rate "
          f"{summary.get('embed_cache_hit_rate')}"
          f"  encoder invocations {summary.get('encoder_invocations')}"
          f" / {summary.get('requests')} prompts"
          f"  (distinct {summary.get('distinct_prompts')})\n")
    if summary.get("decode_batched_fraction") is not None:
        w(f"  reuse     decode batched fraction "
          f"{summary.get('decode_batched_fraction')}"
          f"  ({summary.get('decode_requests')} decodes in "
          f"{summary.get('decode_dispatches')} dispatches)\n")
    if summary.get("fleet"):
        f = summary["fleet"]
        w(f"  fleet     dispatches {f.get('dispatches')}"
          f"  spills {f.get('spills')}  failovers {f.get('failovers')}"
          f"  lost {summary.get('prompts_lost')}\n")
    for role, p in (summary.get("roles") or {}).items():
        disp = (summary.get("fleet") or {}).get("role_dispatches") or {}
        w(f"  role {role:<9} {len(p['hosts'])} hosts  {p['completed']:>3} ok"
          f"  p95 {p.get('latency_p95_s')}s"
          f"  stage-dispatches {disp.get(role)}\n")
    if summary.get("faults_injected") is not None or \
            summary.get("degradations") is not None:
        w(f"  chaos     faults injected {summary.get('faults_injected')}"
          f"  degradation rungs {summary.get('degradations')}\n")
    if summary.get("anomalies_fired") is not None:
        w(f"  anomaly   fired {summary.get('anomalies_fired')}"
          f"  unattributed {summary.get('anomalies_unattributed')}\n")
    if summary.get("roofline_comms_fraction") is not None or \
            summary.get("roofline_host_gap_fraction") is not None:
        w(f"  roofline  comms {summary.get('roofline_comms_fraction')}"
          f"  host-gap {summary.get('roofline_host_gap_fraction')}"
          f"  (fraction of traced wall)\n")
    for hid, h in (summary.get("hosts") or {}).items():
        # Single-server open-loop rows carry no probe fields (dispatches /
        # reachability are fleet-mode diffs) — render what exists.
        role = h.get("role")
        w(f"  host {hid:<20} {h['completed']:>3} ok"
          f"  p50 {h['latency_p50_s']}s  p95 {h['latency_p95_s']}s"
          f"  dispatches {h.get('dispatches')}"
          f"{f'  [{role}]' if role and role != 'all' else ''}"
          f"{'  [UNREACHABLE]' if h.get('reachable') is False else ''}\n")
    for err in summary.get("errors") or []:
        w(f"  error     {err}\n")
    w("─────────────────────────────────────────────────\n")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--base", default="http://127.0.0.1:8188")
    ap.add_argument("--graph", required=True,
                    help="workflow JSON file (ComfyUI /prompt API format)")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests", type=int, default=2,
                    help="prompts per client (closed loop)")
    ap.add_argument("--timeout", type=float, default=300.0)
    ap.add_argument("--seed-key", default=None,
                    help="colon path (node:inputs:seed) made unique per prompt")
    ap.add_argument("--samplers", default=None,
                    help="comma list (euler,heun,dpmpp_2m,...) assigned "
                         "round-robin per prompt — the mixed workload the "
                         "stateful-lane scheduler co-batches; requires "
                         "--sampler-key")
    ap.add_argument("--sampler-key", default=None,
                    help="colon path (node:inputs:sampler_name) the "
                         "round-robin sampler is written to")
    ap.add_argument("--prompt-dist", default=None,
                    help="zipf:<s> — sample each submission's prompt TEXT "
                         "from a seeded zipf over the prompt vocabulary "
                         "(written at --prompt-key): the redundant "
                         "production traffic shape the embed cache "
                         "collapses")
    ap.add_argument("--prompt-key", default=None,
                    help="colon path (node:inputs:text) the sampled prompt "
                         "text is written to")
    ap.add_argument("--prompt-vocab", default=None,
                    help="comma list of prompt texts to sample from "
                         "(default: 32 synthetic 'prompt k' strings)")
    ap.add_argument("--seed-fanout", type=int, default=1,
                    help="group submissions into N-seed siblings of one "
                         "sampled prompt (same text, distinct --seed-key "
                         "values) — the shared-cond fanout shape")
    ap.add_argument("--priority", type=int, default=None)
    ap.add_argument("--deadline-s", type=float, default=None)
    ap.add_argument("--seed", type=int, default=None,
                    help="seed the prompt schedule (the values written at "
                         "--seed-key) so a run is reproducible")
    ap.add_argument("--hosts", default=None,
                    help="comma list of backend base URLs: fleet mode — "
                         "--base is the router; summary adds per-host "
                         "latency/dispatch sections, pa_fleet_* deltas, "
                         "and the CI-gated prompts_lost count")
    ap.add_argument("--fallback-bases", default=None,
                    help="comma list of standby router base URLs (router "
                         "HA): clients fail over to them when --base stops "
                         "answering or replies standby-503")
    ap.add_argument("--openloop", default=None,
                    choices=["poisson", "onoff", "replay"],
                    help="OPEN-loop mode: requests fire on a seeded arrival "
                         "schedule regardless of completions — the regime "
                         "where queues grow. poisson/onoff generate from "
                         "--rps/--duration/--seed; replay needs "
                         "--arrivals-in (a saved schedule or a fleet "
                         "journal). Summary becomes a latency-under-load "
                         "curve + SLO decomposition; ledger kind=openloop")
    ap.add_argument("--rps", default="4",
                    help="comma list of offered request rates — one "
                         "open-loop rung (curve point) per rate")
    ap.add_argument("--duration", type=float, default=3.0,
                    help="seconds of arrivals per open-loop rung")
    ap.add_argument("--on-s", type=float, default=1.0,
                    help="onoff arrivals: busy-window seconds")
    ap.add_argument("--off-s", type=float, default=1.0,
                    help="onoff arrivals: silent-window seconds")
    ap.add_argument("--arrivals-out", default=None,
                    help="persist the generated arrival schedule "
                         "(pa-arrivals/v1 JSON) for replay / the twin")
    ap.add_argument("--arrivals-in", default=None,
                    help="replay arrivals from a pa-arrivals/v1 document "
                         "or a recorded fleet journal (submit timestamps)")
    ap.add_argument("--twin-band", type=float, default=0.5,
                    help="declared twin error band: scripts/twin_report.py "
                         "--check fails when |twin p95 - measured p95| / "
                         "measured exceeds this fraction")
    ap.add_argument("--workload-mix", default=None,
                    help="comma list of capability kinds, optional :frac "
                         "each (txt2img,img2img,controlnet,lora:0.25) — "
                         "sample each submission's KIND from the seeded "
                         "mix and submit that kind's graph (see "
                         "--workload-graph); summary gains workload counts "
                         "+ per-kind lane-capability and inline-fallback "
                         "deltas. Closed-loop only")
    ap.add_argument("--workload-graph", action="append", default=None,
                    metavar="KIND=PATH",
                    help="workflow JSON for one mix kind (repeatable); "
                         "kinds without a graph fall back to --graph")
    ap.add_argument("--trace-sample", type=float, default=0.0,
                    help="tag a seeded, prefix-stable fraction of prompts "
                         "(0..1) for full distributed trace capture and "
                         "fetch each one's stitched timeline after it "
                         "completes; summary gains traced_prompts + "
                         "trace_fetch_rate. Closed-loop only")
    args = ap.parse_args()
    if args.trace_sample and args.openloop:
        ap.error("--trace-sample is closed-loop only (no --openloop)")
    workload_mix = parse_workload_mix(args.workload_mix)  # fail fast
    workload_graphs = {}
    for spec in args.workload_graph or []:
        kind, sep, path = spec.partition("=")
        if not sep or kind not in WORKLOAD_KINDS:
            ap.error(f"--workload-graph wants KIND=PATH with KIND one of "
                     f"{', '.join(WORKLOAD_KINDS)}; got {spec!r}")
        with open(path) as f:
            workload_graphs[kind] = json.load(f)
    if (workload_mix or workload_graphs) and args.openloop:
        ap.error("--workload-mix is closed-loop only (no --openloop)")
    if workload_graphs and not workload_mix:
        ap.error("--workload-graph requires --workload-mix")
    samplers = [s for s in (args.samplers or "").split(",") if s]
    if samplers and not args.sampler_key:
        ap.error("--samplers requires --sampler-key (where to write it)")
    hosts = [h for h in (args.hosts or "").split(",") if h]
    prompt_vocab = [p for p in (args.prompt_vocab or "").split(",") if p]
    if args.prompt_dist and not args.prompt_key:
        ap.error("--prompt-dist requires --prompt-key (where to write it)")
    if args.seed_fanout > 1 and not args.prompt_key:
        # Without a prompt key no fanout schedule is built — recording
        # seed_fanout on plain traffic would bank a misleading record.
        ap.error("--seed-fanout requires --prompt-key (where to write it)")
    parse_prompt_dist(args.prompt_dist)  # fail fast on a typo'd spec
    with open(args.graph) as f:
        graph = json.load(f)
    extra = {}
    if args.priority is not None:
        extra["priority"] = args.priority
    if args.deadline_s is not None:
        extra["deadline_s"] = args.deadline_s
    fallback = [b for b in (args.fallback_bases or "").split(",") if b]
    if args.openloop:
        if args.openloop == "replay" and not args.arrivals_in:
            ap.error("--openloop replay requires --arrivals-in")
        arrivals_doc = (_twin.load_arrivals(args.arrivals_in)
                        if args.arrivals_in else None)
        summary = run_open_load(
            args.base, graph, kind=args.openloop,
            rps_list=[float(r) for r in args.rps.split(",") if r],
            duration_s=args.duration, timeout=args.timeout,
            seed=args.seed if args.seed is not None else 0,
            seed_key=args.seed_key, extra_data=extra or None,
            samplers=samplers or None, sampler_key=args.sampler_key,
            hosts=hosts or None, fallback_bases=fallback or None,
            on_s=args.on_s, off_s=args.off_s,
            arrivals_doc=arrivals_doc, arrivals_out=args.arrivals_out,
            twin_band=args.twin_band,
            prompt_dist=args.prompt_dist, prompt_key=args.prompt_key,
            prompt_vocab=prompt_vocab or None,
            seed_fanout=args.seed_fanout,
        )
        _append_ledger(summary, args.base, kind="openloop")
    else:
        summary = run_load(
            args.base, graph, clients=args.clients, requests=args.requests,
            timeout=args.timeout, seed_key=args.seed_key,
            extra_data=extra or None,
            samplers=samplers or None, sampler_key=args.sampler_key,
            seed=args.seed, hosts=hosts or None,
            fallback_bases=fallback or None,
            prompt_dist=args.prompt_dist, prompt_key=args.prompt_key,
            prompt_vocab=prompt_vocab or None,
            seed_fanout=args.seed_fanout,
            workload_mix=workload_mix,
            workload_graphs=workload_graphs or None,
            trace_sample=args.trace_sample,
        )
        # A disaggregated fleet (some backend declared a role) banks its
        # record under kind="roles" — the role-pool CI smoke's gate record;
        # homogeneous runs keep their historical kinds untouched.
        _append_ledger(summary, args.base,
                       kind="roles" if summary.get("roles")
                       else ("mixed" if workload_mix else "loadgen"))
    print_human_summary(summary)          # operator table → stderr
    print(json.dumps(summary))            # THE one JSON line → stdout


if __name__ == "__main__":
    main()
