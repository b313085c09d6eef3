#!/usr/bin/env bash
# Tier-1 CI gate: the command the driver runs (virtual-mesh CPU test suite on
# six xdist workers, one file a worker at a time, 1,470 s; the commands of
# /root/TESTS_LAST_RUN.json), then the perf-ledger regression check (scripts/perf_ledger.py
# --check — step-time / peak-HBM drift against the banked evidence). Either
# failing fails the script, so a green run means both "tests pass" AND
# "no unexplained performance regression in the ledger".
#
# Everything here is CPU-only (JAX_PLATFORMS=cpu, the virtual 8-device mesh
# of tests/conftest.py): the fleet/chaos/loadgen smokes below start several
# processes, and N backends on one host need one chip EACH — none of them may
# touch an accelerator. The chip check is `python chip_smoke.py`.
set -o pipefail
cd "$(dirname "$0")/.."

# Static analysis FIRST (round 16): scripts/palint.py --check is stdlib-only
# and finishes in ~2s — a standalone-contract drift, an unguarded shared
# write, an undocumented metric/env/fault-site/span-cat, or a host-sync
# violation fails the run before the suite (some ten minutes) spends a dot.
python scripts/palint.py --check || {
    echo "ci_tier1: palint static-analysis gate FAILED" >&2; exit 1; }

# Per-run log and junit file (not fixed /tmp names: concurrent runs must not
# clobber each other's count, and another user's stale file must not wedge
# tee). ALLOW_MULTIPLE_LIBTPU_LOAD: the files that compile for a described
# chip land on several workers. The passes are counted from the junit file,
# as the driver counts them; a run cut by the clock (rc 124) counts only as
# far as it got.
t1log=$(mktemp /tmp/_t1.XXXXXX.log)
t1xml=$(mktemp /tmp/_t1.XXXXXX.xml)
trap 'rm -f "$t1log" "$t1xml"' EXIT
timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
    python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml="$t1xml" \
    -p no:randomly 2>&1 | tee "$t1log"
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' "$t1xml" | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')"
if [ "$rc" -ne 0 ]; then
    echo "ci_tier1: tier-1 tests FAILED (rc=$rc)" >&2
    exit "$rc"
fi

python scripts/perf_ledger.py --check || exit $?

# Numerics drift gate (round 11): latest banked fingerprint per rung vs
# the golden bank (scripts/numerics_audit.py) — latent-fingerprint drift
# or a nonzero nonfinite_events count fails CI exactly like a perf
# regression; an empty/unfingerprinted ledger is SKIP, never a failure.
python scripts/numerics_audit.py --check || exit $?

# Roofline schema gate (round 13): the latest roofline-carrying ledger
# record per (rung, platform) must keep roofline_ratio in (0, 1.2] and its
# attribution buckets non-negative, summing to the recorded wall
# (scripts/roofline_report.py — an empty/unroofed ledger is SKIP, never a
# failure). Runs after the perf and numerics gates: same ledger, third lens.
python scripts/roofline_report.py --check || exit $?

# Plan gate (round 18): the latest kind=plan ledger record per (rung,
# platform) must match-or-beat the shadow hand-rule plan by predicted
# score and keep predicted-vs-actual inside the (0, 1.2] calibration band
# (scripts/plan_report.py reads the planner decisions bench/dryrun banked
# — a plan-free ledger is SKIP, never a failure). Runs right after the
# roofline gate: same ledger, the routing lens.
python scripts/plan_report.py --check || exit $?

# Traffic-twin gate (round 15): the latest kind=openloop ledger record per
# group must keep |twin p95 - measured p95| / measured within the record's
# declared error band (scripts/twin_report.py replays the seeded arrival
# trace through fleet/twin.py against roofline/measured per-host capacity —
# an openloop-free ledger is SKIP, never a failure). Fourth ledger lens,
# after the roofline gate whose calibration store it reads.
python scripts/twin_report.py --check || exit $?

# Anomaly-attribution gate (round 22): every kind=anomaly ledger record the
# online sentinel (utils/anomaly.py) banked must be ATTRIBUTED — explained
# by a declared fault site or load phase (scripts/anomaly_report.py — an
# anomaly-free ledger is SKIP, never a failure: a clean run firing zero is
# the other half of the contract). Fifth ledger lens, after the twin gate.
python scripts/anomaly_report.py --check || exit $?

# Sampler-coverage gate (round 10): one explicit pass over the lane-vs-solo
# equivalence matrix + the registry coverage check, so a LaneStepSpec wired
# into sampling/lane_specs.py but unverified (or missing from
# BATCHABLE_SAMPLERS) fails CI loudly even if someone narrows the main run's
# -m/-k selection. These tests are also part of the tier-1 run above; this
# rerun is the contract, not the coverage.
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_serving.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly \
    -k "LaneEquivalenceMatrix or MixedSamplerDispatch or RegistryCoverage"
rc=$?
[ "$rc" -ne 0 ] && exit "$rc"

# Fleet smoke (round 12): a 2-backend fleet — router + scripts/loadgen.py
# fleet mode, ~10 prompts on CPU — gated on prompts_lost == 0 plus full
# per-host attribution (tests/test_fleet.py::TestFleetSmoke). The fleet
# tier's one non-negotiable: the front door never loses a prompt. Also part
# of the tier-1 run above; this rerun is the explicit contract.
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_fleet.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly -k "FleetSmoke or Failover"
rc=$?
[ "$rc" -ne 0 ] && exit "$rc"

# Lock-order gate (round 16): the FULL fleet + serving suites under
# PA_LOCKCHECK=1 — utils/lockcheck.py wraps every repo lock construction
# and conftest's autouse fixture fails the first test whose code paths
# close a cycle in the acquisition-order graph (a potential deadlock even
# when CI never schedules the interleaving that fires it). The -k reruns
# above stay uninstrumented; THIS step is the documented zero-cycle gate
# over the threaded tier, and the chaos smoke below extends it to the
# fault-injection paths.
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    PA_LOCKCHECK=1 \
    python -m pytest tests/test_fleet.py tests/test_serving.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
rc=$?
[ "$rc" -ne 0 ] && exit "$rc"

# Reuse smoke (round 17): the cross-request compute-reuse gate — a
# zipf(s=1.1) prompt mix through a live 4-worker server must show the
# embed cache collapsing the encode stage (embed_cache_hit_rate > 0,
# encoder_invocations <= 0.5x prompts, prompts_lost == 0), an 8-seed
# fanout must cost exactly ceil(8/width) shared dispatches with latents
# bitwise-equal to solo (the shared-cond broadcast program), and the
# batched decode tail must be engaged — all banked as a kind="reuse"
# ledger record (tests/test_reuse.py::TestReuseSmoke). The unit tier
# (LRU byte bound, demotion correctness, decode allclose) reruns with it.
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_reuse.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly
rc=$?
[ "$rc" -ne 0 ] && exit "$rc"

# Mixed-workload smoke (round 19): scripts/loadgen.py --workload-mix drives
# txt2img + img2img(mask) + controlnet + lora traffic through one live
# 4-worker server — gated on prompts_lost == 0, run-delta shared-dispatch
# fraction >= 0.8, zero inline fallbacks / control-trunk conflicts for
# eligible shapes, every capability kind ticking its
# pa_serving_lane_capability_total delta, and the kind="mixed" ledger
# record landing (tests/test_loadgen_mix.py — slow-marked, so THIS block is
# where the universal-lane-batching contract actually runs).
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_loadgen_mix.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly
rc=$?
[ "$rc" -ne 0 ] && exit "$rc"

# Role-pool smoke (round 20): 1 encode + 2 denoise + 1 decode virtual hosts
# vs 4 homogeneous backends under the SAME mixed load at the SAME host
# count (the BASELINE "Role-pool protocol" comparison rule) — gated on
# prompts_lost == 0, strictly higher disaggregated throughput, the decode
# stage p95 dropping below the homogeneous baseline, and the kind="roles"
# ledger record landing; plus the staged-dispatch e2e tier (pool-respecting
# placement, bitwise vs single-host, mid-denoise role-host kill) and the
# decode-tier kill with standby takeover re-dispatching from the journaled
# denoise handle (tests/test_fleet.py::TestStageLineageReplay — the
# stage-lineage contract). Also part of the tier-1 run above; this rerun is
# the explicit contract.
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_roles.py tests/test_fleet.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly \
    -k "RolePool or StageLineageReplay"
rc=$?
[ "$rc" -ne 0 ] && exit "$rc"

# Forensics gate (round 21): the role-pool failover e2e reruns with
# PA_FORENSICS_DUMP set, banking its stitched /fleet/trace document + the
# client-observed wall; scripts/explain.py --check then gates the
# conservation contract on that prompt — stitched trace fetched (>= 3
# host-labeled tracks under ONE trace_id across the mid-denoise failover),
# every critical-path bucket non-negative, buckets summing to the client
# wall within 10%. The explain step is stdlib-only (standalone-contract:
# it never imports jax).
fdump=$(mktemp /tmp/_forensics.XXXXXX.json)
trap 'rm -f "$t1log" "$t1xml" "$fdump"' EXIT
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    PA_FORENSICS_DUMP="$fdump" \
    python -m pytest tests/test_roles.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly -k "RequestForensics"
rc=$?
[ "$rc" -ne 0 ] && exit "$rc"
python scripts/explain.py --check \
    --trace-file "$fdump" --min-hosts 3 || {
    echo "ci_tier1: request-forensics explain gate FAILED" >&2; exit 1; }

# Telemetry-plane smoke (round 22): the continuous-telemetry contract —
# history-ring byte bound + reset-aware readers, deterministic sentinel
# firing with fault attribution and a postmortem carrying the history
# window, /metrics/history + /fleet/history with a dead host serving its
# cached window marked stale, and scripts/console.py --once --json
# rendering every live host's sparkline data off a real 2-backend fleet
# (tests/test_telemetry_plane.py). Also part of the tier-1 run above;
# this rerun is the explicit contract.
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_telemetry_plane.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly
rc=$?
[ "$rc" -ne 0 ] && exit "$rc"

# Chaos smoke (round 14): a seeded fault plan (backend-http 5xx +
# slow-host, deterministic in the seed) fired against a 2-backend fleet
# while the PRIMARY ROUTER is killed mid-denoise (standby takeover off the
# durable prompt journal, fleet/journal.py) and one backend is killed —
# gated on prompts_lost == 0, every latent bitwise-equal to the fault-free
# baseline, bounded p95 inflation, and every injected fault attributable
# (pa_fault_injected_total); plus an injected stream-OOM absorbed by the
# re-carve degradation rung on a real weight-streamed model
# (tests/test_chaos.py drives scripts/chaos.py in-process). Also part of
# the tier-1 run above; this rerun is the explicit contract. Round 16 runs
# it under PA_LOCKCHECK=1: utils/lockcheck.py records the lock-acquisition-
# order graph across the whole router+standby+backends fleet under fault
# injection, the chaos verdict carries lock_cycles, and conftest fails any
# test that leaves a cycle — the dynamic half of palint's lock-discipline
# pass, gated on ZERO potential deadlocks.
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    PA_LOCKCHECK=1 \
    python -m pytest tests/test_chaos.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly
