"""Roofline-scored auto-parallel planner: search the plan space, not rules.

The ROADMAP's oldest carried-forward item (round 11) and the direct
analogue of topology-aware auto-parallel planning for diffusion-transformer
inference (PAPERS.md: AoiZora, arxiv 2606.17566; MPMD stage-carve search,
arxiv 2412.14374). The reference's entire "planner" is a static free-VRAM
weighting (any_device_parallel.py:737-766); this module replaces the
orchestrator's hand-written routing ladder (replicate → dp → pipeline →
stream, fixed mesh factorization) with a cost-model search:

- **enumerate** candidate plans: mesh factorizations of the device count
  into dp×tp, weight mode (replicate / fsdp-shard / stream, with byte-carve
  candidates from ``models/loader.carve_ranges`` — the same arithmetic the
  streaming executor carves with), pipeline stage carves for the batch==1
  block-placement path, and the attention axis
  (``ops.attention.resolve_route`` — the backend and blocks the rung's
  attention class will take, recorded with the decision);
- **prune** HBM-infeasible plans against the residency budget
  (``devices.memory.usable_hbm_bytes`` / ``ParallelConfig.hbm_budget_bytes``
  — infeasible candidates stay in the score table, marked, and are never
  selected);
- **score** survivors through the calibrated roofline
  (``utils/roofline.py``: ``max(compute, memory) + comms`` per platform
  spec, the ICI collective term for tp/fsdp gather traffic, the ``h2d_bw``
  host→HBM term for streamed weights, and the banked
  ``ledger/roofline_calib.json`` scale for ``plan:<rung>`` keys — measured
  actuals feed back through ``fit_calibration``, so the planner sharpens
  per platform);
- **route** ``parallelize()`` through the winner, keeping the hand rules
  as the ``PA_PLANNER=0`` fallback AND as a shadow comparator: every
  decision records chosen-vs-hand plan and the per-candidate score table
  (``pa_planner_*`` gauges, the ``plan`` section of ``GET /health``, and —
  when bench/dryrun measure the decision — a ``kind="plan"`` perf-ledger
  record carrying predicted-vs-actual).

Flag discipline (``PA_PLANNER``): ``"0"``/``"false"`` disables the planner
entirely — ``parallelize`` routes through the unmodified hand ladder,
bitwise-identical to the pre-planner code; ``"shadow"`` runs the full
search and records the decision but ENACTS the hand plan (the rollout
mode: divergences surface in the ledger before they touch routing);
anything else (the default) enacts the winner. Divergence hysteresis: the
planner only overrides the hand plan when its candidate predicts at least
:data:`_HYSTERESIS` better — cost models are approximate, routing churn is
not free, and "plan ≥ hand on every rung" is the acceptance contract.

Ledger discipline: this module never writes the perf ledger on its own —
``parallelize`` runs inside tests hundreds of times per suite, and the
committed ledger is evidence, not a log. The decision lives in-process
(:func:`snapshot`, gauges); bench.py and the dryrun append the
``kind="plan"`` record explicitly, stamped with the measured actual.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any

from ..utils.roofline import (
    calibration_scale,
    collective_time_s,
    load_calibration,
    platform_spec,
    shape_bucket,
)

# Divergence hysteresis: the planner abandons the hand plan only for a
# >2% predicted win (see module docstring).
_HYSTERESIS = 0.02

# Per-stage dispatch/jit-call overhead the stream-carve model charges each
# stage (host dispatch + prefetch issue; calibration absorbs the truth).
_STAGE_OVERHEAD_S = 5e-4

# Activation headroom fraction of the HBM budget resident placements
# reserve — the streaming builder's 2/5-per-buffer carve leaves 1/5 for
# activations; resident feasibility keeps the same 1/5 reserve.
_ACT_HEADROOM = 0.2

# Nominal tokens-per-step for the FLOPs fallback (2 FLOPs per weight byte
# per token ≈ 2·params·tokens at bf16 storage): absolute magnitude only
# matters for the compute-vs-transfer comparison inside one decision, and
# every candidate shares it.
_NOMINAL_TOKENS = 4096


def mode() -> str:
    """``"off"`` (PA_PLANNER=0/false — the bitwise hand-rule fallback),
    ``"shadow"`` (search + record, enact hand), or ``"on"`` (default)."""
    raw = os.environ.get("PA_PLANNER", "").strip().lower()
    if raw in ("0", "false", "off"):
        return "off"
    if raw == "shadow":
        return "shadow"
    return "on"


def enabled() -> bool:
    return mode() != "off"


@dataclasses.dataclass(frozen=True)
class PlanInputs:
    """Everything a plan decision is a pure function of. Byte/FLOP facts
    come from the caller (orchestrator/bench) so :func:`plan` itself stays
    deterministic and unit-testable without models or devices."""

    n_devices: int
    platform: str = "cpu"
    device_kind: str = ""
    weights_bytes: int = 0
    # Per-device usable HBM budget; None/0 = unknown (CPU backends report
    # none) — feasibility pruning then admits every resident candidate,
    # exactly like the hand ladder's budget check.
    budget_bytes: int | None = None
    segment_bytes: tuple[int, ...] = ()
    flops: float | None = None          # one model forward (per dispatch)
    bytes_accessed: float | None = None
    batch: int | None = None
    seq_len: int | None = None          # attention-axis hints (optional)
    head_dim: int | None = None
    heads: int | None = None
    rung: str = ""                      # context tag for records/calibration


def _flops_of(inp: PlanInputs) -> float:
    if inp.flops and inp.flops > 0:
        return float(inp.flops)
    tokens = max(1, int(inp.batch or 1)) * int(inp.seq_len or _NOMINAL_TOKENS)
    # bf16 storage ≈ params = bytes/2; 2 FLOPs per param per token —
    # ordering inside one decision is what matters, and every candidate
    # shares the estimate.
    return float(max(1, inp.weights_bytes)) * tokens


def _act_bytes_of(inp: PlanInputs) -> float:
    if inp.bytes_accessed and inp.bytes_accessed > inp.weights_bytes:
        return float(inp.bytes_accessed) - float(inp.weights_bytes)
    # Fallback: activation traffic a quarter of weight traffic — diffusion
    # steps are weight-read dominated at serving batch sizes.
    return 0.25 * float(max(1, inp.weights_bytes))


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def _candidate(mode_: str, inp: PlanInputs, spec: dict, calib: dict, *,
               dp: int, tp: int, feasible: bool, why: str,
               compute_s: float, memory_s: float, comms_s: float,
               transfer_s: float = 0.0, fill_s: float = 0.0,
               overhead_s: float = 0.0, n_stages: int | None = None,
               max_stage_bytes: int | None = None) -> dict:
    raw = max(compute_s, memory_s, transfer_s) + comms_s + fill_s + overhead_s
    scale = calibration_scale(
        calib, f"plan:{inp.rung or '?'}", inp.platform,
        shape_bucket(_flops_of(inp)),
    )
    bound = "comms" if comms_s > max(compute_s, memory_s, transfer_s) else (
        "transfer" if transfer_s > max(compute_s, memory_s)
        else "memory" if memory_s > compute_s else "compute"
    )
    return {
        "mode": mode_, "dp": int(dp), "tp": int(tp),
        "n_stages": n_stages, "max_stage_bytes": max_stage_bytes,
        "feasible": bool(feasible), "why": why,
        "compute_s": round(compute_s, 9), "memory_s": round(memory_s, 9),
        "comms_s": round(comms_s, 9), "transfer_s": round(transfer_s, 9),
        "fill_s": round(fill_s, 9), "overhead_s": round(overhead_s, 9),
        "bound": bound,
        "predicted_raw_s": round(raw, 9),
        "predicted_s": round(raw * scale, 9),
        "calib_scale": scale,
    }


def _resident_candidate(inp: PlanInputs, spec: dict, calib: dict,
                        dp: int, tp: int, mode_: str, why: str) -> dict:
    """Score one resident placement. ``replicate``: full weights per chip,
    no collectives. ``tp``: weights 1/tp per chip, per-step activation
    all-reduce over the model axis. ``fsdp``: weights 1/n per chip, the
    full weight pytree all-gathered per step over ICI."""
    n = dp * tp
    flops = _flops_of(inp)
    act = _act_bytes_of(inp)
    w = float(inp.weights_bytes)
    compute_s = flops / n / spec["peak_flops"]
    if mode_ == "replicate":
        comms = 0.0
    elif mode_ == "tp":
        # Per-step activation all-reduces over the model axis (the GSPMD
        # partials of each sharded matmul) — first-order: the per-device
        # activation traffic crosses the tp group once.
        comms = collective_time_s(act / dp, tp, spec)
    else:  # fsdp
        # Every step all-gathers the full weight pytree (ZeRO-3 per-use
        # gather) — each chip still READS full weights from HBM after,
        # only the stored shard is 1/n.
        comms = collective_time_s(w, n, spec)
    hbm_reads = (w if mode_ != "tp" else w / tp) + act / max(1, dp)
    memory_s = hbm_reads / spec["hbm_bw"]
    budget = inp.budget_bytes or 0
    if budget <= 0:
        feasible = True
    elif mode_ == "replicate":
        feasible = w <= budget
    elif mode_ == "tp":
        feasible = w / tp <= budget * (1 - _ACT_HEADROOM)
    else:  # fsdp: stored shard + one layer's gather buffer headroom
        feasible = w / n <= budget * (1 - _ACT_HEADROOM) / 2
    return _candidate(
        mode_, inp, spec, calib, dp=dp, tp=tp, feasible=feasible, why=why,
        compute_s=compute_s, memory_s=memory_s, comms_s=comms,
    )


def _stream_candidates(inp: PlanInputs, spec: dict, calib: dict,
                       hand_only: bool = False) -> list[dict]:
    """Stream carve candidates: the hand carve (budget·2/5 byte cap — what
    ``build_streaming_runner`` does today) plus byte-balanced carves at
    other stage counts from ``loader.carve_ranges``. Single-device by
    construction (the streaming executor runs the lead chip); the cost
    model is the double-buffered schedule itself: steady state
    ``max(compute, weights/h2d)``, plus the stage-0 fill the overlap can
    never hide, plus per-stage dispatch overhead — more stages shrink the
    fill and grow the overhead, which is exactly the tradeoff the search
    walks."""
    from ..models.loader import carve_ranges

    if not inp.segment_bytes:
        return []
    sizes = list(inp.segment_bytes)
    w = float(sum(sizes))
    flops = _flops_of(inp)
    act = _act_bytes_of(inp)
    budget = inp.budget_bytes or 0
    cap = max(1, int(budget) * 2 // 5) if budget > 0 else None
    compute_s = max(flops / spec["peak_flops"],
                    (w + act) / spec["hbm_bw"])
    h2d = spec.get("h2d_bw") or 10e9
    transfer_s = w / h2d

    def build(ranges, why) -> dict:
        stage_bytes = [sum(sizes[s:e]) for s, e in ranges]
        max_stage = max(stage_bytes)
        fill_s = stage_bytes[0] / h2d
        overhead_s = len(ranges) * _STAGE_OVERHEAD_S
        # Feasibility: two buffers of the largest stage + activation
        # headroom must fit the budget — the 2/5 carve rule inverted. A
        # lone oversized segment is still servable (the atomic-unit
        # degradation carve_ranges documents) but only when no finer
        # feasible carve exists; mark it infeasible so the search prefers
        # carves that honor the bound.
        feasible = budget <= 0 or 2 * max_stage <= budget * (1 - _ACT_HEADROOM)
        return _candidate(
            "stream", inp, spec, calib, dp=1, tp=1,
            feasible=feasible, why=why,
            compute_s=compute_s, memory_s=0.0, comms_s=0.0,
            transfer_s=transfer_s, fill_s=fill_s, overhead_s=overhead_s,
            n_stages=len(ranges), max_stage_bytes=max_stage,
        )

    out: list[dict] = []
    seen: set[tuple] = set()

    def add(ranges, why):
        key = tuple(ranges)
        if key in seen:
            return
        seen.add(key)
        out.append(build(ranges, why))

    if cap is not None:
        add(carve_ranges(sizes, max_stage_bytes=cap),
            "hand carve: budget*2/5 byte cap")
    else:
        # No budget: the hand ladder's StreamingRunner default is a
        # 4-stage byte-balanced carve (build_streaming_runner).
        add(carve_ranges(sizes, n_stages=4),
            "hand carve: default 4-stage balance (no budget)")
    if hand_only:
        return out
    for n in (2, 4, 8, 16, len(sizes)):
        if 2 <= n <= len(sizes):
            add(carve_ranges(sizes, n_stages=n),
                f"byte-balanced carve into {n} stage(s)")
    return out


def _count_ranges(n_items: int, n_parts: int) -> list[tuple[int, int]]:
    """Contiguous count-balanced ranges — what the weight-proportional
    pipeline carve degenerates to on a uniform-weight chain (the hand
    behavior the planned byte-balanced carve is compared against)."""
    n_parts = max(1, min(n_items, n_parts))
    base, rem = divmod(n_items, n_parts)
    ranges, start = [], 0
    for i in range(n_parts):
        size = base + (1 if i < rem else 0)
        ranges.append((start, start + size))
        start += size
    return [r for r in ranges if r[0] != r[1]]


def _pipeline_plan(inp: PlanInputs, spec: dict) -> dict | None:
    """The batch==1 block-placement carve axis: byte-balanced stage ranges
    vs the hand count-balanced carve. The pipeline's critical path is the
    largest stage (every stage runs serially, memory-bound per device), so
    the score is max-stage bytes over HBM bandwidth — byte balance wins
    whenever segments are uneven."""
    from ..models.loader import carve_ranges

    if len(inp.segment_bytes) < 2 or inp.n_devices < 2:
        return None
    sizes = list(inp.segment_bytes)
    planned = carve_ranges(sizes, n_stages=inp.n_devices)
    hand = _count_ranges(len(sizes), inp.n_devices)

    def max_stage(ranges):
        return max(sum(sizes[s:e]) for s, e in ranges)

    bw = spec["hbm_bw"]
    pred = max_stage(planned) / bw
    hand_pred = max_stage(hand) / bw
    return {
        "ranges": [list(r) for r in planned],
        "hand_ranges": [list(r) for r in hand],
        "max_stage_bytes": max_stage(planned),
        "hand_max_stage_bytes": max_stage(hand),
        "predicted_s": round(pred, 9),
        "hand_predicted_s": round(hand_pred, 9),
        # The same hysteresis contract as the top-level choice: the planned
        # carve may only be ENACTED (orchestrator._get_pipeline_runner)
        # when it actually differs and predicts clearly better. "enact" is
        # the INTENT; the orchestrator sets "enacted" (and bumps
        # pa_planner_pipeline_carve_total) at the moment a batch==1 runner
        # really builds with the planned ranges.
        "enact": (planned != hand
                  and pred < hand_pred * (1 - _HYSTERESIS)),
        "enacted": False,
    }


def hand_plan(inp: PlanInputs, spec: dict, calib: dict,
              pinned_mode: str | None = None) -> dict:
    """The PA_PLANNER=0 ladder as a scored candidate — the shadow
    comparator every decision records: replicate over every device, except
    weights-don't-fit with a PipelineSpec → stream at the budget-derived
    carve (orchestrator.parallelize's exact auto-routing)."""
    budget = inp.budget_bytes or 0
    streams = _stream_candidates(inp, spec, calib, hand_only=True)
    if pinned_mode == "stream" or (
        budget > 0 and inp.weights_bytes > budget and inp.segment_bytes
    ):
        if streams:
            hand = dict(streams[0])
            hand["why"] = "hand ladder: " + hand["why"]
            return hand
    return _resident_candidate(
        inp, spec, calib, dp=inp.n_devices, tp=1, mode_="replicate",
        why="hand ladder: replicate over every chain device",
    )


def plan(inp: PlanInputs, pinned_mode: str | None = None) -> dict:
    """One decision: enumerate → prune → score → choose, with the hand plan
    as the recorded shadow. ``pinned_mode="stream"`` restricts the space to
    the stream-carve axis (an explicit ``weight_sharding="stream"`` pins
    the mode; the carve is still searched). Pure in ``inp`` + the banked
    calibration store."""
    spec = platform_spec(inp.device_kind, inp.platform)
    calib = load_calibration()
    n = max(1, int(inp.n_devices))

    candidates: list[dict] = []
    if pinned_mode == "stream":
        candidates.extend(_stream_candidates(inp, spec, calib))
    else:
        for tp in _divisors(n):
            dp = n // tp
            if tp == 1:
                candidates.append(_resident_candidate(
                    inp, spec, calib, dp=dp, tp=1, mode_="replicate",
                    why=f"replicate, dp={dp}",
                ))
            else:
                candidates.append(_resident_candidate(
                    inp, spec, calib, dp=dp, tp=tp, mode_="tp",
                    why=f"2-D mesh dp={dp} x tp={tp} (GSPMD)",
                ))
        if n > 1:
            candidates.append(_resident_candidate(
                inp, spec, calib, dp=n, tp=1, mode_="fsdp",
                why=f"fsdp: weights 1/{n} per chip, per-step all-gather",
            ))
        candidates.extend(_stream_candidates(inp, spec, calib))

    hand = hand_plan(inp, spec, calib, pinned_mode=pinned_mode)
    feasible = [c for c in candidates if c["feasible"]]
    fallback = None
    if feasible:
        best = min(feasible, key=lambda c: c["predicted_s"])
        # Hysteresis: diverge from the hand plan only for a clear win.
        if best["predicted_s"] >= hand["predicted_s"] * (1 - _HYSTERESIS):
            chosen = hand
        else:
            chosen = best
    else:
        chosen = hand
        fallback = "no-feasible-candidate"

    attn = None
    if inp.seq_len and inp.head_dim:
        from ..ops.attention import resolve_route

        attn = resolve_route(
            int(inp.seq_len), int(inp.seq_len), int(inp.head_dim),
            int(inp.batch or 1) * int(inp.heads or 1),
        )._asdict()

    pipeline = (
        _pipeline_plan(inp, spec)
        if chosen["mode"] in ("replicate",) else None
    )
    decision = {
        "rung": inp.rung or None,
        "platform": inp.platform,
        "device_kind": inp.device_kind or None,
        "n_devices": n,
        "weights_bytes": int(inp.weights_bytes),
        "budget_bytes": int(inp.budget_bytes) if inp.budget_bytes else None,
        "flops": _flops_of(inp),
        "flops_source": "hint" if inp.flops else "weights-estimate",
        "pinned_mode": pinned_mode,
        "chosen": chosen,
        "hand": hand,
        "candidates": candidates,
        "pipeline": pipeline,
        "attn": attn,
        # Top-level routing divergence (mode/mesh/carve key). The pipeline
        # carve is its OWN dimension: "enact" above records the intent
        # (differs + clears hysteresis), and the orchestrator stamps
        # ``pipeline["enacted"]`` only when the batch==1 runner actually
        # builds with the planned ranges — whether that ever happens
        # depends on runtime facts (batch==1 traffic, uniform weights)
        # this pure decision cannot see, so folding intent into
        # ``divergent`` would report routing changes that never occurred.
        "divergent": _plan_key(chosen) != _plan_key(hand),
        "plan_wins": chosen["predicted_s"] <= hand["predicted_s"] + 1e-12,
        "fallback": fallback,
        "mode_flag": mode(),
    }
    _record_decision(decision)
    return decision


def _plan_key(c: dict) -> tuple:
    return (c["mode"], c["dp"], c["tp"], c.get("n_stages"))


# ---------------------------------------------------------------------------
# in-process decision registry + gauges + health section
# ---------------------------------------------------------------------------


class _State:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.decisions = 0      # guarded-by: _lock
        self.divergences = 0    # guarded-by: _lock
        self.last: dict | None = None  # guarded-by: _lock

    def reset(self) -> None:
        with self._lock:
            self.decisions = 0
            self.divergences = 0
            self.last = None


state = _State()


def _record_decision(decision: dict) -> None:
    with state._lock:
        state.decisions += 1
        if decision["divergent"]:
            state.divergences += 1
        state.last = decision
    try:
        from ..utils.metrics import registry

        registry.counter(
            "pa_planner_decisions_total",
            help="auto-parallel plan decisions taken (parallel/planner.py)",
        )
        if decision["divergent"]:
            registry.counter(
                "pa_planner_divergence_total",
                help="decisions where the scored winner overrode the "
                     "hand-rule plan",
            )
        registry.gauge(
            "pa_planner_predicted_s", decision["chosen"]["predicted_s"],
            labels={"mode": decision["chosen"]["mode"]},
            help="calibrated roofline prediction of the chosen plan's step",
        )
        registry.gauge(
            "pa_planner_hand_predicted_s", decision["hand"]["predicted_s"],
            help="the shadow hand-rule plan's predicted step (chosen <= "
                 "hand is the acceptance contract)",
        )
        registry.gauge(
            "pa_planner_candidates", len(decision["candidates"]),
            help="candidate plans enumerated for the last decision",
        )
    except Exception:
        pass


def _compact(c: dict | None) -> dict | None:
    if not isinstance(c, dict):
        return None
    return {k: c.get(k) for k in (
        "mode", "dp", "tp", "n_stages", "max_stage_bytes", "feasible",
        "predicted_s", "predicted_raw_s", "bound", "why",
    )}


def plan_summary(decision: dict | None) -> dict | None:
    """The compact plan view a bench JSON line carries (null when the
    planner is off or never engaged)."""
    if not isinstance(decision, dict):
        return None
    return {
        "source": "planner" if decision["mode_flag"] == "on" else "shadow",
        "chosen": _compact(decision["chosen"]),
        "hand_predicted_s": decision["hand"]["predicted_s"],
        "divergent": decision["divergent"],
        "plan_wins": decision["plan_wins"],
        "candidates": len(decision["candidates"]),
        "attn_backend": (decision.get("attn") or {}).get("backend"),
    }


def snapshot() -> dict:
    """The ``plan`` section of ``GET /health``."""
    with state._lock:
        last = state.last
        return {
            "mode": mode(),
            "decisions": state.decisions,
            "divergences": state.divergences,
            "last": None if last is None else {
                "rung": last["rung"],
                "n_devices": last["n_devices"],
                "chosen": _compact(last["chosen"]),
                "hand": _compact(last["hand"]),
                "divergent": last["divergent"],
                "plan_wins": last["plan_wins"],
                "candidates": len(last["candidates"]),
            },
        }


def ledger_record(decision: dict, actual_s: float | None = None) -> dict:
    """Flatten a decision into the ``kind="plan"`` perf-ledger record
    (scripts/plan_report.py gates it; ``fit_calibration`` reads
    ``plan_predicted_raw_s``/``plan_actual_s`` back). The caller appends it
    via ``telemetry.append_ledger_record(rec, "plan")`` — see the module
    docstring's ledger discipline.

    Shadow guard: in shadow mode a DIVERGENT decision's chosen plan never
    ran — the measured actual belongs to the enacted hand plan, and pairing
    it with the chosen plan's raw prediction would poison the
    ``plan:<rung>`` calibration fit. The actual is dropped from the record
    there (the decision itself still banks in full)."""
    chosen, hand = decision["chosen"], decision["hand"]
    if actual_s and decision["divergent"] and decision["mode_flag"] != "on":
        actual_s = None
    rec = {
        "rung": decision["rung"] or "?",
        "platform": decision["platform"],
        "n_devices": decision["n_devices"],
        "weights_bytes": decision["weights_bytes"],
        "budget_bytes": decision["budget_bytes"],
        "plan_mode": chosen["mode"],
        "plan_dp": chosen["dp"],
        "plan_tp": chosen["tp"],
        "plan_stages": chosen.get("n_stages"),
        "plan_predicted_s": chosen["predicted_s"],
        "plan_predicted_raw_s": chosen["predicted_raw_s"],
        "plan_flops": decision["flops"],
        "plan_hand_mode": hand["mode"],
        "plan_hand_stages": hand.get("n_stages"),
        "plan_hand_predicted_s": hand["predicted_s"],
        "plan_divergent": decision["divergent"],
        "plan_wins": decision["plan_wins"],
        "plan_pinned_mode": decision["pinned_mode"],
        "plan_mode_flag": decision["mode_flag"],
        "plan_candidates": [_compact(c) for c in decision["candidates"]],
        "plan_attn": (decision.get("attn") or {}).get("backend"),
        # The pipeline-carve axis, its own dimension (see plan()): intent
        # vs actually-applied, with the byte scores behind them.
        "plan_pipeline": (
            None if not decision.get("pipeline") else {
                k: decision["pipeline"][k]
                for k in ("enact", "enacted", "max_stage_bytes",
                          "hand_max_stage_bytes")
            }
        ),
        "plan_actual_s": (
            round(float(actual_s), 6) if actual_s else None
        ),
        "plan_ratio": (
            round(chosen["predicted_s"] / float(actual_s), 4)
            if actual_s else None
        ),
    }
    return rec
