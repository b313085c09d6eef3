"""Auto-parallel planner (parallel/planner.py): the roofline-scored search
over (mesh dp×tp × weight mode × stage-carve × attention) that replaced the
orchestrator's hand routing ladder.

Covers the ISSUE-14 acceptance matrix: every banked rung's geometry plans
at-least-as-well as the hand rules by predicted score (and flux_stream
STRICTLY better — the stage-carve win), infeasible plans are never
selected, ``PA_PLANNER=0`` routes bitwise-identically to the hand ladder,
shadow mode records without enacting, plan actuals calibrate back through
``fit_calibration``, the attention axis agrees with ``attention_local``'s
trace-time resolution, and ``scripts/plan_report.py --check`` gates the
ledger records bench/dryrun append.
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from comfyui_parallelanything_tpu.parallel import planner

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Approximate byte/FLOP geometry of the bench rungs on an 8-chip v5e slice
# (shape arithmetic; on the chip: not measured): enough fidelity for the decision the
# planner must reproduce — weights that fit replicate everywhere, the
# streamed flagship does not.
V5E_BUDGET = int(0.9 * 16 * 2**30)
RUNG_GEOMETRY = {
    # rung: (weights_bytes, flops_per_dispatch, bytes_accessed, batch) —
    # bench passes all of these from the shared step-cost accessor.
    "sd15_16": (1_720_000_000, 1.1e13, 4.0e10, 16),
    "sdxl_8": (5_100_000_000, 1.5e13, 6.0e10, 8),
    "flux_single_heavy_21": (11_600_000_000, 3.4e13, 9.0e10, 7),
    "flux_16_int8": (12_300_000_000, 2.4e13, 8.0e10, 4),
    "wan_video": (2_800_000_000, 8.0e12, 2.5e10, 1),
    "smoke": (120_000_000, 6.0e10, 1.2e9, 8),
}

# flux_stream: full 19/38 flux-dev int8 segment profile (19 double blocks
# ~300 MB, 38 single ~160 MB) against the round-5 usable-HBM budget.
FLUX_STREAM_SEG = tuple([300_000_000] * 19 + [160_000_000] * 38)
FLUX_STREAM_BUDGET = int(10.8 * 2**30)


def _plan_rung(rung, n_devices=8, pinned=None):
    w, flops, nbytes, batch = RUNG_GEOMETRY[rung]
    return planner.plan(
        planner.PlanInputs(
            n_devices=n_devices, platform="tpu", device_kind="TPU v5e",
            weights_bytes=w, budget_bytes=V5E_BUDGET, flops=flops,
            bytes_accessed=nbytes, batch=batch, rung=rung,
        ),
        pinned_mode=pinned,
    )


class TestPlanMatrix:
    @pytest.mark.parametrize("rung", sorted(RUNG_GEOMETRY))
    def test_banked_rungs_match_or_beat_hand(self, rung):
        """Acceptance: on every banked rung the planner is at least as good
        as the hand rules by its own predicted score, and for the resident
        rungs it REPRODUCES the hand choice (replicate over the full
        mesh)."""
        d = _plan_rung(rung)
        assert d["plan_wins"], (rung, d["chosen"], d["hand"])
        assert d["chosen"]["predicted_s"] <= d["hand"]["predicted_s"] + 1e-12
        assert d["chosen"]["mode"] == "replicate", (rung, d["chosen"])
        assert d["chosen"]["dp"] == 8 and d["chosen"]["tp"] == 1
        assert not d["divergent"]

    def test_flux_stream_carve_strictly_beats_hand(self):
        """The strict-win acceptance: at the flagship's real byte geometry
        the stream-carve search finds a finer carve whose predicted step
        beats the hand budget-cap carve (smaller fill exposure)."""
        d = planner.plan(
            planner.PlanInputs(
                n_devices=1, platform="tpu", device_kind="TPU v5e",
                weights_bytes=sum(FLUX_STREAM_SEG),
                budget_bytes=FLUX_STREAM_BUDGET,
                segment_bytes=FLUX_STREAM_SEG, batch=4, seq_len=4608,
                head_dim=128, heads=24, rung="flux_stream",
            ),
            pinned_mode="stream",
        )
        assert d["chosen"]["mode"] == "stream"
        assert d["divergent"]
        assert d["chosen"]["predicted_s"] < d["hand"]["predicted_s"]
        assert d["chosen"]["n_stages"] > d["hand"]["n_stages"]

    def test_candidate_table_covers_the_plan_space(self):
        d = _plan_rung("sd15_16")
        modes = {c["mode"] for c in d["candidates"]}
        assert {"replicate", "tp", "fsdp"} <= modes
        tps = {c["tp"] for c in d["candidates"] if c["mode"] == "tp"}
        assert {2, 4, 8} <= tps  # every dp×tp factorization of 8


class TestFeasibilityPruning:
    def test_infeasible_replicate_never_selected(self):
        """Weights past the budget: replicate is enumerated, marked
        infeasible, and never chosen — the search routes to a placement
        that fits (fsdp on a mesh, stream single-chip)."""
        seg = tuple([2_000_000_000] * 8)
        d = planner.plan(planner.PlanInputs(
            n_devices=8, platform="tpu", device_kind="TPU v5e",
            weights_bytes=sum(seg), budget_bytes=int(4 * 2**30),
            segment_bytes=seg, batch=8, rung="oversized",
        ))
        rep = [c for c in d["candidates"] if c["mode"] == "replicate"]
        assert rep and not rep[0]["feasible"]
        assert d["chosen"]["feasible"]
        assert d["chosen"]["mode"] != "replicate"

    def test_stream_carves_respect_double_buffer_budget(self):
        d = planner.plan(planner.PlanInputs(
            n_devices=1, platform="tpu", device_kind="TPU v5e",
            weights_bytes=sum(FLUX_STREAM_SEG),
            budget_bytes=FLUX_STREAM_BUDGET,
            segment_bytes=FLUX_STREAM_SEG, rung="flux_stream",
        ), pinned_mode="stream")
        for c in d["candidates"]:
            if c["feasible"]:
                assert 2 * c["max_stage_bytes"] <= FLUX_STREAM_BUDGET

    def test_no_feasible_candidate_falls_back_to_hand(self):
        """A single oversized segment under a tiny budget: nothing honors
        the bound, so the decision falls back to the hand plan (bounded
        degradation, the carve_stages atomic-unit rule) and says so."""
        d = planner.plan(planner.PlanInputs(
            n_devices=1, platform="tpu", device_kind="TPU v5e",
            weights_bytes=8_000_000_000, budget_bytes=1_000_000_000,
            segment_bytes=(8_000_000_000,), rung="atomic",
        ), pinned_mode="stream")
        assert d["fallback"] == "no-feasible-candidate"
        assert d["chosen"] == d["hand"]


class TestCalibrationFeedback:
    def test_plan_actuals_fit_and_reprice(self, tmp_path, monkeypatch):
        """kind=plan records with actuals fit ``plan:<rung>`` calibration
        scales (utils/roofline.fit_calibration), and the planner applies
        the banked scale to its candidate scores — the sharpening loop."""
        from comfyui_parallelanything_tpu.utils import roofline

        recs = [
            {"schema": "pa-perf-ledger/v1", "kind": "plan",
             "rung": "sd15_16", "platform": "tpu",
             "plan_predicted_raw_s": 0.5, "plan_actual_s": 1.0,
             "plan_flops": 1.1e13}
            for _ in range(3)
        ]
        scales = roofline.fit_calibration(recs)
        key = roofline.calib_key(
            "plan:sd15_16", "tpu", roofline.shape_bucket(1.1e13)
        )
        assert scales[key]["scale"] == pytest.approx(2.0)
        monkeypatch.setenv("PA_LEDGER_DIR", str(tmp_path))
        assert roofline.save_calibration(scales)
        d = _plan_rung("sd15_16")
        assert d["chosen"]["calib_scale"] == pytest.approx(2.0)
        assert d["chosen"]["predicted_s"] == pytest.approx(
            d["chosen"]["predicted_raw_s"] * 2.0
        )

    def test_dryrun_marked_plan_records_never_fit(self):
        from comfyui_parallelanything_tpu.utils import roofline

        recs = [{"schema": "pa-perf-ledger/v1", "kind": "plan",
                 "rung": "r", "platform": "cpu", "dryrun": True,
                 "plan_predicted_raw_s": 0.5, "plan_actual_s": 1.0}]
        assert roofline.fit_calibration(recs) == {}


class TestAttentionAxis:
    def test_planner_attn_matches_trace_time_resolution(self, monkeypatch):
        """The planner's attention decision and the ``attention_local``
        trace-time resolution are one function's answer, on both sides of
        the XLA family's chunk threshold."""
        import importlib

        import jax.numpy as jnp

        att = importlib.import_module(
            "comfyui_parallelanything_tpu.ops.attention"
        )
        q = jnp.zeros((1, 8, 2, 4), jnp.float32)
        for threshold, expect in ((2**27, "xla"), (64, "xla_chunked")):
            monkeypatch.setattr(att, "_CHUNK_THRESHOLD", threshold)
            monkeypatch.setattr(att, "_RESOLVED", set())
            d = planner.plan(planner.PlanInputs(
                n_devices=1, platform="cpu", weights_bytes=10**6, batch=1,
                seq_len=8, head_dim=4, heads=2,
            ))
            assert d["attn"]["backend"] == expect, d["attn"]
            att.attention_local(q, q, q)
            assert att.resolved_backends() == (expect,)

    # The shape rule (PR 25), case by case: the benchmark cells' four UNet
    # self-attention classes, the cross-attention that rides with them, the
    # VAE's and FLUX's lane-aligned heads, and the same shapes off a TPU.
    # (label, on a TPU, batch, seq_q, seq_k, heads, head_dim, backend, blocks)
    ROUTES = [
        ("sd15-self4096", True, 16, 4096, 4096, 8, 40, "pallas", (256, 4096)),
        ("sd15-self1024", True, 16, 1024, 1024, 8, 80, "pallas", (256, 4096)),
        ("sdxl-self4096", True, 2, 4096, 4096, 10, 64, "pallas", (256, 4096)),
        # 2^25.3 logits: measured 0.333 ms fused against 0.301 — stays on XLA.
        ("sdxl-self1024", True, 2, 1024, 1024, 20, 64, "xla", None),
        ("sd15-cross77", True, 16, 4096, 77, 8, 40, "xla", None),
        ("sd15-self256", True, 16, 256, 256, 8, 160, "xla", None),
        ("sd15-1024sq-self16384", True, 4, 16384, 16384, 8, 40, "pallas", (256, 4096)),
        # Head dims that are multiples of 128 (PR 33): one head's row of keys
        # is ONE key block up to 8 MB of K — the VAE decoder's 512-wide head
        # at 512² (4 MB), FLUX-dev, four rows of it, the cell
        # flux-schnell-b1-1024.closed-unique's one class (256 T5 + 4096 image
        # tokens), FLUX at 512², a short row of keys under many queries, WAN's
        # 16,384 and 32,768 tokens (4 and 8 MB) — and streams 4096 keys a
        # block past it: the decoder at 1024² (16 MB), 65,536 tokens.
        ("vae-mid-512wide", True, 8, 4096, 4096, 1, 512, "pallas", (256, 4096)),
        ("flux-joint-128wide", True, 1, 4608, 4608, 24, 128, "pallas", (256, 4608)),
        ("flux-b4", True, 4, 4608, 4608, 24, 128, "pallas", (256, 4608)),
        ("flux-schnell-joint4352", True, 1, 4352, 4352, 24, 128, "pallas", (256, 4352)),
        ("flux-schnell-512sq-joint1280", True, 1, 1280, 1280, 24, 128, "pallas", (256, 1280)),
        ("wan-cross512", True, 1, 16384, 512, 12, 128, "pallas", (256, 512)),
        ("wan-480p-self16384", True, 1, 16384, 16384, 12, 128, "pallas", (256, 16384)),
        ("wan-long-self32768", True, 1, 32768, 32768, 12, 128, "pallas", (256, 32768)),
        ("vae-mid-1024sq", True, 1, 16384, 16384, 1, 512, "pallas", (256, 4096)),
        ("lane-aligned-65536", True, 1, 65536, 65536, 12, 128, "pallas", (256, 4096)),
        # SD3.5-medium's joint attention (77 text + image tokens): a ragged
        # length goes to the kernel padded and masked from 2^25.8 logits up
        # (the row as one key block), else stays with XLA (PR 26).
        ("sd35m-joint4173", True, 2, 4173, 4173, 24, 64, "pallas", (384, 4224)),
        ("sd35m-joint1101", True, 2, 1101, 1101, 24, 64, "pallas", (384, 1152)),
        # under the smallest measured win, and under 1024 keys: XLA
        ("sd35m-joint1101-b1", True, 1, 1101, 1101, 24, 64, "xla", None),
        ("sd35m-joint333", True, 2, 333, 333, 24, 64, "xla", None),
        # a padded row that 384 does not divide keeps 256-query blocks
        ("ragged-1300", True, 4, 1300, 1300, 24, 64, "pallas", (256, 1408)),
        # past one block's keys the row streams 4096 keys a block
        ("ragged-8269", True, 1, 8269, 8269, 24, 64, "pallas", (256, 4096)),
        ("sd35m-self4096", True, 2, 4096, 4096, 24, 64, "pallas", (256, 4096)),
        # Z-Image-Turbo's two classes at 1 x 1024² (PR 34), 30 heads of 128:
        # the main layers' 4096 image + 32 caption tokens are not a
        # 128-multiple, so the ragged row pads them to 4224 (= 11 x 384) as one
        # key block — a row measured at 64-wide heads only; the noise
        # refiner's 4096 image tokens are lane-aligned, one key block. Read
        # and recorded, not retuned.
        ("zimage-joint4128", True, 1, 4128, 4128, 30, 128, "pallas", (384, 4224)),
        ("zimage-refine4096", True, 1, 4096, 4096, 30, 128, "pallas", (256, 4096)),
        ("sd35m-joint4173-cpu", False, 2, 4173, 4173, 24, 64, "xla_chunked", None),
        ("sd15-self4096-cpu", False, 16, 4096, 4096, 8, 40, "xla_chunked", None),
        ("sd15-self1024-cpu", False, 16, 1024, 1024, 8, 80, "xla", None),
        ("flux-joint-cpu", False, 1, 4608, 4608, 24, 128, "xla_chunked", None),
    ]

    @pytest.mark.parametrize(
        "label,tpu,b,sq,sk,h,d,backend,blocks", ROUTES,
        ids=[r[0] for r in ROUTES],
    )
    def test_route_is_read_from_the_shape(self, monkeypatch, label, tpu, b,
                                          sq, sk, h, d, backend, blocks):
        """``route`` names the backend and blocks of every class from the
        call's shape and the backend alone; ``attention_local`` executes
        that answer, counted once a trace: by backend, and a fused call by
        the row of ``route`` that named its blocks and whether its row of
        keys is one key block."""
        import importlib

        import jax
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.utils.metrics import registry

        att = importlib.import_module(
            "comfyui_parallelanything_tpu.ops.attention"
        )
        fa = importlib.import_module(
            "comfyui_parallelanything_tpu.ops.pallas.flash_attention"
        )
        from comfyui_parallelanything_tpu.ops.pallas.tuning import route

        monkeypatch.setattr(att, "_pallas_available", lambda: tpu)
        calls = []
        monkeypatch.setattr(
            fa, "flash_attention",
            lambda q, k, v, **kw: calls.append(
                (kw["block_q"], kw["block_k"])) or q,
        )
        chosen = route(sq, sk, d, b * h, on_tpu=tpu, chunk_threshold=2**27)
        assert chosen[:3] == (backend, *(blocks or (None, None))), chosen
        assert att.resolve_route(sq, sk, d, b * h) == chosen

        def count():
            return registry.get("pa_attention_route_total",
                                {"backend": backend}) or 0.0

        def key_blocks():
            return {
                keys: registry.get("pa_attention_key_blocks_total",
                                   {"rule": chosen.rule, "keys": keys}) or 0.0
                for keys in ("one", "streamed")}

        before, blocks_before = count(), key_blocks()
        q = jax.ShapeDtypeStruct((b, sq, h, d), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((b, sk, h, d), jnp.bfloat16)
        # A fresh function each case: a trace is cached on (function, shapes),
        # and a cached trace resolves — and counts — nothing.
        fn = jax.jit(lambda q, k, v: att.attention_local(q, k, v))
        assert fn.eval_shape(q, kv, kv).shape == q.shape
        assert count() == before + 1
        fn.eval_shape(q, kv, kv)
        assert count() == before + 1  # once a trace, not once a call
        assert calls == ([blocks] if blocks else [])
        if blocks:
            blocks_before["one" if blocks[1] >= sk else "streamed"] += 1
        assert key_blocks() == blocks_before


    def test_flux_schnell_traces_nine_one_block_calls_a_step(self, monkeypatch):
        """The cell flux-schnell-b1-1024.closed-unique's denoiser — published
        widths, the cut's 3 + 6 blocks, 256 text + 4096 image tokens — holds
        nine attention calls, each the lane-aligned row's with the row of keys
        as one key block: what ``pa_attention_key_blocks_total`` reads on
        ``/metrics`` while the step program compiles."""
        import importlib

        import jax
        import jax.numpy as jnp

        from comfyui_parallelanything_tpu.models.flux import (
            FluxModel, flux_abstract_params, flux_schnell_config,
        )
        from comfyui_parallelanything_tpu.utils.metrics import registry

        att = importlib.import_module(
            "comfyui_parallelanything_tpu.ops.attention"
        )
        fa = importlib.import_module(
            "comfyui_parallelanything_tpu.ops.pallas.flash_attention"
        )
        cfg = flux_schnell_config(depth=3, depth_single_blocks=6)
        shape, txt_len = (1, 128, 128, 16), 256
        params = flux_abstract_params(cfg, shape, txt_len)
        monkeypatch.setattr(att, "_pallas_available", lambda: True)
        calls = []
        monkeypatch.setattr(
            fa, "flash_attention",
            lambda q, k, v, **kw: calls.append(
                (q.shape, kw["block_q"], kw["block_k"])) or q,
        )

        def key_blocks():
            return [registry.get("pa_attention_key_blocks_total",
                                 {"rule": "lane-aligned", "keys": keys}) or 0.0
                    for keys in ("one", "streamed")]

        one, streamed = key_blocks()
        out = jax.eval_shape(
            lambda p, x, t, c, y: FluxModel(cfg).apply(
                {"params": p}, x, t, c, y=y),
            params, jax.ShapeDtypeStruct(shape, jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
            jax.ShapeDtypeStruct((1, txt_len, cfg.context_in_dim), jnp.float32),
            jax.ShapeDtypeStruct((1, cfg.vec_in_dim), jnp.float32),
        )
        assert out.shape == shape
        assert calls == [((1, 4352, 24, 128), 256, 4352)] * 9
        assert key_blocks() == [one + 9, streamed]


# ---------------------------------------------------------------------------
# orchestrator integration: enact / shadow / off
# ---------------------------------------------------------------------------


@pytest.fixture
def flux_model():
    import jax
    import jax.numpy as jnp

    from comfyui_parallelanything_tpu.models.flux import FluxConfig, build_flux

    cfg = FluxConfig(
        in_channels=16, hidden_size=64, num_heads=4, depth=2,
        depth_single_blocks=6, context_in_dim=32, vec_in_dim=16,
        axes_dim=(4, 6, 6), guidance_embed=False, dtype=jnp.float32,
    )
    return build_flux(
        cfg, jax.random.key(0), sample_shape=(1, 8, 8, 4), txt_len=16
    )


def _flux_inputs(batch=2):
    import jax.numpy as jnp

    x = jnp.ones((batch, 8, 8, 4), jnp.float32) * 0.1
    t = jnp.linspace(1.0, 0.1, batch)
    ctx = jnp.zeros((batch, 16, 32), jnp.float32)
    y = jnp.zeros((batch, 16), jnp.float32)
    return x, t, ctx, y


class TestOrchestratorIntegration:
    def test_planner_off_routes_identically_and_attaches_no_plan(
        self, flux_model, monkeypatch
    ):
        """PA_PLANNER=0 is the bitwise hand fallback: same routing, same
        outputs, no plan attached."""
        import jax

        from comfyui_parallelanything_tpu import DeviceChain, parallelize

        chain = DeviceChain.even(
            [f"cpu:{d.id}" for d in jax.devices("cpu")[:8]]
        )
        x, t, ctx, y = _flux_inputs(16)
        monkeypatch.setenv("PA_PLANNER", "1")
        pm_on = parallelize(flux_model, chain)
        out_on = np.asarray(pm_on(x, t, ctx, y=y))
        assert pm_on.plan is not None
        assert pm_on.plan["chosen"]["mode"] == "replicate"
        monkeypatch.setenv("PA_PLANNER", "0")
        pm_off = parallelize(flux_model, chain)
        out_off = np.asarray(pm_off(x, t, ctx, y=y))
        assert pm_off.plan is None
        assert (out_on == out_off).all(), (
            "planner-on replicate routing must be bitwise-identical to the "
            "hand ladder"
        )

    def test_weights_dont_fit_plans_stream_with_enacted_carve(
        self, flux_model, monkeypatch
    ):
        from comfyui_parallelanything_tpu import (
            DeviceChain,
            ParallelConfig,
            parallelize,
        )
        from comfyui_parallelanything_tpu.models.loader import params_nbytes

        monkeypatch.setenv("PA_PLANNER", "1")
        budget = params_nbytes(flux_model.params) // 3
        pm = parallelize(
            flux_model, DeviceChain.even(["cpu:0"]),
            ParallelConfig(hbm_budget_bytes=budget),
        )
        assert pm.is_streaming
        assert pm.plan["chosen"]["mode"] == "stream"
        x, t, ctx, y = _flux_inputs(1)
        pm(x, t, ctx, y=y)
        runner = pm._stream_runner
        assert runner.n_stages >= 2
        # The enacted carve is never COARSER than the hand budget-cap carve
        # (a divergent planned carve only ever refines; the toy model's
        # atomic block segments may individually exceed the cap — the same
        # carve_stages degradation the hand path has).
        monkeypatch.setenv("PA_PLANNER", "0")
        pm_hand = parallelize(
            flux_model, DeviceChain.even(["cpu:0"]),
            ParallelConfig(hbm_budget_bytes=budget),
        )
        pm_hand(x, t, ctx, y=y)
        assert runner.n_stages >= pm_hand._stream_runner.n_stages
        assert (
            runner.max_stage_nbytes <= pm_hand._stream_runner.max_stage_nbytes
        )

    def test_shadow_mode_records_without_enacting(
        self, flux_model, monkeypatch
    ):
        from comfyui_parallelanything_tpu import (
            DeviceChain,
            ParallelConfig,
            parallelize,
        )
        from comfyui_parallelanything_tpu.models.loader import params_nbytes

        budget = params_nbytes(flux_model.params) // 3
        monkeypatch.setenv("PA_PLANNER", "0")
        pm_hand = parallelize(
            flux_model, DeviceChain.even(["cpu:0"]),
            ParallelConfig(weight_sharding="stream", hbm_budget_bytes=budget),
        )
        hand_stages = pm_hand._get_streaming_runner().n_stages
        monkeypatch.setenv("PA_PLANNER", "shadow")
        pm = parallelize(
            flux_model, DeviceChain.even(["cpu:0"]),
            ParallelConfig(weight_sharding="stream", hbm_budget_bytes=budget),
        )
        assert pm.plan is not None and pm.plan["mode_flag"] == "shadow"
        # Shadow never touches the carve: identical to the hand build.
        assert pm.config.stream_stages is None
        assert pm._get_streaming_runner().n_stages == hand_stages

    def test_pipeline_carve_is_byte_balanced_and_equivalent(
        self, flux_model, monkeypatch
    ):
        """batch==1 block placement under the planner: the planned ranges
        are byte-balanced (pm.plan['pipeline']), the runner uses them, and
        the output matches the hand weight-proportional carve (placement
        moves no math)."""
        import jax

        from comfyui_parallelanything_tpu import DeviceChain, parallelize

        chain = DeviceChain.even(
            [f"cpu:{d.id}" for d in jax.devices("cpu")[:4]]
        )
        x, t, ctx, y = _flux_inputs(1)
        monkeypatch.setenv("PA_PLANNER", "0")
        pm_hand = parallelize(flux_model, chain)
        want = np.asarray(pm_hand(x, t, ctx, y=y))
        monkeypatch.setenv("PA_PLANNER", "1")
        pm = parallelize(flux_model, chain)
        got = np.asarray(pm(x, t, ctx, y=y))
        pipe = pm.plan.get("pipeline")
        assert pipe is not None
        assert pipe["max_stage_bytes"] <= pipe["hand_max_stage_bytes"]
        runner = pm._pipeline_runner
        assert runner is not None and runner.n_stages >= 2
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=1e-4)

    def test_pipeline_carve_not_enacted_in_shadow_mode(
        self, flux_model, monkeypatch
    ):
        """Shadow mode records the pipeline-carve axis but must ENACT the
        hand weight-proportional carve — stage placement bitwise-identical
        to PA_PLANNER=0 (the rollout contract)."""
        import jax

        from comfyui_parallelanything_tpu import DeviceChain, parallelize

        chain = DeviceChain.even(
            [f"cpu:{d.id}" for d in jax.devices("cpu")[:4]]
        )
        x, t, ctx, y = _flux_inputs(1)
        monkeypatch.setenv("PA_PLANNER", "0")
        pm_off = parallelize(flux_model, chain)
        pm_off(x, t, ctx, y=y)
        off_stages = [s.labels for s in pm_off._pipeline_runner.stages]
        monkeypatch.setenv("PA_PLANNER", "shadow")
        pm_sh = parallelize(flux_model, chain)
        pm_sh(x, t, ctx, y=y)
        assert pm_sh.plan is not None
        assert pm_sh.plan["mode_flag"] == "shadow"
        sh_stages = [s.labels for s in pm_sh._pipeline_runner.stages]
        assert sh_stages == off_stages

    def test_ledger_record_drops_actual_for_shadow_divergence(
        self, monkeypatch
    ):
        """A shadow-mode DIVERGENT decision's chosen plan never ran: the
        measured actual (which belongs to the enacted hand plan) must not
        bank against the chosen plan's prediction — it would poison the
        plan:<rung> calibration fit."""
        monkeypatch.setenv("PA_PLANNER", "shadow")
        d = planner.plan(
            planner.PlanInputs(
                n_devices=1, platform="tpu", device_kind="TPU v5e",
                weights_bytes=sum(FLUX_STREAM_SEG),
                budget_bytes=FLUX_STREAM_BUDGET,
                segment_bytes=FLUX_STREAM_SEG, batch=4, seq_len=4608,
                rung="flux_stream",
            ),
            pinned_mode="stream",
        )
        assert d["divergent"] and d["mode_flag"] == "shadow"
        rec = planner.ledger_record(d, actual_s=1.0)
        assert rec["plan_actual_s"] is None and rec["plan_ratio"] is None
        # Enacted decisions keep their actuals.
        monkeypatch.setenv("PA_PLANNER", "1")
        d_on = planner.plan(
            planner.PlanInputs(
                n_devices=1, platform="tpu", device_kind="TPU v5e",
                weights_bytes=sum(FLUX_STREAM_SEG),
                budget_bytes=FLUX_STREAM_BUDGET,
                segment_bytes=FLUX_STREAM_SEG, batch=4, seq_len=4608,
                rung="flux_stream",
            ),
            pinned_mode="stream",
        )
        rec_on = planner.ledger_record(d_on, actual_s=1.0)
        assert rec_on["plan_actual_s"] == 1.0

    def test_explicit_fsdp_and_tp_are_never_overridden(
        self, flux_model, monkeypatch
    ):
        import jax

        from comfyui_parallelanything_tpu import (
            DeviceChain,
            ParallelConfig,
            parallelize,
        )

        monkeypatch.setenv("PA_PLANNER", "1")
        chain = DeviceChain.even(
            [f"cpu:{d.id}" for d in jax.devices("cpu")[:8]]
        )
        pm = parallelize(
            flux_model, chain, ParallelConfig(weight_sharding="fsdp")
        )
        assert pm.plan is None  # pinned decision: the planner stays out
        assert pm.config.weight_sharding == "fsdp"
        pm_tp = parallelize(
            flux_model, chain, ParallelConfig(tensor_parallel=2)
        )
        assert pm_tp.plan is None
        assert pm_tp.config.tensor_parallel == 2

    def test_streaming_runner_rejects_carve_past_the_cap(self, flux_model):
        """build_streaming_runner composition rule: an explicit n_stages
        whose balanced carve would blow the 2-buffer byte cap falls back to
        the cap carve."""
        import jax

        from comfyui_parallelanything_tpu.models.loader import params_nbytes
        from comfyui_parallelanything_tpu.parallel.streaming import (
            build_streaming_runner,
        )

        budget = params_nbytes(flux_model.params) // 3
        dev = jax.devices("cpu")[0]
        capped = build_streaming_runner(
            flux_model.pipeline_spec, flux_model.params, dev,
            hbm_budget_bytes=budget,
        )
        # n_stages=2 → stages of ~half the pytree each, far past the cap of
        # budget*2/5 = ~2/15 of the pytree: the cap carve must win.
        planned = build_streaming_runner(
            flux_model.pipeline_spec, flux_model.params, dev,
            hbm_budget_bytes=budget, n_stages=2,
        )
        assert planned.n_stages == capped.n_stages
        assert planned.max_stage_nbytes == capped.max_stage_nbytes


class TestSurfaces:
    def test_health_plan_section_and_gauges(self, monkeypatch):
        from comfyui_parallelanything_tpu.utils.metrics import registry
        from comfyui_parallelanything_tpu.utils.telemetry import (
            health_snapshot,
        )

        monkeypatch.setenv("PA_PLANNER", "1")
        before = registry.get("pa_planner_decisions_total") or 0
        d = _plan_rung("sd15_16")
        snap = health_snapshot().get("plan")
        assert snap is not None and snap["mode"] == "on"
        assert snap["decisions"] >= 1
        assert snap["last"]["chosen"]["mode"] == d["chosen"]["mode"]
        assert (registry.get("pa_planner_decisions_total") or 0) > before
        assert registry.get("pa_planner_hand_predicted_s") is not None

    def test_ledger_record_and_summary_shape(self):
        d = _plan_rung("sd15_16")
        rec = planner.ledger_record(d, actual_s=0.02)
        assert rec["rung"] == "sd15_16" and rec["plan_mode"] == "replicate"
        assert rec["plan_actual_s"] == 0.02
        assert rec["plan_ratio"] == pytest.approx(
            d["chosen"]["predicted_s"] / 0.02, rel=1e-3
        )
        assert rec["plan_wins"] and isinstance(rec["plan_candidates"], list)
        summary = planner.plan_summary(d)
        assert summary["chosen"]["mode"] == "replicate"
        assert summary["source"] == "planner"
        assert planner.plan_summary(None) is None


class TestPlanReportGate:
    def _run(self, tmp_path, records, check=True):
        ledger = tmp_path / "ledger"
        ledger.mkdir(exist_ok=True)
        with open(ledger / "perf_ledger.jsonl", "w") as f:
            for rec in records:
                f.write(json.dumps(rec) + "\n")
        env = dict(os.environ)
        env["PA_LEDGER_DIR"] = str(ledger)
        return subprocess.run(
            [sys.executable, os.path.join(_REPO, "scripts", "plan_report.py")]
            + (["--check"] if check else []),
            env=env, capture_output=True, text=True, timeout=60,
        )

    def _rec(self, **kw):
        base = {
            "schema": "pa-perf-ledger/v1", "kind": "plan", "rung": "r",
            "platform": "cpu", "plan_mode": "replicate", "plan_dp": 8,
            "plan_tp": 1, "plan_predicted_s": 0.01,
            "plan_predicted_raw_s": 0.01, "plan_hand_mode": "replicate",
            "plan_hand_predicted_s": 0.01, "plan_actual_s": 0.02,
        }
        base.update(kw)
        return base

    def test_skip_on_plan_free_ledger(self, tmp_path):
        proc = self._run(tmp_path, [{"schema": "pa-perf-ledger/v1",
                                     "kind": "bench", "rung": "smoke"}])
        assert proc.returncode == 0 and "SKIP" in proc.stdout

    def test_green_on_match_or_beat(self, tmp_path):
        proc = self._run(tmp_path, [self._rec()])
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_fails_when_plan_loses_to_hand(self, tmp_path):
        proc = self._run(tmp_path, [self._rec(
            plan_predicted_s=0.02, plan_hand_predicted_s=0.01,
            plan_actual_s=None,
        )])
        assert proc.returncode == 1 and "WORSE" in proc.stdout

    def test_fails_on_out_of_band_ratio(self, tmp_path):
        proc = self._run(tmp_path, [self._rec(
            plan_predicted_s=0.05, plan_hand_predicted_s=0.05,
            plan_actual_s=0.01,
        )])
        assert proc.returncode == 1 and "ratio" in proc.stdout

    def test_latest_record_wins(self, tmp_path):
        bad = self._rec(plan_predicted_s=0.02, plan_hand_predicted_s=0.01,
                        plan_actual_s=None)
        good = self._rec()
        proc = self._run(tmp_path, [bad, good])
        assert proc.returncode == 0, proc.stdout


def test_carve_ranges_pure_arithmetic():
    """loader.carve_ranges (the factored carve the planner shares with the
    streaming executor): byte-cap packing, count balancing, oversized
    atomic segments."""
    from comfyui_parallelanything_tpu.models.loader import carve_ranges

    sizes = [4, 4, 4, 4]
    assert carve_ranges(sizes, max_stage_bytes=8) == [(0, 2), (2, 4)]
    assert carve_ranges(sizes, n_stages=4) == [
        (0, 1), (1, 2), (2, 3), (3, 4)
    ]
    # A lone oversized segment stays an atomic stage.
    assert carve_ranges([100, 1, 1], max_stage_bytes=2) == [(0, 1), (1, 3)]
    assert carve_ranges([5], n_stages=3) == [(0, 1)]
