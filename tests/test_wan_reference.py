"""Wan2.2-T2V-A14B against its plain reference at tiny widths on the CPU, and
the pieces the configuration forced: two experts of one family loaded at the
depth their files have, resident in bfloat16 behind one compiled step program;
a LoRA baked into 16-bit resident kernels tensor by tensor; the UMT5 tower on
the ``wan`` wire; the video decoder as published (the temporal up-sampler's
first-frame rule) and bounded in time; two ``KSamplerAdvanced`` windows on a
flow table with leftover noise; the loader's residency rule; the spans and
counters that tell all of it apart.

The reference (``benchmark/yardstick/reference_wan.py``) is the benchmark's;
``benchmark/tests`` walks the whole command with it, these tests hold the
program to it inside tier-1."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "benchmark")
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import run  # noqa: E402 — the benchmark's own file loading and preset swap
from yardstick import (reference_sd, reference_wan, safetensors_io,  # noqa: E402
                       synth, traffic)

CELL = "wan22-t2v-a14b-tiny.closed"


def _twin(tmp_path, monkeypatch, dtype, seed=11):
    """The tiny twin's six files and tokenizer tables from a seed under
    ``tmp_path`` and the program's presets swapped for the twin's sizes →
    (cell, what a reference is built from, its keywords)."""
    cell = run.load_cell(CELL)
    config = cell["config_data"]
    run.apply_program_presets(config, monkeypatch.setattr, dtype)
    ref_args, ref_kw, env, _ = run.synthesize(config, str(tmp_path), seed)
    for k, v in {**env, "PA_TOKENIZER_JSON": ""}.items():
        monkeypatch.setenv(k, v)
    return cell, ref_args, ref_kw


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    return _twin(tmp_path, monkeypatch, jnp.float32)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want - want.mean()))


def _file(cell, ref_kw, index):
    return ref_kw["files"][synth.checkpoint_files(cell["config_data"])[index]["file"]]


def _counted(name, **labels):
    from comfyui_parallelanything_tpu.utils.metrics import registry

    return registry.get(name, labels) or 0.0


@pytest.fixture
def fresh_residency(monkeypatch):
    """The loader's rule with no budget and no history, so that one test's
    models never meet another's."""
    from comfyui_parallelanything_tpu.models import loader

    rule = loader.Residency(budget_bytes=0)
    monkeypatch.setattr(loader, "residency", rule)
    return rule


# -- the denoiser ---------------------------------------------------------------


@pytest.mark.parametrize("expert", [0, 1], ids=["high_noise", "low_noise"])
def test_tiny_wan_forward_of_each_expert_equals_the_reference_in_float32(tiny, expert):
    """models/wan.py at 2 blocks a file (the preset says 30: the depth is read
    off the file), full-width q/k RMS norms, a three-axis rotary of 8 / 4 / 4
    (16-wide heads), on a non-square clip, against ``reference_wan.wan``
    written from the published description. Both compute in float32 (conftest
    pins ``highest``); what is left is the order of the sums."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.models import load_wan_checkpoint

    cell, _, ref_kw = tiny
    m = cell["config_data"]["wan"]
    path = _file(cell, ref_kw, expert)
    model = load_wan_checkpoint(path, models.wan_1_3b_config())
    assert model.config.depth == m["num_layers"] == 2
    assert model.config.axes_dim == (8, 4, 4)
    keys = jax.random.split(jax.random.key(3 + expert), 2)
    x = jax.random.normal(keys[0], (1, 3, 4, 8, 16), jnp.float32)  # NTHWC
    context = jax.random.normal(keys[1], (1, 40, m["text_dim"]), jnp.float32)
    t = jnp.asarray([0.875], jnp.float32)
    got = model.apply(model.params, x, t, context)
    w = reference_sd.load_weights(safetensors_io.read(path))
    want = reference_wan.wan("float32", w, m, jnp.transpose(x, (0, 4, 1, 2, 3)), t, context)
    want = jnp.transpose(want, (0, 2, 3, 4, 1))
    assert got.shape == want.shape == x.shape
    assert _rel(got, want) < 1e-4, _rel(got, want)


def test_two_experts_of_one_family_share_one_step_program(tiny):
    """Two files, two parameter sets, two names for the spans and counters —
    and ONE module and ``apply`` function, so what jax traces and compiles for
    the first expert serves the second."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.models import load_wan_checkpoint

    cell, _, ref_kw = tiny
    high = load_wan_checkpoint(_file(cell, ref_kw, 0), models.wan_1_3b_config(), name="high")
    low = load_wan_checkpoint(_file(cell, ref_kw, 1), models.wan_1_3b_config(), name="low")
    assert high.apply is low.apply and high.config == low.config
    assert high.params is not low.params
    a, b = (jax.tree.leaves(m.params["blocks_0"]["ffn_in"])[0] for m in (high, low))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_a_wan_file_is_resident_in_the_type_it_stores(tmp_path, monkeypatch):
    """Under bfloat16 compute every matmul kernel of a bfloat16 file stays
    bfloat16 on the chip — the time path's and the head's too, widened where
    they are used — and norm scales, biases and modulation tables are float32:
    an expert is never whole in float32."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.models import load_wan_checkpoint

    cell, _, ref_kw = _twin(tmp_path, monkeypatch, None)
    model = load_wan_checkpoint(_file(cell, ref_kw, 0), models.wan_1_3b_config())
    p = model.params
    assert model.config.dtype == jnp.bfloat16
    for key in ("patch_embedding", "text_in", "time_in", "time_projection", "head_proj"):
        assert p[key]["kernel"].dtype == jnp.bfloat16, key
        assert p[key]["bias"].dtype == jnp.float32, key
    blk = p["blocks_1"]
    assert {blk[k]["kernel"].dtype for k in ("self_q", "cross_v", "ffn_in", "ffn_out")} \
        == {jnp.dtype(jnp.bfloat16)}
    assert blk["modulation"].dtype == blk["self_q_norm"]["scale"].dtype == jnp.float32
    by_type = {}
    for leaf in jax.tree.leaves(p):
        by_type[str(leaf.dtype)] = by_type.get(str(leaf.dtype), 0) + leaf.size
    assert by_type["bfloat16"] > 30 * by_type["float32"]


# -- LoRA on a 16-bit resident expert -------------------------------------------------


def test_a_lora_is_baked_into_bfloat16_resident_kernels_and_rounded_once(
        tmp_path, monkeypatch):
    """``W + strength · (alpha / r) · up @ down`` on the ten linears of every
    block, the sum in float32 and the result rounded ONCE to the resident
    bfloat16 — the same sum made here by hand, to the last bit but for an
    element in a thousand; every
    other tensor is the unpatched expert's own, and nothing is unmatched."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.models import load_wan_checkpoint

    cell, _, ref_kw = _twin(tmp_path, monkeypatch, None)
    path, lora_path = _file(cell, ref_kw, 0), _file(cell, ref_kw, 2)
    base = load_wan_checkpoint(path, models.wan_1_3b_config())
    baked = load_wan_checkpoint(path, models.wan_1_3b_config(), lora=lora_path,
                                lora_strength=0.75)
    w, lora = safetensors_io.read(path), safetensors_io.read(lora_path)
    names = {"self_attn.q": "self_q", "cross_attn.o": "cross_o", "ffn.0": "ffn_in",
             "ffn.2": "ffn_out"}
    for torch_name, ours in names.items():
        key = f"blocks.1.{torch_name}"
        up = np.asarray(lora[f"diffusion_model.{key}.lora_up.weight"], np.float32)
        down = np.asarray(lora[f"diffusion_model.{key}.lora_down.weight"], np.float32)
        alpha = float(np.asarray(lora[f"diffusion_model.{key}.alpha"], np.float32))
        want = np.asarray(w[f"{key}.weight"], np.float32) \
            + 0.75 * (alpha / down.shape[0]) * (up @ down)
        want = jnp.asarray(want).astype(jnp.bfloat16).T
        got = baked.params["blocks_1"][ours]["kernel"]
        assert got.dtype == jnp.bfloat16
        # the same sum in another order of additions: equal, but for a rare
        # element within a float32 rounding of a bfloat16 boundary (one ulp)
        got32, want32 = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert (got32 != want32).mean() < 1e-3, key
        assert np.abs(got32 - want32).max() <= np.abs(want32).max() * 2.0 ** -7, key
        assert not np.array_equal(np.asarray(got, np.float32), np.asarray(
            base.params["blocks_1"][ours]["kernel"], np.float32))
    for ours in ("self_q_norm", "norm3"):
        assert np.array_equal(np.asarray(baked.params["blocks_1"][ours]["scale"]),
                              np.asarray(base.params["blocks_1"][ours]["scale"]))
    assert np.array_equal(np.asarray(baked.params["time_projection"]["kernel"], np.float32),
                          np.asarray(base.params["time_projection"]["kernel"], np.float32))


def test_bake_lora_touches_only_what_the_lora_names_and_only_when_taken():
    """``convert.bake_lora`` returns the base's own tensors (stored type and
    all) for every key the LoRA does not name, and computes a named one when
    it is taken: float32 on the default device, from whatever type the base
    stores."""
    import ml_dtypes

    from comfyui_parallelanything_tpu.models.convert import bake_lora

    rng = np.random.default_rng(0)
    sd = {"blocks.0.ffn.0.weight": rng.standard_normal((8, 4)).astype(ml_dtypes.bfloat16),
          "blocks.0.ffn.0.bias": rng.standard_normal(8).astype(np.float32),
          "head.head.weight": rng.standard_normal((4, 8)).astype(ml_dtypes.bfloat16)}
    lora = {"diffusion_model.blocks.0.ffn.0.lora_down.weight":
            rng.standard_normal((2, 4)).astype(np.float32),
            "diffusion_model.blocks.0.ffn.0.lora_up.weight":
            rng.standard_normal((8, 2)).astype(np.float32),
            "diffusion_model.blocks.0.ffn.0.alpha": np.asarray(1.0, np.float32)}
    out = bake_lora(sd, lora, 2.0)
    assert set(out) == set(sd) and len(out) == 3
    assert out["head.head.weight"] is sd["head.head.weight"]
    assert out["blocks.0.ffn.0.bias"] is sd["blocks.0.ffn.0.bias"]
    got = out["blocks.0.ffn.0.weight"]
    want = sd["blocks.0.ffn.0.weight"].astype(np.float32) + 2.0 * 0.5 * (
        lora["diffusion_model.blocks.0.ffn.0.lora_up.weight"]
        @ lora["diffusion_model.blocks.0.ffn.0.lora_down.weight"])
    assert got.dtype == jnp.float32 and np.allclose(np.asarray(got), want, rtol=1e-6)


# -- the tower on the wan wire ----------------------------------------------------------


def test_the_umt5_states_on_the_wan_wire_equal_the_reference(tiny, fresh_residency):
    """``CLIPLoader type=wan`` + ``CLIPTextEncode``: 512 rows, the tower's own
    mask inside (padded keys take no part), a position table in EVERY block,
    and zeros after the valid tokens on the way out — against
    ``reference_t5.encode`` with ``per_layer_bias`` and the same zeroing. The
    ``text-encode`` span names the tower and the VALID token count."""
    from comfyui_parallelanything_tpu import nodes_compat
    from comfyui_parallelanything_tpu.nodes import TPUTextEncode
    from comfyui_parallelanything_tpu.utils import tracing

    cell, ref_args, ref_kw = tiny
    config = cell["config_data"]
    name = os.path.basename(_file(cell, ref_kw, 4))
    (clip,) = nodes_compat.CLIPLoader().load(name, "wan")
    assert clip["type"] == "umt5" and clip["encoder"].cfg.per_layer_bias
    text = "harbor lantern meadow granite"
    was_on = tracing.on()
    tracing.enable()
    try:
        (cond,) = TPUTextEncode().encode(clip, text)
        spans = [e for e in tracing.export()["traceEvents"]
                 if e.get("name") == "text-encode" and e["args"].get("tower") == "umt5"]
    finally:
        if not was_on:
            tracing.disable()
    ref = reference_wan.Reference(config, *ref_args, "float32", **ref_kw)
    ids = np.stack([ref_kw["tokenizers"]["t5"].ids(text)])
    valid = int((ids != 0).sum())
    want = np.asarray(ref.text_states(ids, name))
    got = np.asarray(cond["context"])
    assert got.shape == want.shape == (1, 512, config["text_t5"]["d_model"])
    assert 1 < valid < 64
    assert not got[0, valid:].any() and got[0, :valid].any(axis=-1).all()
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 2e-5
    assert cond["pooled"] is None
    assert spans and spans[-1]["args"]["tokens"] == valid
    assert spans[-1]["args"]["cache"] == "miss"


# -- the decoder ---------------------------------------------------------------------


def _vae_pair(cell, ref_kw):
    from comfyui_parallelanything_tpu.models.loader import load_wan_vae_checkpoint

    path = _file(cell, ref_kw, 5)
    return (load_wan_vae_checkpoint(path),
            reference_sd.load_weights(safetensors_io.read(path)))


@pytest.mark.parametrize("latent_frames", [1, 2, 3], ids=["1_frame", "5_frames", "9_frames"])
def test_the_video_decoder_equals_the_reference(tiny, fresh_residency, latent_frames):
    """models/video_vae.py (the time-bounded program) against the decoder
    written from the published description, which walks ONE frame at a time
    with two carried frames a convolution: at 1 frame the image case, at 5 and
    9 the temporal up-samplers' first-frame rule decides every frame (the
    first passes as it is; ``time_conv`` sees zeros, not the first frame, in
    front of the second). The reference clamps to [−1, 1] as published; the
    program clamps where it maps to [0, 1]."""
    cell, _, ref_kw = tiny
    vae, w = _vae_pair(cell, ref_kw)
    z = jax.random.normal(jax.random.key(latent_frames), (1, latent_frames, 4, 8, 16),
                          jnp.float32)
    got = jnp.clip(vae.decode(z), -1.0, 1.0)
    want = reference_wan.wan_vae_decode("float32", w, cell["config_data"]["vae"],
                                        jnp.transpose(z, (0, 4, 1, 2, 3)))
    frames = 4 * (latent_frames - 1) + 1
    assert got.shape == (1, frames, 32, 64, 3) and want.shape == (frames, 3, 32, 64)
    for f in range(frames):
        gap = _rel(jnp.transpose(got[0, f], (2, 0, 1)), want[f])
        assert gap < 1e-4, (f, gap)


@pytest.mark.parametrize("latent_frames", [1, 2, 3], ids=["1_frame", "5_frames", "9_frames"])
def test_the_time_bounded_decode_equals_the_whole_clip_program(tiny, fresh_residency,
                                                               latent_frames):
    """``VideoVAE.decode`` (the first latent frame, then a scan over the
    others with every causal convolution's last two input frames as the
    carry) against ``VideoAutoencoderKL.decode`` on the whole clip with zeros
    padded in front: the same arithmetic."""
    from comfyui_parallelanything_tpu.models.video_vae import VideoAutoencoderKL

    cell, _, ref_kw = tiny
    vae, _ = _vae_pair(cell, ref_kw)
    z = jax.random.normal(jax.random.key(7 + latent_frames),
                          (2, latent_frames, 4, 8, 16), jnp.float32)
    whole = VideoAutoencoderKL(vae.cfg).apply(
        {"params": vae.params}, z, method=VideoAutoencoderKL.decode)
    got = vae.decode(z)
    assert got.shape == whole.shape == (2, 4 * (latent_frames - 1) + 1, 32, 64, 3)
    assert float(jnp.abs(got - whole).max()) < 1e-4 * float(jnp.abs(whole).max())


def test_the_first_frame_rule_is_not_a_plain_causal_convolution(tiny, fresh_residency):
    """What the decoder did before: ``time_conv`` over EVERY frame with the
    first as history, one of the first frame's two outputs dropped. Under the
    published rule the clip's first pixel frame does not depend on
    ``time_conv`` at all, and equals the one-frame clip's image."""
    cell, _, ref_kw = tiny
    vae, _ = _vae_pair(cell, ref_kw)
    z = jax.random.normal(jax.random.key(2), (1, 3, 4, 8, 16), jnp.float32)
    clip, image = vae.decode(z), vae.decode(z[:, :1])
    assert float(jnp.abs(clip[:, :1] - image).max()) < 1e-5
    broken = jax.tree.map(lambda a: a, vae.params)
    for level in ("up_3_upsample", "up_2_upsample"):
        k = broken["decoder"][level]["time_conv"]["conv"]
        k["kernel"], k["bias"] = k["kernel"] * 0.0, k["bias"] * 0.0
    other = dataclasses.replace(vae, params=broken).decode(z)
    assert float(jnp.abs(other[:, :1] - image).max()) < 1e-5
    assert float(jnp.abs(other[:, 1:] - clip[:, 1:]).max()) > 1e-2


def test_the_video_decode_is_counted_once_a_trace(tiny, fresh_residency):
    cell, _, ref_kw = tiny
    vae, _ = _vae_pair(cell, ref_kw)
    before = _counted("pa_video_decode_total", frames="5", form="scan")
    z = jnp.zeros((1, 2, 4, 8, 16), jnp.float32)
    vae.decode(z)
    vae.decode(z + 1.0)  # the same program: traced once
    assert _counted("pa_video_decode_total", frames="5", form="scan") == before + 1


# -- the whole graph ---------------------------------------------------------------------


def _graph(cell, seed=5, index=0):
    sched = traffic.Schedule(cell["mix"], seed, 10)
    return traffic.fill_graph(cell["template"], cell["mix"], sched.request(index))


def test_the_whole_tiny_graph_through_the_host_equals_the_reference(
        tiny, tmp_path, fresh_residency):
    """ComfyUI's Wan2.2 two-expert graph through ``run_workflow``: two
    ``UNETLoader`` + ``LoraLoaderModelOnly`` + ``ModelSamplingSD3`` chains,
    ``CLIPLoader type=wan``, ``EmptyHunyuanLatentVideo``, two
    ``KSamplerAdvanced`` (steps 0–2 with noise and leftover noise on the
    high-noise expert, 2–4 without on the low-noise one: sigmas 1, 0.9375,
    0.8333, 0.625, 0 at shift 5), the video decode, 9 PNG files — against the
    reference's four Euler steps with the expert changed at step 2. The second
    run continues the first's state exactly: what it receives is that state
    over 1 − σ₂, as stock hands it on."""
    import comfyui_parallelanything_tpu as pa
    from comfyui_parallelanything_tpu.utils import tracing
    from yardstick import client

    cell, ref_args, ref_kw = tiny
    config = cell["config_data"]
    graph = _graph(cell)
    req = reference_wan.describe(graph)
    assert (req["steps"], req["switch_step"], req["shift"], req["frames"]) == (4, 2, 5.0, 9)
    assert req["lora_strengths"] == [1.0, 1.0]
    calls = {k: _counted("pa_denoiser_calls_total", program=f"model-apply:{k}") for k in (
        "wan2.2_t2v_high_noise_14B_bf16+lora", "wan2.2_t2v_low_noise_14B_bf16+lora")}
    was_on = tracing.on()
    tracing.enable()
    try:
        res = pa.run_workflow(graph)
        events = [e for e in tracing.export()["traceEvents"] if e.get("ph") == "X"]
    finally:
        if not was_on:
            tracing.disable()
    ref = reference_wan.Reference(config, *ref_args, "float32", **ref_kw)
    want_latent = ref.latent(req)
    final = jnp.transpose(res["58"][0]["samples"], (0, 4, 1, 2, 3))
    assert final.shape == want_latent.shape == (1, 16, 3, 4, 8)
    assert _rel(final, want_latent) < 5e-3, _rel(final, want_latent)
    # the first window's output is its state at sigma_2 over (1 - sigma_2)
    sigmas = reference_wan.simple_sigmas(4, 5.0)
    assert abs(sigmas[2] - 5 / 6) < 1e-6
    handed = np.asarray(res["57"][0]["samples"]) * (1.0 - sigmas[2])
    w = ref.expert(req["experts"][1])
    ids = np.stack([ref_kw["tokenizers"]["t5"].ids(req["positive"])])
    context = ref.text_states(ids, req["clip_name"])

    def velocity(x, sigma):
        return reference_wan.wan("float32", w, config["wan"], x,
                                 jnp.full((1,), sigma, jnp.float32), context)

    continued = reference_wan.sample_euler(
        velocity, jnp.transpose(jnp.asarray(handed), (0, 4, 1, 2, 3)), sigmas[2:])
    assert _rel(final, continued) < 1e-3, _rel(final, continued)
    # the frames: 9 PNG files of 32 x 64, against the reference's float images
    paths = res["9"][0]
    assert len(paths) == 9
    rows = [0, 4, 8]
    with open(paths[0], "rb") as f:
        assert client.decode_png(f.read()).shape == (32, 64, 3)
    served = np.stack([client.decode_png(open(paths[k], "rb").read()) for k in rows])
    want = ref.images(req, rows)
    assert served.shape == want.shape == (3, 32, 64, 3)
    assert _rel(served.astype(np.float32) / 255.0, want) < 2e-2
    # 2 + 2 step spans and 2 + 2 denoise spans, each pair under its expert's name
    steps = [e for e in events if e["name"] == "step"]
    denoise = [e["args"]["program"] for e in events if e["name"] == "denoise"]
    assert len(steps) == 4
    assert denoise == ["model-apply:wan2.2_t2v_high_noise_14B_bf16+lora"] * 2 \
        + ["model-apply:wan2.2_t2v_low_noise_14B_bf16+lora"] * 2
    for k, n in calls.items():
        assert _counted("pa_denoiser_calls_total", program=f"model-apply:{k}") == n + 2
    png = [e for e in events if e["name"] == "png-encode"]
    assert len(png) == 1


def test_ksampler_advanced_hands_leftover_noise_on_as_stock_does(tiny, fresh_residency):
    """On a flow model a window that ends above σ = 0 with
    ``return_with_leftover_noise`` returns its state over (1 − σ_end); the
    next window, its noise disabled, multiplies it back. Two windows equal one
    run of all four steps."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.models import load_wan_checkpoint
    from comfyui_parallelanything_tpu.nodes import TPUKSamplerAdvanced

    cell, _, ref_kw = tiny
    model = load_wan_checkpoint(_file(cell, ref_kw, 0), models.wan_1_3b_config())
    model.sampler_prefs = {"shift": 5.0}
    ctx = {"context": jax.random.normal(jax.random.key(1), (1, 8, 32), jnp.float32),
           "pooled": None}
    latent = {"samples": jnp.zeros((1, 2, 4, 4, 16), jnp.float32)}
    common = dict(noise_seed=9, steps=4, cfg=1.0, sampler_name="euler", scheduler="simple",
                  positive=ctx, negative=ctx)
    node = TPUKSamplerAdvanced()
    (whole,) = node.sample(model, "enable", latent_image=latent, start_at_step=0,
                           end_at_step=4, return_with_leftover_noise="disable", **common)
    (first,) = node.sample(model, "enable", latent_image=latent, start_at_step=0,
                           end_at_step=2, return_with_leftover_noise="enable", **common)
    (second,) = node.sample(model, "disable", latent_image=first, start_at_step=2,
                            end_at_step=10000, return_with_leftover_noise="disable",
                            **common)
    assert _rel(second["samples"], whole["samples"]) < 1e-5


# -- the residency rule ----------------------------------------------------------------


def _residency_events():
    from comfyui_parallelanything_tpu.utils.metrics import registry

    m = registry._metrics.get("pa_model_residency_total") or {"values": {}}
    return {tuple(sorted(dict(k).items())): v for k, v in m["values"].items()}


def _moved(before):
    now = _residency_events()
    return {dict(k)["model"] + ":" + dict(k)["event"]: v - before.get(k, 0.0)
            for k, v in now.items() if v != before.get(k, 0.0)}


def test_nothing_moves_when_everything_fits(tiny, tmp_path, monkeypatch):
    """A budget that holds every model and every program's temporaries: the
    rule is asked at each load and at each node that computes, and nothing
    leaves — what the five cells that were there see of it."""
    import comfyui_parallelanything_tpu as pa
    from comfyui_parallelanything_tpu.models import loader

    cell, _, _ = tiny
    rule = loader.Residency(budget_bytes=1 << 40)
    monkeypatch.setattr(loader, "residency", rule)
    before = _residency_events()
    cache = pa.WorkflowCache()
    pa.run_workflow(_graph(cell), outputs=cache)
    pa.run_workflow(_graph(cell, index=1), outputs=cache)
    assert _moved(before) == {}
    assert all(e["on_chip"] for e in rule._entries.values())
    assert len(rule._entries) == 6


def test_what_nothing_computes_with_leaves_when_the_models_do_not_fit(
        tiny, tmp_path, monkeypatch):
    """The rule with a small budget passed as an argument: room for both
    patched experts, the autoencoder and the decode program's temporaries
    (which ``VideoVAE.decode`` asks for like a load's bytes), not for the
    tower and the LoRAs' unpatched copies beside them. In the first prompt
    those three — the least recently used: the tower has spoken, nothing
    samples through an unpatched copy — leave the chip, dropped, their
    loaders able to read them again. A second prompt with the same text asks
    for nothing. A prompt with a new text brings the tower back, and it
    leaves again for the decode. Every move is a ``model-residency`` span and
    a count, the gauge follows, and the frames are those of a run with no
    budget at all."""
    import comfyui_parallelanything_tpu as pa
    from comfyui_parallelanything_tpu.models import loader
    from comfyui_parallelanything_tpu.utils import tracing
    from comfyui_parallelanything_tpu.utils.metrics import registry

    cell, _, _ = tiny
    free = loader.Residency(budget_bytes=0)
    monkeypatch.setattr(loader, "residency", free)
    plain = pa.run_workflow(_graph(cell))
    sizes = {e["model"]: e["bytes"] for e in free._entries.values()}
    tower, vae = sizes["t5"], sizes["video-vae"]
    patched = sizes["wan2.2_t2v_high_noise_14B_bf16+lora"]
    base = sizes["wan2.2_t2v_high_noise_14B_bf16"]
    (program,) = plain["39"][0]._decode_compiled.values()
    temporaries = program.memory_analysis().temp_size_in_bytes
    assert temporaries > 0
    steady = 2 * patched + vae
    budget = steady + temporaries + tower // 2
    # what the scenario below rests on: the decode is the first thing that
    # does not fit, and by then all three have to go
    assert tower + 2 * base + steady <= budget < steady + temporaries + min(tower, base)

    rule = loader.Residency(budget_bytes=budget)
    monkeypatch.setattr(loader, "residency", rule)
    before = _residency_events()
    cache = pa.WorkflowCache()
    was_on = tracing.on()
    tracing.enable()
    try:
        first = pa.run_workflow(_graph(cell), outputs=cache)
        spans = [e["args"] for e in tracing.export()["traceEvents"]
                 if e.get("name") == "model-residency"]
    finally:
        if not was_on:
            tracing.disable()
    assert _moved(before) == {
        "t5:evict": 1.0,
        "wan2.2_t2v_high_noise_14B_bf16:evict": 1.0,
        "wan2.2_t2v_low_noise_14B_bf16:evict": 1.0}
    assert [(a["model"], a["event"], a["bytes"]) for a in spans[-3:]] == [
        ("t5", "evict", tower),  # least recently used first
        ("wan2.2_t2v_high_noise_14B_bf16", "evict", base),
        ("wan2.2_t2v_low_noise_14B_bf16", "evict", base)]
    # a model is dropped, placeholders left in place: nothing is copied, and
    # it is read again when it is asked for
    encoder = first["38"][0]["encoder"]
    assert all(isinstance(leaf, loader.OffChip)
               for leaf in jax.tree.leaves(encoder.params))
    assert all(isinstance(leaf, jax.Array)
               for leaf in jax.tree.leaves(first["54"][0].params))
    assert registry.get("pa_params_resident_bytes",
                        {"model": "t5", "dtype": "float32"}) == 0.0
    assert rule.resident_bytes() == steady
    for k in range(9):
        assert open(first["9"][0][k], "rb").read() == open(plain["9"][0][k], "rb").read()

    # the same text, another seed: the conditioning comes from the node cache
    mark = _residency_events()
    pa.run_workflow(_graph(cell, index=1), outputs=cache)
    assert _moved(mark) == {}

    # a new text: the tower computes again, and makes way for the decode
    again = _graph(cell, index=2)
    again["6"]["inputs"]["text"] = "granite meadow lantern"
    third = pa.run_workflow(again, outputs=cache)
    assert _moved(mark) == {"t5:restore": 1.0, "t5:evict": 1.0}
    assert rule.resident_bytes() == steady
    monkeypatch.setattr(loader, "residency", free)
    want = pa.run_workflow(again)
    for k in range(9):
        assert open(third["9"][0][k], "rb").read() == open(want["9"][0][k], "rb").read()


def _made_again(name, value):
    """A model of one 64-byte tensor whose loader can make it again."""
    from comfyui_parallelanything_tpu.models.api import DiffusionModel

    def build():
        return {"w": {"kernel": jnp.full((16,), value, jnp.float32)}}

    model = DiffusionModel(apply=lambda p, x, t, c=None, **kw: x * p["w"]["kernel"][0],
                           params=build(), name=name)
    return model, build


def _on_chip(rule):
    return {e["model"] for e in rule._entries.values() if e["on_chip"]}


def test_the_rule_for_a_load_a_restore_and_a_programs_temporaries():
    """One rule, three askers, 64-byte models in a 160-byte budget: a load
    that does not fit sends the least recently used out; a model asked to
    compute again comes back and sends the next one out; a program's
    temporaries are made room for like a load's bytes; a model whose loader
    cannot make it again counts and stays."""
    from comfyui_parallelanything_tpu.models import loader

    rule = loader.Residency(budget_bytes=160)
    (a, build_a), (b, build_b), (c, build_c) = (
        _made_again(n, v) for n, v in (("a", 1.0), ("b", 2.0), ("c", 3.0)))
    rule.admit("a", a, build_a)
    rule.admit("b", b, build_b)
    assert _on_chip(rule) == {"a", "b"}
    rule.make_room(64)  # what a stored-type loader asks before it reads
    rule.admit("c", c, build_c)
    assert _on_chip(rule) == {"b", "c"} and rule.resident_bytes() == 128
    assert isinstance(a.params["w"]["kernel"], loader.OffChip)
    rule.ensure(a.params)  # a computes again: b, least recently used, leaves
    assert _on_chip(rule) == {"a", "c"}
    np.testing.assert_array_equal(np.asarray(a.params["w"]["kernel"]), np.ones(16))
    rule.ensure(a.params, beside=64)  # a's program needs 64 bytes beside it
    assert _on_chip(rule) == {"a"}
    rule.ensure(a.params, beside=1 << 20)  # more than there is: a itself stays
    assert _on_chip(rule) == {"a"}
    fixed, _ = _made_again("fixed", 4.0)
    rule.admit("fixed", fixed)  # no way to make it again
    rule.ensure(c.params)  # 64 + 64 + 64 > 160: a leaves, never ``fixed``
    assert _on_chip(rule) == {"c", "fixed"}
    rule.ensure(b.params, beside=64)
    assert _on_chip(rule) == {"b", "fixed"}


def test_an_evicted_model_comes_back_wherever_its_tensors_are_taken(monkeypatch):
    """Beyond the four compute entry points: a model the rule sent out is
    brought back where ``parallelize`` places its pytree on a chain, where
    ``quantize_model`` reads it, where a LoRA's serving factors are taken
    against it and where a sampler merges per-request factors into it — and
    whoever computes with the placeholders without asking gets an error that
    names the model and the rule."""
    import comfyui_parallelanything_tpu as pa
    from comfyui_parallelanything_tpu.models import loader
    from comfyui_parallelanything_tpu.models.quantize import quantize_model
    from comfyui_parallelanything_tpu.nodes_compat import LoraLoader
    from comfyui_parallelanything_tpu.sampling.runner import run_sampler

    rule = loader.Residency(budget_bytes=100)
    monkeypatch.setattr(loader, "residency", rule)
    model, build = _made_again("m", 2.0)
    other, build_other = _made_again("other", 3.0)
    x, t = jnp.ones((8, 4)), jnp.ones((8,))

    def sent_out():
        rule.admit("m", model, build)
        rule.admit("other", other, build_other)  # 128 bytes do not fit 100
        assert isinstance(model.params["w"]["kernel"], loader.OffChip)

    sent_out()
    with pytest.raises(loader.ModelOffChip, match="m: a tensor .* off the chip"):
        np.asarray(model.params["w"]["kernel"])
    with pytest.raises(loader.ModelOffChip, match="residency.ensure"):
        model(x, t)  # a jitted call that never asked
    with pytest.raises(loader.ModelOffChip):
        model.params["w"]["kernel"].astype(jnp.bfloat16)

    # replication over a device chain
    chain = pa.DeviceChain.even([f"cpu:{i}" for i in range(4)])
    replicated = pa.parallelize(model, chain)
    np.testing.assert_allclose(np.asarray(replicated(x, t)), 2.0 * np.ones((8, 4)))
    assert _on_chip(rule) == {"m"}

    sent_out()
    assert quantize_model(model, min_size=1 << 30).n_params() == 16
    assert _on_chip(rule) == {"m"}

    sent_out()
    patched, _ = _made_again("m+lora", 2.5)
    assert LoraLoader._lane_delegate(model, patched) is None  # a bias-like delta
    assert _on_chip(rule) == {"m"}

    sent_out()
    out = run_sampler(model, jnp.ones((1, 4)), None, sampler="euler", steps=2,
                      prediction="flow", lora={})
    assert np.isfinite(np.asarray(out)).all() and _on_chip(rule) == {"m"}


def test_the_loaders_state_what_the_reference_asks_of_them():
    """``reference_wan`` looks at the program in one place: before it reads a
    tensor it asks what the loaders STATE of themselves, by name."""
    from comfyui_parallelanything_tpu.models import loader

    assert reference_wan.NEEDS <= loader.CAPABILITIES


def test_a_model_the_cache_lets_go_of_is_forgotten(fresh_residency):
    """The rule holds its models weakly: one that is let go of is gone the
    next time the rule looks, and counts for nothing."""
    import gc

    from comfyui_parallelanything_tpu.models.api import DiffusionModel

    model = DiffusionModel(apply=lambda p, x, t, c=None: x,
                           params={"w": {"kernel": jnp.ones((4, 4))}}, name="m")
    fresh_residency.budget_bytes = 100
    fresh_residency.admit("m", model, lambda: None)
    assert fresh_residency.resident_bytes() == 64
    del model
    gc.collect()
    fresh_residency.make_room(64)  # would pick it: it is gone, and nothing is moved
    assert fresh_residency.resident_bytes() == 0 and fresh_residency._entries == {}


# -- routes and counters at the cell's shapes ---------------------------------------------


def test_the_attention_routes_at_the_cells_shapes():
    """The two attention classes of the cell by ``tuning.route`` on a TPU: the
    self-attention (1, 20280, 40, 128) is ragged (20,280 is no multiple of
    128) and past ``RAGGED_ONE_BLOCK`` keys, so the fused kernel streams
    4096-key blocks and masks the last; the cross-attention's 512 keys are
    under ``PADDED_DIM_MIN_KEYS`` and stay with the XLA family."""
    import importlib

    from comfyui_parallelanything_tpu.ops.pallas import tuning

    threshold = importlib.import_module(
        "comfyui_parallelanything_tpu.ops.attention")._CHUNK_THRESHOLD
    tokens = 13 * 30 * 52
    assert tokens == 20280 and tokens % 128
    own = tuning.route(tokens, tokens, 128, 40, on_tpu=True, chunk_threshold=threshold)
    assert (own.backend, own.rule, own.block_k) == ("pallas", "ragged", 4096)
    assert tokens > tuning.RAGGED_ONE_BLOCK
    cross = tuning.route(tokens, 512, 128, 40, on_tpu=True, chunk_threshold=threshold)
    assert cross.backend.startswith("xla") and 512 < tuning.PADDED_DIM_MIN_KEYS


def test_the_route_and_prologue_counters_for_one_forward_at_the_cells_shapes(monkeypatch):
    """One block of the published widths traced (nothing computed:
    ``jax.eval_shape``) at the cell's 20,280 tokens routed as on the
    chip: the self-attention counts under
    ``pa_attention_key_blocks_total{rule=ragged, keys=streamed}``, and the
    full-width q/k norm with its rotary under
    ``pa_qk_prologue_total{path=xla}`` — the fused prologue serves neither."""
    from comfyui_parallelanything_tpu.models.wan import WanModel, build_wan, wan_14b_config
    import importlib

    att = importlib.import_module("comfyui_parallelanything_tpu.ops.attention")
    cfg = wan_14b_config(depth=1)
    model = build_wan(cfg, params={})
    shapes = jax.eval_shape(lambda: WanModel(cfg).init(
        jax.random.key(0), jnp.zeros((1, 13, 60, 104, 16)), jnp.zeros((1,)),
        jnp.zeros((1, 512, 4096)))["params"])
    keys = _counted("pa_attention_key_blocks_total", rule="ragged", keys="streamed")
    prologue = _counted("pa_qk_prologue_total", path="xla", rope="interleaved")
    routed = _counted("pa_attention_route_total", backend="pallas")
    xla = _counted("pa_attention_route_total", backend="xla_chunked") \
        + _counted("pa_attention_route_total", backend="xla")
    # as on the chip: ``route`` under ``auto`` with the fused kernel in reach
    monkeypatch.setattr(att, "_pallas_available", lambda: True)
    out = jax.eval_shape(
        model.apply, shapes, jax.ShapeDtypeStruct((1, 13, 60, 104, 16), jnp.float32),
        jax.ShapeDtypeStruct((1,), jnp.float32),
        jax.ShapeDtypeStruct((1, 512, 4096), jnp.float32))
    assert out.shape == (1, 13, 60, 104, 16)
    assert _counted("pa_qk_prologue_total", path="xla", rope="interleaved") == prologue + 1
    assert _counted("pa_attention_key_blocks_total", rule="ragged",
                    keys="streamed") == keys + 1
    assert _counted("pa_attention_route_total", backend="pallas") == routed + 1
    assert _counted("pa_attention_route_total", backend="xla_chunked") \
        + _counted("pa_attention_route_total", backend="xla") == xla + 1


# -- the tracer switched off ----------------------------------------------------------------


def test_with_the_tracer_off_each_new_site_is_the_one_flag_check(fresh_residency):
    """``model-residency`` around a move and the ``text-encode`` span's new
    attribute hang off ``tracing.span``: with the tracer off that is the flag
    check that returns the null span — nothing is recorded and no clock is
    read."""
    from comfyui_parallelanything_tpu.models import loader
    from comfyui_parallelanything_tpu.models.api import DiffusionModel
    from comfyui_parallelanything_tpu.utils import tracing

    assert not tracing.on()
    assert tracing.span("model-residency", cat="graph", model="m", event="evict",
                        bytes=1) is tracing._NULL
    rule = loader.Residency(budget_bytes=100)
    (a, build_a), (b, build_b) = _made_again("a", 1.0), _made_again("b", 2.0)
    n = len(tracing.export()["traceEvents"])
    rule.admit("a", a, build_a)
    rule.admit("b", b, build_b)  # 128 bytes do not fit 100: a leaves
    assert isinstance(a.params["w"]["kernel"], loader.OffChip)
    rule.ensure(a.params)  # and comes back, b leaving
    assert isinstance(a.params["w"]["kernel"], jax.Array)
    assert isinstance(b.params["w"]["kernel"], loader.OffChip)
    assert len(tracing.export()["traceEvents"]) == n
