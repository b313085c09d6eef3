"""FLUX.1-schnell against its plain reference at tiny widths on the CPU, and
the pieces the configuration forced: the denoiser's and the T5 tower's kernels
resident in bfloat16 (the other families' in float32 as before), a depth-cut
file loaded at the depth it has, T5 for the ``flux-dual`` wire at 256 tokens
without a mask, one row a step at CFG 1.0, and the ``text-encode`` span with
its counters.

The reference (``benchmark/yardstick/reference_flux.py``) is the benchmark's;
``benchmark/tests`` walks the whole command with it, these tests hold the
program to it inside tier-1."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from twins import (  # noqa: F401 — a fixture; benchmark/ on the path
    _BENCH, _float32_image, _rel, _serve, _twin, _twin_file, twin_files)
from yardstick import (client, reference_flux, reference_sd, reference_t5,
                       safetensors_io, synth, traffic)

CELL = "flux-schnell-tiny.closed-unique"


@pytest.fixture
def tiny(twin_files, monkeypatch):
    return _twin(twin_files, monkeypatch, CELL, jnp.float32)


def _flux_path(cell, ref_kw):
    return ref_kw["files"][synth.checkpoint_files(cell["config_data"])[0]["file"]]


@pytest.fixture(scope="module")
def float32_image(twin_files):
    """(request 0, its float32 reference image), once for this file."""
    return _float32_image(twin_files, CELL, reference_flux)


def test_tiny_flux_forward_equals_the_reference_in_float32(tiny):
    """models/flux.py at 2 double + 4 single blocks with three-axis rotary
    tables (8 / 12 / 12), q/k RMS norm and 32-wide heads, on a non-square
    latent, against ``reference_flux.flux`` written from BFL's description
    (its patch features ordered channel, row, column: the converter permutes
    ``img_in`` and the last layer). Both compute in float32 (conftest pins
    ``highest``); what is left is the order of the sums — the tolerance is a
    hundred float32 roundings of an O(1) output, far under the 1e-2 the
    stated precision opens."""
    from comfyui_parallelanything_tpu.models import flux_schnell_config, load_flux_checkpoint

    cell, _, ref_kw = tiny
    m = cell["config_data"]["flux"]
    path = _flux_path(cell, ref_kw)
    model = load_flux_checkpoint(path, flux_schnell_config())
    assert (model.config.depth, model.config.depth_single_blocks) == (2, 4)
    assert model.config.axes_dim == (8, 12, 12) and not model.config.guidance_embed
    keys = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(keys[0], (2, 24, 16, 16), jnp.float32)  # NHWC
    context = jax.random.normal(keys[1], (2, 40, m["context_in_dim"]), jnp.float32)
    y = jax.random.normal(keys[2], (2, m["vec_in_dim"]), jnp.float32)
    t = jnp.asarray([0.75, 0.25], jnp.float32)
    got = jax.jit(model.apply)(model.params, x, t, context, y=y)
    w = reference_sd.load_weights(safetensors_io.read(path))
    # (one program each side, not a walk that compiles every operation alone)
    want = jax.jit(lambda x, t, c, y: reference_flux.flux("float32", w, m, x, t, c, y))(
        jnp.transpose(x, (0, 3, 1, 2)), t, context, y)
    want = jnp.transpose(want, (0, 2, 3, 1))
    assert got.shape == want.shape == x.shape
    assert _rel(got, want) < 1e-4, _rel(got, want)


def test_the_t5_tower_unmasked_at_a_padded_length_equals_the_reference(tiny):
    """The ``flux-dual`` wire's T5: 256 ids padded with 0 and NO mask, so the
    padded keys take part — the program's tower and ``reference_t5`` agree to
    float32 rounding (2e-5: benchmark/tests/test_t5.py gives the reading),
    and the masked tower is another tower."""
    from comfyui_parallelanything_tpu.models import load_t5_checkpoint, text_encoders

    cell, _, ref_kw = tiny
    config = cell["config_data"]
    path = ref_kw["files"]["models/text_encoders/t5xxl_fp16.safetensors"]
    ids = np.stack([ref_kw["tokenizers"]["t5"].ids(s) for s in
                    ("harbor lantern meadow granite", "")])
    assert ids.shape == (2, 256) and (ids[1, 1:] == 0).all()
    enc = load_t5_checkpoint(path, text_encoders.t5_xxl_config())
    got = np.asarray(enc(jnp.asarray(ids)))
    w = safetensors_io.read(path)  # host views, a block at a time (reference_flux)
    want = np.asarray(reference_t5.encode("float32", w, config["text_t5"], ids, None))

    def gap(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    assert gap(got, want) < 2e-5, gap(got, want)
    masked = np.asarray(enc(jnp.asarray(ids), mask=jnp.asarray(ids != 0)))
    assert gap(masked, want) > 0.05




def test_the_whole_tiny_graph_through_the_server_equals_the_reference(tiny, float32_image):
    """ComfyUI's FLUX.1-schnell graph posted to ``server.py``: UNETLoader on a
    depth-cut file, DualCLIPLoader type flux (T5 at 256 tokens unmasked +
    CLIP-L pooled), VAELoader, EmptySD3LatentImage, euler over ``simple`` at
    shift 1.0 (sigmas 1, 0.75, 0.5, 0.25, 0), CFG 1.0, 16-channel decode, PNG.
    The served image against the reference's float image: the tolerance is
    the PNG's 8-bit rounding, well under the 1e-2 the stated precision opens.
    The same graph under another seed samples again and encodes nothing."""
    from comfyui_parallelanything_tpu.utils.metrics import registry

    cell, ref_args, ref_kw = tiny

    def counted(name, **labels):
        return registry.get(name, labels) or 0.0

    before = {k: counted("pa_text_encode_total", tower=t, cache=c)
              for k, (t, c) in {"t5m": ("t5", "miss"), "t5h": ("t5", "hit"),
                                "lm": ("clip-l", "miss"), "lh": ("clip-l", "hit")}.items()}
    loops = counted("pa_sampler_loop_total", path="planned", sampler="euler")
    calls = counted("pa_denoiser_calls_total", program="model-apply:flux-schnell")
    sched = traffic.Schedule(cell["mix"], 5, 10)
    graph = traffic.fill_graph(cell["template"], cell["mix"], sched.request(0))
    reseeded = traffic.fill_graph(cell["template"], cell["mix"], sched.request(0))
    reseeded["3"]["inputs"]["seed"] += 1
    (res, again), spans = _serve(cell, [graph, reseeded])
    assert res.ok, res.error
    assert again.ok and again.images != res.images
    served = np.stack([client.decode_png(p) for p in res.images]).astype(np.float32) / 255.0
    req = reference_flux.describe(graph)
    assert (req["steps"], req["cfg"], req["scheduler"]) == (4, 1.0, "simple")
    assert req == float32_image[0]
    want = float32_image[1]
    assert served.shape == want.shape == (1, 192, 192, 3)
    assert _rel(served, want) < 1e-2, _rel(served, want)

    # CFG 1.0: one row a forward, 4 forwards and 4 steps a prompt, under the
    # FLUX program's name; the planned loop ran.
    def of(r):
        return [e for e in spans["traceEvents"] if e.get("ph") == "X"
                and e.get("args", {}).get("prompt_id") == r.prompt_id]

    denoise = [e for e in of(res) if e["name"] == "denoise"]
    assert len(denoise) == 4 == sum(e["name"] == "step" for e in of(res))
    assert {e["args"]["rows"] for e in denoise} == {1}
    assert {e["args"]["program"] for e in denoise} == {"model-apply:flux-schnell"}
    assert counted("pa_denoiser_calls_total",
                   program="model-apply:flux-schnell") == calls + 8
    assert counted("pa_sampler_loop_total", path="planned", sampler="euler") == loops + 2
    classes = {e["args"].get("class_type") for e in of(res) if e["name"] == "workflow-node"}
    assert {"UNETLoader", "DualCLIPLoader", "VAELoader", "KSampler"} <= classes
    # text-encode: one span a tower a CLIPTextEncode call — positive and the
    # empty negative, T5 and CLIP-L each, all misses. The second prompt
    # changed its seed only: its encode nodes come from the node cache whole,
    # so it has no such span and the counters stay.
    assert not [e for e in of(again) if e["name"] == "text-encode"]
    first = [e["args"] for e in of(res) if e["name"] == "text-encode"]
    assert sorted((a["tower"], a["tokens"], a["cache"]) for a in first) == [
        ("clip-l", 77, "miss"), ("clip-l", 77, "miss"),
        ("t5", 256, "miss"), ("t5", 256, "miss")]
    assert counted("pa_text_encode_total", tower="t5", cache="miss") == before["t5m"] + 2
    assert counted("pa_text_encode_total", tower="clip-l", cache="miss") == before["lm"] + 2


def test_a_repeated_text_is_a_hit_and_a_new_one_a_miss(tiny):
    """``pa_text_encode_total{tower=,cache=}`` and the span's ``cache``: the
    stock ``CLIPTextEncode`` on the flux-dual wire, the same text twice."""
    from comfyui_parallelanything_tpu.nodes import TPUTextEncode
    from comfyui_parallelanything_tpu.nodes_compat import DualCLIPLoader
    from comfyui_parallelanything_tpu.utils import tracing
    from comfyui_parallelanything_tpu.utils.metrics import registry

    (clip,) = DualCLIPLoader().load("t5xxl_fp16.safetensors", "clip_l.safetensors", "flux")
    assert clip["type"] == "flux-dual" and clip["t5"]["attention_mask"] is False
    assert clip["t5"]["tokenizer"].max_len == 256

    def counts():
        return {(t, c): registry.get("pa_text_encode_total", {"tower": t, "cache": c}) or 0.0
                for t in ("t5", "clip-l") for c in ("hit", "miss")}

    tracing.enable()
    try:
        c0 = counts()
        (a,) = TPUTextEncode().encode(clip, "ember glacier willow")
        c1 = counts()
        (b,) = TPUTextEncode().encode(clip, "ember glacier willow")
        c2 = counts()
        events = [e for e in tracing.export()["traceEvents"]
                  if e.get("name") == "text-encode"]
    finally:
        tracing.disable()
    assert a["context"].shape == (1, 256, 192) and a["pooled"].shape == (1, 64)
    assert b["context"] is a["context"]  # the cache's own arrays
    for tower in ("t5", "clip-l"):
        assert c1[tower, "miss"] == c0[tower, "miss"] + 1 and c1[tower, "hit"] == c0[tower, "hit"]
        assert c2[tower, "hit"] == c1[tower, "hit"] + 1 and c2[tower, "miss"] == c1[tower, "miss"]
    assert [e["args"]["cache"] for e in events[-4:]] == ["miss", "miss", "hit", "hit"]
    assert {e["cat"] for e in events} == {"graph"}


RESIDENT = [
    # (twin's cell, loader, the part's sizes key, compute type, what stays 16-bit)
    ("flux-schnell-tiny.closed-unique", "flux", "flux", jnp.bfloat16, "bfloat16"),
    ("flux-schnell-tiny.closed-unique", "t5", "text_t5", jnp.bfloat16, "bfloat16"),
    ("flux-schnell-tiny.closed-unique", "t5", "text_t5", jnp.float32, "float32"),
    ("flux-schnell-tiny.closed-unique", "clip", "text", jnp.bfloat16, "float32"),
    ("flux-schnell-tiny.closed-unique", "vae", "vae", jnp.bfloat16, "float32"),
    ("sd15-tiny.closed", "unet", "unet", jnp.bfloat16, "float32"),
    ("sd35m-tiny.closed", "mmdit", "mmdit", jnp.bfloat16, "float32"),
]


@pytest.mark.parametrize("cell_name,loader,sizes,dtype,kernels", RESIDENT,
                         ids=[f"{r[1]}-{jnp.dtype(r[3]).name}" for r in RESIDENT])
def test_what_a_loader_keeps_resident(twin_files, tmp_path, monkeypatch, cell_name,
                                      loader,
                                      sizes, dtype, kernels):
    """The load policy by the path a family takes: FLUX's and T5's matmul
    kernels and embeddings stay in bfloat16 (a bfloat16 file as it is, an
    fp16 one rounded once, under bfloat16 compute), their norm scales and
    biases in float32; T5 under float32 compute, CLIP-L, the autoencoder, the
    UNet and the MMDiT keep every parameter in float32 as before.
    ``pa_params_resident_bytes{model=,dtype=}`` says the same in bytes."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.utils.metrics import registry

    path = _twin_file(twin_files, tmp_path, monkeypatch, cell_name, sizes, dtype,
                      home=CELL)
    load = {
        "flux": lambda: models.load_flux_checkpoint(
            path, models.flux_schnell_config(), name="flux-schnell"),
        "t5": lambda: models.load_t5_checkpoint(path),
        "clip": lambda: models.load_clip_text_checkpoint(path),
        "vae": lambda: models.load_vae_checkpoint(path),
        "unet": lambda: models.load_sd_unet_checkpoint(path, models.sd15_config()),
        "mmdit": lambda: models.load_mmdit_checkpoint(path, models.sd35_medium_config()),
    }[loader]
    label = {"flux": "flux-schnell", "t5": "t5", "clip": "clip-text", "vae": "vae",
             "unet": "sd-unet", "mmdit": "mmdit"}[loader]
    params = load().params
    leaves = jax.tree_util.tree_leaves_with_path(params)
    named = [(jax.tree_util.keystr(k), v) for k, v in leaves]
    operands = ("'kernel']", "'embedding']")  # what a matmul or a lookup reads
    assert {str(v.dtype) for k, v in named if k.endswith(operands)} == {kernels}
    assert {str(v.dtype) for k, v in named if not k.endswith(operands)} <= {"float32"}
    by_dtype: dict = {}
    for _, v in leaves:
        by_dtype[str(v.dtype)] = by_dtype.get(str(v.dtype), 0) + v.size * v.dtype.itemsize
    for name, nbytes in by_dtype.items():
        assert registry.get("pa_params_resident_bytes",
                            {"model": label, "dtype": name}) == nbytes
    if kernels == "bfloat16":
        assert by_dtype["bfloat16"] > 20 * by_dtype["float32"] or loader == "t5"


def test_unet_loader_reads_a_cut_files_family_and_depths(tiny):
    """A FLUX file with no guidance embedder and 2 + 4 blocks — the published
    1 : 2 ratio — is schnell's at the depth it has, whatever the preset's
    depths; the family's flow table is at shift 1.0, so ``simple`` over 4
    steps is BFL's linear ladder."""
    from comfyui_parallelanything_tpu.models.convert import flux_depths
    from comfyui_parallelanything_tpu.models.loader import peek_safetensors, sniff_model_family
    from comfyui_parallelanything_tpu.nodes_compat import UNETLoader
    from comfyui_parallelanything_tpu.sampling.k_samplers import flow_sigma_table, make_sigmas

    cell, _, ref_kw = tiny
    keys = peek_safetensors(_flux_path(cell, ref_kw))
    assert flux_depths(keys) == (2, 4) and sniff_model_family(keys) == "flux-schnell"
    (model,) = UNETLoader().load_unet("flux1-schnell.safetensors")
    assert model.source["family"] == "flux-schnell" and model.name == "flux-schnell"
    assert model.block_lists == {"double_blocks": 2, "single_blocks": 4}
    assert model.sampler_prefs == {"shift": 1.0}
    got = np.asarray(make_sigmas("simple", 4, sigma_table=flow_sigma_table(1.0)))
    np.testing.assert_allclose(got, reference_flux.schnell_schedule(4), atol=1e-6)


def test_lower_precisions_open_the_gap_the_limits_stand_in(tiny, float32_image):
    cell, ref_args, ref_kw = tiny
    req, float32 = float32_image
    img = {p: reference_flux.Reference(cell["config_data"], *ref_args, p,
                                       **ref_kw).images(req, [0])
           for p in ("bfloat16", "int8")}
    img["float32"] = float32
    g = {p: _rel(img[p], img["float32"]) for p in ("bfloat16", "int8")}
    assert 2e-3 < g["bfloat16"] < g["int8"], g


def test_the_twin_names_the_programs_presets():
    """Every preset the twin swaps exists where the stock loaders look it up."""
    import importlib

    with open(os.path.join(_BENCH, "configs", "flux-schnell-tiny.json")) as f:
        presets = json.load(f)["program_presets"]
    for target in presets:
        mod, name = target.split(":")
        assert callable(getattr(importlib.import_module(mod), name))
