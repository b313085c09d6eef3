"""Z-Image-Turbo against its plain reference at tiny widths on the CPU, and the
pieces the configuration forced: the single-stream denoiser with its refiner
stacks, pad tokens and three-axis rotary tables, the first decoder-only text
tower (causal, grouped-query heads, the state before the last layer), the
byte-level BPE tokenizer behind the chat template at a bucketed length, the
new families' kernels resident in bfloat16 (the other families' as before),
a depth-cut file loaded at the depth it has, ``CLIPLoader type=lumina2``
picking the tower from the file's keys, one row a step at CFG 1.0, the
caption-bucket counter and the ``text-encode`` span's labels.

The reference (``benchmark/yardstick/reference_zimage.py``) is the
benchmark's; ``benchmark/tests`` walks the whole command with it, these tests
hold the program to it inside tier-1."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from twins import (  # noqa: F401 — a fixture; benchmark/ on the path
    _BENCH, _counted, _float32_image, _rel, _serve, _twin, _twin_file, twin_files)
from yardstick import client, reference_sd, reference_zimage, safetensors_io, traffic

CELL = "zimage-turbo-tiny.closed-unique"
DENOISER = "models/diffusion_models/z_image_turbo_bf16.safetensors"
TOWER = "models/text_encoders/qwen_3_4b.safetensors"


@pytest.fixture
def tiny(twin_files, monkeypatch):
    return _twin(twin_files, monkeypatch, CELL, jnp.float32)


@pytest.fixture(scope="module")
def float32_image(twin_files):
    """(request 0, its float32 reference image), once for this file."""
    return _float32_image(twin_files, CELL, reference_zimage)


def test_tiny_zimage_forward_equals_the_reference_in_float32(tiny):
    """models/zimage.py at 2 + 2 refiner and 3 main layers with three-axis
    rotary tables (8 / 12 / 12), sandwich RMS norms, tanh gates and SwiGLU on
    a non-square latent of 12 x 10 patches — 120 image tokens, padded to 128
    with the learned pad token — and a caption of 21 valid tokens in a
    32-token bucket, against ``reference_zimage.zimage`` written from the
    published description, which takes the valid tokens alone. The program
    takes σ and returns the velocity; the reference's model takes t = 1 − σ
    and its output is negated by the pipeline. Both compute in float32
    (conftest pins ``highest``); what is left is the order of the sums — the
    tolerance is a hundred float32 roundings of an O(1) output, far under the
    1e-2 the stated precision opens."""
    from comfyui_parallelanything_tpu.models import load_zimage_checkpoint, zimage_turbo_config

    cell, _, ref_kw = tiny
    m = cell["config_data"]["zimage"]
    path = ref_kw["files"][DENOISER]
    model = load_zimage_checkpoint(path, zimage_turbo_config())
    assert (model.config.n_layers, model.config.n_refiner_layers) == (3, 2)
    assert model.config.axes_dims == (8, 12, 12) and model.config.ffn_dim == 341
    keys = jax.random.split(jax.random.key(3), 2)
    x = jax.random.normal(keys[0], (2, 24, 20, 16), jnp.float32)  # NHWC
    states = jax.random.normal(keys[1], (2, 32, m["cap_feat_dim"]), jnp.float32)
    sigma = jnp.asarray([0.75, 0.25], jnp.float32)
    valid = 21
    got = jax.jit(model.apply)(model.params, x, sigma, states,
                      y=jnp.full((2, 1), float(valid), jnp.float32))
    w = reference_sd.load_weights(safetensors_io.read(path))
    # (one program each side, not a walk that compiles every operation alone)
    want = -jax.jit(lambda x, t, c: reference_zimage.zimage("float32", w, m, x, t, c))(
        jnp.transpose(x, (0, 3, 1, 2)), 1.0 - sigma, states[:, :valid])
    want = jnp.transpose(want, (0, 2, 3, 1))
    assert got.shape == want.shape == x.shape
    assert _rel(got, want) < 1e-4, _rel(got, want)
    # the rows past the valid count are the pad token's, whatever they held
    junk = states.at[:, valid:].set(7.0)
    again = jax.jit(model.apply)(model.params, x, sigma, junk,
                        y=jnp.full((2, 1), float(valid), jnp.float32))
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))
    # and another count is another caption
    other = jax.jit(model.apply)(model.params, x, sigma, states,
                        y=jnp.full((2, 1), 20.0, jnp.float32))
    assert _rel(other, want) > 1e-3


def test_the_causal_grouped_query_tower_equals_the_reference_state_by_state(tiny):
    """The first decoder-only tower: 4 query heads on 2 key/value heads,
    per-head q/k RMS norm, half-split rotary positions, SwiGLU, causal. The
    program's tower (2 of the twin's 3 layers: the state BEFORE the last) and
    ``reference_zimage.qwen3_states`` agree to float32 rounding at every
    position; a token after position i does not move state i, so a prompt
    padded to its bucket keeps its valid states; running the last layer too
    is another tower."""
    from comfyui_parallelanything_tpu.models import load_qwen3_checkpoint

    cell, _, ref_kw = tiny
    config = cell["config_data"]
    path = ref_kw["files"][TOWER]
    enc = load_qwen3_checkpoint(path)
    assert (enc.cfg.num_layers, enc.cfg.output_layers) == (3, 2)
    assert (enc.cfg.num_heads, enc.cfg.num_kv_heads, enc.cfg.head_dim) == (4, 2, 32)
    assert "layers_2" not in enc.params and "layers_1" in enc.params
    ids = np.asarray([ref_kw["tokenizers"]["qwen"].ids("harbor lantern meadow granite")])
    assert ids.shape == (1, 12)
    w = safetensors_io.read(path)  # host views, a layer at a time (reference_zimage)
    want = np.asarray(reference_zimage.qwen3_states("float32", w, config["text"], ids))

    def gap(a, b):
        return float(np.abs(a - b).max() / np.abs(b).max())

    got = np.asarray(enc(jnp.asarray(ids)))
    assert got.shape == want.shape == (1, 12, 64)
    assert max(gap(got[0, i], want[0, i]) for i in range(12)) < 2e-5
    padded = np.full((1, 32), 151643, np.int32)
    padded[:, :12] = ids
    at_bucket = np.asarray(enc(jnp.asarray(padded)))
    assert gap(at_bucket[:, :12], want) < 2e-5
    changed = padded.copy()
    changed[0, 7] = 300  # a token after position 6 ...
    moved = np.asarray(enc(jnp.asarray(changed)))
    np.testing.assert_array_equal(moved[:, :7], at_bucket[:, :7])  # ... moves no state up to 6
    assert gap(moved[:, 7:12], want[:, 7:]) > 1e-2
    last = np.asarray(reference_zimage.qwen3_states("float32", w, config["text"], ids, upto=3))
    assert gap(last, want) > 0.05


def test_the_tokenizer_and_chat_template_id_for_id_with_the_harness(tiny):
    """The program's tokenizer (the ``tokenizers`` package on the written
    ``tokenizer.json``, behind the chat template, at a 32-token bucket) and
    the harness's own encoder: the same valid ids, the pad id after."""
    from comfyui_parallelanything_tpu.utils.tokenizer import load_chat_tokenizer_json

    cell, _, ref_kw = tiny
    ours = ref_kw["tokenizers"]["qwen"]
    theirs = load_chat_tokenizer_json(os.environ["PA_QWEN_TOKENIZER_JSON"])
    sched = traffic.Schedule(cell["mix"], 5, 10)
    for text in [sched.request(i).positive for i in range(6)] + [
            "", "a Watercolor lighthouse, at dawn!", "x " * 40]:
        ids, mask = theirs([text])
        n = int(mask[0].sum())
        assert list(ours.ids(text)) == list(ids[0][:n]), text
        assert ids.shape[1] == -(-n // 32) * 32 and (ids[0][n:] == theirs.pad_id).all()
    ids, mask = theirs(["harbor", ("harbor lantern " * 20).strip()])
    assert ids.shape == (2, 64) and list(mask.sum(-1)) == [9, 48]
    assert ids[0][0] == 151644 and ids[0][mask[0].sum() - 5] == 151645




def test_the_whole_tiny_graph_through_the_server_equals_the_reference(tiny, float32_image):
    """ComfyUI's Z-Image-Turbo graph posted to ``server.py``: UNETLoader on a
    depth-cut file in the published key spelling, CLIPLoader type lumina2
    (the Qwen3 tower, picked from the file's keys), VAELoader,
    ModelSamplingAuraFlow shift 3.0, EmptySD3LatentImage, euler over
    ``simple``, 8 steps at CFG 1.0, 16-channel decode, PNG. The served image
    against the reference's float image: the tolerance is the PNG's 8-bit
    rounding, well under the 1e-2 the stated precision opens. A second
    prompt with another text and seed runs the SAME step program: one
    caption bucket, no new trace."""
    cell, ref_args, ref_kw = tiny
    program = "model-apply:zimage-turbo"
    before = {"miss": _counted("pa_text_encode_total", tower="qwen3", cache="miss"),
              "calls": _counted("pa_denoiser_calls_total", program=program),
              "loops": _counted("pa_sampler_loop_total", path="planned", sampler="euler"),
              "bucket": _counted("pa_caption_bucket_total", tokens="32")}
    sched = traffic.Schedule(cell["mix"], 5, 10)
    graphs = [traffic.fill_graph(cell["template"], cell["mix"], sched.request(i))
              for i in (0, 1)]
    (res, again), spans = _serve(cell, graphs)
    assert res.ok, res.error
    assert again.ok and again.images != res.images
    served = np.stack([client.decode_png(p) for p in res.images]).astype(np.float32) / 255.0
    req = reference_zimage.describe(graphs[0])
    assert (req["steps"], req["cfg"], req["scheduler"], req["shift"]) == (8, 1.0, "simple", 3.0)
    assert req == float32_image[0]
    want = float32_image[1]
    assert served.shape == want.shape == (1, 192, 192, 3)
    assert _rel(served, want) < 1e-2, _rel(served, want)

    def of(r):
        return [e for e in spans["traceEvents"] if e.get("ph") == "X"
                and e.get("args", {}).get("prompt_id") == r.prompt_id]

    # CFG 1.0: one row a forward, 8 forwards and 8 steps a prompt, under the
    # Z-Image program's name; the planned loop ran.
    for r in (res, again):
        denoise = [e for e in of(r) if e["name"] == "denoise"]
        assert len(denoise) == 8 == sum(e["name"] == "step" for e in of(r))
        assert {e["args"]["rows"] for e in denoise} == {1}
        assert {e["args"]["program"] for e in denoise} == {program}
    assert _counted("pa_denoiser_calls_total", program=program) == before["calls"] + 16
    assert _counted("pa_sampler_loop_total", path="planned",
                    sampler="euler") == before["loops"] + 2
    classes = {e["args"].get("class_type") for e in of(res) if e["name"] == "workflow-node"}
    assert {"UNETLoader", "CLIPLoader", "VAELoader", "ModelSamplingAuraFlow",
            "KSampler"} <= classes
    # text-encode: the first prompt encodes its text and the empty negative
    # (8 template tokens), the second its own text alone (the negative node is
    # cached whole); the span carries the tower and the VALID count.
    first = [e["args"] for e in of(res) if e["name"] == "text-encode"]
    assert sorted((a["tower"], a["tokens"], a["cache"]) for a in first) == [
        ("qwen3", 8, "miss"), ("qwen3", 16, "miss")]
    second = [e["args"] for e in of(again) if e["name"] == "text-encode"]
    assert [(a["tower"], a["tokens"], a["cache"]) for a in second] == [("qwen3", 16, "miss")]
    assert _counted("pa_text_encode_total", tower="qwen3", cache="miss") == before["miss"] + 3
    # ONE step program for both prompts: the denoiser was traced at a
    # 32-token caption once (the planned loop's own trace of it included).
    traced = _counted("pa_caption_bucket_total", tokens="32") - before["bucket"]
    assert 1 <= traced <= 2
    from comfyui_parallelanything_tpu.utils.metrics import registry

    others = [k for k in registry.render().splitlines()
              if k.startswith("pa_caption_bucket_total{") and 'tokens="32"' not in k]
    assert not others, others


def test_a_repeated_text_is_a_hit_and_the_wire_carries_the_valid_count(tiny):
    """``CLIPLoader type=lumina2`` resolves its tower from the file's keys
    (``model.layers.0.self_attn.q_norm.weight`` → Qwen3), not from a table
    that says T5; the wire's context is the bucket's states and its pooled
    slot the count of valid tokens; ``pa_text_encode_total{tower="qwen3"}``
    and the span's ``cache``."""
    from comfyui_parallelanything_tpu.nodes import TPUTextEncode
    from comfyui_parallelanything_tpu.nodes_compat import CLIPLoader
    from comfyui_parallelanything_tpu.utils import tracing

    (clip,) = CLIPLoader().load("qwen_3_4b.safetensors", "lumina2")
    assert clip["type"] == "qwen3" and clip["tokenizer"].bucket == 32
    tracing.enable()
    try:
        c0 = (_counted("pa_text_encode_total", tower="qwen3", cache="miss"),
              _counted("pa_text_encode_total", tower="qwen3", cache="hit"))
        (a,) = TPUTextEncode().encode(clip, "ember glacier willow")
        (b,) = TPUTextEncode().encode(clip, "ember glacier willow")
        c2 = (_counted("pa_text_encode_total", tower="qwen3", cache="miss"),
              _counted("pa_text_encode_total", tower="qwen3", cache="hit"))
        events = [e for e in tracing.export()["traceEvents"]
                  if e.get("name") == "text-encode"]
    finally:
        tracing.disable()
    assert a["context"].shape == (1, 32, 64) and a["pooled"].shape == (1, 1)
    assert float(a["pooled"][0, 0]) == 11.0  # 3 words + the template's 8
    assert b["context"] is a["context"]  # the cache's own arrays
    assert c2 == (c0[0] + 1, c0[1] + 1)
    assert [(e["args"]["tower"], e["args"]["tokens"], e["args"]["cache"])
            for e in events[-2:]] == [("qwen3", 11, "miss"), ("qwen3", 11, "hit")]


def test_cliploader_lumina2_refuses_a_file_without_a_qwen3_tower(twin_files, tmp_path,
                                                                  monkeypatch):
    """The type no longer maps to T5: a T5 file under it is refused by name."""
    from comfyui_parallelanything_tpu.nodes_compat import CLIPLoader

    # the FLUX twin's T5 file alone, where the loader looks a name up
    path = _twin_file(twin_files, tmp_path, monkeypatch,
                      "flux-schnell-tiny.closed-unique", "text_t5", jnp.float32, home=CELL)
    assert path == str(tmp_path / "models/text_encoders/t5xxl_fp16.safetensors")
    monkeypatch.setenv("PA_MODELS_DIR", str(tmp_path / "models"))
    with pytest.raises(ValueError, match="no Qwen3 tower"):
        CLIPLoader().load("t5xxl_fp16.safetensors", "lumina2")
    assert CLIPLoader._TYPE_TOWER["lumina2"] is None


RESIDENT = [
    # (twin's cell, loader, the part's sizes key, compute type, what stays 16-bit)
    (CELL, "zimage", "zimage", jnp.bfloat16, "bfloat16"),
    (CELL, "qwen3", "text", jnp.bfloat16, "bfloat16"),
    (CELL, "qwen3", "text", jnp.float32, "bfloat16"),  # a bfloat16 file stays as stored
    (CELL, "vae", "vae", jnp.bfloat16, "float32"),
    ("sd15-tiny.closed", "unet", "unet", jnp.bfloat16, "float32"),
    ("sd35m-tiny.closed", "mmdit", "mmdit", jnp.bfloat16, "float32"),
    ("flux-schnell-tiny.closed-unique", "flux", "flux", jnp.bfloat16, "bfloat16"),
]


@pytest.mark.parametrize("cell_name,loader,sizes,dtype,kernels", RESIDENT,
                         ids=[f"{r[1]}-{jnp.dtype(r[3]).name}" for r in RESIDENT])
def test_what_a_loader_keeps_resident(twin_files, tmp_path, monkeypatch, cell_name,
                                      loader,
                                      sizes, dtype, kernels):
    """The load policy by the path a family takes: Z-Image's and Qwen3's
    matmul kernels and the embedding stay in bfloat16 as their files store
    them (never whole in float32; the float32-computing embedders' too:
    widening them back is exact), every norm scale, pad token and bias in
    float32; a tiny ``sd15`` / ``sd35m`` / ``flux-schnell``
    checkpoint's as before. ``pa_params_resident_bytes{model=,dtype=}`` says
    the same in bytes."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.utils.metrics import registry

    path = _twin_file(twin_files, tmp_path, monkeypatch, cell_name, sizes, dtype,
                      home=CELL)
    load = {
        "zimage": lambda: models.load_zimage_checkpoint(path, models.zimage_turbo_config()),
        "qwen3": lambda: models.load_qwen3_checkpoint(path),
        "vae": lambda: models.load_vae_checkpoint(path),
        "unet": lambda: models.load_sd_unet_checkpoint(path, models.sd15_config()),
        "mmdit": lambda: models.load_mmdit_checkpoint(path, models.sd35_medium_config()),
        "flux": lambda: models.load_flux_checkpoint(
            path, models.flux_schnell_config(), name="flux-schnell"),
    }[loader]
    label = {"zimage": "zimage-turbo", "qwen3": "qwen3", "vae": "vae", "unet": "sd-unet",
             "mmdit": "mmdit", "flux": "flux-schnell"}[loader]
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
    if loader == "zimage":
        assert by_dtype["bfloat16"] > 5 * by_dtype["float32"]


def test_unet_loader_reads_a_cut_files_family_and_depth(tiny):
    """A file in the published key spelling with 3 main layers and 2 + 2
    refiner layers is Z-Image's at the depth it has, whatever the preset's
    ``n_layers``; the family's flow table is at shift 3.0, and ``simple``
    over 8 steps is the reference's ladder."""
    from comfyui_parallelanything_tpu.models.convert_zimage import zimage_depths
    from comfyui_parallelanything_tpu.models.loader import peek_safetensors, sniff_model_family
    from comfyui_parallelanything_tpu.nodes_compat import ModelSamplingAuraFlow, UNETLoader
    from comfyui_parallelanything_tpu.sampling.k_samplers import flow_sigma_table, make_sigmas

    cell, _, ref_kw = tiny
    keys = peek_safetensors(ref_kw["files"][DENOISER])
    assert "layers.2.attention.to_q.weight" in keys and "noise_refiner.1.feed_forward.w3.weight" in keys
    assert zimage_depths(keys) == (3, 2) and sniff_model_family(keys) == "zimage-turbo"
    (model,) = UNETLoader().load_unet("z_image_turbo_bf16.safetensors")
    assert model.source["family"] == "zimage-turbo" and model.name == "zimage-turbo"
    assert model.block_lists == {"noise_refiner": 2, "context_refiner": 2, "layers": 3}
    assert model.config.prediction == "flow" and model.sampler_prefs == {"shift": 3.0}
    (patched,) = ModelSamplingAuraFlow().patch(model, shift=2.0)
    assert patched.sampler_prefs == {"shift": 2.0} and model.sampler_prefs == {"shift": 3.0}
    got = np.asarray(make_sigmas("simple", 8, sigma_table=flow_sigma_table(3.0)))
    np.testing.assert_allclose(got, reference_zimage.simple_sigmas(8, 3.0), atol=1e-6)


def test_grouped_causal_attention_is_plain_attention_with_heads_repeated():
    """``ops/attention.grouped_causal_attention``: query head h reads
    key/value head h // group, under a causal mask — equal to repeating the
    key/value heads and masking a plain softmax; counted as an ``xla`` route
    once a trace."""
    from comfyui_parallelanything_tpu.ops.attention import grouped_causal_attention

    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (2, 9, 6, 16))
    k = jax.random.normal(kk, (2, 9, 2, 16))
    v = jax.random.normal(kv, (2, 9, 2, 16))
    got = grouped_causal_attention(q, k, v)
    kr, vr = jnp.repeat(k, 3, axis=2), jnp.repeat(v, 3, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / 4.0
    logits = jnp.where(jnp.tril(jnp.ones((9, 9), bool)), logits, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), vr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    before = _counted("pa_attention_route_total", backend="xla")
    jax.jit(grouped_causal_attention).eval_shape(q, k, v)
    assert _counted("pa_attention_route_total", backend="xla") == before + 1
    with pytest.raises(ValueError, match="do not group"):
        grouped_causal_attention(q, k[:, :, :1].repeat(4, 2), v[:, :, :1].repeat(4, 2))


def test_lower_precisions_open_the_gap_the_limits_stand_in(tiny, float32_image):
    cell, ref_args, ref_kw = tiny
    req, float32 = float32_image
    img = {p: reference_zimage.Reference(cell["config_data"], *ref_args, p,
                                         **ref_kw).images(req, [0])
           for p in ("bfloat16", "int8")}
    img["float32"] = float32
    g = {p: _rel(img[p], img["float32"]) for p in ("bfloat16", "int8")}
    assert 2e-3 < g["bfloat16"] < g["int8"], g


def test_the_twin_names_the_programs_presets():
    """Every preset the twin swaps exists where the stock loaders look it up."""
    import importlib

    with open(os.path.join(_BENCH, "configs", "zimage-turbo-tiny.json")) as f:
        presets = json.load(f)["program_presets"]
    assert len(presets) == 3
    for target in presets:
        mod, name = target.split(":")
        assert callable(getattr(importlib.import_module(mod), name))
