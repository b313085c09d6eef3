"""SD3.5-medium (MMDiT-X) against its plain reference at tiny widths on the
CPU, and the pieces the configuration forced: the stock loader reading the
text towers an ``*_incl_clips`` file bundles, and ragged sequence lengths
(77 text + image tokens) reaching the fused kernel padded and masked.

The reference (``benchmark/yardstick/reference_mmdit.py``) is the benchmark's;
``benchmark/tests`` walks the whole command with it, these tests hold the
program to it inside tier-1."""

import importlib
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from twins import (  # noqa: F401 — a fixture; benchmark/ on the path
    _float32_image, _rel, _twin, twin_files)
from yardstick import client, reference_mmdit, safetensors_io, traffic

CELL = "sd35m-tiny.closed"


@pytest.fixture
def tiny(twin_files, monkeypatch):
    """The tiny twin's checkpoint and tokenizer tables (written once for this
    file), the program's presets swapped for the twin's sizes in float32."""
    cell, (ckpt, tok), _ = _twin(twin_files, monkeypatch, CELL, jnp.float32)
    return cell, ckpt, tok


@pytest.fixture(scope="module")
def float32_image(twin_files):
    """(request 0, its float32 reference image), once for this file."""
    return _float32_image(twin_files, CELL, reference_mmdit)


def test_tiny_mmditx_forward_equals_the_reference_in_float32(tiny):
    """models/mmdit.py at depth 4 with dual attention in blocks 0-1, q/k RMS
    norm and a 32² position table cropped to 12², on a non-square latent,
    against ``reference_mmdit.mmdit``. Both compute in float32 (conftest pins
    ``highest``); what is left is the order of the sums — the tolerance is a
    hundred float32 roundings of an O(1) output, far under the 2e-2 the
    stated precision opens."""
    from comfyui_parallelanything_tpu.models import (
        load_mmdit_checkpoint,
        sd35_medium_config,
    )

    cell, ckpt, _ = tiny
    m = cell["config_data"]["mmdit"]
    model = load_mmdit_checkpoint(ckpt, sd35_medium_config())
    assert model.config.x_block_self_attn_layers == (0, 1) and model.config.qk_norm
    assert model.config.depth == 4 and model.sampler_prefs == {"shift": 3.0}
    keys = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(keys[0], (2, 24, 16, 16), jnp.float32)  # NHWC
    context = jax.random.normal(keys[1], (2, 77, m["joint_attention_dim"]), jnp.float32)
    y = jax.random.normal(keys[2], (2, m["pooled_projection_dim"]), jnp.float32)
    t = jnp.asarray([0.8, 0.25], jnp.float32)
    got = jax.jit(model.apply)(model.params, x, t, context, y=y)
    w = reference_mmdit.sd.load_weights(
        safetensors_io.read(ckpt, "model.diffusion_model."))
    # (one program each side, not a walk that compiles every operation alone)
    want = jax.jit(lambda x, t, c, y: reference_mmdit.mmdit("float32", w, m, x, t, c, y))(
        jnp.transpose(x, (0, 3, 1, 2)), 1000.0 * t, context, y)
    want = jnp.transpose(want, (0, 2, 3, 1))
    assert got.shape == want.shape == x.shape
    assert _rel(got, want) < 1e-4, _rel(got, want)


def test_the_whole_tiny_graph_through_the_server_equals_the_reference(tiny, float32_image):
    """ComfyUI's SD3.5 graph posted to ``server.py``: checkpoint loader with
    bundled towers, EmptySD3LatentImage, flow Euler over sgm_uniform at the
    family's shift, 16-channel decode, PNG. The served image against the
    reference's float image: the tolerance is the PNG's 8-bit rounding
    (1/255/sqrt(12) over an image whose spread is 0.1 or more), well under
    the 2.3e-2 the stated precision opens at this size."""
    from comfyui_parallelanything_tpu.server import make_server

    cell, ckpt, tok = tiny
    sched = traffic.Schedule(cell["mix"], 5, 10)
    graph = traffic.fill_graph(cell["template"], cell["mix"], sched.request(0))
    # where the twin's variables send SaveImage's files
    srv, q = make_server(port=0, output_dir=os.environ["PA_OUTPUT_DIR"], trace=True)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        res = client.run_request(base, graph, cell["template"]["output_node"], 0,
                                 time.perf_counter(), 0.02, 600)
        spans = client.http(base, "/trace")
    finally:
        srv.shutdown()
        srv.server_close()
        q.shutdown()
        thread.join(timeout=30)
    assert res.ok, res.error
    served = np.stack([client.decode_png(p) for p in res.images]).astype(np.float32) / 255.0
    req = reference_mmdit.describe(graph)
    assert req == float32_image[0]
    want = float32_image[1]
    assert served.shape == want.shape == (1, 192, 192, 3)
    assert _rel(served, want) < 1e-2, _rel(served, want)
    # One denoiser call a step, both halves of CFG in one batch, under the
    # MMDiT's program name; the new node classes have their spans.
    events = [e for e in spans["traceEvents"] if e.get("ph") == "X"
              and e.get("args", {}).get("prompt_id") == res.prompt_id]
    denoise = [e for e in events if e["name"] == "denoise"]
    assert len(denoise) == req["steps"] == sum(e["name"] == "step" for e in events)
    assert {e["args"]["rows"] for e in denoise} == {2}
    assert {e["args"]["program"] for e in denoise} == {"model-apply:mmdit"}
    classes = {e["args"].get("class_type") for e in events if e["name"] == "workflow-node"}
    assert {"CheckpointLoaderSimple", "EmptySD3LatentImage", "KSampler"} <= classes


def test_lower_precisions_open_the_gap_the_limits_stand_in(tiny, float32_image):
    cell, ckpt, tok = tiny
    req, float32 = float32_image
    img = {p: reference_mmdit.Reference(cell["config_data"], ckpt, tok, p).images(req, [0])
           for p in ("bfloat16", "int8")}
    img["float32"] = float32
    g = {p: _rel(img[p], img["float32"]) for p in ("bfloat16", "int8")}
    assert 5e-3 < g["bfloat16"] < g["int8"], g


@pytest.mark.parametrize("with_towers", [True, False], ids=["incl_clips", "no_towers"])
def test_bundled_clip_reads_the_incl_clips_towers(tiny, tmp_path, with_towers):
    """A file with ``text_encoders.clip_l`` / ``clip_g`` gives the sd3-triple
    wire with no T5; one without them keeps the error wire."""
    from comfyui_parallelanything_tpu.nodes import TPUTextEncode
    from comfyui_parallelanything_tpu.nodes_compat import (
        SD3_BUNDLED_TOWERS,
        CheckpointLoaderSimple,
    )

    cell, ckpt, _ = tiny
    path = ckpt
    if not with_towers:  # the twin's file less its two towers' tensors
        bare = {k: v for k, v in safetensors_io.read(ckpt).items()
                if not k.startswith(SD3_BUNDLED_TOWERS)}
        assert 0 < len(bare) < len(safetensors_io.read(ckpt))
        path = str(tmp_path / "bare.safetensors")
        safetensors_io.write(path, [(k, v.shape, v.dtype, [v]) for k, v in bare.items()])
    wire = CheckpointLoaderSimple()._bundled_clip(path, "sd35-medium")
    if not with_towers:
        assert wire["type"] == "error" and "do not bundle" in wire["tokenizer_error"]
        return
    assert wire["type"] == "sd3-triple" and wire["t5"] is None
    assert wire["tokenizer_error"] is None
    assert wire["l"]["model_key"] != wire["g"]["model_key"]
    assert set(reference_mmdit.BUNDLED_TOWERS) == set(SD3_BUNDLED_TOWERS)
    (cond,) = TPUTextEncode().encode(wire, "a watercolor lighthouse at dawn")
    m = cell["config_data"]["mmdit"]
    assert cond["context"].shape == (1, 77, m["joint_attention_dim"])
    assert cond["pooled"].shape == (1, m["pooled_projection_dim"])
    used = cell["config_data"]["text"]["hidden_size"] + cell["config_data"]["text_g"]["hidden_size"]
    assert float(jnp.abs(cond["context"][..., used:]).max()) == 0.0
    assert float(jnp.abs(cond["context"][..., :used]).max()) > 0.0


def test_sgm_uniform_on_a_flow_table_is_the_hosts_spacing():
    """The program's schedule for this graph equals the reference's, which
    restates ComfyUI's: the dropped end point is timestep(sigma_min) =
    1000·sigma_min, not index 0."""
    from comfyui_parallelanything_tpu.sampling.k_samplers import (
        flow_sigma_table,
        make_sigmas,
    )

    got = np.asarray(make_sigmas("sgm_uniform", 20, sigma_table=flow_sigma_table(3.0)))
    want = reference_mmdit.sgm_uniform_sigmas(20, 3.0)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    assert got[0] == 1.0 and got[-1] == 0.0 and (np.diff(got) < 0).all()


# -- ragged lengths and the fused kernel ------------------------------------------


def _att():
    return importlib.import_module("comfyui_parallelanything_tpu.ops.attention")


def test_padded_and_masked_kernel_equals_plain_xla_at_a_ragged_length():
    """77 + 144 tokens through the kernel with the ragged rule's kind of
    blocks (the row padded to the next 128-multiple as one key block, query
    blocks that do not divide it), in the Pallas interpreter, against plain
    XLA: bfloat16-free float32 operands, so what is left is the online
    softmax's order of sums."""
    from comfyui_parallelanything_tpu.ops.pallas.flash_attention import flash_attention

    att = _att()
    s = 77 + 144
    q, k, v = (jax.random.normal(key, (2, s, 4, 64), jnp.float32)
               for key in jax.random.split(jax.random.key(1), 3))
    want = att._xla_attention(q, k, v, 64 ** -0.5)
    got = flash_attention(q, k, v, block_q=64, block_k=256, interpret=True)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_a_long_ragged_key_block_is_walked_in_equal_tiles(monkeypatch):
    """Past ``_CHUNK_K`` keys a block is split into equal 128-multiple tiles:
    333 keys under a 128-key tile limit are three tiles of 128 with the last
    51 columns masked — the same numbers as one tile."""
    fa = importlib.import_module("comfyui_parallelanything_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_CHUNK_K", 128)
    q, k, v = (jax.random.normal(key, (1, 333, 2, 64), jnp.float32)
               for key in jax.random.split(jax.random.key(2), 3))
    want = _att()._xla_attention(q, k, v, 64 ** -0.5)
    got = fa._flash_attention(q, k, v, scale=64 ** -0.5, block_q=128, block_k=384,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# Every attention class the two UNet cells trace (CFG doubles the batch): the
# UNets' self- and cross-attention at each level and the decoder's mid block.
# (label, batch, seq_q, seq_k, heads, head_dim)
UNET_CELL_CLASSES = [
    ("sd15.self4096", 16, 4096, 4096, 8, 40),
    ("sd15.cross4096", 16, 4096, 77, 8, 40),
    ("sd15.self1024", 16, 1024, 1024, 8, 80),
    ("sd15.cross1024", 16, 1024, 77, 8, 80),
    ("sd15.self256", 16, 256, 256, 8, 160),
    ("sd15.cross256", 16, 256, 77, 8, 160),
    ("sd15.self64", 16, 64, 64, 8, 160),
    ("sd15.cross64", 16, 64, 77, 8, 160),
    ("sd15.vae4096", 8, 4096, 4096, 1, 512),
    ("sdxl.self4096", 2, 4096, 4096, 10, 64),
    ("sdxl.cross4096", 2, 4096, 77, 10, 64),
    ("sdxl.self1024", 2, 1024, 1024, 20, 64),
    ("sdxl.cross1024", 2, 1024, 77, 20, 64),
    ("sdxl.vae16384", 1, 16384, 16384, 1, 512),
]


@pytest.mark.parametrize("label,b,sq,sk,h,d", UNET_CELL_CLASSES,
                         ids=[c[0] for c in UNET_CELL_CLASSES])
def test_auto_backend_is_unchanged_at_every_class_of_the_unet_cells(
        monkeypatch, label, b, sq, sk, h, d):
    """The rule before this configuration, restated: the fused kernel at
    128-multiple lengths where the head dim is lane-aligned or the keys and
    logits reach the padded-dim thresholds, XLA otherwise. The ragged
    rule leaves cross-attention's 77 keys where they were, so ``sd15`` and
    ``sdxl`` trace the denoisers they did; the decoder's 512-wide head takes
    the lane-aligned row's blocks (PR 33)."""
    from comfyui_parallelanything_tpu.ops.pallas import tuning

    att = _att()
    monkeypatch.setattr(att, "_pallas_available", lambda: True)
    fused = sq % 128 == 0 and sk % 128 == 0 and (d % 128 == 0 or (
        sk >= tuning.PADDED_DIM_MIN_KEYS
        and b * h * sq * sk >= tuning.PADDED_DIM_MIN_LOGITS))
    got = att.resolve_route(sq, sk, d, b * h)
    if fused:
        assert got[:3] == ("pallas", *(
            tuning.PADDED_DIM_BLOCKS if d % 128
            else tuning.lane_aligned_route(sk, d, 2)))
    else:
        assert got[:3] == ("xla", None, None)


def test_padded_calls_are_counted_once_a_trace():
    from comfyui_parallelanything_tpu.utils.metrics import registry

    att = _att()

    def count(name):
        return registry.get(name, {"backend": "pallas"}) or 0.0

    before = count("pa_attention_padded_total"), count("pa_attention_route_total")
    prev = att.get_attention_backend()
    att.set_attention_backend("pallas")  # off a TPU: the interpreter
    try:
        ragged = jax.ShapeDtypeStruct((1, 77 + 64, 2, 64), jnp.float32)
        even = jax.ShapeDtypeStruct((1, 128, 2, 64), jnp.float32)
        fn = jax.jit(lambda q, k, v: att.attention_local(q, k, v))
        fn.eval_shape(ragged, ragged, ragged)
        fn.eval_shape(ragged, ragged, ragged)  # a cached trace counts nothing
        fn.eval_shape(even, even, even)
    finally:
        att.set_attention_backend(prev)
    assert count("pa_attention_padded_total") == before[0] + 1
    assert count("pa_attention_route_total") == before[1] + 2
