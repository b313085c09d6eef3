"""Qwen-Image against its plain reference at tiny widths on the CPU: the
double-stream denoiser (FLUX's block, the timestep's modulation alone, rotary
positions centred on the image), the Qwen2.5-VL tower as a second
configuration of the one causal-tower class with the system prompt's states
cut off by position, the file's key map, kernels resident in the type stored,
a LoRA baked into them tensor by tensor, the 3-D autoencoder on one frame, the
loader's residency rule sending the denoiser out for the tower and the tower
out for the denoiser, the whole graph through ``server.py``, the routes and
counters at the cell's shapes. ``twins.py`` writes the twin's four files and
tokenizer tables once for this file; one set serves the float32 and the
bfloat16 program alike.

The reference (``benchmark/yardstick/reference_qwen_image.py``) is the
benchmark's; ``benchmark/tests`` walks the whole command with it, these tests
hold the program to it inside tier-1."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from twins import (  # noqa: F401 — fixtures; benchmark/ on the path
    _counted, _float32_image, _moved, _rel, _residency_events, _serve, _twin, twin_files)
from wan_twin import fresh_residency  # noqa: F401 — a fixture
from yardstick import client, reference_qwen_image, reference_sd, safetensors_io, traffic

CELL = "qwen-image-tiny.closed"
DENOISER = "models/diffusion_models/qwen_image_bf16.safetensors"
LORA = "models/loras/Qwen-Image-Lightning-8steps-V1.1.safetensors"
TOWER = "models/text_encoders/qwen_2.5_vl_7b.safetensors"
VAE = "models/vae/qwen_image_vae.safetensors"
TEXT = "a watercolor lighthouse at dawn"
PATCHED = "qwen_image_bf16+lora"


@pytest.fixture
def tiny(twin_files, monkeypatch):
    """The twin under the program's presets at its sizes, in float32."""
    return _twin(twin_files, monkeypatch, CELL, jnp.float32)


@pytest.fixture
def tiny_bf16(twin_files, monkeypatch):
    """The same files under the type the presets give: bfloat16 compute."""
    return _twin(twin_files, monkeypatch, CELL, None)


@pytest.fixture(scope="module")
def float32_image(twin_files):
    """(request 0, its float32 reference image), once for this file."""
    return _float32_image(twin_files, CELL, reference_qwen_image)


def _graph(cell, index=0):
    sched = traffic.Schedule(cell["mix"], 5, 10)
    return traffic.fill_graph(cell["template"], cell["mix"], sched.request(index))


# -- the denoiser ---------------------------------------------------------------


def test_tiny_qwen_image_forward_equals_the_reference_in_float32(tiny):
    """models/qwen_image.py at 2 blocks (the preset says 60: the depth is read
    off the file), FLUX's double-stream block with the timestep's modulation
    alone, a three-axis rotary of 4 / 6 / 6 (16-wide heads) over positions
    centred on a 3 x 5 grid (odd both ways, so the centring is lopsided) and 7
    text rows placed after the grid's half-extent, against
    ``reference_qwen_image.qwen_image`` written from the published
    description. Both compute in float32 (conftest pins ``highest``); what is
    left is the order of the sums — the tolerance is a hundred float32
    roundings of an O(1) output, far under the 1e-2 the stated precision
    opens."""
    from comfyui_parallelanything_tpu import models

    cell, _, ref_kw = tiny
    m = cell["config_data"]["transformer"]
    path = ref_kw["files"][DENOISER]
    model = models.load_qwen_image_checkpoint(path, models.qwen_image_config())
    assert model.config.depth == m["num_layers"] == 2
    assert model.config.axes_dim == (4, 6, 6)
    assert model.block_lists == {"transformer_blocks": 2}
    keys = jax.random.split(jax.random.key(3), 2)
    x = jax.random.normal(keys[0], (1, 6, 10, 16), jnp.float32)  # NHWC
    states = jax.random.normal(keys[1], (1, 7, m["joint_attention_dim"]), jnp.float32)
    t = jnp.asarray([0.875], jnp.float32)
    got = jax.jit(model.apply)(model.params, x, t, states)
    w = reference_sd.load_weights(safetensors_io.read(path))
    want = jax.jit(lambda x, t, c: reference_qwen_image.qwen_image("float32", w, m, x, t, c))(
        jnp.transpose(x, (0, 3, 1, 2)), t, states)
    want = jnp.transpose(want, (0, 2, 3, 1))
    assert got.shape == want.shape == x.shape
    assert _rel(got, want) < 1e-4, _rel(got, want)
    # the staged forward (what the block-range placement runs) walks the same
    # methods with each stage's own keys of the pytree: traced, not computed
    spec = model.pipeline_spec

    def staged(params, x, t, states):
        carry = spec.prepare({k: params[k] for k in spec.prepare_keys}, x, t, states)
        for seg in spec.segments:
            carry = seg.fn({k: params[k] for k in seg.param_keys}, carry)
        return spec.finalize({k: params[k] for k in spec.finalize_keys}, carry, x.shape)

    assert len(spec.segments) == 2
    assert jax.eval_shape(staged, model.params, x, t, states).shape == x.shape


@pytest.mark.parametrize("hp,wp,n", [(83, 83, 10), (64, 64, 12), (3, 5, 7), (6, 4, 1)],
                         ids=["83x83", "64x64", "3x5", "6x4"])
def test_the_centred_positions_at_odd_and_even_grids(hp, wp, n):
    """``QwenEmbedRope`` with ``scale_rope``, written out: the rows run from
    −(h − ⌊h/2⌋) up (−42 … 40 at h = 83, −32 … 31 at 64), the columns
    likewise, the frame axis is 0; text token k sits at max(⌊h/2⌋, ⌊w/2⌋) + k
    on all three axes; text first."""
    from comfyui_parallelanything_tpu.models.qwen_image import centred_position_ids

    got = np.asarray(centred_position_ids(hp, wp, n))
    want = np.zeros((n + hp * wp, 3), np.int64)
    for k in range(n):
        want[k] = max(hp // 2, wp // 2) + k
    for i in range(hp):
        for j in range(wp):
            want[n + i * wp + j] = (0, i - (hp - hp // 2), j - (wp - wp // 2))
    assert np.array_equal(got, want)
    assert np.array_equal(got, reference_qwen_image.position_ids(n, hp, wp))
    if hp == 83:
        assert (got[n:, 1].min(), got[n:, 1].max()) == (-42, 40)
        assert got[0, 0] == 41


def test_the_files_key_map_round_trip(tiny):
    """Every tensor of the file lands in the pytree once: the parameter
    counts agree; q, k and v of a stream lie side by side in the fused kernel
    (in, 3, H, D); a token's features, (c, ph, pw) in the file, are (ph, pw,
    c) in ``img_in``'s rows and ``proj_out``'s columns; ``norm_out.linear``'s
    halves stay (scale, shift)."""
    from comfyui_parallelanything_tpu import models

    cell, _, ref_kw = tiny
    path = ref_kw["files"][DENOISER]
    w = {k: np.asarray(v, np.float32) for k, v in safetensors_io.read(path).items()}
    p = models.load_qwen_image_checkpoint(path, models.qwen_image_config()).params
    assert sum(l.size for l in jax.tree.leaves(p)) == sum(v.size for v in w.values())
    blk, b = p["transformer_blocks_1"], "transformer_blocks.1"
    H, D = 4, 16
    for stream, names in (("img", ("to_q", "to_k", "to_v")),
                          ("txt", ("add_q_proj", "add_k_proj", "add_v_proj"))):
        kernel = np.asarray(blk[f"{stream}_attn_qkv"]["kernel"])
        bias = np.asarray(blk[f"{stream}_attn_qkv"]["bias"])
        for i, n in enumerate(names):
            assert np.array_equal(kernel[:, i].reshape(64, H * D), w[f"{b}.attn.{n}.weight"].T)
            assert np.array_equal(bias[i].reshape(-1), w[f"{b}.attn.{n}.bias"])
    assert np.array_equal(np.asarray(blk["txt_attn_norm"]["key_norm"]),
                          w[f"{b}.attn.norm_added_k.weight"])
    assert np.array_equal(np.asarray(blk["img_mlp_out"]["kernel"]),
                          w[f"{b}.img_mlp.net.2.weight"].T)
    assert np.array_equal(np.asarray(blk["txt_mod"]["lin"]["kernel"]), w[f"{b}.txt_mod.1.weight"].T)
    file_row = lambda c, ph, pw: c * 4 + ph * 2 + pw  # noqa: E731
    ours_row = lambda c, ph, pw: (ph * 2 + pw) * 16 + c  # noqa: E731
    img_in, proj = np.asarray(p["img_in"]["kernel"]), np.asarray(p["final_proj"]["kernel"])
    for c, ph, pw in ((0, 0, 1), (5, 1, 0), (15, 1, 1)):
        assert np.array_equal(img_in[ours_row(c, ph, pw)], w["img_in.weight"][:, file_row(c, ph, pw)])
        assert np.array_equal(proj[:, ours_row(c, ph, pw)], w["proj_out.weight"][file_row(c, ph, pw)])
        assert np.asarray(p["final_proj"]["bias"])[ours_row(c, ph, pw)] \
            == w["proj_out.bias"][file_row(c, ph, pw)]
    assert np.array_equal(np.asarray(p["final_mod"]["kernel"]), w["norm_out.linear.weight"].T)


def test_the_files_are_resident_in_the_type_they_store(tiny_bf16):
    """Under bfloat16 compute every matmul kernel of the denoiser's and the
    tower's bfloat16 files stays bfloat16 on the chip — the modulations' and
    the head's too, widened where they are used — and norm scales and biases
    are float32: neither is ever whole in float32. The tower's ``visual.*``
    and ``lm_head`` keys, where a file has them, are not read and not
    counted."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.models import loader

    cell, _, ref_kw = tiny_bf16
    model = models.load_qwen_image_checkpoint(ref_kw["files"][DENOISER],
                                              models.qwen_image_config())
    p = model.params
    assert model.config.dtype == jnp.bfloat16
    for key in ("img_in", "txt_in", "final_mod", "final_proj"):
        assert p[key]["kernel"].dtype == jnp.bfloat16, key
        assert p[key]["bias"].dtype == jnp.float32, key
    blk = p["transformer_blocks_1"]
    assert {blk[k]["kernel"].dtype for k in
            ("img_attn_qkv", "txt_attn_qkv", "img_attn_proj", "txt_mlp_in", "img_mlp_out")} \
        == {jnp.dtype(jnp.bfloat16)}
    assert blk["img_mod"]["lin"]["kernel"].dtype == jnp.bfloat16
    assert blk["img_attn_norm"]["query_norm"].dtype == p["txt_norm"]["scale"].dtype == jnp.float32

    def by_type(tree):
        out = {}
        for leaf in jax.tree.leaves(tree):
            out[str(leaf.dtype)] = out.get(str(leaf.dtype), 0) + leaf.size
        return out

    assert by_type(p)["bfloat16"] > 30 * by_type(p)["float32"]
    tower_file = dict(safetensors_io.read(ref_kw["files"][TOWER]))
    plain = models.load_qwen25vl_checkpoint(tower_file)
    tower_file["visual.blocks.0.attn.qkv.weight"] = np.zeros((96, 128), np.float32)
    tower_file["lm_head.weight"] = np.zeros((8, 128), np.float32)
    enc = models.load_qwen25vl_checkpoint(tower_file)
    assert by_type(enc.params) == by_type(plain.params)
    assert by_type(enc.params)["bfloat16"] > 30 * by_type(enc.params)["float32"]
    assert enc.params["layers_0"]["q_proj"]["bias"].dtype == jnp.float32
    assert "q_norm" not in enc.params["layers_0"] and "norm" in enc.params
    assert loader.params_nbytes(enc.params) == _counted(
        "pa_params_resident_bytes", model="qwen25vl", dtype="bfloat16") + _counted(
        "pa_params_resident_bytes", model="qwen25vl", dtype="float32")


def test_a_lora_is_baked_into_bfloat16_resident_kernels_and_rounded_once(tiny_bf16):
    """``W + strength · (alpha / r) · up @ down`` on the twelve linears of
    every block, the sum in float32 and the result rounded ONCE to the
    resident bfloat16 — the same sum made here by hand, to the last bit but
    for an element in a thousand; every other tensor is the unpatched
    model's own, and nothing is unmatched."""
    from comfyui_parallelanything_tpu import models

    cell, _, ref_kw = tiny_bf16
    path, lora_path = ref_kw["files"][DENOISER], ref_kw["files"][LORA]
    base = models.load_qwen_image_checkpoint(path, models.qwen_image_config())
    baked = models.load_qwen_image_checkpoint(path, models.qwen_image_config(),
                                              lora=lora_path, lora_strength=0.75)
    w, lora = safetensors_io.read(path), safetensors_io.read(lora_path)

    def by_hand(name):
        key = f"transformer_blocks.1.{name}"
        up = np.asarray(lora[f"{key}.lora_up.weight"], np.float32)
        down = np.asarray(lora[f"{key}.lora_down.weight"], np.float32)
        alpha = float(np.asarray(lora[f"{key}.alpha"], np.float32))
        want = np.asarray(w[f"{key}.weight"], np.float32) \
            + 0.75 * (alpha / down.shape[0]) * (up @ down)
        return np.asarray(jnp.asarray(want).astype(jnp.bfloat16).T, np.float32)

    blk, plain = baked.params["transformer_blocks_1"], base.params["transformer_blocks_1"]
    taken = {
        "attn.to_k": lambda b: b["img_attn_qkv"]["kernel"][:, 1].reshape(64, 64),
        "attn.add_v_proj": lambda b: b["txt_attn_qkv"]["kernel"][:, 2].reshape(64, 64),
        "attn.to_add_out": lambda b: b["txt_attn_proj"]["kernel"],
        "img_mlp.net.0.proj": lambda b: b["img_mlp_in"]["kernel"],
        "txt_mlp.net.2": lambda b: b["txt_mlp_out"]["kernel"],
    }
    for name, take in taken.items():
        got = take(blk)
        assert got.dtype == jnp.bfloat16
        # the same sum in another order of additions: equal, but for a rare
        # element within a float32 rounding of a bfloat16 boundary (one ulp)
        got32, want32 = np.asarray(got, np.float32), by_hand(name)
        assert (got32 != want32).mean() < 1e-3, name
        assert np.abs(got32 - want32).max() <= np.abs(want32).max() * 2.0 ** -7, name
        assert not np.array_equal(got32, np.asarray(take(plain), np.float32))
    for a, b in ((blk["img_attn_norm"]["query_norm"], plain["img_attn_norm"]["query_norm"]),
                 (blk["img_mod"]["lin"]["kernel"], plain["img_mod"]["lin"]["kernel"]),
                 (baked.params["txt_in"]["kernel"], base.params["txt_in"]["kernel"])):
        assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


# -- the tower ------------------------------------------------------------------


def _wire(ref_kw):
    from comfyui_parallelanything_tpu.nodes_compat import CLIPLoader

    (clip,) = CLIPLoader().load(os.path.basename(ref_kw["files"][TOWER]), type="qwen_image")
    return clip


@pytest.mark.parametrize("words", [5, 25], ids=["in_a_bucket", "fills_the_bucket"])
def test_the_tower_states_with_the_prefix_cut_equal_the_reference(
        tiny, fresh_residency, words):
    """``CLIPLoader type=qwen_image`` → ``CLIPTextEncode``: the Qwen2.5-VL
    language model (biases on q / k / v, no q/k norms, the last layer through
    ``model.norm``) on the system-prompted template, padded to its 64-token
    bucket — 34 + 5 + 5 tokens of one, 34 + 25 + 5 = 64 that fill it — and the
    states from the first token of the prompt to the last valid one handed
    on: the cut found by position is the published 34 under this table, and
    the span says what was kept and what was dropped. Against the reference,
    which runs the valid tokens alone, in float32: the order of the sums."""
    from comfyui_parallelanything_tpu.nodes import TPUTextEncode
    from comfyui_parallelanything_tpu.utils import tracing

    cell, ref_args, ref_kw = tiny
    text = TEXT if words == 5 else " ".join(
        open(os.path.join(os.path.dirname(traffic.__file__), "..", "traffic",
                          "words.txt")).read().split()[:words])
    clip = _wire(ref_kw)
    assert clip["type"] == "qwen25vl"
    ids, mask = clip["tokenizer"]([text])
    assert ids.shape == (1, 64) and int(mask.sum()) == 34 + words + 5
    assert clip["tokenizer"].prefix_length(ids[0]) == 34
    misses = _counted("pa_text_encode_total", tower="qwen25vl", cache="miss")
    was_on = tracing.on()
    tracing.enable()
    try:
        (cond,) = TPUTextEncode().encode(clip, text)
        spans = [e["args"] for e in tracing.export()["traceEvents"]
                 if e.get("name") == "text-encode"]
    finally:
        if not was_on:
            tracing.disable()
    assert (spans[-1]["tower"], spans[-1]["tokens"], spans[-1]["dropped"],
            spans[-1]["cache"]) == ("qwen25vl", words + 5, 34, "miss")
    assert _counted("pa_text_encode_total", tower="qwen25vl", cache="miss") == misses + 1
    assert cond["pooled"] is None
    ref = reference_qwen_image.Reference(cell["config_data"], *ref_args, "float32", **ref_kw)
    want = ref.encode(text, os.path.basename(TOWER))
    assert cond["context"].shape == want.shape == (1, words + 5, 128)
    assert _rel(cond["context"], want) < 1e-4, _rel(cond["context"], want)
    # the harness's own encoder and the program's agree id for id
    tok = ref.toks["qwen"]
    assert tok.pieces(reference_qwen_image.TEMPLATE.format(text)) \
        == [int(t) for t in ids[0][: int(mask.sum())]]


def test_cliploader_qwen_image_refuses_a_file_without_the_tower(tiny, tmp_path, monkeypatch):
    """The type says which tower the file must hold; a Qwen3 file (q/k norms,
    no biases) is refused by its keys, before anything is loaded."""
    from comfyui_parallelanything_tpu.nodes_compat import CLIPLoader

    import ml_dtypes
    from safetensors.numpy import save_file

    wrong = tmp_path / "qwen_3_4b.safetensors"
    save_file({"model.layers.0.self_attn.q_norm.weight": np.ones((8,), ml_dtypes.bfloat16),
               "model.embed_tokens.weight": np.ones((4, 8), ml_dtypes.bfloat16)}, str(wrong))
    with pytest.raises(ValueError, match="Qwen2.5-VL"):
        CLIPLoader().load(str(wrong), type="qwen_image")


# -- the autoencoder on one frame ---------------------------------------------------


def test_an_image_latent_through_the_video_autoencoders_one_frame_path(tiny):
    """``VAELoader`` sniffs the 3-D autoencoder's keys; ``VAEDecode`` hands it
    a 4-D image latent, which is a clip of one frame: the decoder's
    first-frame path (zeros as every causal convolution's history, no
    ``time_conv``), one program ``jit_video_decode`` counted under
    ``form="frame"``. Against ``reference_wan.wan_vae_decode`` on one frame, in
    float32."""
    from comfyui_parallelanything_tpu.models.video_vae import VideoVAE
    from comfyui_parallelanything_tpu.nodes_compat import VAELoader
    from comfyui_parallelanything_tpu.nodes import TPUVAEDecode
    from yardstick.reference_wan import wan_vae_decode

    cell, _, ref_kw = tiny
    (vae,) = VAELoader().load(os.path.basename(ref_kw["files"][VAE]))
    assert isinstance(vae, VideoVAE)
    before = _counted("pa_video_decode_total", frames="1", form="frame")
    z = jax.random.normal(jax.random.key(7), (1, 6, 10, 16), jnp.float32)
    (img,) = TPUVAEDecode().decode(vae, {"samples": z})
    assert img.shape == (1, 48, 80, 3)
    assert _counted("pa_video_decode_total", frames="1", form="frame") == before + 1
    w = reference_sd.load_weights(safetensors_io.read(ref_kw["files"][VAE]))
    want = wan_vae_decode("float32", w, cell["config_data"]["vae"],
                          jnp.transpose(z, (0, 3, 1, 2))[:, :, None], last_frame=0)
    # the benchmark's own one-frame decoder (the first-frame path written out,
    # without the walk's carried frames) is that walk's frame 0
    own = reference_qwen_image.decode_frame(
        "float32", w, cell["config_data"]["vae"], jnp.transpose(z, (0, 3, 1, 2)))
    assert float(jnp.abs(own - want).max()) < 1e-5
    want = jnp.clip(jnp.transpose(want, (0, 2, 3, 1)) * 0.5 + 0.5, 0.0, 1.0)
    assert _rel(img, want) < 1e-4, _rel(img, want)


# -- the graph, the server and the residency rule ------------------------------------






def test_the_towers_load_sends_the_denoiser_out_and_its_return_sends_the_tower_out(
        tiny, monkeypatch):
    """The cell's warm-up at tiny sizes, under a budget passed as an argument
    that holds the tower and the autoencoder, or the denoiser with its LoRA
    and the autoencoder, but not the tower beside a denoiser — the chip's
    5.5 + 14.1 GB against 15.2. The graph loads the denoiser and bakes its
    LoRA first; the TOWER's load sends both copies out (least recently used
    first), dropped, their loader able to read them again; the sampler asks
    for the baked one back, and its return sends the tower out: both
    directions through the one rule. Every move is a ``model-residency`` span
    and a count; a second prompt on the same text moves nothing; the image
    is that of a run with no budget at all."""
    import comfyui_parallelanything_tpu as pa
    from comfyui_parallelanything_tpu.models import loader
    from comfyui_parallelanything_tpu.utils import tracing

    cell, _, _ = tiny
    free = loader.Residency(budget_bytes=0)
    monkeypatch.setattr(loader, "residency", free)
    plain = pa.run_workflow(_graph(cell))
    sizes = {e["model"]: e["bytes"] for e in free._entries.values()}
    tower, vae = sizes["qwen25vl"], sizes["video-vae"]
    base, patched = sizes["qwen_image_bf16"], sizes[PATCHED]
    budget = tower + vae + patched // 2
    assert tower + vae <= budget < tower + vae + min(base, patched)
    assert base + patched + vae <= budget

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
    assert _moved(before) == {"qwen_image_bf16:evict": 1.0, f"{PATCHED}:evict": 1.0,
                              "qwen25vl:evict": 1.0, f"{PATCHED}:restore": 1.0}
    assert [(a["model"], a["event"], a["bytes"]) for a in spans[-4:]] == [
        ("qwen_image_bf16", "evict", base), (PATCHED, "evict", patched),
        ("qwen25vl", "evict", tower), (PATCHED, "restore", patched)]
    encoder = first["38"][0]["encoder"]
    assert all(isinstance(leaf, loader.OffChip) for leaf in jax.tree.leaves(encoder.params))
    assert all(isinstance(leaf, jax.Array) for leaf in jax.tree.leaves(first["73"][0].params))
    assert rule.resident_bytes() == patched + vae
    assert open(first["60"][0][0], "rb").read() == open(plain["60"][0][0], "rb").read()
    # the same text, another seed: the conditioning comes from the node cache
    mark = _residency_events()
    pa.run_workflow(_graph(cell, index=1), outputs=cache)
    assert _moved(mark) == {}




def test_the_whole_tiny_graph_through_the_server_equals_the_reference(
        tiny, fresh_residency, float32_image):
    """ComfyUI's Qwen-Image graph posted to ``server.py``: UNETLoader on a
    depth-cut file in the published key spelling (family and depth read off
    its keys), LoraLoaderModelOnly, ModelSamplingAuraFlow shift 3.1,
    CLIPLoader type qwen_image, VAELoader on the 3-D autoencoder,
    EmptySD3LatentImage, euler over ``simple``, 8 steps at CFG 1.0, the
    one-frame decode, PNG. The served image against the reference's float
    image: the tolerance is the PNG's 8-bit rounding, well under the 1e-2 the
    stated precision opens. A second prompt with another seed runs the SAME
    step program, named for the baked file: one text length, no new trace."""
    cell, _, _ = tiny
    program = f"model-apply:{PATCHED}"
    before = {"calls": _counted("pa_denoiser_calls_total", program=program),
              "loops": _counted("pa_sampler_loop_total", path="planned", sampler="euler"),
              "bucket": _counted("pa_caption_bucket_total", tokens="10")}
    graphs = [_graph(cell, i) for i in (0, 1)]
    (res, again), spans = _serve(cell, graphs)
    assert res.ok, res.error
    assert again.ok and again.images != res.images
    served = np.stack([client.decode_png(p) for p in res.images]).astype(np.float32) / 255.0
    req = reference_qwen_image.describe(graphs[0])
    assert (req["steps"], req["cfg"], req["scheduler"], req["shift"]) == (8, 1.0, "simple", 3.1)
    assert req == float32_image[0]
    want = float32_image[1]
    assert served.shape == want.shape == (1, 48, 80, 3)
    assert _rel(served, want) < 1e-2, _rel(served, want)

    def of(r):
        return [e for e in spans["traceEvents"] if e.get("ph") == "X"
                and e.get("args", {}).get("prompt_id") == r.prompt_id]

    for r in (res, again):
        denoise = [e for e in of(r) if e["name"] == "denoise"]
        assert len(denoise) == 8 == sum(e["name"] == "step" for e in of(r))
        assert {e["args"]["rows"] for e in denoise} == {1}
        assert {e["args"]["program"] for e in denoise} == {program}
    assert _counted("pa_denoiser_calls_total", program=program) == before["calls"] + 16
    assert _counted("pa_sampler_loop_total", path="planned",
                    sampler="euler") == before["loops"] + 2
    classes = {e["args"].get("class_type") for e in of(res) if e["name"] == "workflow-node"}
    assert {"UNETLoader", "LoraLoaderModelOnly", "CLIPLoader", "VAELoader",
            "ModelSamplingAuraFlow", "KSampler", "VAEDecode"} <= classes
    first = [e["args"] for e in of(res) if e["name"] == "text-encode"]
    assert sorted((a["tower"], a["tokens"], a["dropped"]) for a in first) == [
        ("qwen25vl", 5, 34), ("qwen25vl", 10, 34)]
    assert not [e for e in of(again) if e["name"] == "text-encode"]  # cached whole
    # ONE step program for both prompts: traced at 10 text rows once (the
    # planned loop's own trace of it included), and at no other length
    assert 1 <= _counted("pa_caption_bucket_total", tokens="10") - before["bucket"] <= 2
    assert _counted("pa_caption_bucket_total", tokens="5") == 0


def test_unet_loader_reads_the_family_and_depth_off_the_file(tiny, fresh_residency):
    from comfyui_parallelanything_tpu.models.loader import peek_safetensors, sniff_model_family
    from comfyui_parallelanything_tpu.nodes_compat import UNETLoader

    cell, _, ref_kw = tiny
    path = ref_kw["files"][DENOISER]
    assert sniff_model_family(peek_safetensors(path)) == "qwen-image"
    (model,) = UNETLoader().load_unet(os.path.basename(path))
    assert model.source["family"] == "qwen-image" and model.name == "qwen_image_bf16"
    assert model.config.depth == 2 and model.sampler_prefs == {"shift": 3.1}


# -- routes and counters at the cell's shapes ---------------------------------------


def test_the_route_and_prologue_counters_for_one_forward_at_the_cells_shapes(monkeypatch):
    """One block of the published widths traced (nothing computed:
    ``jax.eval_shape``) at the cell's 6,889 + 10 tokens routed as on the chip:
    the joint attention counts under ``pa_attention_key_blocks_total{rule=
    ragged, keys=streamed}`` (6,912 padded keys are over RAGGED_ONE_BLOCK),
    the image stream's q/k norm and rotary take the fused prologue, the text
    stream's 10 rows stay with XLA — and the text length is counted once."""
    import importlib

    from comfyui_parallelanything_tpu.models.qwen_image import (
        QwenImageModel, build_qwen_image, qwen_image_config)
    from comfyui_parallelanything_tpu.ops.pallas import tuning

    att = importlib.import_module("comfyui_parallelanything_tpu.ops.attention")
    cfg = qwen_image_config(depth=1)
    model = build_qwen_image(cfg, params={})
    args = (jax.ShapeDtypeStruct((1, 166, 166, 16), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
            jax.ShapeDtypeStruct((1, 10, 3584), jnp.float32))
    shapes = jax.eval_shape(lambda: QwenImageModel(cfg).init(
        jax.random.key(0), *(jnp.zeros(a.shape) for a in args))["params"])
    assert sum(l.size for l in jax.tree.leaves(shapes)) == 339_831_296 + 40_523_328
    route = tuning.route(6899, 6899, 128, 24, on_tpu=True, chunk_threshold=2 ** 27)
    assert (route.backend, route.block_q, route.block_k, route.rule) == (
        "pallas", 384, 4096, "ragged")
    from comfyui_parallelanything_tpu.ops.pallas import qk_prologue

    assert qk_prologue.supports(24, 128, rope=True)
    counts = {k: _counted(*k[:1], **dict(k[1])) for k in (
        ("pa_attention_key_blocks_total", (("rule", "ragged"), ("keys", "streamed"))),
        ("pa_qk_prologue_total", (("path", "fused"), ("rope", "interleaved"))),
        ("pa_qk_prologue_total", (("path", "xla"), ("rope", "interleaved"))),
        ("pa_attention_route_total", (("backend", "pallas"),)),
        ("pa_caption_bucket_total", (("tokens", "10"),)))}
    monkeypatch.setattr(att, "_pallas_available", lambda: True)
    out = jax.eval_shape(model.apply, shapes, *args)
    assert out.shape == (1, 166, 166, 16)
    for k, was in counts.items():
        assert _counted(*k[:1], **dict(k[1])) == was + 1, k
