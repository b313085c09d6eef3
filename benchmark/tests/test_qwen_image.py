"""The Qwen-Image configuration: the layouts' counts, the shape functions
against a hand count, the table of merges with the system prompt's words, the
synthesised files against the program's converters, the graph as ``describe``
reads it, the whole command on the tiny twin, and the metric files of the
cell."""

import json
import os

import numpy as np
import pytest

import run
from yardstick import (layout, layout_qwen_image, reference_qwen_image,
                       shapes_qwen_image, synth, traffic)

CELL = "qwen-image-b1-1328.closed"
TWIN = "qwen-image-tiny.closed"
TEXT = "a watercolor lighthouse at dawn"


def test_layout_counts_at_the_published_depth_at_the_cut_and_the_tower():
    config = run.load_cell(CELL)["config_data"]
    m = config["transformer"]
    assert m["num_layers"] == 8
    at = {n: layout.count(layout_qwen_image.qwen_image_layout(dict(m, num_layers=n)))
          for n in (0, 1, 7, 8, 60)}
    assert at[1] - at[0] == 339_831_296
    assert at[0] == 40_523_328
    assert at[60] == 20_430_401_088
    assert at[8] == 2_759_173_696 and at[7] == at[8] - 339_831_296
    # a block by its parts: two modulations, the attention, two feed-forwards
    d, ff = 3072, 12288
    mod = d * 6 * d + 6 * d
    attn = 8 * (d * d + d) + 4 * 128
    mlp = d * ff + ff + ff * d + d
    assert (mod, attn, mlp) == (56_641_536, 75_522_560, 75_512_832)
    assert 2 * mod + attn + 2 * mlp == at[1] - at[0]
    text = layout.count(layout_qwen_image.qwen25vl_layout(config["text"]))
    layer = 3584 * 3584 + 3584 + 2 * (3584 * 512 + 512) + 3584 * 3584 \
        + 3 * 3584 * 18944 + 2 * 3584
    assert layer == 233_057_792
    assert text == 28 * layer + 152064 * 3584 + 3584 == 7_070_619_136
    vae = layout.count(layout_qwen_image.qwen_image_vae_layout(config["vae"]))
    lora = layout.count(layout_qwen_image.qwen_image_lora_layout(config["lora"]))
    assert vae == 126_892_531
    assert lora == 8 * (12 + 64 * (8 * 2 * d + 2 * 2 * (d + ff)))
    stated = config["checkpoint"]["parameters"]
    assert (stated["transformer_block"], stated["transformer_besides_blocks"],
            stated["transformer"], stated["transformer_published_depth"], stated["text"],
            stated["vae"], stated["lora"]) == (
        at[1] - at[0], at[0], at[8], at[60], text, vae, lora)


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    doc = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    entry = next(c for c in doc["configs"] if c["name"] == "qwen-image")
    config = run.load_json("configs", "qwen-image")
    assert entry["reduced"] == config["reduced"] == ["num_layers"]
    assert entry["source"] == config["source"] and "Qwen/Qwen-Image" in entry["source"]
    published = {"attention_head_dim": 128, "axes_dims_rope": [16, 56, 56],
                 "guidance_embeds": False, "in_channels": 64, "joint_attention_dim": 3584,
                 "num_attention_heads": 24, "out_channels": 16, "patch_size": 2}
    assert {k: config["transformer"][k] for k in published} == published
    assert config["transformer"]["num_layers"] == 8  # published: 60
    assert layout_qwen_image.mlp_hidden(config["transformer"]) == 12288
    t = config["text"]
    assert (t["hidden_size"], t["intermediate_size"], t["num_hidden_layers"],
            t["num_attention_heads"], t["num_key_value_heads"], t["vocab_size"],
            t["rope_theta"], t["rms_norm_eps"]) == (3584, 18944, 28, 28, 4, 152064, 1e6, 1e-6)
    v = config["vae"]
    assert (v["base_dim"], v["z_dim"], v["dim_mult"], v["num_res_blocks"],
            v["temperal_downsample"]) == (96, 16, [1, 2, 4, 4], 2, [False, True, True])
    wan = run.load_json("configs", "wan22-t2v-a14b")["vae"]
    assert (v["latents_mean"], v["latents_std"]) == (wan["latents_mean"], wan["latents_std"])
    d = config["deployment"]
    assert (d["chips"], d["this_chip"], d["num_layers_published"], d["num_layers_held"]) == (
        8, "lead", 60, 8)
    assert config["precision"] == "bfloat16"
    for key in ("graph", "file_names", "lora", "chat_template", "tower_output", "tokenizer",
                "visual_tower", "vae_dtype", "sampling", "text_wire"):
        assert key in config["assumed"]
    files = [f["file"] for f in config["checkpoint"]["files"]]
    assert [os.path.dirname(f) for f in files] == [
        "models/diffusion_models", "models/loras", "models/text_encoders", "models/vae"]
    assert {f["dtype"] for f in config["checkpoint"]["files"]} == {"bfloat16"}
    tok = config["tokenizers"][0]
    assert (tok["writer"], tok["vocab_size"], tok["env"]) == (
        "tokenizer_bpe_words", 152064, {"PA_QWEN_TOKENIZER_JSON": "tokenizer_json"})
    assert set(TEXT.split()) <= set(tok["words"])
    assert set(config["trace_modules"]) >= {"denoiser", "decode", "text"}
    # the twin differs in sizes and in nothing of the wiring
    twin = run.load_json("configs", "qwen-image-tiny")
    assert twin["rehearsal"] and twin["assumed"] == config["assumed"]
    assert [f["file"] for f in twin["checkpoint"]["files"]] == files


def test_shapes_against_a_hand_count():
    """One forward at the cut and the cell's image: 6,889 image tokens and the
    10 of the fixed text; a block is 2 x 113.2 M multiply-adds a token on both
    streams and one joint attention."""
    cell = run.load_cell(CELL)
    config, mix = cell["config_data"], cell["mix"]
    n_img, n_txt = shapes_qwen_image.image_tokens(config, mix), shapes_qwen_image.text_tokens(mix)
    assert (n_img, n_txt) == (83 * 83, 10) == (6889, 10)
    d, ff, n = 3072, 12288, 6899
    attention = 4 * n * n * d
    assert shapes_qwen_image.joint_attention(config, mix, 1)["flops"] == attention
    per_token = 4 * d * d + 2 * d * ff
    assert per_token == 113_246_208
    block = 2 * n * per_token + attention + 2 * 2 * d * 6 * d
    outside = 2 * n_img * (64 * d + d * 64) + 2 * n_txt * 3584 * d \
        + 2 * (256 * d + d * d) + 2 * d * 2 * d
    step = shapes_qwen_image.denoiser_step(config, mix, 1)
    assert step["flops"] == 8 * block + outside
    assert 17.1e12 < step["flops"] < 17.3e12
    assert step["params"] == 2_759_173_696
    # compute-bound: 87 ms of operations against 16 ms of bytes on a v5e
    assert step["flops"] / 197e12 > 5 * step["bytes"] / 819e9
    # the decoder: one latent frame stays one frame through every stage
    from yardstick import shapes_wan

    assert shapes_wan._stage_frames(dict(config["vae"], dim=96), 1, True) == [1, 1, 1, 1]
    dec = shapes_qwen_image.decode_image(config, mix, 1)
    last = 2 * 27 * 96 * 96 * 1328 * 1328  # one 3x3x3 convolution of the last stage
    assert dec["flops"] > 6 * last and 19e12 < dec["flops"] < 21e12


def test_the_table_learns_the_system_prompt_and_the_fixed_text(tmp_path):
    """34 tokens through ``<|im_start|>user\\n`` as under the published table,
    5 of the fixed text and 5 after it, under two seeds; the harness's encoder
    and the program's agree id for id, and the cut by position is 34."""
    from comfyui_parallelanything_tpu.utils.tokenizer import (
        QWEN_IMAGE_CHAT_TEMPLATE, load_chat_tokenizer_json)

    config = run.load_json("configs", "qwen-image-tiny")
    assert QWEN_IMAGE_CHAT_TEMPLATE == reference_qwen_image.TEMPLATE
    tables = []
    for seed in (3, 2 ** 31 + 5):
        named, env = run.write_tokenizers(config, str(tmp_path / str(seed)), seed)
        ours = named["qwen"]
        ids = ours.pieces(reference_qwen_image.TEMPLATE.format(TEXT))
        assert len(ids) == 34 + 5 + 5
        turn = ours.special[reference_qwen_image.TURN]
        assert reference_qwen_image.prefix_length(ids, turn) == 34
        theirs = load_chat_tokenizer_json(env["PA_QWEN_TOKENIZER_JSON"], max_len=1058,
                                          template=QWEN_IMAGE_CHAT_TEMPLATE)
        got, mask = theirs([TEXT])
        assert got.shape == (1, 64) and list(got[0][:44]) == ids and mask.sum() == 44
        assert theirs.prefix_length(got[0]) == 34
        tables.append(ids)
    assert tables[0] != tables[1]  # another table under another seed, the same counts


def test_describe_reads_the_graph_as_sent():
    cell = run.load_cell(CELL)
    sched = traffic.Schedule(cell["mix"], 9, 45)
    g = traffic.fill_graph(cell["template"], cell["mix"], sched.request(3))
    req = reference_qwen_image.describe(g)
    assert req["seed"] == sched.request(3).noise_seed
    assert (req["steps"], req["cfg"], req["sampler_name"], req["scheduler"], req["shift"]) == (
        8, 1.0, "euler", "simple", 3.1)
    assert (req["width"], req["height"], req["batch_size"]) == (1328, 1328, 1)
    assert (req["positive"], req["negative"], req["lora_strength"]) == (TEXT, "", 1.0)
    assert (req["unet"], req["lora"], req["clip_name"], req["vae_name"]) == (
        "qwen_image_bf16.safetensors", "Qwen-Image-Lightning-8steps-V1.1.safetensors",
        "qwen_2.5_vl_7b.safetensors", "qwen_image_vae.safetensors")
    sig = reference_qwen_image.simple_sigmas(8, 3.1)
    assert list(np.round(sig, 4)) == [round(3.1 * t / (1 + 2.1 * t), 4)
                                      for t in np.arange(8, 0, -1) / 8] + [0.0]
    # the denoiser and its LoRA come first in the file: the tower's load finds
    # them on the chip
    assert list(g)[:4] == ["37", "73", "66", "38"]
    broken = json.loads(json.dumps(g))
    broken["38"]["inputs"]["type"] = "lumina2"
    with pytest.raises(ValueError):
        reference_qwen_image.describe(broken)


def test_the_synthesised_files_have_the_keys_the_converters_read(tmp_path):
    """The tiny twin's files through the program's own loaders: every key the
    converters ask for is there, nothing of the LoRA is unmatched."""
    from comfyui_parallelanything_tpu.models import loader, qwen_image_config
    from comfyui_parallelanything_tpu.models.convert import _lora_pairs
    from comfyui_parallelanything_tpu.models.text_encoders import qwen25_vl_7b_config
    from comfyui_parallelanything_tpu.models.video_vae import wan_vae_config

    config = run.load_cell(TWIN)["config_data"]
    files, _ = synth.write_checkpoints(str(tmp_path), 3, config)
    paths = list(files.values())
    presets = config["program_presets"]
    cfg = qwen_image_config(**{k: tuple(v) if isinstance(v, list) else v for k, v in presets[
        "comfyui_parallelanything_tpu.models:qwen_image_config"].items()})
    base = loader.load_qwen_image_checkpoint(paths[0], cfg)
    assert base.config.depth == config["transformer"]["num_layers"]
    lora = loader.open_safetensors(paths[1])
    assert len(_lora_pairs(lora)) == 12 * config["transformer"]["num_layers"]
    baked = loader.load_qwen_image_checkpoint(paths[0], cfg, lora=paths[1])
    a, b = (np.asarray(m.params["transformer_blocks_0"]["txt_mlp_in"]["kernel"], np.float32)
            for m in (baked, base))
    assert not np.array_equal(a, b)
    enc = loader.load_qwen25vl_checkpoint(paths[2], qwen25_vl_7b_config(**presets[
        "comfyui_parallelanything_tpu.models.text_encoders:qwen25_vl_7b_config"]))
    assert "norm" in enc.params and "bias" in enc.params["layers_0"]["v_proj"]
    vae = loader.load_wan_vae_checkpoint(paths[3], wan_vae_config(**presets[
        "comfyui_parallelanything_tpu.models.video_vae:wan_vae_config"]))
    assert "time_conv" in vae.params["decoder"]["up_3_upsample"]


def _run(capsys, *argv):
    run.main([*argv, "--rehearse"])
    out = capsys.readouterr().out.strip().splitlines()
    phases = {}
    for ln in out[:-1]:
        if ln.startswith("{"):
            doc = json.loads(ln)
            phases[doc["phase"]] = doc
    return json.loads(out[-1]), phases


@pytest.mark.parametrize("trace", [0, 1])
def test_the_whole_command_walks_on_the_twin(restorable, monkeypatch, capsys, trace):
    monkeypatch.setenv("PA_QWEN_TOKENIZER_JSON", "")  # run.py sets it: comes back
    line, phases = _run(capsys, "--workload", TWIN, "--seed", str(2 ** 31 + 42),
                        "--seconds", "4", "--trace", str(trace))
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert [os.path.dirname(f["file"]) for f in phases["synthesize"]["files"]] == [
        "models/diffusion_models", "models/loras", "models/text_encoders", "models/vae"]
    assert phases["synthesize"]["tokenizers"] == ["clip", "qwen"]
    gap = [c for c in phases["correct"]["compared"] if "image_gap" in c["number"]]
    assert len(gap) == 1 and all(0 < c["value"] <= c["limit"] for c in gap)
    exact = {c["number"]: c["value"] for c in phases["correct"]["compared"]
             if "image_gap" not in c["number"]}
    assert set(exact.values()) == {0}
    if trace:
        steps = next(c for c in phases["correct"]["compared"] if "sampler_steps" in c["number"])
        assert steps["asked"] == 8 and steps["seen"] == [8]
        assert line["metrics"]["programs.compiles_in_window"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"images_per_s", "time_to_image_p50_s", "setup_s"}


def test_every_new_metric_file_names_the_cell_and_an_existing_reader():
    from yardstick import readers

    doc = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in doc["per_layer"] if m["name"].startswith("qwenimage.")}
    assert set(entries) == {
        "qwenimage.step_ms", "qwenimage.denoiser_roofline", "qwenimage.decode_ms",
        "qwenimage.decode_roofline", "qwenimage.fused_attention_ms", "qwenimage.qk_prologue_ms",
        "qwenimage.steps_per_request", "qwenimage.denoiser_calls_per_request",
        "qwenimage.dispatch_ms", "qwenimage.png_encode_ms", "qwenimage.non_sampler_ms"}
    for name, entry in entries.items():
        m = run.load_json("layer_metrics", name)
        assert m["reader"] in readers.READERS and m["workloads"] == [CELL] == entry["workloads"]
        assert {k: m[k] for k in entry} == entry
    applies = {m["name"] for m in run.layer_metrics_for(
        CELL, {"images_per_s", "time_to_image_p50_s", "setup_s"})}
    without_a_list = {m["name"] for m in doc["per_layer"] if "workloads" not in m}
    assert applies == set(entries) | without_a_list
    assert {"step_mfu", "device.idle_share", "programs.compiles_in_window"} <= without_a_list
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("qwen-image", "b1-1328.closed", 1)
    assert cell == {k: run.load_json("workloads", CELL)[k] for k in cell}
    assert doc["workloads"][-1] == cell and doc["configs"][-1]["name"] == "qwen-image"


def test_the_reference_refuses_a_program_that_does_not_state_what_it_needs():
    """The reference asks the program's loaders what they STATE
    (``models/loader.CAPABILITIES``) once, at import, and a checkout that
    states less — the parent of the PR that brought this cell — leaves with a
    message and exit code 1 before a byte is written: seconds, not the 20 GB
    and the minutes of a run that would fail after them."""
    import subprocess
    import sys

    from comfyui_parallelanything_tpu.models import loader

    assert reference_qwen_image.NEEDS <= loader.CAPABILITIES
    code = ("import sys; sys.path[:0] = [%r, %r]\n"
            "from comfyui_parallelanything_tpu.models import loader\n"
            "loader.CAPABILITIES = frozenset({'wan-depth-from-file', 'residency'})\n"
            "import yardstick.reference_qwen_image\n") % (
        os.path.join(run.ROOT, "benchmark"), run.ROOT)
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert done.returncode == 1 and "cannot run here" in done.stderr
