"""The Wan2.2-T2V-A14B configuration: the layouts' counts, the shape
functions against a hand count, the synthesised files against the program's
converters, the graph as ``describe`` reads it, the whole command on the tiny
twin, and the metric files of the cell."""

import json
import os

import numpy as np
import pytest

import run
from yardstick import layout, layout_wan, reference_wan, shapes_wan, synth, traffic

CELL = "wan22-t2v-a14b-480p.closed"
TWIN = "wan22-t2v-a14b-tiny.closed"


def _count(config, sizes, fn):
    return layout.count(fn(config[sizes]))


def test_layout_counts_at_the_published_depth_at_the_cut_and_the_tower():
    config = run.load_cell(CELL)["config_data"]
    wan = config["wan"]
    assert wan["num_layers"] == 5
    at = {n: layout.count(layout_wan.wan_layout(dict(wan, num_layers=n))) for n in (0, 1, 5, 40)}
    assert at[1] - at[0] == 351_394_304
    assert at[0] == 232_719_424
    assert at[40] == 14_288_491_584
    assert at[5] == 1_989_690_944
    assert _count(config, "text_t5", layout_wan.umt5_layout) == 5_680_910_336
    assert _count(config, "vae", layout_wan.wan_vae_layout) == 126_892_531
    assert _count(config, "lora", layout_wan.wan_lora_layout) == 5 * 10 * 1 + 64 * 5 * (
        8 * 2 * 5120 + 2 * (5120 + 13824))
    stated = config["checkpoint"]["parameters"]
    assert (stated["wan_block"], stated["wan_besides_blocks"], stated["wan"],
            stated["wan_published_depth"], stated["text_t5"], stated["vae"]) == (
        at[1] - at[0], at[0], at[5], at[40], 5_680_910_336, 126_892_531)
    # every block of UMT5 has its own position table; 24 of them
    tables = [k for k, _, _ in layout_wan.umt5_layout(config["text_t5"])
              if k.endswith("relative_attention_bias.weight")]
    assert len(tables) == 24


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    doc = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    entry = next(c for c in doc["configs"] if c["name"] == "wan22-t2v-a14b")
    config = run.load_json("configs", "wan22-t2v-a14b")
    assert entry["reduced"] == config["reduced"] == ["num_layers"]
    assert entry["source"] == config["source"] and "Wan-AI/Wan2.2-T2V-A14B" in entry["source"]
    published = {"dim": 5120, "ffn_dim": 13824, "freq_dim": 256, "in_dim": 16, "out_dim": 16,
                 "num_heads": 40, "text_len": 512, "text_dim": 4096, "eps": 1e-06,
                 "patch_size": [1, 2, 2], "qk_norm": True, "cross_attn_norm": True,
                 "boundary": 0.875}
    assert {k: config["wan"][k] for k in published} == published
    assert config["wan"]["num_layers"] == 5  # published: 40
    t5 = config["text_t5"]
    assert (t5["num_layers"], t5["d_model"], t5["d_ff"], t5["num_heads"], t5["d_kv"],
            t5["vocab_size"], t5["relative_attention_num_buckets"],
            t5["relative_attention_max_distance"]) == (24, 4096, 10240, 64, 64, 256384, 32, 128)
    vae = config["vae"]
    assert (vae["dim"], vae["z_dim"], vae["dim_mult"], vae["num_res_blocks"],
            vae["temperal_downsample"], vae["stride"]) == (
        96, 16, [1, 2, 4, 4], 2, [False, True, True], [4, 8, 8])
    assert len(vae["latents_mean"]) == len(vae["latents_std"]) == 16
    assert config["deployment"]["chips"] == 8 and config["precision"] == "bfloat16"
    for key in ("shift", "lora", "temporal_upsampler", "tokenizer", "graph"):
        assert key in config["assumed"]
    files = [f["file"] for f in config["checkpoint"]["files"]]
    assert [os.path.dirname(f) for f in files] == [
        "models/diffusion_models", "models/diffusion_models", "models/loras",
        "models/loras", "models/text_encoders", "models/vae"]
    tok = config["tokenizers"][0]
    assert (tok["writer"], tok["vocab_size"], tok["max_length"], tok["env"]) == (
        "tokenizer_unigram", 256384, 512, {"PA_T5_TOKENIZER_JSON": "tokenizer_json"})
    assert set(config["trace_modules"]) >= {"denoiser", "decode", "text"}


def test_shapes_against_a_hand_count():
    """One forward at the cut and the cell's clip: a block is 12.12 TFLOP of
    linear layers on the tokens, 8.42 of self-attention and 0.27 of
    cross-attention (its two text projections among them)."""
    cell = run.load_cell(CELL)
    config, mix = cell["config_data"], cell["mix"]
    assert shapes_wan.clip_shape(config, mix) == (13, 60, 104)
    n = shapes_wan.tokens(config, mix)
    assert n == 13 * 30 * 52 == 20280
    d, ff, txt = 5120, 13824, 512
    own = 4 * n * n * d
    cross = 4 * n * txt * d
    assert shapes_wan.self_attention(config, mix, 1)["flops"] == own == 8_422_981_632_000
    assert shapes_wan.cross_attention(config, mix, 1)["flops"] == cross
    block = 2 * n * (6 * d * d + 2 * d * ff) + own + cross + 2 * txt * 2 * d * d
    outside = 2 * n * (64 * d + d * 64) + 2 * txt * (4096 * d + d * d) \
        + 2 * (256 * d + d * d + d * 6 * d)
    step = shapes_wan.denoiser_step(config, mix, 1)
    assert step["flops"] == 5 * block + outside
    assert 104.0e12 < step["flops"] < 104.3e12
    assert step["params"] == 1_989_690_944
    # compute-bound: 528 ms of operations against 38 ms of bytes on a v5e
    assert step["flops"] / 197e12 > 10 * step["bytes"] / 819e9
    # the decoder: 13 latent frames become 13 / 25 / 49 / 49 frames by stage
    assert shapes_wan._stage_frames(config["vae"], 13, True) == [13, 25, 49, 49]
    assert shapes_wan._stage_frames(config["vae"], 1, False) == [1, 2, 4, 4]
    dec = shapes_wan.decode_clip(config, mix, 1)
    last = 2 * 27 * 96 * 96 * 49 * 480 * 832  # one 3x3x3 convolution of the last stage
    assert dec["flops"] > 6 * last and 166e12 < dec["flops"] < 168e12
    # frames are traffic: at 81 the same functions give the source's default clip
    mix81 = {"latent": dict(mix["latent"], batch_size=81)}
    assert shapes_wan.tokens(config, mix81) == 21 * 30 * 52


def test_describe_reads_the_graph_as_sent():
    cell = run.load_cell(CELL)
    sched = traffic.Schedule(cell["mix"], 9, 45)
    g = traffic.fill_graph(cell["template"], cell["mix"], sched.request(3))
    req = reference_wan.describe(g)
    assert req["seed"] == sched.request(3).noise_seed
    assert (req["steps"], req["switch_step"], req["cfg"], req["sampler_name"],
            req["scheduler"], req["shift"]) == (4, 2, 1.0, "euler", "simple", 5.0)
    assert (req["width"], req["height"], req["frames"]) == (832, 480, 49)
    assert g["40"]["inputs"]["length"] == 49 and g["40"]["inputs"]["batch_size"] == 1
    assert req["lora_strengths"] == [1.0, 1.0]
    assert "high_noise" in req["experts"][0]["unet"] and "low_noise" in req["experts"][1]["unet"]
    assert list(np.round(reference_wan.simple_sigmas(4, 5.0), 4)) == [
        1.0, 0.9375, 0.8333, 0.625, 0.0]
    # the tower and its encodes come first in the file: it has spoken before
    # the experts load
    assert list(g)[:3] == ["38", "6", "7"]
    broken = json.loads(json.dumps(g))
    broken["58"]["inputs"]["start_at_step"] = 3
    with pytest.raises(ValueError):
        reference_wan.describe(broken)


def test_the_synthesised_files_have_the_keys_the_converters_read(tmp_path):
    """The tiny twin's files through the program's own loaders: every key the
    converters ask for is there, nothing of the LoRA is unmatched, and the
    experts differ."""
    from comfyui_parallelanything_tpu.models import load_wan_checkpoint, loader
    from comfyui_parallelanything_tpu.models.convert import _lora_pairs
    from comfyui_parallelanything_tpu.models.wan import wan_1_3b_config
    from comfyui_parallelanything_tpu.models.text_encoders import umt5_xxl_config
    from comfyui_parallelanything_tpu.models.video_vae import wan_vae_config

    config = run.load_cell(TWIN)["config_data"]
    files, _ = synth.write_checkpoints(str(tmp_path), 3, config)
    paths = list(files.values())
    m = config["wan"]
    cfg = wan_1_3b_config(hidden_size=m["dim"], ffn_dim=m["ffn_dim"], num_heads=m["num_heads"],
                          text_dim=m["text_dim"], freq_dim=m["freq_dim"])
    experts = [load_wan_checkpoint(p, cfg) for p in paths[:2]]
    assert all(e.config.depth == m["num_layers"] for e in experts)
    lora = loader.open_safetensors(paths[2])
    assert len(_lora_pairs(lora)) == 10 * m["num_layers"]
    baked = load_wan_checkpoint(paths[0], cfg, lora=paths[2])
    a = np.asarray(baked.params["blocks_0"]["ffn_in"]["kernel"], np.float32)
    b = np.asarray(experts[0].params["blocks_0"]["ffn_in"]["kernel"], np.float32)
    assert not np.array_equal(a, b)
    t = config["text_t5"]
    enc = loader.load_t5_checkpoint(paths[4], umt5_xxl_config(
        vocab_size=t["vocab_size"], d_model=t["d_model"], num_layers=t["num_layers"],
        num_heads=t["num_heads"], d_kv=t["d_kv"], d_ff=t["d_ff"]))
    assert "rel_bias_1" in enc.params
    v = config["vae"]
    vae = loader.load_wan_vae_checkpoint(paths[5], wan_vae_config(
        base_channels=v["dim"], num_res_blocks=v["num_res_blocks"]))
    assert "time_conv" in vae.params["decoder"]["up_3_upsample"]
    assert "time_conv" not in vae.params["decoder"]["up_1_upsample"]


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
def test_the_whole_command_walks_on_the_twin(restorable, capsys, trace):
    line, phases = _run(capsys, "--workload", TWIN, "--seed", str(2 ** 31 + 39),
                        "--seconds", "6", "--trace", str(trace))
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert [os.path.dirname(f["file"]) for f in phases["synthesize"]["files"]] == [
        "models/diffusion_models", "models/diffusion_models", "models/loras",
        "models/loras", "models/text_encoders", "models/vae"]
    assert phases["synthesize"]["tokenizers"] == ["clip", "t5"]
    assert len(phases["reference"]["rows"]) == 3
    gap = [c for c in phases["correct"]["compared"] if "image_gap" in c["number"]]
    assert len(gap) == 3 and all(0 < c["value"] <= c["limit"] for c in gap)
    exact = {c["number"]: c["value"] for c in phases["correct"]["compared"]
             if "image_gap" not in c["number"]}
    assert set(exact.values()) == {0}
    if trace:
        steps = next(c for c in phases["correct"]["compared"] if "sampler_steps" in c["number"])
        assert steps["asked"] == 4 and steps["seen"] == [4]
        assert line["metrics"]["programs.compiles_in_window"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"images_per_s", "time_to_image_p50_s", "setup_s"}


def test_every_new_metric_file_names_the_cell_and_an_existing_reader():
    from yardstick import readers

    doc = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in doc["per_layer"] if m["name"].startswith("wan.")}
    assert set(entries) == {
        "wan.step_ms", "wan.denoiser_roofline", "wan.decode_ms", "wan.decode_roofline",
        "wan.fused_attention_ms", "wan.steps_per_request", "wan.denoiser_calls_per_request",
        "wan.dispatch_ms", "wan.png_encode_ms", "wan.non_sampler_ms", "wan.fetch_ms"}
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
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("wan22-t2v-a14b", "v480p.closed", 1)
    assert cell == {k: run.load_json("workloads", CELL)[k] for k in cell}


def test_the_reference_refuses_a_program_that_does_not_state_what_it_needs(monkeypatch):
    """Importing the reference asks nothing; building one asks the program's
    loaders what they STATE (``models/loader.CAPABILITIES``), and a checkout
    that states less — the parent of the PR that brought this cell states
    nothing — leaves with a message and a non-zero code before a tensor is
    read."""
    from comfyui_parallelanything_tpu.models import loader
    from yardstick import reference_wan

    config = run.load_json("configs", "wan22-t2v-a14b")
    assert reference_wan.NEEDS <= loader.CAPABILITIES
    reference_wan.Reference(config, "nowhere", None, "float32")  # reads nothing yet
    for stated in (frozenset(), frozenset({"residency"})):
        monkeypatch.setattr(loader, "CAPABILITIES", stated)
        with pytest.raises(SystemExit, match="cannot run here"):
            reference_wan.Reference(config, "nowhere", None, "float32")
    monkeypatch.delattr(loader, "CAPABILITIES")
    with pytest.raises(SystemExit, match="wan-depth-from-file"):
        reference_wan.Reference(config, "nowhere", None, "float32")

