"""The FLUX.1-schnell configuration's own modules at tiny widths on the CPU:
the layout's parameter counts at the published depths and at the cut, the
shape functions against a hand count, what ``describe`` reads off the graph,
the ``unique`` mix's texts through both unigram encoders id for id, and the
whole command walked on the twin."""

import json
import os

import numpy as np
import pytest

import run
from yardstick import layout, layout_flux, reference_flux, shapes_flux, traffic

CELL, TWIN = "flux-schnell-b1-1024.closed-unique", "flux-schnell-tiny.closed-unique"


def _count(config, sizes):
    part = next(p for s in config["checkpoint"]["files"] for p in s["parts"]
                if p["sizes"] == sizes)
    return layout.count(layout.checkpoint_layout(config, [part]))


def test_layout_counts_at_the_published_depths_and_at_the_cut():
    c = run.load_json("configs", "flux-schnell")
    pinned = c["checkpoint"]["parameters"]
    for sizes, n in pinned.items():
        assert _count(c, sizes) == n, sizes
    whole = dict(c, flux=dict(c["flux"], depth=19, depth_single_blocks=38))
    # "12B" as published: flux1-schnell without dev's guidance embedder
    assert _count(whole, "flux") == 11_891_178_560
    # a double block 339.8 M, a single one 141.6 M, 3 + 6 of them and 54 M of
    # embedders and last layer
    assert pinned["flux"] == 1_922_939_968
    one_double = _count(dict(c, flux=dict(c["flux"], depth=4)), "flux") - pinned["flux"]
    one_single = _count(dict(c, flux=dict(c["flux"], depth_single_blocks=7)), "flux") - pinned["flux"]
    assert (one_double, one_single) == (339_831_296, 141_591_808)
    # T5-XXL whole (google/t5-v1_1-xxl's encoder), CLIP-L, the 16-channel autoencoder
    assert pinned["text_t5"] == 4_762_310_656
    assert pinned["text"] == run.load_json("configs", "sdxl")["checkpoint"]["parameters"]["text"]
    assert pinned["vae"] == run.load_json("configs", "sd35m")["checkpoint"]["parameters"]["vae"]
    keys = {k for k, _, _ in layout_flux.flux_layout(c["flux"])}
    assert "double_blocks.2.txt_attn.norm.key_norm.scale" in keys
    assert "double_blocks.3.img_mod.lin.weight" not in keys
    assert "single_blocks.5.linear1.weight" in keys and "single_blocks.6.linear2.bias" not in keys
    assert not any(k.startswith("guidance_in.") for k in keys)
    shapes = {k: s for k, s, _ in layout_flux.flux_layout(c["flux"])}
    assert shapes["single_blocks.0.linear1.weight"] == (3 * 3072 + 12288, 3072)
    assert shapes["single_blocks.0.linear2.weight"] == (3072, 3072 + 12288)
    assert shapes["img_in.weight"] == (3072, 64) and shapes["txt_in.weight"] == (3072, 4096)


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    c = run.load_json("configs", "flux-schnell")
    m = c["flux"]
    assert (m["hidden_size"], m["num_heads"], m["mlp_ratio"], m["axes_dim"], m["theta"],
            m["context_in_dim"], m["vec_in_dim"], m["in_channels"], m["qkv_bias"],
            m["guidance_embed"]) == (3072, 24, 4.0, [16, 56, 56], 10000, 4096, 768, 64,
                                     True, False)
    assert (m["depth"], m["depth_single_blocks"]) == (3, 6)
    assert c["reduced"] == ["depth", "depth_single_blocks"]
    t = c["text_t5"]
    assert (t["d_model"], t["num_layers"], t["num_heads"], t["d_kv"], t["d_ff"]) == (
        4096, 24, 64, 64, 10240) and t["attention_mask"] is False
    assert c["tokenizers"][0]["max_length"] == 256 and c["tokenizers"][0]["vocab_size"] == 32100
    assert (c["vae"]["scale_factor"], c["vae"]["shift_factor"]) == (0.3611, 0.1159)
    assert [f["dtype"] for f in c["checkpoint"]["files"]] == [
        "bfloat16", "float16", "float16", "float16"]
    doc = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    entry = next(e for e in doc["configs"] if e["name"] == "flux-schnell")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "flux-schnell", "b1-1024.closed-unique", 1)
    mix = run.load_json("traffic", cell["traffic"])
    assert mix["draws"]["prompt"] == {"kind": "unique", "words": 8}
    assert mix["draws"]["negative"] == "" and mix["clients"] == 1 and mix["loop"] == "closed"


def test_shapes_against_a_hand_count_at_tiny_sizes():
    """One row, hidden 128 (4 heads of 32), 2 double and 4 single blocks, 144
    image tokens and 256 text tokens, counted by hand."""
    c = run.load_json("configs", "flux-schnell-tiny")
    h, t, n, mlp, ctx, vec = 128, 144, 256, 512, 192, 64
    s = t + n
    flops = params = 0

    def lin(i, o, tok):
        nonlocal flops, params
        flops += 2 * i * o * tok
        params += i * o + o

    lin(64, h, t), lin(ctx, h, n)
    for width in (256, vec):
        lin(width, h, 1), lin(h, h, 1)
    for _ in range(2):
        for tok in (t, n):
            lin(h, 6 * h, 1), lin(h, 3 * h, tok), lin(h, h, tok)
            lin(h, mlp, tok), lin(mlp, h, tok)
            params += 2 * 32
        flops += 4 * s * s * h
    for _ in range(4):
        lin(h, 3 * h, 1), lin(h, 3 * h + mlp, s), lin(h + mlp, h, s)
        flops += 4 * s * s * h
        params += 2 * 32
    lin(h, 2 * h, 1), lin(h, 64, t)
    got = shapes_flux.flux_forward(c["flux"], 1, t, n)
    assert (got["flops"], got["params"]) == (flops, params)
    assert params == layout.count(layout_flux.flux_layout(c["flux"]))
    mix = {"latent": {"width": 192, "height": 192, "batch_size": 1}}
    assert shapes_flux.denoiser_step(c, mix, 1)["flops"] == flops
    assert shapes_flux.joint_attention(c, mix, 1)["flops"] == 4 * s * s * h


def test_the_cells_step_its_attention_class_and_its_towers():
    c, mix = run.load_json("configs", "flux-schnell"), run.load_json(
        "traffic", "b1-1024.closed-unique")
    step = shapes_flux.denoiser_step(c, mix, 1)
    att = shapes_flux.joint_attention(c, mix, 1)
    # ISSUE 32's reckoning: 1.218 TFLOP a block at 4352 tokens, 0.233 of it attention
    assert abs(att["flops"] / 0.2328e12 - 1) < 0.01
    assert abs(step["flops"] / (9 * 1.218e12) - 1) < 0.02 and step["params"] == 1_922_939_968
    towers = shapes_flux.text_towers(c, mix, 1)
    assert abs(towers["flops"] / 2.4e12 - 1) < 0.1 and abs(towers["bytes"] / 10.6e9 - 1) < 0.02  # 9.7 GB of kernels, 0.9 of activations


def test_describe_reads_the_graph_as_sent():
    cell = run.load_cell(CELL)
    req = traffic.Schedule(cell["mix"], 7, 45).request(2)
    graph = traffic.fill_graph(cell["template"], cell["mix"], req)
    d = reference_flux.describe(graph)
    assert (d["steps"], d["cfg"], d["sampler_name"], d["scheduler"]) == (4, 1.0, "euler", "simple")
    assert (d["width"], d["height"], d["batch_size"]) == (1024, 1024, 1)
    assert d["seed"] == req.noise_seed and d["positive"] == req.positive and d["negative"] == ""
    assert len(req.positive.split()) == 8
    graph["12"]["class_type"] = "CheckpointLoaderSimple"
    with pytest.raises(ValueError, match="does not know"):
        reference_flux.describe(graph)
    graph["12"]["class_type"] = "UNETLoader"
    graph["11"]["inputs"]["type"] = "sd3"
    with pytest.raises(ValueError, match="type flux"):
        reference_flux.describe(graph)
    np.testing.assert_allclose(reference_flux.schnell_schedule(4), [1, .75, .5, .25, 0])


def test_the_unique_mixs_texts_encode_id_for_id_on_both_sides(tmp_path):
    """Every request brings a new eight-word text: the harness's Viterbi
    encoder and the program's (the ``tokenizers`` package on the written
    ``tokenizer.json``) give the same 256 ids, ``</s>`` once, id 0 after."""
    from comfyui_parallelanything_tpu.utils.tokenizer import load_tokenizer_json

    cell = run.load_cell(CELL)
    named, env = run.write_tokenizers(cell["config_data"], str(tmp_path), 2 ** 31 + 77)
    theirs = load_tokenizer_json(env["PA_T5_TOKENIZER_JSON"], max_len=256, eos_id=1)
    sched = traffic.Schedule(cell["mix"], 2 ** 31 + 77, 45)
    texts = [sched.request(i).positive for i in range(40)] + [""]
    assert len(set(texts)) == 41
    for text in texts:
        ours = named["t5"].ids(text)
        ids, mask = theirs([text])
        assert ours.shape == (256,) and list(ours) == list(np.asarray(ids)[0]), text
        n = int(np.asarray(mask)[0].sum())
        assert ours[n - 1] == 1 and (ours[n:] == 0).all() and n <= 80


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
    line, phases = _run(capsys, "--workload", TWIN, "--seed", str(2 ** 31 + 45),
                        "--seconds", "6", "--trace", str(trace))
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert [f["file"] for f in phases["synthesize"]["files"]] == [
        "models/diffusion_models/flux1-schnell.safetensors",
        "models/text_encoders/t5xxl_fp16.safetensors",
        "models/text_encoders/clip_l.safetensors", "models/vae/ae.safetensors"]
    assert phases["synthesize"]["tokenizers"] == ["clip", "t5"]
    gap = [c for c in phases["correct"]["compared"] if "image_gap" in c["number"]]
    assert len(gap) == 1 and 0 < gap[0]["value"] <= gap[0]["limit"]
    if trace:
        steps = next(c for c in phases["correct"]["compared"] if "sampler_steps" in c["number"])
        assert steps["asked"] == 4 and steps["seen"] == [4]
        assert line["metrics"]["programs.compiles_in_window"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"images_per_s", "time_to_image_p50_s", "setup_s"}


def test_every_new_metric_file_names_the_cell_and_an_existing_reader():
    from yardstick import readers

    doc = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in doc["per_layer"] if m["name"].startswith("flux.")}
    assert len(entries) == 11
    for name, entry in entries.items():
        m = run.load_json("layer_metrics", name)
        assert m["reader"] in readers.READERS and m["workloads"] == [CELL] == entry["workloads"]
        assert {k: m[k] for k in entry} == entry
    applies = {m["name"] for m in run.layer_metrics_for(
        CELL, {"images_per_s", "time_to_image_p50_s", "setup_s"})}
    assert applies == set(entries) | {"device.idle_share", "programs.compiles_in_window",
                                      "server.overhead_ms", "server.queue_wait_ms", "step_mfu"}
