"""The Z-Image-Turbo configuration's own modules at tiny widths on the CPU:
the layout's parameter counts at the published depth, at the cut and for the
tower, the shape functions against a hand count, what ``describe`` reads off
the graph, the seeded byte-level BPE table (one token a word, a constant
count for the mix's texts, id for id with the program's encoder), and the
whole command walked on the twin."""

import json
import os

import numpy as np
import pytest

import run
from yardstick import (layout, layout_zimage, reference_zimage, shapes_zimage,
                       tokenizer_bpe, traffic)

CELL, TWIN = "zimage-turbo-b1-1024.closed-unique", "zimage-turbo-tiny.closed-unique"


def _count(config, sizes):
    part = next(p for s in config["checkpoint"]["files"] for p in s["parts"]
                if p["sizes"] == sizes)
    return layout.count(layout.checkpoint_layout(config, [part]))


def test_layout_counts_at_the_published_depth_at_the_cut_and_the_tower():
    c = run.load_json("configs", "zimage-turbo")
    pinned = c["checkpoint"]["parameters"]
    for sizes in ("zimage", "text", "vae"):
        assert _count(c, sizes) == pinned[sizes], sizes
    whole = dict(c, zimage=dict(c["zimage"], n_layers=30))
    # "6B" as published: 6,155 M; at the cut 2,175 M; Qwen3-4B 4,022 M
    assert _count(whole, "zimage") == pinned["zimage_published_depth"] == 6_154_908_736
    assert pinned["zimage"] == 2_174_881_344 and pinned["text"] == 4_022_468_096
    # a modulated block 180.91 M, an unmodulated one less its modulation
    one = _count(dict(c, zimage=dict(c["zimage"], n_layers=9)), "zimage") - pinned["zimage"]
    assert one == 180_910_336 and one - (256 * 4 * 3840 + 4 * 3840) == 176_962_816
    assert pinned["vae"] == run.load_json("configs", "flux-schnell")["checkpoint"]["parameters"]["vae"]
    shapes = {k: s for k, s, _ in layout_zimage.zimage_layout(c["zimage"])}
    assert shapes["layers.7.attention.to_q.weight"] == (3840, 3840)
    assert "layers.8.attention.to_q.weight" not in shapes
    assert shapes["layers.0.feed_forward.w1.weight"] == (10240, 3840)
    assert shapes["layers.0.feed_forward.w2.weight"] == (3840, 10240)
    assert shapes["layers.0.adaLN_modulation.0.weight"] == (4 * 3840, 256)
    assert shapes["noise_refiner.1.adaLN_modulation.0.bias"] == (4 * 3840,)
    assert "context_refiner.0.adaLN_modulation.0.weight" not in shapes
    assert shapes["context_refiner.1.attention.norm_k.weight"] == (128,)
    assert not any(k.endswith("to_q.bias") or k.endswith("w1.bias") for k in shapes)
    assert shapes["all_x_embedder.2-1.weight"] == (3840, 64)
    assert shapes["all_final_layer.2-1.adaLN_modulation.1.weight"] == (3840, 256)
    assert shapes["cap_embedder.1.weight"] == (3840, 2560) and shapes["x_pad_token"] == (1, 3840)
    assert shapes["t_embedder.mlp.0.weight"] == (1024, 256)
    tower = {k: s for k, s, _ in layout_zimage.qwen3_layout(c["text"])}
    assert tower["model.layers.35.self_attn.q_proj.weight"] == (4096, 2560)
    assert tower["model.layers.0.self_attn.k_proj.weight"] == (1024, 2560)
    assert tower["model.layers.0.self_attn.q_norm.weight"] == (128,)
    assert tower["model.embed_tokens.weight"] == (151936, 2560) and "lm_head.weight" not in tower


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    c = run.load_json("configs", "zimage-turbo")
    m = c["zimage"]
    assert (m["dim"], m["n_heads"], m["n_kv_heads"], m["n_refiner_layers"], m["cap_feat_dim"],
            m["axes_dims"], m["rope_theta"], m["t_scale"], m["norm_eps"], m["qk_norm"],
            m["in_channels"], m["all_patch_size"]) == (
        3840, 30, 30, 2, 2560, [32, 48, 48], 256.0, 1000.0, 1e-5, True, 16, [2])
    assert layout_zimage.ffn_hidden(m) == 10240 and layout_zimage.head_dim(m) == 128
    assert m["n_layers"] == 8 and c["reduced"] == ["n_layers"]
    t = c["text"]
    assert (t["hidden_size"], t["num_hidden_layers"], t["num_attention_heads"],
            t["num_key_value_heads"], t["head_dim"], t["intermediate_size"], t["vocab_size"],
            t["rope_theta"]) == (2560, 36, 32, 8, 128, 9728, 151936, 1000000)
    assert (c["vae"]["scale_factor"], c["vae"]["shift_factor"]) == (0.3611, 0.1159)
    assert [f["dtype"] for f in c["checkpoint"]["files"]] == ["bfloat16", "bfloat16", "float16"]
    assert c["tokenizers"][0]["vocab_size"] == 151936
    assert c["tokenizers"][0]["env"] == {"PA_QWEN_TOKENIZER_JSON": "tokenizer_json"}
    assert c["schedule"]["shift"] == 3.0 and c["precision"] == "bfloat16"
    doc = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    entry = next(e for e in doc["configs"] if e["name"] == "zimage-turbo")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    assert len(c["source"]) <= 200
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "zimage-turbo", "b1-1024.closed-unique", 1)
    assert len(doc["workloads"]) == 5 and not [w for w in doc["workloads"] if w["chips"] != 1]


def test_shapes_against_a_hand_count_at_tiny_sizes():
    """One row, dim 128 (4 heads of 32, SwiGLU 341), 2 + 2 refiner and 3 main
    layers, 144 image tokens padded to 160 and 16 caption tokens padded to 32,
    counted by hand."""
    c = run.load_json("configs", "zimage-turbo-tiny")
    d, ff, img, cap, feat = 128, 341, 160, 32, 64
    flops = params = 0

    def lin(i, o, tok, bias=True):
        nonlocal flops, params
        flops += 2 * i * o * tok
        params += i * o + (o if bias else 0)

    def block(seq, modulated):
        nonlocal flops, params
        if modulated:
            lin(128, 4 * d, 1)
        for _ in range(4):
            lin(d, d, seq, bias=False)
        flops += 4 * seq * seq * d
        lin(d, ff, seq, False), lin(d, ff, seq, False), lin(ff, d, seq, False)
        params += 4 * d + 2 * 32

    lin(64, d, img), lin(feat, d, cap), lin(256, 1024, 1), lin(1024, 128, 1)
    params += feat + 2 * d
    for _ in range(2):
        block(img, True), block(cap, False)
    for _ in range(3):
        block(img + cap, True)
    lin(128, d, 1), lin(d, 64, img)
    assert layout_zimage.ffn_hidden(c["zimage"]) == ff
    got = shapes_zimage.zimage_forward(c["zimage"], 1, img, cap)
    assert (got["flops"], got["params"]) == (flops, params)
    assert params == layout.count(layout_zimage.zimage_layout(c["zimage"]))
    mix = run.load_json("traffic", "tiny-xl.closed-unique")
    assert shapes_zimage.caption_tokens(mix) == 16
    assert shapes_zimage.denoiser_step(c, mix, 1)["flops"] == flops
    assert shapes_zimage.joint_attention(c, mix, 1)["flops"] == 4 * 192 * 192 * d
    assert shapes_zimage.refiner_attention(c, mix, 1)["flops"] == 4 * 160 * 160 * d
    tower = shapes_zimage.qwen3_forward(c["text"], 1, 32)
    whole = shapes_zimage.qwen3_forward(c["text"], 1, 32, layers=3)
    # two of the three layers run; with the embedding and the final norm the
    # third makes the file's count
    assert whole["params"] + 151936 * 64 + 64 == layout.count(
        layout_zimage.qwen3_layout(c["text"]))
    assert tower["params"] * 3 == whole["params"] * 2


def test_the_cells_step_its_attention_classes_and_its_tower():
    c, mix = run.load_json("configs", "zimage-turbo"), run.load_json(
        "traffic", "b1-1024.closed-unique")
    step = shapes_zimage.denoiser_step(c, mix, 1)
    # ISSUE 34's reckoning: 17.22 TFLOP a forward at 4128 tokens, floor 87.4 ms
    assert abs(step["flops"] / 17.22e12 - 1) < 0.002 and step["params"] == 2_174_881_344
    assert abs(step["flops"] / 197e12 * 1e3 - 87.4) < 0.1
    assert abs(shapes_zimage.joint_attention(c, mix, 1)["flops"] / 0.262e12 - 1) < 0.01
    assert abs(shapes_zimage.refiner_attention(c, mix, 1)["flops"] / 0.258e12 - 1) < 0.01
    tower = shapes_zimage.text_tower(c, mix, 1)
    # 35 layers' weights read once: 7.06 GB, 8.6 ms at 819 GB/s (+ activations)
    assert abs(tower["bytes"] / 7.06e9 - 1) < 0.03


def test_describe_reads_the_graph_as_sent():
    cell = run.load_cell(CELL)
    req = traffic.Schedule(cell["mix"], 7, 45).request(2)
    graph = traffic.fill_graph(cell["template"], cell["mix"], req)
    d = reference_zimage.describe(graph)
    assert (d["steps"], d["cfg"], d["sampler_name"], d["scheduler"], d["shift"]) == (
        8, 1.0, "euler", "simple", 3.0)
    assert (d["width"], d["height"], d["batch_size"]) == (1024, 1024, 1)
    assert d["seed"] == req.noise_seed and d["positive"] == req.positive and d["negative"] == ""
    assert {n["class_type"] for n in graph.values()} == {
        "UNETLoader", "CLIPLoader", "VAELoader", "ModelSamplingAuraFlow", "CLIPTextEncode",
        "EmptySD3LatentImage", "KSampler", "VAEDecode", "SaveImage"}
    graph["3"]["inputs"]["model"] = ["12", 0]
    with pytest.raises(ValueError, match="ModelSamplingAuraFlow"):
        reference_zimage.describe(graph)
    graph["3"]["inputs"]["model"] = ["13", 0]
    graph["11"]["inputs"]["type"] = "flux"
    with pytest.raises(ValueError, match="lumina2"):
        reference_zimage.describe(graph)
    # the simple scheduler on the shift-3 table: sigma(t) = 3t / (1 + 2t)
    t = np.asarray([1, .875, .75, .625, .5, .375, .25, .125])
    np.testing.assert_allclose(reference_zimage.simple_sigmas(8, 3.0),
                               [*(3 * t / (1 + 2 * t)), 0.0], atol=1e-12)


def test_one_token_a_word_and_a_constant_count_id_for_id_on_both_sides(tmp_path):
    """The seeded table makes every word of ``words.txt`` ONE token, with and
    without its leading space, so the mix's eight-word texts are 16 tokens
    with the chat template whatever the seed; the harness's encoder and the
    program's (the ``tokenizers`` package on the written ``tokenizer.json``)
    agree id for id, on other text too; the specials sit at their published
    ids."""
    from comfyui_parallelanything_tpu.utils.tokenizer import load_chat_tokenizer_json

    cell = run.load_cell(CELL)
    seed = 2 ** 31 + 77
    named, env = run.write_tokenizers(cell["config_data"], str(tmp_path), seed)
    ours = named["qwen"]
    theirs = load_chat_tokenizer_json(env["PA_QWEN_TOKENIZER_JSON"])
    assert (ours.special["<|endoftext|>"], ours.special["<|im_start|>"],
            ours.special["<|im_end|>"], ours.special["</think>"]) == (
        151643, 151644, 151645, 151668)
    assert ours.size == 151669 and theirs.pad_id == 151643
    for w in tokenizer_bpe._words() + ["user", "assistant"]:
        assert len(ours.pieces(w)) == 1 == len(ours.pieces(" " + w)), w
    sched = traffic.Schedule(cell["mix"], seed, 45)
    texts = [sched.request(i).positive for i in range(40)]
    assert len(set(texts)) == 40
    for text in texts + ["", "A photo of 3 cats, they're happy!\n  two  spaces",
                         "harborlantern x", "it's 42 o'clock -- (ok)\t tab"]:
        a = ours.ids(text)
        ids, mask = theirs([text])
        n = int(mask[0].sum())
        assert list(a) == list(ids[0][:n]), text
        assert ids.shape[1] % 32 == 0 and (ids[0][n:] == 151643).all()
        if text in texts:
            assert n == 16 and ids.shape == (1, 32)
    assert list(ours.ids("")[:3]) == [151644, ours.vocab["user"], ours.vocab["Ċ"]]
    assert len(ours.ids("")) == 8
    # another seed, another table, the same counts
    other, _ = run.write_tokenizers(cell["config_data"], str(tmp_path / "b"), 5)
    assert len(other["qwen"].ids(texts[0])) == 16
    assert list(other["qwen"].ids(texts[0])) != list(ours.ids(texts[0]))


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
    line, phases = _run(capsys, "--workload", TWIN, "--seed", str(2 ** 31 + 45),
                        "--seconds", "6", "--trace", str(trace))
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert [f["file"] for f in phases["synthesize"]["files"]] == [
        "models/diffusion_models/z_image_turbo_bf16.safetensors",
        "models/text_encoders/qwen_3_4b.safetensors", "models/vae/ae.safetensors"]
    assert phases["synthesize"]["tokenizers"] == ["clip", "qwen"]
    gap = [c for c in phases["correct"]["compared"] if "image_gap" in c["number"]]
    assert len(gap) == 1 and 0 < gap[0]["value"] <= gap[0]["limit"]
    if trace:
        steps = next(c for c in phases["correct"]["compared"] if "sampler_steps" in c["number"])
        assert steps["asked"] == 8 and steps["seen"] == [8]
        assert line["metrics"]["programs.compiles_in_window"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"images_per_s", "time_to_image_p50_s", "setup_s"}


def test_every_new_metric_file_names_the_cell_and_an_existing_reader():
    from yardstick import readers

    doc = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in doc["per_layer"] if m["name"].startswith("zimage.")}
    assert len(entries) == 11
    for name, entry in entries.items():
        m = run.load_json("layer_metrics", name)
        assert m["reader"] in readers.READERS and m["workloads"] == [CELL] == entry["workloads"]
        assert {k: m[k] for k in entry} == entry
    applies = {m["name"] for m in run.layer_metrics_for(
        CELL, {"images_per_s", "time_to_image_p50_s", "setup_s"})}
    assert applies == set(entries) | {"device.idle_share", "programs.compiles_in_window",
                                      "server.overhead_ms", "server.queue_wait_ms", "step_mfu"}
