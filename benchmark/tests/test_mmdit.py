"""The SD3.5-medium configuration's own modules at tiny widths on the CPU:
the layout's parameter counts against the published ones, the shape
functions against a hand count, what ``describe`` reads off the graph, the
reference against the program, and the whole command walked on the twin."""

import json
import os

import numpy as np
import pytest

import run
from yardstick import layout, layout_mmdit, reference_mmdit, shapes_mmdit, synth, traffic
from yardstick.tokenizer import BPE

CELL, TWIN = "sd35m-b1-1024.closed", "sd35m-tiny.closed"


def test_checkpoint_layout_has_the_published_parameter_counts():
    c = run.load_json("configs", "sd35m")
    xl = run.load_json("configs", "sdxl")["checkpoint"]["parameters"]
    pinned = c["checkpoint"]["parameters"]
    for part in c["checkpoint"]["parts"]:
        one = dict(c, checkpoint=dict(c["checkpoint"], parts=[part]))
        assert layout.count(layout.checkpoint_layout(one)) == pinned[part["sizes"]], part
    # "2.5B" as published, the 384² x 1536 position table included
    assert pinned["mmdit"] == 2_469_663_936 and abs(pinned["mmdit"] / 2.5e9 - 1) < 0.02
    # the same two towers SDXL bundles, bigG in the HF layout here
    assert pinned["text"] == xl["text"] and pinned["text_g"] == xl["text_g"]
    # the kl-f8 autoencoder at 16 latent channels, without the quant convolutions
    assert pinned["vae"] == 83_819_683
    keys = {k for k, _, _ in layout.checkpoint_layout(c)}
    assert "model.diffusion_model.joint_blocks.12.x_block.attn2.qkv.weight" in keys
    assert "model.diffusion_model.joint_blocks.13.x_block.attn2.qkv.weight" not in keys
    assert "model.diffusion_model.joint_blocks.23.context_block.attn.proj.weight" not in keys
    assert "model.diffusion_model.joint_blocks.23.context_block.attn.ln_k.weight" in keys
    assert not any("quant_conv" in k for k in keys)
    assert "text_encoders.clip_g.transformer.text_projection.weight" in keys


def test_the_configuration_is_whole():
    c = run.load_json("configs", "sd35m")
    m = c["mmdit"]
    assert (m["num_layers"], m["num_attention_heads"], m["attention_head_dim"]) == (24, 24, 64)
    assert m["dual_attention_layers"] == list(range(13)) and m["qk_norm"] == "rms_norm"
    assert (m["pos_embed_max_size"], m["joint_attention_dim"], m["pooled_projection_dim"],
            m["patch_size"], m["in_channels"]) == (384, 4096, 2048, 2, 16)
    assert c["reduced"] == ["text_encoder_3"]
    entry = next(e for e in json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))["configs"]
                 if e["name"] == "sd35m")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]


def test_shapes_against_a_hand_count_at_tiny_sizes():
    """One row, hidden 256 (4 heads of 64), 4 blocks with dual attention in
    the first two, 144 image tokens and 77 text tokens, counted by hand."""
    m = run.load_json("configs", "sd35m-tiny")["mmdit"]
    h, t, n, mlp = 256, 144, 77, 1024
    s = t + n
    lin = lambda i, o, tok, bias=True: (2 * i * o * tok, i * o + (o if bias else 0))  # noqa: E731
    parts = [lin(64, h, t), lin(4096, h, n), lin(256, h, 1), lin(h, h, 1),
             lin(128, h, 1), lin(h, h, 1), lin(h, 2 * h, 1), lin(h, 64, t)]
    attn = 0
    for i in range(4):
        last, dual = i == 3, i < 2
        parts += [lin(h, (2 if last else 6) * h, 1), lin(h, (9 if dual else 6) * h, 1),
                  lin(h, 3 * h, n), lin(h, 3 * h, t), lin(h, h, t),
                  lin(h, mlp, t), lin(mlp, h, t)]
        attn += 4 * s * s * h
        norms = 4 * 64
        if dual:
            parts += [lin(h, 3 * h, t), lin(h, h, t)]
            attn += 4 * t * t * h
            norms += 2 * 64
        if not last:
            parts += [lin(h, h, n), lin(h, mlp, n), lin(mlp, h, n)]
        parts.append((0, norms))
    got = shapes_mmdit.mmdit_forward(m, 1, t, n)
    assert got["flops"] == sum(f for f, _ in parts) + attn
    assert got["params"] == sum(p for _, p in parts)
    # the layout counts the same parameters, plus the stored position table
    assert layout.count(layout_mmdit.mmdit_layout(m)) == got["params"] + 32 * 32 * h
    assert got["bytes"] > 2 * got["params"]


def test_the_cells_step_and_its_two_attention_classes():
    c, mix = run.load_json("configs", "sd35m"), run.load_json("traffic", "b1-1024.closed")
    step = shapes_mmdit.denoiser_step(c, mix, 1)
    assert step["flops"] == 21_169_234_280_448  # 107.5 ms at 197 TFLOP/s
    assert step["params"] == c["checkpoint"]["parameters"]["mmdit"] - 384 * 384 * 1536
    joint, dual = shapes_mmdit.joint_attention(c, mix, 1), shapes_mmdit.dual_attention(c, mix, 1)
    assert joint["flops"] == 4 * 4173 ** 2 * 1536 * 2 and joint["bytes"] == 4 * 4173 * 1536 * 2 * 2
    assert dual["flops"] == 4 * 4096 ** 2 * 1536 * 2
    share = (24 * joint["flops"] + 13 * dual["flops"]) / step["flops"]
    assert 0.36 < share < 0.38  # attention is 37% of a step's operations
    assert shapes_mmdit.denoiser_step(c, mix, 2)["flops"] * 2 < step["flops"] * 1.01


def test_describe_reads_the_graph_as_sent():
    cell = run.load_cell(CELL)
    r = traffic.Schedule(cell["mix"], 9, 45).request(4)
    graph = traffic.fill_graph(cell["template"], cell["mix"], r)
    d = reference_mmdit.describe(graph)
    assert d["seed"] == r.noise_seed and d["batch_size"] == 1
    assert (d["width"], d["height"], d["steps"], d["cfg"]) == (1024, 1024, 20, 4.01)
    assert (d["sampler_name"], d["scheduler"]) == ("euler", "sgm_uniform")
    assert d["positive"] == "a watercolor lighthouse at dawn"
    patched = json.loads(json.dumps(graph))
    patched["11"] = {"class_type": "ModelSamplingSD3", "inputs": {"model": ["4", 0], "shift": 3.0}}
    patched["3"]["inputs"]["model"] = ["11", 0]
    with pytest.raises(ValueError, match="ModelSamplingSD3"):
        reference_mmdit.describe(patched)
    patched = json.loads(json.dumps(graph))
    patched["5"]["class_type"] = "EmptyLatentImage"
    with pytest.raises(ValueError, match="SD3 txt2img"):
        reference_mmdit.describe(patched)


def test_the_flow_schedule_is_the_published_one():
    s = reference_mmdit.sgm_uniform_sigmas(20, 3.0)
    assert len(s) == 21 and s[0] == 1.0 and s[-1] == 0.0 and (np.diff(s) < 0).all()
    # second point: t = 1 - (1 - 0.002994)/20, through 3t / (1 + 2t)
    t1 = 1.0 - (1.0 - 3.0 * 0.001 / 1.002) / 20
    assert abs(s[1] - 3 * t1 / (1 + 2 * t1)) < 1e-12
    assert abs(reference_mmdit.flow_sigma(0.5, 3.0) - 0.75) < 1e-12
    np.testing.assert_allclose(reference_mmdit.sgm_uniform_sigmas(7, 1.0)[:-1],
                               np.linspace(1.0, 0.001, 8)[:-1], rtol=1e-12)


@pytest.fixture
def twin(tmp_path, monkeypatch):
    import jax

    cell = run.load_cell(TWIN)
    config = cell["config_data"]
    run.apply_program_presets(config, monkeypatch.setattr, jax.numpy.float32)
    ckpt = str(tmp_path / config["checkpoint"]["file"])
    synth.write_checkpoint(ckpt, 11, config)
    vocab, merges = synth.write_tokenizer(str(tmp_path / "tok"), 11,
                                          config["text"]["vocab_size"])
    for k, v in (("PA_MODELS_DIR", str(tmp_path / "models")),
                 ("PA_OUTPUT_DIR", str(tmp_path / "output")),
                 ("PA_CLIP_VOCAB", vocab), ("PA_CLIP_MERGES", merges),
                 ("PA_TOKENIZER_JSON", "")):
        monkeypatch.setenv(k, v)
    return cell, ckpt, BPE(vocab, merges)


def test_reference_agrees_with_the_program_in_float32(twin):
    """As ``test_reference.py`` holds the UNets: with the program computing
    in float32 the two agree to float32 rounding (text towers from the
    bundle, MMDiT-X with dual attention, flow Euler with CFG, the 16-channel
    decoder); the lower precisions then open the gap the limits stand in."""
    import jax

    import comfyui_parallelanything_tpu as pa

    cell, ckpt, tok = twin
    graph = traffic.fill_graph(cell["template"], cell["mix"],
                               traffic.Schedule(cell["mix"], 5, 10).request(0))
    with jax.default_matmul_precision("highest"):
        out = pa.run_workflow(json.loads(json.dumps(graph)))
    served = np.asarray(out["8"][0], np.float32)
    req = reference_mmdit.describe(graph)
    img = {p: reference_mmdit.Reference(cell["config_data"], ckpt, tok, p).images(req, [0])
           for p in reference_mmdit.PRECISIONS}
    ref = img["float32"]
    gap = lambda v: float(np.linalg.norm(v - ref) / np.linalg.norm(ref - ref.mean()))  # noqa: E731
    assert gap(served) < 2e-3, gap(served)
    assert 5e-3 < gap(img["bfloat16"]) < gap(img["int8"])
    # the control comes out as not correct by the twin's limit, the stated
    # precision passes by construction (it is the unit)
    put = [np.round(i * 255.0).astype(np.uint8) for i in img["int8"]]
    ok, nums = run.compare_images(put, ref, img["bfloat16"], cell["config_data"]["limits"])
    assert not ok and nums[0]["value"] > 1.3 * nums[0]["limit"], nums




@pytest.mark.parametrize("trace", [0, 1])
def test_the_whole_command_walks_on_the_twin(restorable, capsys, trace):
    run.main(["--workload", TWIN, "--seed", str(2 ** 31 + 11), "--seconds", "4",
              "--trace", str(trace), "--rehearse"])
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    phases = [json.loads(ln) for ln in out[:-1] if ln.startswith("{")]
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert line["device"]["platform"] == "cpu"
    compared = next(p for p in phases if p["phase"] == "correct")["compared"]
    gap = [c for c in compared if "image_gap" in c["number"]]
    assert len(gap) == 1 and 0 < gap[0]["value"] <= gap[0]["limit"]
    if trace:
        steps = next(c for c in compared if "sampler_steps" in c["number"])
        assert (steps["value"], steps["seen"]) == (0, [20])
        assert line["metrics"]["programs.compiles_in_window"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"images_per_s", "time_to_image_p50_s", "setup_s"}


def test_every_new_metric_file_names_the_cell_and_an_existing_reader():
    from yardstick import readers

    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    listed = {m["name"]: m for m in bench["per_layer"]}
    names = [fn[:-5] for fn in os.listdir(os.path.join(run.HERE, "layer_metrics"))
             if fn.startswith("sd35m.")]
    assert len(names) == 8
    for name in names:
        m = run.load_json("layer_metrics", name)
        assert m["reader"] in readers.READERS and m["workloads"] == [CELL]
        entry = listed[name]
        assert all(entry[k] == m[k] for k in entry), name
    applied = {m["name"] for m in run.layer_metrics_for(
        CELL, {"images_per_s", "time_to_image_p50_s", "setup_s"})}
    assert set(names) <= applied and "sampler.step_ms" not in applied
    old = {m["name"] for m in run.layer_metrics_for(
        "sdxl-b1-1024.closed", {"images_per_s", "time_to_image_p50_s", "setup_s"})}
    assert not any(n.startswith("sd35m.") for n in old)
