"""The whole command walked on the CPU at tiny widths — and with the timed
path broken underneath, where ``correct`` has to come out false."""

import json
import os
import subprocess
import sys


import run


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), [json.loads(ln) for ln in out[:-1] if ln.startswith("{")]




def test_rehearsal_prints_a_result_line_that_names_the_cpu(restorable, capsys):
    run.main(["--workload", "sd15-tiny.closed", "--seed", str(2 ** 31 + 7),
              "--seconds", "5", "--trace", "0", "--rehearse"])
    line, phases = _last_line(capsys)
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"images_per_s", "time_to_image_p50_s", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    compared = next(p for p in phases if p["phase"] == "correct")["compared"]
    assert all("limit" in c and "value" in c for c in compared)
    gap = [c for c in compared if c["number"].endswith("image_gap_in_stated_precision_units[1]")]
    assert gap and 0 < gap[0]["value"] <= gap[0]["limit"]


def test_a_batch_gathered_in_the_wrong_order_comes_out_not_correct(
        restorable, capsys, monkeypatch):
    """The timed path broken underneath: the sampler's answer is altered where
    it is produced — the rows of the batch come back rolled by one, as a
    split-and-gather fault would leave them. Every request still succeeds with
    plausible, distinct images; only the comparison with the reference tells.
    (A skipped denoiser step is NOT such a fault here: DPM-Solver++ corrects
    it on the next step and the image moves by less than the stated
    precision's own gap — PERF.md, Open questions.)"""
    import jax.numpy as jnp

    from comfyui_parallelanything_tpu.sampling import runner

    real = runner.K_SAMPLERS["dpmpp_2m"]

    def broken(denoise, x, sigmas, callback=None, **kw):
        return jnp.roll(real(denoise, x, sigmas, callback=callback, **kw), 1, axis=0)

    monkeypatch.setitem(runner.K_SAMPLERS, "dpmpp_2m", broken)
    run.main(["--workload", "sd15-tiny.closed", "--seed", "12345",
              "--seconds", "5", "--trace", "0", "--rehearse"])
    line, phases = _last_line(capsys)
    assert line["failed"] == 0 and line["correct"] is False
    compared = next(p for p in phases if p["phase"] == "correct")["compared"]
    over = [c for c in compared if c["value"] > c["limit"]]
    assert over and all("image_gap" in c["number"] for c in over)


STEPS = "prompts_whose_sampler_steps_differ_from_the_graph"


def test_traced_rehearsal_reads_layers_and_counts_every_prompts_steps(restorable, capsys):
    run.main(["--workload", "sd15-tiny.closed", "--seed", "77", "--seconds", "5",
              "--trace", "1", "--rehearse"])
    line, phases = _last_line(capsys)
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert line["metrics"]["programs.compiles_in_window"]["value"] == 0
    assert "images_per_s" not in line["metrics"] and "breakdown" in line
    compared = next(p for p in phases if p["phase"] == "correct")["compared"]
    steps = next(c for c in compared if c["number"] == STEPS)
    assert (steps["value"], steps["limit"], steps["seen"]) == (0, 0, [steps["asked"]])
    # two rows of the checked request are compared, each beside its limit
    assert sum("image_gap" in c["number"] for c in compared) == 2


def test_a_skipped_denoiser_step_comes_out_not_correct_in_the_traced_run(
        restorable, capsys, monkeypatch):
    """A sampler that leaves one step out: DPM-Solver++ corrects it on the
    next step and the image stays inside what sound runs read, so only the
    exact count of the program's ``step`` spans against the graph's ``steps``
    tells — in the traced run, where the span tracer is on."""
    import jax.numpy as jnp

    from comfyui_parallelanything_tpu.sampling import runner

    real = runner.K_SAMPLERS["dpmpp_2m"]

    def skipping(denoise, x, sigmas, callback=None, **kw):
        return real(denoise, x, jnp.delete(sigmas, 9), callback=callback, **kw)

    monkeypatch.setitem(runner.K_SAMPLERS, "dpmpp_2m", skipping)
    run.main(["--workload", "sd15-tiny.closed", "--seed", "78", "--seconds", "5",
              "--trace", "1", "--rehearse"])
    line, phases = _last_line(capsys)
    assert line["failed"] == 0 and line["correct"] is False
    compared = next(p for p in phases if p["phase"] == "correct")["compared"]
    over = [c["number"] for c in compared if c["value"] > c["limit"]]
    assert STEPS in over


def test_control_precision_fails_the_limits_at_tiny_widths(restorable, tmp_path):
    """The control — the reference computed on int8 operands, one precision
    below what the configuration states — kept as a test at a size a test run
    can hold: put in the program's place it comes out as not correct, on every
    seed, while the stated precision's own output passes by construction (it
    is the unit)."""
    import numpy as np

    from yardstick import reference_sd, synth, traffic
    from yardstick.tokenizer import BPE

    cell = run.load_cell("sd15-tiny.closed")
    config = cell["config_data"]
    ckpt = str(tmp_path / "ck.safetensors")
    for seed in (1, 2, 3):
        synth.write_checkpoint(ckpt, seed, config)
        vocab, merges = synth.write_tokenizer(str(tmp_path / "tok"), seed,
                                              config["text"]["vocab_size"])
        req = reference_sd.describe(traffic.fill_graph(
            cell["template"], cell["mix"],
            traffic.Schedule(cell["mix"], seed, 5).request(0)))
        img = {p: reference_sd.Reference(config, ckpt, BPE(vocab, merges), p)
               .images(req, [0]) for p in ("float32", "bfloat16", "int8")}
        served = [np.round(i * 255.0).astype(np.uint8) for i in img["int8"]]
        ok, nums = run.compare_images(served, img["float32"], img["bfloat16"],
                                      config["limits"])
        assert not ok, nums
        assert nums[0]["value"] > 1.3 * config["limits"][
            "image_gap_in_stated_precision_units"], nums


def test_no_tpu_no_result(tmp_path):
    """On a machine without a TPU the command prints an error, no result line,
    and exits non-zero; so does a checkout that holds only the benchmark."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "sd15-b8-512.closed", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=run.ROOT, timeout=300)
    assert r.returncode != 0 and "no TPU" in r.stderr
    assert '"correct"' not in r.stdout
    r = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "sd15-b8-512.closed", "--seed", "1", "--seconds", "1", "--trace", "0",
         "--rehearse"],
        capture_output=True, text=True, env=env, cwd=run.ROOT, timeout=300)
    assert r.returncode != 0 and "tiny twins only" in r.stderr
    import shutil

    bare = tmp_path / "bare"
    shutil.copytree(run.HERE, bare / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "sd15-b8-512.closed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=bare, timeout=300)
    assert r.returncode != 0 and "not in this checkout" in r.stderr
    assert '"correct"' not in r.stdout
