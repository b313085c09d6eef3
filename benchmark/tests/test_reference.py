"""The plain reference against the system at tiny widths on the CPU: with the
program computing in float32 the two agree to float32 rounding, so the
reference and the program describe the same mathematics (text towers, UNet
with FreeU_V2 or SDXL's vector conditioning, Karras/DPM-Solver++(2M) with
CFG, the kl-f8 decoder). The lower precisions then open the
gap the benchmark's limits are set between."""

import json

import numpy as np
import pytest

import run
from yardstick import reference_sd, synth, traffic
from yardstick.tokenizer import BPE


def _setup(cell_name, tmp_path, monkeypatch, dtype="float32"):
    import jax

    cell = run.load_cell(cell_name)
    config = cell["config_data"]
    run.apply_program_presets(config, monkeypatch.setattr, getattr(jax.numpy, dtype))
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


@pytest.mark.parametrize("cell_name", ["sd15-tiny.closed", "sdxl-tiny.closed"])
def test_reference_agrees_with_the_program_in_float32(cell_name, tmp_path, monkeypatch):
    import jax

    import comfyui_parallelanything_tpu as pa

    cell, ckpt, tok = _setup(cell_name, tmp_path, monkeypatch)
    sched = traffic.Schedule(cell["mix"], 5, 10)
    graph = traffic.fill_graph(cell["template"], cell["mix"], sched.request(0))
    with jax.default_matmul_precision("highest"):
        out = pa.run_workflow(json.loads(json.dumps(graph)))
    served = np.asarray(out["8"][0], np.float32)
    req = reference_sd.describe(graph)
    rows = list(range(served.shape[0]))[:2]
    ref = reference_sd.Reference(cell["config_data"], ckpt, tok, "float32").images(req, rows)
    gap = np.linalg.norm(served[rows] - ref) / np.linalg.norm(ref - ref.mean())
    assert gap < 2e-3, gap
    # The lower precisions move the result, the control further than the
    # stated one: what the benchmark's limits are set between.
    low = {p: reference_sd.Reference(cell["config_data"], ckpt, tok, p).images(req, rows)
           for p in ("bfloat16", "int8")}
    g = {p: np.linalg.norm(v - ref) / np.linalg.norm(ref - ref.mean())
         for p, v in low.items()}
    assert 5e-3 < g["bfloat16"] < g["int8"], g


def test_reference_tokenizer_equals_the_programs(tmp_path):
    from comfyui_parallelanything_tpu.utils.tokenizer import CLIPBPETokenizer

    vocab, merges = synth.write_tokenizer(str(tmp_path), 3, 49408)
    ours, theirs = BPE(vocab, merges), CLIPBPETokenizer.from_files(vocab, merges)
    for text in ("a watercolor lighthouse at dawn", "blurry, low quality",
                 "harbor lantern meadow granite willow copper canyon velvet",
                 "quiet harbor 42 boats; mist's edge"):
        assert (ours.ids(text, 77) == theirs(text)[0][0]).all(), text
    zero_padded = CLIPBPETokenizer.from_files(vocab, merges, pad_id=0)
    assert (ours.ids("a b", 77, pad_id=0) == zero_padded("a b")[0][0]).all()


def test_describe_reads_the_graph_as_sent():
    cell = run.load_cell("sd15-b8-512.closed")
    sched = traffic.Schedule(cell["mix"], 9, 45)
    r = sched.request(4)
    d = reference_sd.describe(traffic.fill_graph(cell["template"], cell["mix"], r))
    assert d["seed"] == r.noise_seed and d["batch_size"] == 8
    assert (d["width"], d["height"], d["steps"], d["cfg"]) == (512, 512, 20, 7.0)
    assert d["freeu"] == (1.3, 1.4, 0.9, 0.2)
    xl = run.load_cell("sdxl-b1-1024.closed")
    d = reference_sd.describe(traffic.fill_graph(xl["template"], xl["mix"], r))
    assert d["freeu"] is None and d["batch_size"] == 1
    assert (d["width"], d["height"]) == (1024, 1024)


@pytest.mark.parametrize("shape", [(2, 8, 12, 16, 3, 1, None), (2, 8, 12, 16, 3, 2, 1),
                                   (1, 8, 4, 9, 1, 1, None), (2, 6, 6, 15, 3, 2, 1)])
def test_a_laid_out_convolution_is_the_convolution(shape, monkeypatch):
    """``_conv`` as one matrix product over the laid-out window against
    ``lax.conv_general_dilated``: the same sums in another order, in every
    arithmetic (the tolerance is float32 rounding of sums of this length)."""
    import jax.numpy as jnp
    from jax import lax

    n, c, o, h, k, stride, pad = shape
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, c, h, h), dtype=np.float32))
    w = jnp.asarray(0.1 * rng.standard_normal((o, c, k, k), dtype=np.float32)).astype(jnp.float16)
    b = jnp.asarray(rng.standard_normal(o, dtype=np.float32)).astype(jnp.float16)
    pp = k // 2 if pad is None else pad
    true = lax.conv_general_dilated(
        x, w.astype(jnp.float32), (stride, stride), [(pp, pp), (pp, pp)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=lax.Precision.HIGHEST
    ) + b.astype(jnp.float32)[None, :, None, None]
    for p in reference_sd.PRECISIONS:
        laid_out = reference_sd._conv(p, x, w, b, stride=stride, pad=pad)
        with monkeypatch.context() as m:
            m.setattr(reference_sd, "IM2COL_BYTES", 0)
            as_conv = reference_sd._conv(p, x, w, b, stride=stride, pad=pad)
        assert laid_out.shape == true.shape
        assert float(jnp.abs(laid_out - as_conv).max()) < 2e-6, p
    assert float(jnp.abs(reference_sd._conv("float32", x, w, b, stride=stride, pad=pad)
                         - true).max()) < 2e-6


@pytest.mark.parametrize("shape", [(2, 5, 8, 8), (2, 3, 16, 32), (1, 4, 64, 64), (1, 2, 6, 10)])
def test_the_four_lowest_frequencies_are_the_fourier_mask(shape):
    """FreeU_V2's filter as the node writes it (FFT, centred 2 x 2 mask,
    inverse FFT, real part), in float64, against ``_lowest_frequencies``."""
    x = np.random.default_rng(1).standard_normal(shape) + 0.5
    f = np.fft.fftshift(np.fft.fftn(x, axes=(-2, -1)), axes=(-2, -1))
    cy, cx = shape[-2] // 2, shape[-1] // 2
    mask = np.ones(shape[-2:])
    mask[cy - 1:cy + 1, cx - 1:cx + 1] = 0.2
    want = np.fft.ifftn(np.fft.ifftshift(f * mask, axes=(-2, -1)), axes=(-2, -1)).real
    x32 = np.asarray(x, np.float32)
    got = x32 + (0.2 - 1.0) * np.asarray(reference_sd._lowest_frequencies(x32))
    assert np.abs(got - want).max() < 1e-6
