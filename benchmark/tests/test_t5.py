"""The T5 tower: the plain reference against the program's ``T5Encoder`` on
seeded weights at tiny widths, the whole command walked on the twin that has
the tower (four weight files, two tokenizers), and the three older twins
printing the numbers they printed before this PR."""

import json
import os

import numpy as np
import pytest

import run
from yardstick import reference_sd, reference_t5, safetensors_io, synth
from yardstick import tokenizer_unigram as tu

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TWIN = "sd35m-t5-tiny.closed"
TEXTS = ["a watercolor lighthouse at dawn", "blurry, low quality",
         "harbor lantern meadow granite willow copper canyon velvet ember glacier"]


def _gap(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _theirs(path, t, ids, mask, **kw):
    """The program's encoder loaded from the file in float32, at ``highest``."""
    import jax
    import jax.numpy as jnp

    from comfyui_parallelanything_tpu.models import load_t5_checkpoint, t5_xxl_config

    cfg = t5_xxl_config(vocab_size=t["vocab_size"], d_model=t["d_model"],
                        num_layers=t["num_layers"], num_heads=t["num_heads"],
                        d_kv=t["d_kv"], d_ff=t["d_ff"], dtype=jnp.float32, **kw)
    enc = load_t5_checkpoint(path, cfg)
    with jax.default_matmul_precision("highest"):
        return np.asarray(enc(jnp.asarray(ids), mask=jnp.asarray(mask)))


@pytest.fixture(scope="module")
def tower(tmp_path_factory):
    """The twin's T5 file and table from a seed, the program's encoder loaded
    from that file in float32, and its states for ``TEXTS``."""
    tmp = tmp_path_factory.mktemp("t5tower")
    config = run.load_json("configs", "sd35m-t5-tiny")
    t = config["text_t5"]
    index = next(i for i, s in enumerate(synth.checkpoint_files(config))
                 if s["parts"][0]["sizes"] == "text_t5")
    path = str(tmp / "t5.safetensors")
    synth.write_checkpoint(path, 2 ** 31 + 41, config, index)
    named, _ = run.write_tokenizers(config, str(tmp), 2 ** 31 + 41)
    ids = np.stack([named["t5"].ids(s) for s in TEXTS])
    mask = (ids != tu.PAD).astype(np.int32)
    return config, path, ids, mask, _theirs(path, t, ids, mask)


def _ours(path, sizes, ids, mask, change=None, precision="float32"):
    w = {k: np.asarray(v) for k, v in safetensors_io.read(path).items()}
    if change:
        change(w)
    return np.asarray(reference_t5.encode(
        precision, reference_sd.load_weights(w), sizes, ids, mask))


def test_the_reference_agrees_with_the_programs_encoder_in_float32(tower):
    """Both in float32 (the program at ``highest``, the reference's six-term
    sum): what is left is float32 rounding through two blocks. Read on three
    seeds (PR 30, this CPU): 1.09e-6 to 1.25e-6 of the states' norm, against
    1.1e-2 to 1.3e-2 for the reference on bfloat16 operands and 0.57 to 0.70
    for the three wrong towers below — 2e-5 is sixteen times the reading and
    a five-hundredth of the stated precision's own gap."""
    config, path, ids, mask, theirs = tower
    ours = _ours(path, config["text_t5"], ids, mask)
    assert ours.shape == theirs.shape == (len(TEXTS), 77, config["text_t5"]["d_model"])
    assert _gap(theirs, ours) < 2e-5, _gap(theirs, ours)
    low = _ours(path, config["text_t5"], ids, mask, precision="bfloat16")
    assert 1e-3 < _gap(low, ours) < 0.2


def test_the_one_over_root_d_scale_put_back_fails_it(tower):
    config, path, ids, mask, theirs = tower
    d = config["text_t5"]["d_kv"]

    def scaled(w):  # softmax(q k^T / sqrt(d)): the same as q's matrix / sqrt(d)
        for k in w:
            if k.endswith("SelfAttention.q.weight"):
                w[k] = (w[k].astype(np.float32) * d ** -0.5).astype(w[k].dtype)

    assert _gap(theirs, _ours(path, config["text_t5"], ids, mask, scaled)) > 0.1


def test_a_position_bias_of_each_layers_own_fails_it(tower, tmp_path):
    """The UMT5 variant — every block looks its bias up in a table of its own
    — on a T5 file is another tower: the program shares block 0's."""
    config, path, ids, mask, theirs = tower
    own = dict(config["text_t5"], per_layer_bias=True)
    per_layer = dict(config, text_t5=own)
    index = next(i for i, s in enumerate(synth.checkpoint_files(config))
                 if s["parts"][0]["sizes"] == "text_t5")
    p2 = str(tmp_path / "umt5.safetensors")
    synth.write_checkpoint(p2, 2 ** 31 + 41, per_layer, index)
    assert _gap(theirs, _ours(p2, own, ids, mask)) > 0.1
    # ... and agrees with the program's encoder told the same (the UMT5 switch)
    umt5 = _theirs(p2, own, ids, mask, per_layer_bias=True)
    assert _gap(umt5, _ours(p2, own, ids, mask)) < 2e-5


def test_the_tower_without_its_padding_mask_is_another_tower(tower):
    """What ``text_t5.attention_mask`` decides: ComfyUI's and diffusers' SD3
    hand T5 no mask, the program masks padded keys (PERF.md, open questions).
    The reference follows the configuration; the two are far apart."""
    config, path, ids, mask, theirs = tower
    assert _gap(theirs, _ours(path, config["text_t5"], ids, None)) > 0.1


def test_relative_buckets_are_the_published_bidirectional_scheme():
    b = reference_t5.relative_buckets(200, 200, 32, 128)
    assert b[0, 0] == 0 and b[5, 0] == 5 and b[0, 5] == 16 + 5  # exact below 8
    assert b[8, 0] == 8 and b[0, 8] == 24                        # first log bucket
    assert b[199, 0] == 15 and b[0, 199] == 31                   # clamped at 128 and past it
    assert b[127, 0] == 15 and b[90, 0] == 14
    assert (np.diff(b[:, 0]) >= 0).all() and set(np.unique(b)) == set(range(32)) - {16}  # key after the query at distance 0: no such pair


# -- the whole command -----------------------------------------------------------


def _run(capsys, *argv):
    run.main([*argv, "--rehearse"])
    cap = capsys.readouterr()
    out = cap.out.strip().splitlines()
    phases = {}
    for ln in out[:-1]:
        if ln.startswith("{"):
            doc = json.loads(ln)
            phases[doc["phase"]] = doc
    return json.loads(out[-1]), phases, cap.err


@pytest.mark.parametrize("trace", [0, 1])
def test_the_whole_command_walks_on_the_twin_with_the_t5_tower(restorable, capsys, trace):
    line, phases, err = _run(capsys, "--workload", TWIN, "--seed", str(2 ** 31 + 43),
                             "--seconds", "8", "--trace", str(trace))
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    synthesized = phases["synthesize"]
    assert [f["file"] for f in synthesized["files"]] == [
        "models/checkpoints/sd3.5_medium.safetensors",
        "models/text_encoders/clip_l.safetensors",
        "models/text_encoders/clip_g.safetensors",
        "models/text_encoders/t5xxl_fp16.safetensors"]
    assert synthesized["tokenizers"] == ["clip", "t5"]
    assert synthesized["env"] == ["PA_T5_TOKENIZER_JSON"]
    # the program read the T5 table from the variable the configuration named:
    # the harness set it to the file it wrote, and nothing else did
    assert os.environ["PA_T5_TOKENIZER_JSON"] == os.path.join(
        str(restorable), "work", "sd35m-t5-tiny", "tokenizer", "t5", "tokenizer.json")
    gap = [c for c in phases["correct"]["compared"] if "image_gap" in c["number"]]
    assert len(gap) == 1 and 0 < gap[0]["value"] <= gap[0]["limit"]
    # each number compared beside its limit: last in the line, last on stderr
    assert list(line)[-1] == "compared"
    assert [c["number"] for c in line["compared"]] == [
        c["number"] for c in phases["correct"]["compared"]]
    last = [ln for ln in err.strip().splitlines() if ln.startswith("compared ")]
    assert len(last) == len(line["compared"]) and "image_gap" in last[-1]
    assert err.strip().splitlines()[-1] == last[-1]
    if trace:
        assert line["metrics"]["programs.compiles_in_window"]["value"] == 0


def test_the_t5_states_zeroed_in_the_reference_come_out_not_correct(
        restorable, capsys, monkeypatch):
    """The tower is on both sides, and it matters: a reference whose T5
    states are zeros (the image the CLIP towers alone would condition) is far
    from what the program serves."""
    import jax.numpy as jnp

    from yardstick import reference_mmdit

    real = reference_mmdit.Reference.t5_states
    monkeypatch.setattr(reference_mmdit.Reference, "t5_states",
                        lambda self, ids, mask: jnp.zeros_like(real(self, ids, mask)))
    line, phases, _ = _run(capsys, "--workload", TWIN, "--seed", str(2 ** 31 + 43),
                           "--seconds", "8", "--trace", "0")
    assert line["failed"] == 0 and line["correct"] is False
    over = [c for c in phases["correct"]["compared"] if c["value"] > c["limit"]]
    assert over and all("image_gap" in c["number"] for c in over)
    assert over[0]["value"] > 2 * over[0]["limit"]


with open(os.path.join(DATA, "parent_twins.json")) as f:
    BEFORE = json.load(f)


@pytest.mark.parametrize("cell", sorted(BEFORE["cells"]))
def test_an_older_twin_prints_the_numbers_it_printed_before(restorable, capsys, cell):
    """``data/parent_twins.json``: what PR 29's tree printed for this seed —
    the same weights, tokens and reference come out of the new path."""
    want = BEFORE["cells"][cell]
    line, phases, _ = _run(capsys, "--workload", cell, "--seed", str(BEFORE["seed"]),
                           "--seconds", "5", "--trace", "0")
    assert line["correct"] is True
    assert {k: phases["synthesize"][k] for k in ("parameters", "bytes", "tensors")} == \
        want["synthesize"]
    assert phases["reference"]["requests"] == want["requests"]
    assert phases["reference"]["rows"] == want["rows"]
    got = {c["number"]: c["value"] for c in phases["correct"]["compared"]
           if "image_gap" in c["number"]}
    assert got == want["image_gap"]
