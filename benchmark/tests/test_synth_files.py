"""Weight files and tokenizers by name: a configuration that lists neither
writes what the parent wrote, byte for byte; one that lists several gets each
file from a draw of its own, in the type it names."""

import hashlib
import json
import os

import ml_dtypes
import numpy as np
import pytest

import run
from yardstick import layout, safetensors_io, synth

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
with open(os.path.join(DATA, "parent_synth.sha256.json")) as f:
    PARENT = json.load(f)


def _sha(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


@pytest.mark.parametrize("name", sorted(PARENT["configs"]))
def test_a_configuration_without_the_new_keys_writes_the_parents_bytes(name, tmp_path):
    """``data/parent_synth.sha256.json`` holds what PR 29's ``synth.py`` wrote
    for this seed (made from a ``git archive`` of that commit)."""
    config, want, seed = run.load_json("configs", name), PARENT["configs"][name], PARENT["seed"]
    assert "files" not in config["checkpoint"] and "tokenizers" not in config
    paths, info = synth.write_checkpoints(str(tmp_path), seed, config)
    assert list(paths) == [config["checkpoint"]["file"]]
    (path,) = paths.values()
    assert path == os.path.join(str(tmp_path), config["checkpoint"]["file"])
    assert _sha(path) == want["checkpoint"]
    assert {k: info[k] for k in ("parameters", "bytes", "tensors")} == {
        k: want[k] for k in ("parameters", "bytes", "tensors")}
    assert info["files"] == [{"file": config["checkpoint"]["file"], "dtype": "float16",
                              **{k: want[k] for k in ("parameters", "bytes", "tensors")}}]
    vocab, merges = synth.write_tokenizer(str(tmp_path / "tok"), seed,
                                          config["text"]["vocab_size"])
    assert (_sha(vocab), _sha(merges)) == (want["vocab.json"], want["merges.txt"])
    named, env = run.write_tokenizers(config, str(tmp_path), seed)
    assert named == {} and env == {}


def test_several_files_each_from_its_own_draw_in_its_own_type(tmp_path):
    config = run.load_json("configs", "sd35m-t5-tiny")
    specs = synth.checkpoint_files(config)
    assert [s["file"].split("/")[1] for s in specs] == [
        "checkpoints", "text_encoders", "text_encoders", "text_encoders"]
    assert [s["dtype"] for s in specs] == ["float16"] * 3 + ["bfloat16"]
    paths, info = synth.write_checkpoints(str(tmp_path), 7, config)
    assert list(paths) == [s["file"] for s in specs] and len(info["files"]) == 4
    for key in ("parameters", "bytes", "tensors"):
        assert info[key] == sum(f[key] for f in info["files"])
    for spec, one in zip(specs, info["files"]):
        assert one["parameters"] == layout.count(
            layout.checkpoint_layout(config, spec["parts"]))
        assert one["bytes"] == os.path.getsize(paths[spec["file"]])
    # file 0 is what a single-file configuration of the same parts would get
    alone = dict(config, checkpoint=dict(
        layouts=config["checkpoint"]["layouts"], **{
            k: specs[0][k] for k in ("file", "dtype", "parts")}))
    synth.write_checkpoint(str(tmp_path / "alone.safetensors"), 7, alone)
    assert _sha(str(tmp_path / "alone.safetensors")) == _sha(paths[specs[0]["file"]])
    # ... and file 2 is not file 0's draw again: same layout function, other offsets
    as_first = dict(config, checkpoint=dict(
        layouts=config["checkpoint"]["layouts"], **{
            k: specs[1][k] for k in ("file", "dtype", "parts")}))
    synth.write_checkpoint(str(tmp_path / "first.safetensors"), 7, as_first)
    assert _sha(str(tmp_path / "first.safetensors")) != _sha(paths[specs[1]["file"]])
    # the bfloat16 file reads back as bfloat16, in the public T5 key layout
    t5 = safetensors_io.read(paths[specs[3]["file"]])
    assert {v.dtype for v in t5.values()} == {np.dtype(ml_dtypes.bfloat16)}
    assert t5["shared.weight"].shape == (32128, 192)
    bias = "encoder.block.{}.layer.0.SelfAttention.relative_attention_bias.weight"
    assert bias.format(0) in t5 and bias.format(1) not in t5
    table = np.asarray(t5[bias.format(0)], np.float32)
    assert table.shape == (32, 4) and 0.5 < table.std() < 1.5
    # the program's loader reads the same values
    from comfyui_parallelanything_tpu.models.loader import load_safetensors

    theirs = load_safetensors(paths[specs[3]["file"]])
    assert (theirs[bias.format(0)] == table).all()
    # equal seeds, equal bytes
    again, _ = synth.write_checkpoints(str(tmp_path / "again"), 7, config)
    assert [_sha(p) for p in again.values()] == [_sha(p) for p in paths.values()]


def test_t5_layout_counts_the_published_encoder():
    from yardstick import layout_mmdit

    xxl = {"vocab_size": 32128, "d_model": 4096, "d_kv": 64, "d_ff": 10240,
           "num_layers": 24, "num_heads": 64, "relative_attention_num_buckets": 32}
    entries = layout_mmdit.t5_layout(xxl)
    assert layout.count(entries) == 4_762_310_656  # "4.76B": T5-XXL's encoder
    assert sum("relative_attention_bias" in k for k, _, _ in entries) == 1
    umt5 = dict(xxl, vocab_size=256384, per_layer_bias=True)
    assert sum("relative_attention_bias" in k
               for k, _, _ in layout_mmdit.t5_layout(umt5)) == 24


def test_named_tokenizers_are_written_by_their_writer_and_handed_on(tmp_path):
    config = run.load_json("configs", "sd35m-t5-tiny")
    named, env = run.write_tokenizers(config, str(tmp_path), 9)
    assert list(named) == ["t5"] and list(env) == ["PA_T5_TOKENIZER_JSON"]
    assert env["PA_T5_TOKENIZER_JSON"] == os.path.join(
        str(tmp_path), "tokenizer", "t5", "tokenizer.json")
    assert named["t5"].size == 32100 and named["t5"].max_length == 77
