"""The seeded unigram table: its published id layout and size, and the
harness's own Viterbi encoder against the ``tokenizers`` package loading the
same written ``tokenizer.json`` (what the program does), id for id."""

import json

import numpy as np
import pytest

from yardstick import tokenizer_unigram as tu
from yardstick import traffic

T5 = {"name": "t5", "writer": "tokenizer_unigram", "vocab_size": 32100,
      "max_length": 77, "env": {"PA_T5_TOKENIZER_JSON": "tokenizer_json"}}


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    written = tu.write(str(tmp_path_factory.mktemp("t5")), 2 ** 31 + 30, T5)
    return written, tu.load(written, T5)


def test_the_table_has_t5s_published_id_layout_and_size(table):
    written, own = table
    with open(written["tokenizer_json"], encoding="utf-8") as f:
        doc = json.load(f)
    vocab = doc["model"]["vocab"]
    assert doc["model"]["type"] == "Unigram" and doc["model"]["unk_id"] == 2
    assert len(vocab) == 32100 == own.size  # against an embedding of 32,128 rows
    assert [v[0] for v in vocab[:3]] == ["<pad>", "</s>", "<unk>"]
    # 32,000 sentencepiece ids, then the 100 sentinels counting down to the top
    assert vocab[32000][0] == "<extra_id_99>" and vocab[32099][0] == "<extra_id_0>"
    pieces = [p for p, _ in vocab[3:32000]]
    assert len(set(pieces)) == len(pieces) and not any(p.startswith("<extra") for p in pieces)
    assert all(s < 0 for _, s in vocab[3:32000])  # log-probabilities
    assert sum(p.startswith(tu.MARK) for p in pieces) > 8000  # the word marker
    for ch in map(chr, range(33, 127)):  # every character alone, marked or not
        assert ch in own.score and tu.MARK + ch in own.score
    special = {t["id"] for t in doc["added_tokens"]}
    assert special == {0, 1, 2, *range(32000, 32100)}
    # the same seed writes the same bytes; another seed another table
    again = tu.write(written["tokenizer_json"] + ".d", 2 ** 31 + 30, T5)
    other = tu.write(written["tokenizer_json"] + ".e", 2 ** 31 + 31, T5)
    read = lambda p: open(p, "rb").read()  # noqa: E731
    assert read(again["tokenizer_json"]) == read(written["tokenizer_json"])
    assert read(other["tokenizer_json"]) != read(written["tokenizer_json"])


def test_a_smaller_table_keeps_the_layout(tmp_path):
    entry = dict(T5, vocab_size=700, sentinels=10)
    own = tu.load(tu.write(str(tmp_path), 5, entry), entry)
    assert own.size == 700 and int(own.ids("otter pine")[-1]) == tu.PAD
    with pytest.raises(ValueError, match="ids or more"):
        tu.write(str(tmp_path), 5, dict(T5, vocab_size=150))


def test_the_harness_encoder_and_the_tokenizers_package_agree_id_for_id(table):
    from tokenizers import Tokenizer

    from comfyui_parallelanything_tpu.utils.tokenizer import load_tokenizer_json

    written, own = table
    pkg = Tokenizer.from_file(written["tokenizer_json"])
    program = load_tokenizer_json(written["tokenizer_json"], max_len=77, eos_id=1)
    mix = {"loop": "closed", "draws": {"prompt": {"kind": "unique", "words": 9},
                                       "negative": "blurry, low quality"}}
    schedule = traffic.Schedule(mix, 2 ** 31 + 30, 5)
    prompts = [schedule.request(i).positive for i in range(200)]
    fixed = ["a watercolor lighthouse at dawn", "blurry, low quality",
             "  Two   spaces, CAPS & (signs) #1!  ", ""]
    for text in tu._words() + prompts + fixed:
        ids = own.ids(text)
        assert ids.shape == (77,) and ids.dtype == np.int32
        body = [int(i) for i in ids if i != tu.PAD]
        assert body == pkg.encode(text).ids, text           # the package's own run
        assert (ids == program([text])[0][0]).all(), text    # as the program pads it
        assert tu.UNK not in body and body.count(tu.EOS) == 1 and body[-1] == tu.EOS
        assert (ids[len(body):] == tu.PAD).all()
    # longer than the window: cut to 76 pieces and </s>, as the program cuts
    long = " ".join(prompts[:12])
    assert len(pkg.encode(long).ids) > 77
    ids = own.ids(long)
    assert int(ids[-1]) == tu.EOS and (ids == program([long])[0][0]).all()


def test_a_character_the_table_lacks_is_an_error_not_an_unk(table):
    with pytest.raises(ValueError, match="<unk>"):
        table[1].ids("café")
