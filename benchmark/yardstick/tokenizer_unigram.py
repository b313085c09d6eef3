"""A SentencePiece-unigram tokenizer table of the published T5 shape, drawn
from ``--seed``, and the harness's own encoder for it.

The table (``write``): ``<pad>`` 0, ``</s>`` 1, ``<unk>`` 2, then scored pieces
up to ``vocab_size - sentinels`` ids, then the ``<extra_id_*>`` sentinels at
the top, counting down (``<extra_id_0>`` is the last id) — T5's published
layout: 32,000 + 100 = 32,100 ids against an embedding of 32,128 rows. Pieces
are fragments of the words of ``traffic/words.txt`` and random letter runs
over its alphabet, half of them with the ``▁`` word marker; every printable
ASCII character is a piece with and without the marker, so no text of the
traffic yields ``<unk>``. Scores are negative log-probabilities drawn from the
seed on a grid of 2**-16, so every path's sum is exact in float64 and two
implementations of the same search cannot differ by rounding.

It is written as an HF ``tokenizer.json`` (``Unigram`` model, whitespace split
then ``Metaspace``, ``</s>`` appended) by hand, without the ``tokenizers``
package; the program loads that file through the package. ``Unigram`` below is
the reference's encoder: plain Viterbi over the scores, the best-scoring
segmentation of each ``▁``-marked word. What the published file has and this
one lacks: the precompiled NFKC character map (the traffic is ASCII)."""

from __future__ import annotations

import json
import os
import zlib

import numpy as np

MARK = "▁"  # ▁
SPECIALS = ("<pad>", "</s>", "<unk>")
PAD, EOS, UNK = 0, 1, 2
GRID = 2.0 ** -16
MAX_PIECE = 12


def _words() -> list[str]:
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "traffic", "words.txt")) as f:
        return [w for w in f.read().split() if w]


def _pieces(rng, n: int) -> list[tuple[str, float]]:
    """``n`` distinct (piece, score): the single characters first (scored
    lowest, the fallback of every search), then multi-character pieces."""
    chars = [chr(c) for c in range(33, 127)]
    singles = [MARK] + chars + [MARK + c for c in chars]
    if n < len(singles):
        raise ValueError(f"a unigram table needs {len(singles) + 3} ids or more")
    words = _words()
    letters = sorted({c for w in words for c in w})
    seen, multi = set(singles), []
    while len(multi) < n - len(singles):
        m = 4 * (n - len(singles) - len(multi))
        # Draws in bulk: a python-level rng call per piece would cost seconds.
        kind, iw, a, b, mark = (rng.random(m), rng.integers(len(words), size=m),
                                rng.random(m), rng.random(m), rng.random(m) < 0.5)
        run = rng.integers(len(letters), size=(m, MAX_PIECE))
        for j in range(m):
            if len(multi) >= n - len(singles):
                break
            if kind[j] < 0.6:  # a fragment of a word of the traffic
                w = words[iw[j]]
                lo = int(a[j] * len(w))
                piece = w[lo:lo + 2 + int(b[j] * (len(w) - lo))]
                marked = lo == 0 and mark[j]
            else:  # a run of its letters
                piece = "".join(letters[k] for k in run[j, :2 + int(a[j] * 7)])
                marked = mark[j]
            piece = (MARK + piece if marked else piece)[:MAX_PIECE]
            if len(piece) < 2 or piece in seen:
                continue
            seen.add(piece)
            multi.append(piece)
    pieces = singles + multi
    score = np.concatenate([-(14.0 + 4.0 * rng.random(len(singles))),
                            -(4.0 + 9.0 * rng.random(len(multi)))])
    score = np.round(score / GRID) * GRID
    return list(zip(pieces, (float(s) for s in score)))


def write(dirname: str, seed: int, entry: dict) -> dict:
    """The table of ``entry`` (``vocab_size`` ids, of them ``sentinels`` at
    the top, 100 unless given) drawn from ``seed`` → ``{"tokenizer_json":
    path}``."""
    size, sentinels = int(entry["vocab_size"]), int(entry.get("sentinels", 100))
    rng = np.random.default_rng([seed, 3, zlib.crc32(entry["name"].encode())])
    vocab = [[s, 0.0] for s in SPECIALS]
    vocab += [[p, s] for p, s in _pieces(rng, size - sentinels - len(SPECIALS))]
    vocab += [[f"<extra_id_{k}>", 0.0] for k in reversed(range(sentinels))]
    special = [(i, v[0]) for i, v in enumerate(vocab)
               if i < len(SPECIALS) or i >= size - sentinels]
    doc = {
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [
            {"id": i, "content": s, "single_word": False, "lstrip": False,
             "rstrip": False, "normalized": False, "special": True}
            for i, s in special],
        "normalizer": None,
        "pre_tokenizer": {"type": "Sequence", "pretokenizers": [
            {"type": "WhitespaceSplit"},
            {"type": "Metaspace", "replacement": MARK,
             "prepend_scheme": "always", "split": True}]},
        "post_processor": {
            "type": "TemplateProcessing",
            "single": [{"Sequence": {"id": "A", "type_id": 0}},
                       {"SpecialToken": {"id": "</s>", "type_id": 0}}],
            "pair": [{"Sequence": {"id": "A", "type_id": 0}},
                     {"SpecialToken": {"id": "</s>", "type_id": 0}},
                     {"Sequence": {"id": "B", "type_id": 0}},
                     {"SpecialToken": {"id": "</s>", "type_id": 0}}],
            "special_tokens": {"</s>": {"id": "</s>", "ids": [EOS],
                                        "tokens": ["</s>"]}}},
        "decoder": {"type": "Metaspace", "replacement": MARK,
                    "prepend_scheme": "always", "split": True},
        "model": {"type": "Unigram", "unk_id": UNK, "vocab": vocab,
                  "byte_fallback": False},
    }
    os.makedirs(dirname, exist_ok=True)
    path = os.path.join(dirname, "tokenizer.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, ensure_ascii=False)
    return {"tokenizer_json": path}


def load(written: dict, entry: dict) -> "Unigram":
    return Unigram(written["tokenizer_json"], int(entry["max_length"]))


class Unigram:
    """The reference's encoder over a written table."""

    def __init__(self, path: str, max_length: int):
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        self.max_length = max_length
        special = {t["id"] for t in doc["added_tokens"]}
        self.size = len(doc["model"]["vocab"])
        self.score = {p: (s, i) for i, (p, s) in enumerate(doc["model"]["vocab"])
                      if i not in special}
        self.longest = max(map(len, self.score))

    def _word(self, word: str) -> list[int]:
        """The segmentation of ``word`` whose scores sum highest."""
        n = len(word)
        best = [0.0] + [None] * n      # best[e]: score of the best path to e
        back = [None] * (n + 1)        # (start, id) of its last piece
        for e in range(1, n + 1):
            for s in range(max(0, e - self.longest), e):
                hit = self.score.get(word[s:e])
                if hit is None or best[s] is None:
                    continue
                if best[e] is None or best[s] + hit[0] > best[e]:
                    best[e], back[e] = best[s] + hit[0], (s, hit[1])
        if best[n] is None:
            raise ValueError(f"{word!r} has a character the table lacks (<unk>)")
        ids, e = [], n
        while e > 0:
            s, i = back[e]
            ids.append(i)
            e = s
        return ids[::-1]

    def ids(self, text: str, max_length: int | None = None) -> np.ndarray:
        """(max_length,) int32: the text's pieces, ``</s>`` once, ``<pad>``."""
        n = max_length or self.max_length
        body = [i for w in text.split() for i in self._word(MARK + w)]
        row = body[: n - 1] + [EOS]
        out = np.full((n,), PAD, np.int32)
        out[: len(row)] = row
        return out
