"""The reference's own CLIP byte-BPE tokenizer, from the published scheme:
lower-case, collapse whitespace, split into letter runs / single digits /
symbol runs, map bytes to the printable alphabet, mark the word end with
``</w>``, merge by rank, frame with BOS/EOS and pad to the window."""

from __future__ import annotations

import json
import re

import numpy as np

from .synth import bytes_to_unicode

# The benchmark's prompts are ASCII, where this equals CLIP's unicode pattern.
_SPLIT = re.compile(r"'s|'t|'re|'ve|'m|'ll|'d|[a-z]+|[0-9]|[^\sa-z0-9]+")


class BPE:
    def __init__(self, vocab_path: str, merges_path: str):
        with open(vocab_path, encoding="utf-8") as f:
            self.vocab = json.load(f)
        with open(merges_path, encoding="utf-8") as f:
            lines = [ln.strip() for ln in f]
        pairs = [tuple(ln.split()) for ln in lines
                 if ln and not ln.startswith("#version")]
        self.rank = {p: i for i, p in enumerate(pairs)}
        self.bytes = bytes_to_unicode()
        self.bos = self.vocab["<|startoftext|>"]
        self.eos = self.vocab["<|endoftext|>"]

    def _word(self, token: str) -> list[str]:
        word = list(token[:-1]) + [token[-1] + "</w>"]
        while len(word) > 1:
            best = min(range(len(word) - 1), key=lambda i: self.rank.get(
                (word[i], word[i + 1]), float("inf")))
            pair = (word[best], word[best + 1])
            if pair not in self.rank:
                break
            merged, i = [], 0
            while i < len(word):  # every occurrence of the pair, left to right
                if i < len(word) - 1 and (word[i], word[i + 1]) == pair:
                    merged.append(word[i] + word[i + 1])
                    i += 2
                else:
                    merged.append(word[i])
                    i += 1
            word = merged
        return word

    def ids(self, text: str, max_len: int, pad_id: int | None = None):
        """(max_len,) int32 ids: BOS, the text, EOS, padding (EOS for CLIP-L,
        0 for the OpenCLIP towers)."""
        if not text.isascii():
            raise ValueError("the reference tokenizer handles ASCII prompts")
        body: list[int] = []
        for tok in _SPLIT.findall(" ".join(text.lower().split())):
            mapped = "".join(self.bytes[b] for b in tok.encode())
            body += [self.vocab[p] for p in self._word(mapped)]
        row = [self.bos, *body[: max_len - 2], self.eos]
        out = np.full((max_len,), self.eos if pad_id is None else pad_id,
                      np.int32)
        out[: len(row)] = row
        return out
