"""A byte-level BPE tokenizer table of Qwen's published shape, drawn from
``--seed``, and the harness's own encoder for it.

The table (``write``): GPT-2's 256 byte symbols as ids 0–255, then one id a
merge in rank order up to ``bpe_size`` ids (151,643: Qwen2/Qwen3's count of
ordinary tokens), then the added special tokens at their PUBLISHED ids —
``<|endoftext|>`` 151643, ``<|im_start|>`` 151644, ``<|im_end|>`` 151645 and
the 23 further ones up to ``</think>`` 151668 — against an embedding of
``vocab_size`` (151,936) rows; the ids above 151,668 name no token, as
published.

The first merges are LEARNED, by plain BPE training (most frequent pair
first, ties by the seed), on the words of ``traffic/words.txt`` with and
without their leading space and on the chat template's ``user`` and
``assistant``, until each is ONE token — as common English words are in the
published table. Encoding replays training, so every request's text of k
words is k tokens, whatever the words, and the templated prompt 8 more:
the mix ``b1-1024.closed-unique`` (8 words) gives 16 tokens every request,
one 32-token caption bucket. The remaining merges join random pairs of
tokens that exist, drawn from the seed; they rank after every learned merge
and so cannot split a learned word.

It is written as an HF ``tokenizer.json`` by hand (``BPE`` model, Qwen's
split pattern then ``ByteLevel`` without its own regex, NFC), without the
``tokenizers`` package; the program loads that file through the package.
``ByteBPE`` below is the reference's encoder: the chat template, the special
tokens cut out, the split pattern, the byte alphabet, merges by rank. What
the published file has and this one lacks: the published merges (not in the
container) and non-ASCII text (the traffic is ASCII; ``ByteBPE`` refuses
anything else, because its pattern spells letters and digits as ASCII)."""

from __future__ import annotations

import json
import os
import re
import zlib

import numpy as np

from .synth import bytes_to_unicode

# Qwen2 / Qwen3 tokenizer.json's split pattern.
PATTERN = (r"(?i:'s|'t|'re|'ve|'m|'ll|'d)|[^\r\n\p{L}\p{N}]?\p{L}+|\p{N}| ?"
           r"[^\s\p{L}\p{N}]+[\r\n]*|\s*[\r\n]+|\s+(?!\S)|\s+")
# The same for ASCII text, in the standard library's dialect.
_ASCII = re.compile(r"(?i:'s|'t|'re|'ve|'m|'ll|'d)|[^\r\nA-Za-z0-9]?[A-Za-z]+|[0-9]| ?"
                    r"[^\sA-Za-z0-9]+[\r\n]*|\s*[\r\n]+|\s+(?!\S)|\s+")

TEMPLATE = "<|im_start|>user\n{}<|im_end|>\n<|im_start|>assistant\n"
BPE_SIZE = 151643
SPECIALS = (
    "<|endoftext|>", "<|im_start|>", "<|im_end|>", "<|object_ref_start|>",
    "<|object_ref_end|>", "<|box_start|>", "<|box_end|>", "<|quad_start|>",
    "<|quad_end|>", "<|vision_start|>", "<|vision_end|>", "<|vision_pad|>",
    "<|image_pad|>", "<|video_pad|>", "<tool_call>", "</tool_call>",
    "<|fim_prefix|>", "<|fim_middle|>", "<|fim_suffix|>", "<|fim_pad|>",
    "<|repo_name|>", "<|file_sep|>", "<tool_response>", "</tool_response>",
    "<think>", "</think>")
MAX_PIECE = 16


def _words() -> list[str]:
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "traffic", "words.txt")) as f:
        return [w for w in f.read().split() if w]


def _learn(words: list[tuple], rng) -> list[tuple[str, str]]:
    """Plain BPE training on ``words`` (each a tuple of symbols, counted
    once) until every word is one symbol: the most frequent adjacent pair
    first, ties broken by a random order drawn once from ``rng``."""
    merges: list[tuple[str, str]] = []
    words = [list(w) for w in words]
    tie: dict = {}
    while any(len(w) > 1 for w in words):
        counts: dict = {}
        for w in words:
            for pair in zip(w[:-1], w[1:]):
                counts[pair] = counts.get(pair, 0) + 1
        for pair in counts:  # in order of first sight: the same every run
            if pair not in tie:
                tie[pair] = rng.random()
        best = max(counts, key=lambda pr: (counts[pr], tie[pr]))
        merges.append(best)
        for w in words:
            i = 0
            while i < len(w) - 1:
                if (w[i], w[i + 1]) == best:
                    w[i:i + 2] = [w[i] + w[i + 1]]
                else:
                    i += 1
    return merges


def write(dirname: str, seed: int, entry: dict) -> dict:
    """The table of ``entry`` (``bpe_size`` ordinary ids, 151,643 unless
    given; the specials follow) drawn from ``seed`` → ``{"tokenizer_json":
    path}``."""
    size = int(entry.get("bpe_size", BPE_SIZE))
    if size + len(SPECIALS) > int(entry["vocab_size"]):
        raise ValueError("the specials do not fit under vocab_size")
    rng = np.random.default_rng([seed, 4, zlib.crc32(entry["name"].encode())])
    byte = bytes_to_unicode()
    alphabet = [byte[b] for b in range(256)]
    space = byte[ord(" ")]
    plain = _words() + ["user", "assistant"]
    corpus = [tuple(w) for w in plain] + [tuple(space + w) for w in plain]
    merges = _learn(corpus, rng)
    vocab = alphabet + [a + b for a, b in merges]
    if len(vocab) > size:
        raise ValueError(f"bpe_size {size} is under the {len(vocab)} ids the "
                         "traffic's words need")
    seen = set(vocab)
    # Filler: random pairs of tokens that exist, ranked after every learned
    # merge. Draws in bulk: a python-level rng call per merge costs seconds.
    letters = [c for c in alphabet if c.isascii() and c.isalpha()] + [space]
    while len(vocab) < size:
        m = 4 * (size - len(vocab))
        coin, ia, ib = rng.random(m), rng.random(m), rng.random(m)
        for j in range(m):
            n = len(vocab)
            if n >= size:
                break
            # half the time a single letter in front, so pieces stay short
            a = (vocab[int(ia[j] * n)] if coin[j] < 0.5
                 else letters[int(ia[j] * len(letters))])
            b = vocab[int(ib[j] * n)]
            if len(a) + len(b) > MAX_PIECE or a + b in seen:
                continue
            seen.add(a + b)
            vocab.append(a + b)
            merges.append((a, b))
    doc = {
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [
            {"id": size + i, "content": s, "single_word": False, "lstrip": False,
             "rstrip": False, "normalized": False, "special": True}
            for i, s in enumerate(SPECIALS)],
        "normalizer": {"type": "NFC"},
        "pre_tokenizer": {"type": "Sequence", "pretokenizers": [
            {"type": "Split", "pattern": {"Regex": PATTERN},
             "behavior": "Isolated", "invert": False},
            {"type": "ByteLevel", "add_prefix_space": False,
             "trim_offsets": False, "use_regex": False}]},
        "post_processor": None,
        "decoder": {"type": "ByteLevel", "add_prefix_space": False,
                    "trim_offsets": False, "use_regex": False},
        "model": {"type": "BPE", "dropout": None, "unk_token": None,
                  "continuing_subword_prefix": "", "end_of_word_suffix": "",
                  "fuse_unk": False, "byte_fallback": False,
                  "ignore_merges": False,
                  "vocab": {tok: i for i, tok in enumerate(vocab)},
                  "merges": [f"{a} {b}" for a, b in merges]},
    }
    os.makedirs(dirname, exist_ok=True)
    path = os.path.join(dirname, "tokenizer.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, ensure_ascii=False)
    return {"tokenizer_json": path}


def load(written: dict, entry: dict) -> "ByteBPE":
    return ByteBPE(written["tokenizer_json"], int(entry["max_length"]))


class ByteBPE:
    """The reference's encoder over a written table."""

    def __init__(self, path: str, max_length: int):
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        self.max_length = max_length
        self.vocab = doc["model"]["vocab"]
        self.rank = {tuple(m.split(" ")): i
                     for i, m in enumerate(doc["model"]["merges"])}
        self.special = {t["content"]: t["id"] for t in doc["added_tokens"]}
        self._cut = re.compile(
            "(" + "|".join(re.escape(s) for s in sorted(
                self.special, key=len, reverse=True)) + ")")
        self.bytes = bytes_to_unicode()
        self.size = len(self.vocab) + len(self.special)

    def _word(self, word: list[str]) -> list[str]:
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

    def pieces(self, text: str) -> list[int]:
        """Plain text (special tokens cut out first) → ids."""
        if not text.isascii():
            raise ValueError("the reference tokenizer handles ASCII prompts")
        out: list[int] = []
        for part in self._cut.split(text):
            if part in self.special:
                out.append(self.special[part])
                continue
            for tok in _ASCII.findall(part):
                mapped = [self.bytes[b] for b in tok.encode()]
                out += [self.vocab[p] for p in self._word(mapped)]
        return out

    def ids(self, text: str, max_length: int | None = None) -> np.ndarray:
        """(n,) int32: the chat-templated prompt's VALID tokens, n at most
        ``max_length``; a causal tower takes no padding."""
        n = max_length or self.max_length
        return np.asarray(self.pieces(TEMPLATE.format(text))[:n], np.int32)
