"""Tokenization: prompts → the int32 id arrays models/text_encoders.py consumes.

The reference never tokenizes (conditioning arrives pre-encoded at its forward
boundary, any_device_parallel.py:1287); a standalone framework needs prompt → ids.
This image ships no tokenizer tables and has no egress, so everything here loads
from user-supplied files:

- ``CLIPBPETokenizer`` — a from-scratch implementation of CLIP's byte-BPE scheme
  (bytes→unicode alphabet, end-of-word ``</w>`` marker, lowercasing, merge ranks)
  reading the standard ``vocab.json`` + ``merges.txt`` pair.
- ``load_tokenizer_json`` — wraps the HF ``tokenizers`` runtime (present in this
  image) for ``tokenizer.json`` files (T5 and modern CLIP exports).
- ``load_chat_tokenizer_json`` — the same runtime on a byte-level BPE
  ``tokenizer.json`` of Qwen's kind behind its chat template, at a bucketed
  length (Z-Image's Qwen3 tower).

Output convention matches the SD ecosystem: fixed ``max_len`` windows, BOS/EOS
framing for CLIP, right-padding with a configurable pad id (CLIP-L pads with EOS,
OpenCLIP-G with 0), plus a 0/1 mask for T5-style encoders.
"""

from __future__ import annotations

import functools
import json
import os

import numpy as np


@functools.cache
def _bytes_to_unicode() -> dict[int, str]:
    """CLIP/GPT-2's reversible byte→printable-unicode table: printable ASCII and
    latin-1 map to themselves, the rest shift into 256+."""
    bs = (
        list(range(ord("!"), ord("~") + 1))
        + list(range(ord("¡"), ord("¬") + 1))
        + list(range(ord("®"), ord("ÿ") + 1))
    )
    cs = bs[:]
    n = 0
    for b in range(256):
        if b not in bs:
            bs.append(b)
            cs.append(256 + n)
            n += 1
    return dict(zip(bs, map(chr, cs)))


def _word_pairs(word: tuple[str, ...]) -> set[tuple[str, str]]:
    return set(zip(word[:-1], word[1:]))


class CLIPBPETokenizer:
    """CLIP's byte-BPE with ``</w>`` word suffix, built from vocab.json+merges.txt.

    ``__call__`` returns (ids, mask): ids is (B, max_len) int32 with
    BOS ... EOS padding, mask marks BOS..EOS inclusive.
    """

    def __init__(
        self,
        vocab: dict[str, int],
        merges: list[tuple[str, str]],
        max_len: int = 77,
        bos: str = "<|startoftext|>",
        eos: str = "<|endoftext|>",
        pad_id: int | None = None,
    ):
        self.vocab = vocab
        self.ranks = {pair: i for i, pair in enumerate(merges)}
        self.max_len = max_len
        self.bos_id = vocab[bos]
        self.eos_id = vocab[eos]
        self.pad_id = self.eos_id if pad_id is None else pad_id
        self.byte_map = _bytes_to_unicode()
        try:
            import regex
        except ImportError as e:  # pragma: no cover - present in this image
            raise ImportError(
                "CLIPBPETokenizer needs the 'regex' package (unicode categories in "
                "the CLIP split pattern) — pip install "
                "comfyui-parallelanything-tpu[text]"
            ) from e

        # CLIP's pattern: contractions, letter runs, digit runs, other symbols.
        self._pat = regex.compile(
            r"'s|'t|'re|'ve|'m|'ll|'d|[\p{L}]+|[\p{N}]|[^\s\p{L}\p{N}]+",
            regex.IGNORECASE,
        )
        self._cache: dict[str, list[int]] = {}

    @classmethod
    def from_files(cls, vocab_path: str, merges_path: str, **kw) -> "CLIPBPETokenizer":
        with open(vocab_path, encoding="utf-8") as f:
            vocab = json.load(f)
        merges: list[tuple[str, str]] = []
        with open(merges_path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line or line.startswith("#version"):
                    continue
                a, b = line.split()
                merges.append((a, b))
        return cls(vocab, merges, **kw)

    def _bpe(self, token: str) -> list[str]:
        word = tuple(token[:-1]) + (token[-1] + "</w>",)
        pairs = _word_pairs(word)
        if not pairs:
            return [token + "</w>"]
        while True:
            pair = min(pairs, key=lambda p: self.ranks.get(p, float("inf")))
            if pair not in self.ranks:
                break
            first, second = pair
            out: list[str] = []
            i = 0
            while i < len(word):
                if (
                    i < len(word) - 1
                    and word[i] == first
                    and word[i + 1] == second
                ):
                    out.append(first + second)
                    i += 2
                else:
                    out.append(word[i])
                    i += 1
            word = tuple(out)
            if len(word) == 1:
                break
            pairs = _word_pairs(word)
        return list(word)

    def encode(self, text: str) -> list[int]:
        """Text → token ids, unframed/unpadded."""
        ids: list[int] = []
        text = " ".join(text.lower().strip().split())
        for tok in self._pat.findall(text):
            key = tok
            cached = self._cache.get(key)
            if cached is None:
                mapped = "".join(self.byte_map[b] for b in tok.encode("utf-8"))
                try:
                    cached = [self.vocab[piece] for piece in self._bpe(mapped)]
                except KeyError as e:
                    # Silently dropping pieces would condition the model on a
                    # different prompt than the user wrote.
                    raise KeyError(
                        f"BPE piece {e.args[0]!r} (from token {tok!r}) missing from "
                        "the vocab — vocab.json/merges.txt pair mismatch?"
                    ) from e
                self._cache[key] = cached
            ids.extend(cached)
        return ids

    def __call__(self, texts: str | list[str]) -> tuple[np.ndarray, np.ndarray]:
        if isinstance(texts, str):
            texts = [texts]
        ids = np.full((len(texts), self.max_len), self.pad_id, np.int32)
        mask = np.zeros((len(texts), self.max_len), np.int32)
        for r, text in enumerate(texts):
            body = self.encode(text)[: self.max_len - 2]
            row = [self.bos_id, *body, self.eos_id]
            ids[r, : len(row)] = row
            mask[r, : len(row)] = 1
        return ids, mask


class JsonTokenizer:
    """tokenizer.json (HF fast format) wrapper — covers T5/modern-CLIP exports.
    Pads/truncates to ``max_len``; appends ``eos_id`` when set (T5 convention)."""

    def __init__(self, tok, max_len: int, eos_id: int | None = None, pad_id: int = 0):
        self._tok = tok
        self.max_len = max_len
        self.eos_id = eos_id
        self.pad_id = pad_id

    def __call__(self, texts: str | list[str]) -> tuple[np.ndarray, np.ndarray]:
        if isinstance(texts, str):
            texts = [texts]
        ids = np.full((len(texts), self.max_len), self.pad_id, np.int32)
        mask = np.zeros((len(texts), self.max_len), np.int32)
        for r, text in enumerate(texts):
            row = self._tok.encode(text).ids
            if self.eos_id is not None:
                # HF T5 tokenizer.json files append </s> via their post-processor
                # already — strip it first so EOS appears exactly once.
                while row and row[-1] == self.eos_id:
                    row = row[:-1]
                row = row[: self.max_len - 1] + [self.eos_id]
            else:
                row = row[: self.max_len]
            ids[r, : len(row)] = row
            mask[r, : len(row)] = 1
        return ids, mask


def load_tokenizer_json(
    path: str | os.PathLike, max_len: int = 512, eos_id: int | None = None,
    pad_id: int = 0,
) -> JsonTokenizer:
    try:
        from tokenizers import Tokenizer
    except ImportError as e:  # pragma: no cover - present in this image
        raise ImportError(
            "tokenizer.json loading needs the 'tokenizers' package; "
            "use CLIPBPETokenizer.from_files for vocab.json+merges.txt"
        ) from e
    return JsonTokenizer(
        Tokenizer.from_file(os.fspath(path)), max_len, eos_id, pad_id
    )


# Qwen3's chat template with a generation prompt and thinking left on, as
# Z-Image's pipeline applies it to one user message.
QWEN_CHAT_TEMPLATE = "<|im_start|>user\n{}<|im_end|>\n<|im_start|>assistant\n"


# Qwen-Image's: a SYSTEM prompt before the user's message
# (``pipeline_qwenimage.py``'s ``prompt_template_encode``). The states of
# everything through ``<|im_start|>user\n`` are cut off before the denoiser
# sees them (``ChatBucketTokenizer.prefix_length``).
QWEN_IMAGE_CHAT_TEMPLATE = (
    "<|im_start|>system\nDescribe the image by detailing the color, shape, size, "
    "texture, quantity, text, spatial relationships of the objects and "
    "background:<|im_end|>\n<|im_start|>user\n{}<|im_end|>\n<|im_start|>assistant\n")


class ChatBucketTokenizer:
    """A byte-level BPE ``tokenizer.json`` of Qwen's kind (HF fast format: the
    split pattern, the byte alphabet, merges by rank, ``<|im_start|>`` /
    ``<|im_end|>`` as added special tokens) behind a chat ``template``, for a
    decoder-only tower: no fixed window. ``__call__`` returns (ids, mask)
    with each text templated, truncated to ``max_len`` tokens and the batch
    padded with ``pad_id`` to the next multiple of ``bucket`` tokens; the
    mask marks the VALID tokens, which is all a causal tower's caller keeps.
    ``prefix_length`` says how many of a row's leading states a template with
    a system prompt has the caller drop."""

    PAD_TOKEN = "<|endoftext|>"
    TURN_TOKEN = "<|im_start|>"

    def __init__(self, tok, bucket: int = 32, max_len: int = 512,
                 template: str = QWEN_CHAT_TEMPLATE):
        self._tok = tok
        self.bucket, self.max_len, self.template = bucket, max_len, template
        self.pad_id = tok.token_to_id(self.PAD_TOKEN)
        if self.pad_id is None:
            raise ValueError(f"tokenizer.json has no {self.PAD_TOKEN!r} token to pad with")
        self._turn_id = tok.token_to_id(self.TURN_TOKEN)
        # How many turns open before the user's text: the states through the
        # last of them and the two tokens after it (``user``, a newline) are
        # the template's own, not the prompt's.
        self._turns_before_text = template.split("{}")[0].count(self.TURN_TOKEN)

    def encode(self, text: str) -> list[int]:
        """Text → the templated prompt's token ids, unpadded."""
        return self._tok.encode(self.template.format(text),
                                add_special_tokens=False).ids[: self.max_len]

    def prefix_length(self, row) -> int:
        """How many leading states of a templated row stand before the user's
        text, found BY POSITION as ComfyUI finds it — the turn that holds the
        text opens at the template's last ``<|im_start|>`` before it, and the
        role and the newline follow — so it holds under any table of merges
        (the published pipeline's constant 34 is this count under the
        published table). 0 for a template of one turn: nothing is cut."""
        if self._turns_before_text < 2:
            return 0
        turns = [i for i, t in enumerate(row) if t == self._turn_id]
        if len(turns) < self._turns_before_text:
            return 0
        return turns[self._turns_before_text - 1] + 3

    def __call__(self, texts: str | list[str]) -> tuple[np.ndarray, np.ndarray]:
        if isinstance(texts, str):
            texts = [texts]
        rows = [self.encode(t) for t in texts]
        width = -(-max(map(len, rows)) // self.bucket) * self.bucket
        ids = np.full((len(rows), width), self.pad_id, np.int32)
        mask = np.zeros((len(rows), width), np.int32)
        for r, row in enumerate(rows):
            ids[r, : len(row)] = row
            mask[r, : len(row)] = 1
        return ids, mask


def load_chat_tokenizer_json(path: str | os.PathLike, bucket: int = 32,
                             max_len: int = 512,
                             template: str = QWEN_CHAT_TEMPLATE) -> ChatBucketTokenizer:
    from tokenizers import Tokenizer

    return ChatBucketTokenizer(Tokenizer.from_file(os.fspath(path)), bucket,
                               max_len, template)
