"""``tokenizer_bpe``'s seeded Qwen table with MORE words learned as one token
each: the entry's ``words`` beside ``traffic/words.txt``.

A configuration whose template carries a system prompt, and whose traffic is
one fixed text outside ``words.txt``, needs them: under ``tokenizer_bpe``'s
table such a text falls into random pieces, another count under every seed,
and here the count of text tokens is part of the denoiser's compiled shape
(Qwen-Image hands the tower's valid states on unpadded). With the system
prompt's words, the fixed text's and ``system`` learned, the template's prefix
is the published table's 34 tokens and "a watercolor lighthouse at dawn" its 5
— as common English words are one token each in the published table — under
every seed.

``tokenizer_bpe.py`` is not edited (a PR that adds a configuration may only
add files): ``write`` lends its word list the entry's words for the length of
one call. The table's format, the specials' ids and the harness's own encoder
(``ByteBPE``, which ``load`` returns) are ``tokenizer_bpe``'s."""

from __future__ import annotations

from unittest import mock

from . import tokenizer_bpe
from .tokenizer_bpe import ByteBPE, load  # noqa: F401 — the writer's interface

__all__ = ["write", "load", "ByteBPE"]


def write(dirname: str, seed: int, entry: dict) -> dict:
    """The table of ``entry`` drawn from ``seed`` with ``entry["words"]``
    learned beside the traffic's words → ``{"tokenizer_json": path}``."""
    words = list(dict.fromkeys([*tokenizer_bpe._words(), *entry.get("words", ())]))
    with mock.patch.object(tokenizer_bpe, "_words", lambda: words):
        return tokenizer_bpe.write(dirname, seed, entry)
