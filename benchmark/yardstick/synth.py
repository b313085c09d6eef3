"""Synthetic weights and tokenizer tables from ``--seed``, in seconds.

A checkpoint of a billion fp16 (or bfloat16) values is not drawn value by value: one block of
2**24 standard normals is drawn from the seed, scaled once per distinct kind
(norm scale, bias/embedding, each kernel fan-in), and every tensor is a window
into its kind's block at an offset drawn from the same seed (tensors larger
than the block wrap around it). Equal seeds give equal bytes; activations stay
O(1) through every block, as with independent draws of the same variances."""

from __future__ import annotations

import json
import os

import ml_dtypes  # numpy's bfloat16; a dependency of jax
import numpy as np

from . import layout, safetensors_io

BLOCK = 1 << 24


DTYPES = {"float16": np.float16, "bfloat16": ml_dtypes.bfloat16}


def _scaled_blocks(seed: int, kinds, dtype=np.float16) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    base = rng.standard_normal(BLOCK, dtype=np.float32)
    out = {}
    for kind in sorted(kinds):
        if kind == "norm":
            out[kind] = (1.0 + 0.05 * base).astype(dtype)
        elif kind in ("bias", "emb"):
            out[kind] = (0.02 * base).astype(dtype)
        else:  # "w:<fan_in>"
            fan_in = int(kind.split(":")[1])
            out[kind] = (base * np.float32(fan_in ** -0.5)).astype(dtype)
    return out


def _chunks(block: np.ndarray, offset: int, n: int):
    """The ``n`` values of ``block`` from ``offset`` on, wrapping."""
    while n > 0:
        take = min(n, BLOCK - offset)
        yield block[offset:offset + take]
        n -= take
        offset = 0


def checkpoint_files(config: dict) -> list[dict]:
    """The configuration's weight files as ``{file, dtype, parts}``, in the
    order they are written: ``checkpoint.files`` where the configuration
    lists several, else the one ``file`` + ``parts`` (fp16 unless it says
    otherwise)."""
    ck = config["checkpoint"]
    files = ck.get("files") or [{k: ck[k] for k in ("file", "dtype", "parts") if k in ck}]
    return [{"dtype": "float16", **f} for f in files]


def write_checkpoint(path: str, seed: int, config: dict, index: int = 0) -> dict:
    """Weight file ``index`` of the configuration drawn from ``seed``. File 0
    draws its offsets from ``[seed, 1]`` and file i from ``[seed, 1, i]``, so
    a single-file configuration's bytes are what they always were."""
    spec = checkpoint_files(config)[index]
    entries = layout.checkpoint_layout(config, spec["parts"])
    dtype = DTYPES[spec["dtype"]]
    blocks = _scaled_blocks(seed, {k for _, _, k in entries}, dtype)
    key = [seed, 1] if index == 0 else [seed, 1, index]
    offsets = np.random.default_rng(key).integers(0, BLOCK, len(entries))
    os.makedirs(os.path.dirname(path), exist_ok=True)

    def numel(shape):
        return int(np.prod(shape, dtype=np.int64))

    n = safetensors_io.write(path, [
        (key, shape, dtype,
         _chunks(blocks[kind], int(off), numel(shape)))
        for (key, shape, kind), off in zip(entries, offsets)
    ])
    return {"parameters": n, "bytes": os.path.getsize(path),
            "tensors": len(entries)}


def write_checkpoints(work: str, seed: int, config: dict) -> tuple[dict, dict]:
    """Every weight file of the configuration under ``work`` → (``{file:
    path}`` as the configuration spells ``file``, what was written: per file
    and in sum)."""
    paths, per_file = {}, []
    for i, spec in enumerate(checkpoint_files(config)):
        paths[spec["file"]] = os.path.join(work, spec["file"])
        per_file.append({"file": spec["file"], "dtype": spec["dtype"],
                         **write_checkpoint(paths[spec["file"]], seed, config, i)})
    info = {k: sum(f[k] for f in per_file) for k in ("parameters", "bytes", "tensors")}
    return paths, {**info, "files": per_file}


def bytes_to_unicode() -> dict[int, str]:
    """GPT-2/CLIP's reversible byte → printable-unicode table."""
    bs = (list(range(ord("!"), ord("~") + 1))
          + list(range(ord("¡"), ord("¬") + 1))
          + list(range(ord("®"), ord("ÿ") + 1)))
    cs = bs[:]
    n = 0
    for b in range(256):
        if b not in bs:
            bs.append(b)
            cs.append(256 + n)
            n += 1
    return dict(zip(bs, map(chr, cs)))


def write_tokenizer(dirname: str, seed: int, vocab_size: int) -> tuple[str, str]:
    """A CLIP byte-BPE ``vocab.json`` + ``merges.txt`` pair of the published
    size and id layout — 256 byte symbols, their ``</w>`` forms, merges, then
    BOS and EOS as the last two ids — with the merges drawn from ``seed``.
    Every byte symbol is in the vocab, so any text tokenizes."""
    rng = np.random.default_rng([seed, 2])
    alphabet = list(bytes_to_unicode().values())
    vocab = alphabet + [c + "</w>" for c in alphabet]
    seen = set(vocab)
    merges: list[tuple[str, str]] = []
    letters = [c for c in alphabet if c.isalpha() and c.isascii()]
    # Draws in bulk: a python-level rng call per merge would cost seconds.
    while len(vocab) < vocab_size - 2:
        m = 4 * (vocab_size - len(vocab))
        coin, ia, ib, il = (rng.random(m), rng.random(m), rng.random(m),
                            rng.integers(len(letters), size=m))
        for j in range(m):
            if len(vocab) >= vocab_size - 2:
                break
            a = (vocab[int(ia[j] * len(vocab))] if coin[j] < 0.5
                 else letters[il[j]])
            b = vocab[int(ib[j] * len(vocab))]
            if a.endswith("</w>") or len(a) + len(b) > 12 or a + b in seen:
                continue
            seen.add(a + b)
            vocab.append(a + b)
            merges.append((a, b))
    vocab += ["<|startoftext|>", "<|endoftext|>"]
    os.makedirs(dirname, exist_ok=True)
    vocab_path = os.path.join(dirname, "vocab.json")
    merges_path = os.path.join(dirname, "merges.txt")
    with open(vocab_path, "w", encoding="utf-8") as f:
        json.dump({tok: i for i, tok in enumerate(vocab)}, f)
    with open(merges_path, "w", encoding="utf-8") as f:
        f.write("#version: 0.2\n")
        f.writelines(f"{a} {b}\n" for a, b in merges)
    return vocab_path, merges_path
