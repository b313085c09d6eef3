"""Minimal safetensors writer/reader (the public single-file layout): an
8-byte little-endian header length, a JSON header of
``{name: {dtype, shape, data_offsets}}``, then the raw little-endian buffers.
The benchmark writes its synthetic checkpoints with this and its reference
reads them back with it — nothing of the program is involved."""

from __future__ import annotations

import json
import struct

import ml_dtypes  # numpy's bfloat16; a dependency of jax
import numpy as np

_DTYPES = {"F16": np.float16, "F32": np.float32, "BF16": ml_dtypes.bfloat16,
           "I64": np.int64}
_NAMES = {np.dtype(v): k for k, v in _DTYPES.items()}


def write(path: str, entries) -> int:
    """``entries``: iterable of ``(name, shape, dtype, chunks)`` where
    ``chunks`` is an iterable of C-contiguous 1-D arrays whose concatenation is
    the tensor's buffer. Returns the number of elements written. Two passes:
    the header needs every offset, so ``entries`` must be re-iterable."""
    header, offset, total = {}, 0, 0
    for name, shape, dtype, _ in entries:
        n = int(np.prod(shape, dtype=np.int64)) if len(shape) else 1
        nbytes = n * np.dtype(dtype).itemsize
        header[name] = {"dtype": _NAMES[np.dtype(dtype)], "shape": list(shape),
                        "data_offsets": [offset, offset + nbytes]}
        offset += nbytes
        total += n
    blob = json.dumps(header, separators=(",", ":")).encode()
    blob += b" " * (-len(blob) % 8)
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(blob)))
        f.write(blob)
        for _, _, _, chunks in entries:
            for c in chunks:
                # as bytes: bfloat16 has no buffer-protocol format of its own
                f.write(memoryview(np.ascontiguousarray(c).reshape(-1).view(np.uint8)))
    return total


def read(path: str, prefix: str = "") -> dict[str, np.ndarray]:
    """Name → array views over a memory map of ``path`` (read-only), for the
    names that start with ``prefix`` (the prefix is stripped)."""
    with open(path, "rb") as f:
        (n,) = struct.unpack("<Q", f.read(8))
        header = json.loads(f.read(n))
    data = np.memmap(path, dtype=np.uint8, mode="r", offset=8 + n)
    out = {}
    for name, meta in header.items():
        if name == "__metadata__" or not name.startswith(prefix):
            continue
        dtype = _DTYPES[meta["dtype"]]
        a, b = meta["data_offsets"]
        out[name[len(prefix):]] = data[a:b].view(dtype).reshape(meta["shape"])
    return out
