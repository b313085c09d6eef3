"""PNG files from a decoded batch, in two halves: the quantise and the adaptive
row filter as one program on the devices that hold the images, and the deflate
as row strips across the host's cores, joined into one zlib stream a file.

The result is what PIL's writer (the save node's encoder before this one) made
of the same floats: the same uint8 pixels, the filtered plane byte for byte
(its four candidates a row — None, Up, Sub, Paeth; it leaves Average to
``optimize=True`` — least sum of the bytes read as signed, the earlier winning
a tie), and zlib at PIL's settings. One strip a file is PIL's IDAT payload to
the byte; more strips cost a full flush each (a few bytes, and the 32 KiB
window restarts: +0.24% at 48 strips of a smooth 1024^2 image).
"""

from __future__ import annotations

import os
import struct
import zlib
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from . import tracing
from .metrics import registry

# One pool for every prompt of the process; its threads start at the first
# submit. zlib releases the GIL while it deflates, so strips run side by side.
THREADS = len(os.sched_getaffinity(0))
_POOL = ThreadPoolExecutor(max_workers=THREADS, thread_name_prefix="pa-png")

# Under this a strip's full flush and cold window cost more than its thread saves.
MIN_STRIP_BYTES = 64 << 10

SIGNATURE = b"\x89PNG\r\n\x1a\n"
_COLOR_TYPE = {3: 2, 4: 6}  # channels -> PNG colour type (RGB, RGBA), 8 bits each
# PNG filter ids in the order PIL's writer tries them; argmin keeps the first
# of equal sums, as its strict "less than" does.
_FILTER_IDS = (0, 2, 1, 4)


@jax.jit
def filter_program(images):
    """(B, H, W, C) floats in [0, 1] (one image (H, W, C), or video frames
    (B, F, H, W, C)) -> every row's PNG filter id, (N*H,) uint8, and every row
    filtered, (N*H*W*C,) uint8. Both flat: a device keeps an array of several
    dimensions in an order of its own choosing (the chip stores (8, 512, 1537)
    bytes rows-minor), and one dimension is the only shape whose bytes come
    back in row-major order without a transpose on the host. The id apart from
    its row: rows of 1 + W*C bytes laid end to end are no multiple of the
    chip's 128-byte tiles, and its compiler takes 7 to 24 s over them (2 to
    3 s for these two; compiles for a described v5e at 8 x 512^2 and 1024^2).
    The quantise is numpy's ``(clip(x, 0, 1) * 255 + 0.5)`` in float32,
    truncated. Every shift stays inside one image, so a batch sharded over its
    leading axis stays sharded."""
    x = images.astype(jnp.float32)
    x = x.reshape((-1,) + x.shape[-3:])
    n, h, w, c = x.shape
    q = (jnp.clip(x, 0.0, 1.0) * 255.0 + 0.5).astype(jnp.uint8)
    raw = q.reshape(n, h, w * c).astype(jnp.int32)
    # The byte one pixel to the left, the byte above, and the one above-left;
    # zero outside the image.
    left = jnp.pad(raw, ((0, 0), (0, 0), (c, 0)))[:, :, : w * c]
    up = jnp.pad(raw, ((0, 0), (1, 0), (0, 0)))[:, :h]
    upleft = jnp.pad(up, ((0, 0), (0, 0), (c, 0)))[:, :, : w * c]
    pa, pb, pc = abs(up - upleft), abs(left - upleft), abs(left + up - 2 * upleft)
    paeth = jnp.where((pa <= pb) & (pa <= pc), left, jnp.where(pb <= pc, up, upleft))
    cands = [(raw - pred) & 255 for pred in (0, up, left, paeth)]
    sums = jnp.stack([jnp.where(f < 128, f, 256 - f).sum(-1) for f in cands])
    pick = jnp.argmin(sums, axis=0)
    rows = jnp.select([pick[..., None] == k for k in range(3)], cands[:3], cands[3])
    ids = jnp.asarray(_FILTER_IDS, jnp.int32)[pick]
    return ids.astype(jnp.uint8).reshape(-1), rows.astype(jnp.uint8).reshape(-1)


def filter_rows(images) -> np.ndarray:
    """The images' filtered planes on the host, (N, H, 1 + W*C) uint8: every
    row's filter byte, then the row filtered. ``filter_program`` runs on the
    devices that hold ``images`` (a numpy input is put first)."""
    images = jnp.asarray(images)
    h, w, c = images.shape[-3:]
    ids, rows = filter_program(images)
    planes = np.empty((ids.size // h, h, 1 + w * c), np.uint8)
    planes[:, :, 0] = np.asarray(ids).reshape(-1, h)
    planes[:, :, 1:] = np.asarray(rows).reshape(-1, h, w * c)
    return planes


def strip_rows(rows: int, row_bytes: int, images: int) -> int:
    """Rows a strip: all images' strips together fill the pool's threads twice
    over (strips deflate at uneven speeds; the second round evens the ends),
    and no strip is smaller than ``MIN_STRIP_BYTES``."""
    strips = min(-(-2 * THREADS // images), rows * row_bytes // MIN_STRIP_BYTES)
    return -(-rows // max(1, strips))


def _deflate(strip, last: bool) -> bytes:
    # Raw deflate at PIL's settings (zlib's default level, memLevel 9,
    # Z_FILTERED). A full flush ends the strip on a byte boundary with nothing
    # pending, so the strips of one image concatenate into one stream.
    z = zlib.compressobj(zlib.Z_DEFAULT_COMPRESSION, zlib.DEFLATED, -15, 9,
                         zlib.Z_FILTERED)
    return z.compress(strip) + z.flush(zlib.Z_FINISH if last else zlib.Z_FULL_FLUSH)


def _chunk(cid: bytes, *parts: bytes) -> list[bytes]:
    crc = zlib.crc32(cid)
    for p in parts:
        crc = zlib.crc32(p, crc)
    return [struct.pack(">I", sum(map(len, parts))), cid, *parts,
            struct.pack(">I", crc)]


def write_pngs(planes: np.ndarray, channels: int, paths, chunks=()) -> None:
    """Write ``planes[i]`` (``filter_rows``' bytes, fetched) to ``paths[i]`` as
    an 8-bit PNG: signature, IHDR, ``chunks`` (``PngInfo.chunks``, verbatim),
    one IDAT, IEND. Every image's strips are submitted at once; the caller's
    thread sums the Adler-32 of the planes while they deflate and writes each
    file when its strips are in. One ``png-encode`` span covers it all. An
    exception from a strip reaches the caller and leaves the files of that
    image and of those after it unwritten."""
    if channels not in _COLOR_TYPE:
        raise ValueError(
            f"a PNG takes 3 (RGB) or 4 (RGBA) channels, not {channels}")
    n, rows, row_bytes = planes.shape
    per = strip_rows(rows, row_bytes, n)
    cuts = range(0, rows, per)
    strips_total = n * len(cuts)
    with tracing.span("png-encode", cat="graph", images=n, threads=THREADS) as sp:
        futures = [[_POOL.submit(_deflate, plane[lo:lo + per], lo + per >= rows)
                    for lo in cuts] for plane in planes]
        try:
            head = [SIGNATURE, *_chunk(b"IHDR", struct.pack(
                ">IIBBBBB", (row_bytes - 1) // channels, rows, 8,
                _COLOR_TYPE[channels], 0, 0, 0))]
            for cid, data, *_ in chunks:
                head += _chunk(cid, data)
            written = 0
            for plane, path, strips in zip(planes, paths, futures):
                adler = struct.pack(">I", zlib.adler32(plane))
                idat = _chunk(b"IDAT", b"\x78\x9c", *[f.result() for f in strips],
                              adler)
                written += _write(path, head + idat + _chunk(b"IEND"))
        finally:
            for strips in futures:
                for f in strips:
                    f.cancel()
        sp.set(strips=strips_total, bytes=written)
    registry.counter("pa_png_images_total", n,
                     help="PNG files written by the save node")
    registry.counter("pa_png_strips_total", strips_total,
                     help="row strips deflated for them on the pool's threads "
                          "(over pa_png_images_total: strips a file)")


def _write(path, parts) -> int:
    try:
        with open(path, "wb") as f:
            f.writelines(parts)
    except BaseException:
        if os.path.exists(path):
            os.unlink(path)
        raise
    return sum(map(len, parts))
