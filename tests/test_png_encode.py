"""The save node's PNG encoder (utils/png_encode.py): quantise and row filter
as one program where the images are, deflate in row strips on one pool, one
zlib stream a file. PIL is the decoder and the oracle: what its own writer made
of the same floats is what these files must hold."""

from __future__ import annotations

import io
import struct
import sys
import threading
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from PIL import Image
from PIL.PngImagePlugin import PngInfo

from comfyui_parallelanything_tpu.nodes import TPUSaveImage
from comfyui_parallelanything_tpu.utils import png_encode
from comfyui_parallelanything_tpu.utils.metrics import registry


def quantise(x) -> np.ndarray:
    """What the node wrote before this encoder, and must still write."""
    return (np.clip(np.asarray(x), 0.0, 1.0) * 255.0 + 0.5).astype(np.uint8)


def floats(shape, seed=0) -> np.ndarray:
    """Uniform floats a little outside [0, 1], so the clip has work."""
    return np.random.default_rng(seed).uniform(-0.1, 1.1, size=shape).astype(np.float32)


def structured(h, w, seed=0) -> np.ndarray:
    """Blocks + gradients + noise, as floats in [0, 1]."""
    r = np.random.default_rng(seed)
    y, x = np.mgrid[0:h, 0:w]
    img = np.stack([x / max(w - 1, 1), y / max(h - 1, 1), ((x + y) % 256) / 255.0], -1)
    img[h // 4: h // 2, w // 4: w // 2] = [0.8, 0.1, 0.35]
    img[h // 2:, : w // 3] += r.normal(0, 0.05, size=(h - h // 2, w // 3, 3))
    return np.clip(img, 0.0, 1.0).astype(np.float32)


def chunks_of(data: bytes) -> list[tuple[bytes, bytes]]:
    """(type, data) of every chunk, each CRC checked."""
    assert data[:8] == png_encode.SIGNATURE
    out, pos = [], 8
    while pos < len(data):
        (n,) = struct.unpack(">I", data[pos:pos + 4])
        cid, body = data[pos + 4:pos + 8], data[pos + 8:pos + 8 + n]
        (crc,) = struct.unpack(">I", data[pos + 8 + n:pos + 12 + n])
        assert crc == zlib.crc32(cid + body), cid
        out.append((cid, body))
        pos += 12 + n
    return out


def pil_file(img_u8, pnginfo=None) -> bytes:
    buf = io.BytesIO()
    Image.fromarray(img_u8).save(buf, format="PNG", pnginfo=pnginfo)
    return buf.getvalue()


def plane_of(data: bytes) -> bytes:
    """The filtered plane a file's IDAT chunks inflate to (Adler-32 checked)."""
    return zlib.decompress(b"".join(b for c, b in chunks_of(data) if c == b"IDAT"))


def reference_filter(img_u8) -> bytes:
    """PIL's adaptive filter, row by row in plain Python integers: None, Up,
    Sub, Paeth in that order, the least sum of the filtered bytes read as
    signed, a later candidate only on a strictly smaller sum."""
    h, w, c = img_u8.shape
    rows = img_u8.reshape(h, w * c).tolist()
    above, out = [0] * (w * c), bytearray()

    def paeth(a, b, cc):
        p = a + b - cc
        pa, pb, pc = abs(p - a), abs(p - b), abs(p - cc)
        return a if pa <= pb and pa <= pc else b if pb <= pc else cc

    for row in rows:
        left = [0] * c + row[:-c]
        upleft = [0] * c + above[:-c]
        cands = [
            (0, row),
            (2, [(v - b) & 255 for v, b in zip(row, above)]),
            (1, [(v - a) & 255 for v, a in zip(row, left)]),
            (4, [(v - paeth(a, b, cc)) & 255
                 for v, a, b, cc in zip(row, left, above, upleft)]),
        ]
        best = None
        for fid, f in cands:
            cost = sum(v if v < 128 else 256 - v for v in f)
            if best is None or cost < best[0]:
                best = (cost, fid, f)
        out.append(best[1])
        out.extend(best[2])
        above = row
    return bytes(out)


def save(images, tmp_path, **kw):
    (paths,) = TPUSaveImage().save(images, "t", str(tmp_path), **kw)
    return paths


def counters():
    return (registry.get("pa_png_images_total") or 0.0,
            registry.get("pa_png_strips_total") or 0.0)


class TestPixels:
    @pytest.mark.parametrize("batch", [1, 3, 8])
    @pytest.mark.parametrize("hw", [(16, 16), (48, 64), (1, 7), (7, 1), (512, 512)])
    def test_files_decode_to_the_quantised_floats(self, hw, batch, tmp_path):
        x = floats((batch, *hw, 3), seed=batch)
        paths = save(jnp.asarray(x), tmp_path)
        assert len(paths) == batch
        for p, want in zip(paths, quantise(x)):
            Image.open(p).verify()
            got = Image.open(p)
            assert got.mode == "RGB" and got.size == hw[::-1]
            np.testing.assert_array_equal(np.asarray(got), want)

    @pytest.mark.parametrize("kind", ["numpy", "one-image", "video", "rgba", "float16"])
    def test_other_inputs_take_the_same_path(self, kind, tmp_path):
        x = {
            "numpy": floats((2, 12, 20, 3)),
            "one-image": floats((12, 20, 3)),
            "video": floats((2, 3, 12, 20, 3)),  # every frame of every clip, in order
            "rgba": floats((2, 12, 20, 4)),
            "float16": floats((2, 12, 20, 3)).astype(np.float16),
        }[kind]
        paths = save(x if kind == "numpy" else jnp.asarray(x), tmp_path)
        want = quantise(x.astype(np.float32)).reshape((-1,) + x.shape[-3:])
        assert len(paths) == len(want)
        for p, w in zip(paths, want):
            np.testing.assert_array_equal(np.asarray(Image.open(p)), w)

    def test_quantise_is_numpys_over_a_whole_binade(self, tmp_path):
        """Every float32 in [0.25, 0.5) (2^23 less a row's end, the .5 boundaries
        of 64 … 127 among them), then the floats on both sides of all 256."""
        lo, hi = (np.float32(v).view(np.int32) for v in (0.25, 0.5))
        binade = np.arange(lo, hi, dtype=np.int32).view(np.float32)
        edges = (np.arange(256, dtype=np.float32)[:, None] + 0.5) / 255.0
        edges = np.concatenate([np.nextafter(edges, np.float32(d)) for d in (0, 1)]
                               + [edges, edges + np.float32(1e-7)], axis=1)
        for v, shape in ((binade, (1, 2048, 1365, 3)), (edges, (1, 8, 32, 3))):
            x = v.ravel()[: np.prod(shape)].reshape(shape)
            (p,) = save(jnp.asarray(x), tmp_path)
            np.testing.assert_array_equal(np.asarray(Image.open(p)), quantise(x)[0])

    def test_other_channel_counts_are_refused_before_any_file(self, tmp_path):
        with pytest.raises(ValueError, match="3 .RGB. or 4 .RGBA. channels"):
            save(jnp.zeros((1, 8, 8, 2)), tmp_path)
        assert not list(tmp_path.iterdir())


class TestFilter:
    @pytest.mark.parametrize("name", ["structured", "noise", "flat", "one-row", "rgba"])
    def test_device_filter_is_the_reference_and_pils_plane(self, name):
        x = {
            "structured": structured(96, 80),
            "noise": floats((40, 33, 3)),
            "flat": np.full((9, 9, 3), 0.5, np.float32),  # ties: Sub = Paeth on top, Up = Paeth below
            "one-row": floats((1, 50, 3)),
            "rgba": floats((24, 24, 4)),
        }[name]
        got = png_encode.filter_rows(jnp.asarray(x))
        assert got.shape == (1, x.shape[0], 1 + x.shape[1] * x.shape[2])
        assert got.dtype == np.uint8 and got.flags.c_contiguous
        assert got.tobytes() == reference_filter(quantise(x))
        assert got.tobytes() == plane_of(pil_file(quantise(x)))

    def test_a_sharded_batch_stays_sharded(self):
        """No gather before or inside the filter program: every shift is
        inside one image."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devices = jax.devices()[:4]
        if len(devices) < 4:
            pytest.skip("needs 4 (virtual) devices")
        x = floats((8, 16, 24, 3))
        sharded = jax.device_put(x, NamedSharding(Mesh(devices, ("data",)), P("data")))
        for out, row in zip(png_encode.filter_program(sharded), (1, 72)):
            assert {s.data.shape for s in out.addressable_shards} == {(2 * 16 * row,)}
            assert {s.device for s in out.addressable_shards} == set(devices)
        hlo = png_encode.filter_program.lower(sharded).compile().as_text()
        assert not [w for w in ("all-gather", "all-reduce", "all-to-all",
                                "collective-permute") if w in hlo]
        np.testing.assert_array_equal(
            png_encode.filter_rows(sharded), png_encode.filter_rows(x))


class TestStream:
    @pytest.mark.parametrize("rows_a_strip", [16, 6, 5, 1])
    def test_forced_strips_are_one_valid_stream(self, rows_a_strip, tmp_path,
                                                monkeypatch):
        """16 rows as 1, 3 (6 + 6 + 4), 4 (5 + 5 + 5 + 1) and 16 strips."""
        monkeypatch.setattr(png_encode, "strip_rows", lambda *a: rows_a_strip)
        x = floats((2, 16, 24, 3))
        before = counters()
        paths = save(jnp.asarray(x), tmp_path)
        strips = -(-16 // rows_a_strip)
        assert counters() == (before[0] + 2, before[1] + 2 * strips)
        for p, want in zip(paths, quantise(x)):
            data = Path(p).read_bytes()
            kinds = [c for c, _ in chunks_of(data)]
            assert kinds == [b"IHDR", b"IDAT", b"IEND"]
            assert plane_of(data) == reference_filter(want)
            Image.open(p).verify()
            np.testing.assert_array_equal(np.asarray(Image.open(p)), want)

    def test_one_strip_is_pils_idat_to_the_byte(self, tmp_path, monkeypatch):
        monkeypatch.setattr(png_encode, "strip_rows", lambda rows, *a: rows)
        x = structured(64, 64)
        (p,) = save(jnp.asarray(x), tmp_path)
        ours = dict(chunks_of(Path(p).read_bytes()))
        pils = chunks_of(pil_file(quantise(x)))
        assert ours[b"IHDR"] == dict(pils)[b"IHDR"]
        assert ours[b"IDAT"] == b"".join(b for c, b in pils if c == b"IDAT")

    @pytest.mark.parametrize("name", ["structured", "noise"])
    def test_size_within_one_percent_of_pils(self, name, tmp_path):
        x = structured(512, 512) if name == "structured" else floats((512, 512, 3))
        before = counters()
        (p,) = save(jnp.asarray(x), tmp_path)
        if png_encode.THREADS > 1:
            assert counters()[1] - before[1] > 1  # strips engaged at this size
        ours, pils = Path(p).stat().st_size, len(pil_file(quantise(x)))
        assert abs(ours - pils) <= 0.01 * pils, (ours, pils)

    @pytest.mark.parametrize("rows,row_bytes,images,want", [
        (16, 49, 1, 1),  # a 16 x 16 test image is one strip
        (16, 49, 8, 1),
        (1024, 3073, 1, lambda t: min(2 * t, 48)),  # never under 64 KiB a strip
        (512, 1537, 8, lambda t: min(-(-2 * t // 8), 12)),
        (512, 1537, 64, lambda t: max(1, min(-(-2 * t // 64), 12))),
    ])
    def test_strip_count_comes_from_rows_images_and_cores(self, rows, row_bytes,
                                                          images, want):
        want = want(png_encode.THREADS) if callable(want) else want
        per = png_encode.strip_rows(rows, row_bytes, images)
        assert 1 <= per <= rows
        assert abs(-(-rows // per) - want) <= 1  # ceil of a ceil
        if want > 1:
            assert per * row_bytes >= png_encode.MIN_STRIP_BYTES


class TestText:
    @pytest.mark.parametrize("metadata", ["prompt: a lighthouse",
                                          "un phare — 灯台 ✓"])  # second: not latin-1
    def test_text_chunks_are_pils_and_read_back(self, metadata, tmp_path):
        graph = {"3": {"class_type": "KSampler", "inputs": {"seed": 7, "t": "é"}}}
        x = floats((2, 8, 8, 3))
        paths = save(jnp.asarray(x), tmp_path, metadata=metadata, prompt=graph)
        import json

        info = PngInfo()
        info.add_text("parameters", metadata)
        info.add_text("prompt", json.dumps(graph, default=repr))
        for p, want in zip(paths, quantise(x)):
            im = Image.open(p)
            assert im.text == {"parameters": metadata,
                               "prompt": json.dumps(graph, default=repr)}
            assert json.loads(im.text["prompt"]) == graph
            ours = [c for c in chunks_of(Path(p).read_bytes()) if c[0] != b"IDAT"]
            pils = [c for c in chunks_of(pil_file(want, info)) if c[0] != b"IDAT"]
            assert ours == pils  # the same chunks in the same places

    def test_no_text_no_chunk(self, tmp_path):
        (p,) = save(jnp.zeros((1, 4, 4, 3)), tmp_path)
        assert Image.open(p).text == {}


class TestPool:
    def test_one_pool_whose_threads_do_not_grow(self, tmp_path):
        pool = png_encode._POOL
        x = jnp.asarray(floats((3, 16, 16, 3)))

        def ours():
            return [t for t in threading.enumerate() if t.name.startswith("pa-png")]

        save(x, tmp_path)
        for _ in range(50):
            save(x, tmp_path)
            assert len(ours()) <= png_encode.THREADS
        assert png_encode._POOL is pool
        assert png_encode.THREADS == len(__import__("os").sched_getaffinity(0))
        assert len(list(tmp_path.iterdir())) == 51 * 3

    def test_a_failing_strip_reaches_the_caller_and_leaves_no_partial_file(
            self, tmp_path, monkeypatch):
        monkeypatch.setattr(png_encode, "strip_rows", lambda *a: 4)  # 4 strips a file
        deflate, seen = png_encode._deflate, []
        lock = threading.Lock()

        def failing(strip, last):
            with lock:
                seen.append(last)
                k = len(seen)
            if k == 6:  # the second image's second strip
                raise MemoryError("strip 6")
            return deflate(strip, last)

        monkeypatch.setattr(png_encode, "_deflate", failing)
        before = counters()
        with pytest.raises(MemoryError, match="strip 6"):
            save(jnp.asarray(floats((3, 16, 16, 3))), tmp_path)
        left = sorted(tmp_path.iterdir())
        assert len(left) <= 1  # the first image at most: it was whole
        for p in left:
            Image.open(p).verify()
        assert counters() == before
        # and the pool still serves the next prompt
        monkeypatch.setattr(png_encode, "_deflate", deflate)
        assert len(save(jnp.asarray(floats((3, 16, 16, 3))), tmp_path)) == 3

    def test_concurrent_prompts_share_the_pool(self, tmp_path, monkeypatch):
        """More callers than cores, a short switch interval: every file is
        whole and its own."""
        monkeypatch.setattr(png_encode, "strip_rows", lambda *a: 3)
        callers = 2 * png_encode.THREADS
        batches = [floats((2, 16, 16, 3), seed=i) for i in range(callers)]
        results, errors = {}, []

        def run(i):
            try:
                d = tmp_path / str(i)
                d.mkdir()
                results[i] = save(jnp.asarray(batches[i]), d)
            except Exception as e:  # reported below
                errors.append(e)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=run, args=(i,)) for i in range(callers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        finally:
            sys.setswitchinterval(old)
        assert not errors and not any(t.is_alive() for t in threads)
        assert len(results) == callers
        for i, paths in results.items():
            for p, want in zip(paths, quantise(batches[i])):
                np.testing.assert_array_equal(np.asarray(Image.open(p)), want)
