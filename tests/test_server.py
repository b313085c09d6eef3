"""ComfyUI-compatible HTTP API (server.py): POST /prompt → history → /view,
over the real workflow host with a persistent cross-prompt cache."""

import json
import time
import urllib.request

import numpy as np
import pytest

from comfyui_parallelanything_tpu.server import make_server
from tests.test_stock_nodes import _synthetic_stock_env


@pytest.fixture
def server(tmp_path, monkeypatch):
    out_dir = tmp_path / "out"
    srv, q = make_server(port=0, output_dir=str(out_dir))
    thread = __import__("threading").Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    yield base, q, str(out_dir)
    srv.shutdown()
    q.shutdown()


@pytest.fixture
def server_mt(tmp_path, monkeypatch):
    """Multi-worker server: 2 concurrent prompt workers + the installed
    continuous-batching scheduler (the serving-mode configuration)."""
    out_dir = tmp_path / "out"
    srv, q = make_server(port=0, output_dir=str(out_dir), workers=2)
    thread = __import__("threading").Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    yield base, q, str(out_dir)
    srv.shutdown()
    q.shutdown()


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        ct = r.headers.get("Content-Type", "")
        body = r.read()
    return json.loads(body) if "json" in ct else body


def _post(base, path, payload=None):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload or {}).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def _wait_history(base, pid, timeout=300):
    t0 = time.time()
    while time.time() - t0 < timeout:
        hist = _get(base, f"/history/{pid}")
        if pid in hist:
            return hist[pid]
        time.sleep(0.5)
    raise TimeoutError(f"prompt {pid} never completed")


def _stock_graph(ckpt, out_dir):
    return {
        "4": {"class_type": "CheckpointLoaderSimple",
              "inputs": {"ckpt_name": ckpt}},
        "5": {"class_type": "EmptyLatentImage",
              "inputs": {"width": 32, "height": 32, "batch_size": 1}},
        "6": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "a watercolor lighthouse", "clip": ["4", 1]}},
        "3": {"class_type": "KSampler",
              "inputs": {"seed": 3, "steps": 2, "cfg": 1.0,
                         "sampler_name": "euler", "scheduler": "normal",
                         "denoise": 1.0, "model": ["4", 0],
                         "positive": ["6", 0], "latent_image": ["5", 0]}},
        "8": {"class_type": "VAEDecode",
              "inputs": {"samples": ["3", 0], "vae": ["4", 2]}},
        "9": {"class_type": "SaveImage",
              "inputs": {"images": ["8", 0], "filename_prefix": "api",
                         "output_dir": out_dir}},
    }


class TestServer:
    def test_prompt_history_view_roundtrip(self, server, tmp_path, monkeypatch):
        base, q, out_dir = server
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        wf = _stock_graph(paths["ckpt"], out_dir)

        resp = _post(base, "/prompt", {"prompt": wf})
        assert "prompt_id" in resp
        entry = _wait_history(base, resp["prompt_id"])
        assert entry["status"]["status_str"] == "success", entry["status"]
        images = entry["outputs"]["9"]["images"]
        assert len(images) == 1
        png = _get(
            base,
            f"/view?filename={images[0]['filename']}"
            f"&subfolder={images[0]['subfolder']}",
        )
        assert png[:8] == b"\x89PNG\r\n\x1a\n"

        # Second prompt reuses the cache: the checkpoint node must not
        # re-execute (same signature), only the edited subgraph.
        wf2 = json.loads(json.dumps(wf))
        wf2["3"]["inputs"]["seed"] = 4
        sig_keys = set(q.cache.results)
        resp2 = _post(base, "/prompt", {"prompt": wf2})
        entry2 = _wait_history(base, resp2["prompt_id"])
        assert entry2["status"]["status_str"] == "success"
        assert set(q.cache.results) >= sig_keys  # loader entry survived

    def test_error_lands_in_history(self, server):
        base, _, _ = server
        resp = _post(base, "/prompt", {"prompt": {
            "1": {"class_type": "NoSuchNode", "inputs": {}}
        }})
        entry = _wait_history(base, resp["prompt_id"])
        assert entry["status"]["status_str"] == "error"
        assert "NoSuchNode" in entry["status"]["message"]

    def test_bad_request_rejected(self, server):
        base, _, _ = server
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(base, "/prompt", {"not_prompt": 1})
        assert err.value.code == 400

    def test_object_info_exposes_registry(self, server):
        base, _, _ = server
        info = _get(base, "/object_info/KSampler")
        assert info["KSampler"]["display_name"]
        assert "seed" in json.dumps(info["KSampler"]["input"])
        everything = _get(base, "/object_info")
        assert {"CheckpointLoaderSimple", "TPUKSampler",
                "ParallelAnything"} <= set(everything)

    def test_view_path_escape_rejected(self, server):
        base, _, _ = server
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(base, "/view?filename=../../etc/passwd")
        assert err.value.code == 403

    def test_queue_and_interrupt(self, server):
        base, q, _ = server
        state = _get(base, "/queue")
        assert state == {"queue_running": [], "queue_pending": []}
        assert _post(base, "/interrupt")["dropped"] == 0

    def test_system_stats_lists_devices(self, server):
        base, _, _ = server
        stats = _get(base, "/system_stats")
        assert isinstance(stats["devices"], list) and stats["devices"]

    def _ws_connect(self, base, raw=False):
        """Open /ws; returns (sock, read_event) — RFC 6455 client handshake.
        ``raw=True`` returns frames as (opcode, payload bytes) instead of
        parsed JSON (binary preview frames are not JSON)."""
        import base64 as b64
        import socket
        import struct

        port = int(base.rsplit(":", 1)[1])
        sock = socket.create_connection(("127.0.0.1", port), timeout=120)
        key = b64.b64encode(b"0123456789abcdef").decode()
        sock.sendall(
            (f"GET /ws HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
             f"Upgrade: websocket\r\nConnection: Upgrade\r\n"
             f"Sec-WebSocket-Key: {key}\r\nSec-WebSocket-Version: 13\r\n"
             "\r\n").encode()
        )
        f = sock.makefile("rb")
        assert b"101" in f.readline()
        while f.readline() not in (b"\r\n", b""):
            pass

        def read_frame():
            hdr = f.read(2)
            n = hdr[1] & 0x7F
            if n == 126:
                n = struct.unpack(">H", f.read(2))[0]
            elif n == 127:
                n = struct.unpack(">Q", f.read(8))[0]
            return hdr[0] & 0x0F, f.read(n)

        def read_event():
            opcode, payload = read_frame()
            assert opcode == 0x1, f"expected text frame, got opcode {opcode}"
            return json.loads(payload)

        return sock, (read_frame if raw else read_event)

    def test_websocket_node_and_progress_events(self, server, tmp_path,
                                                monkeypatch):
        # The full frontend protocol: per-node `executing` events in graph
        # order and per-sampler-step `progress` events
        # — what a stock ComfyUI client renders its progress bars from.
        base, _, out_dir = server
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        wf = _stock_graph(paths["ckpt"], out_dir)
        sock, read_event = self._ws_connect(base)
        pid = _post(base, "/prompt", {"prompt": wf})["prompt_id"]
        events = []
        for _ in range(200):
            evt = read_event()
            events.append(evt)
            if (evt["type"] == "executing"
                    and evt["data"].get("node") is None
                    and evt["data"].get("prompt_id") == pid):
                break
        else:
            raise AssertionError("no completion event")
        sock.close()

        executing = [e["data"]["node"] for e in events
                     if e["type"] == "executing" and e["data"]["node"]]
        # Every graph node executes exactly once, deps before dependents.
        assert set(executing) == set(wf)
        assert executing.index("4") < executing.index("3") < executing.index("9")
        progress = [e["data"] for e in events if e["type"] == "progress"]
        assert [p["value"] for p in progress] == [1, 2]  # steps=2
        assert all(p["max"] == 2 and p["prompt_id"] == pid for p in progress)
        assert all(p["node"] == "3" for p in progress)  # tagged to the KSampler
        executed = [e["data"] for e in events if e["type"] == "executed"]
        assert [d["node"] for d in executed] == ["9"]  # the SaveImage node
        assert executed[0]["output"]["images"][0]["filename"]

        # Second prompt with one edit: unchanged upstream nodes are announced
        # as cache-served via execution_cached.
        sock, read_event = self._ws_connect(base)
        wf2 = json.loads(json.dumps(wf))
        wf2["3"]["inputs"]["seed"] = 99
        pid2 = _post(base, "/prompt", {"prompt": wf2})["prompt_id"]
        cached = None
        for _ in range(200):
            evt = read_event()
            if evt["type"] == "execution_cached":
                cached = evt["data"]
            if (evt["type"] == "executing"
                    and evt["data"].get("node") is None
                    and evt["data"].get("prompt_id") == pid2):
                break
        sock.close()
        assert cached is not None and cached["prompt_id"] == pid2
        # The loader/encoders survive the seed edit; the sampler chain reruns.
        assert "4" in cached["nodes"] and "3" not in cached["nodes"]

    def test_interrupt_stops_running_prompt(self, server, tmp_path,
                                            monkeypatch):
        # POST /interrupt must stop the RUNNING prompt between sampler steps
        # (cooperative flag), not just drop pending ones — ComfyUI's Cancel.
        base, _, out_dir = server
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        wf = _stock_graph(paths["ckpt"], out_dir)
        wf["3"]["inputs"]["steps"] = 500  # long enough to interrupt mid-loop
        sock, read_event = self._ws_connect(base)
        pid = _post(base, "/prompt", {"prompt": wf})["prompt_id"]
        # Wait until the sampler is demonstrably inside its loop.
        for _ in range(200):
            evt = read_event()
            if evt["type"] == "progress":
                break
        else:
            raise AssertionError("sampler never reported progress")
        _post(base, "/interrupt")
        saw_interrupt_event = False
        for _ in range(600):
            evt = read_event()
            if evt["type"] == "execution_interrupted":
                assert evt["data"]["prompt_id"] == pid
                saw_interrupt_event = True
            if (evt["type"] == "executing"
                    and evt["data"].get("node") is None):
                break
        sock.close()
        assert saw_interrupt_event
        entry = _wait_history(base, pid)
        assert entry["status"]["status_str"] == "interrupted"
        assert entry["status"]["completed"] is False

    def test_websocket_completion_events(self, server):
        # The ComfyUI API-client pattern: open /ws, POST /prompt, block on
        # the 'executing' event with node=None and the prompt_id — no
        # history polling.
        import base64 as b64
        import socket
        import struct

        base, _, _ = server
        port = int(base.rsplit(":", 1)[1])
        sock = socket.create_connection(("127.0.0.1", port), timeout=60)
        key = b64.b64encode(b"0123456789abcdef").decode()
        sock.sendall(
            (f"GET /ws HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
             f"Upgrade: websocket\r\nConnection: Upgrade\r\n"
             f"Sec-WebSocket-Key: {key}\r\nSec-WebSocket-Version: 13\r\n"
             "\r\n").encode()
        )
        f = sock.makefile("rb")
        status = f.readline()
        assert b"101" in status
        while f.readline() not in (b"\r\n", b""):  # drain handshake headers
            pass

        def read_event():
            hdr = f.read(2)
            n = hdr[1] & 0x7F
            if n == 126:
                n = struct.unpack(">H", f.read(2))[0]
            return json.loads(f.read(n))

        # An intentionally failing prompt still completes with events.
        resp = _post(base, "/prompt", {"prompt": {
            "1": {"class_type": "NoSuchNode", "inputs": {}}
        }})
        pid = resp["prompt_id"]
        seen = []
        for _ in range(6):
            evt = read_event()
            seen.append(evt["type"])
            if (evt["type"] == "executing"
                    and evt["data"]["node"] is None
                    and evt["data"]["prompt_id"] == pid):
                break
        else:
            raise AssertionError(f"no completion event; saw {seen}")
        assert "status" in seen  # queue-change event arrived too
        sock.close()


class TestServingServer:
    """Round 7: the serving-mode server (workers>1 + continuous batching) and
    the protocol additions that ride along (per-prompt delete, 429, /metrics)."""

    def test_concurrent_ws_event_ordering(self, server_mt, tmp_path,
                                          monkeypatch):
        """Two clients submit concurrently to a 2-worker server: every event
        stream stays correctly tagged — each prompt's `progress` values count
        1..N in order under its own prompt_id and node id, `executed` and the
        completion signal carry the right prompt_id — even while both prompts
        execute (and co-batch) simultaneously."""
        base, q, out_dir = server_mt
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        wf0 = _stock_graph(paths["ckpt"], out_dir)
        wf0["3"]["inputs"]["steps"] = 1
        # Warm the workflow cache (loader/encoders) so the two concurrent
        # prompts share ONE model object — the same-bucket co-batching case.
        warm = _post(base, "/prompt", {"prompt": wf0})["prompt_id"]
        assert _wait_history(base, warm)["status"]["status_str"] == "success"

        wf1 = _stock_graph(paths["ckpt"], out_dir)
        # 8 steps: wide enough a window that the second prompt reliably
        # joins the first one's in-flight batch (the sharing assertion).
        wf1["3"]["inputs"]["steps"] = 8
        wf1["3"]["inputs"]["seed"] = 76
        wf2 = json.loads(json.dumps(wf1))
        wf2["3"]["inputs"]["seed"] = 77

        dispatches_before = q.scheduler.total_dispatches()
        sock1, read1 = TestServer()._ws_connect(base)
        sock2, read2 = TestServer()._ws_connect(base)
        pid1 = _post(base, "/prompt", {"prompt": wf1})["prompt_id"]
        pid2 = _post(base, "/prompt", {"prompt": wf2})["prompt_id"]

        def collect(read_event, pids):
            events, done = [], set()
            for _ in range(600):
                evt = read_event()
                events.append(evt)
                if (evt["type"] == "executing"
                        and evt["data"].get("node") is None):
                    done.add(evt["data"]["prompt_id"])
                    if done >= pids:
                        return events
            raise AssertionError("not all prompts completed on this socket")

        events = collect(read1, {pid1, pid2})
        events2 = collect(read2, {pid1, pid2})
        sock1.close()
        sock2.close()

        for evs in (events, events2):
            for pid in (pid1, pid2):
                progress = [e["data"] for e in evs
                            if e["type"] == "progress"
                            and e["data"]["prompt_id"] == pid]
                # Per-prompt ordering survives concurrency: 1..4, each event
                # tagged to the prompt's own KSampler node.
                assert [p["value"] for p in progress] == list(range(1, 9))
                assert all(p["max"] == 8 and p["node"] == "3"
                           for p in progress)
                executed = [e["data"] for e in evs
                            if e["type"] == "executed"
                            and e["data"]["prompt_id"] == pid]
                assert [d["node"] for d in executed] == ["9"]
                starts = [e for e in evs if e["type"] == "execution_start"
                          and e["data"]["prompt_id"] == pid]
                assert len(starts) == 1
            # Both prompts started before either finished (they really ran
            # concurrently — 2 workers, one shared batch).
            idx_start = [i for i, e in enumerate(evs)
                         if e["type"] == "execution_start"]
            idx_done = [i for i, e in enumerate(evs)
                        if e["type"] == "executing"
                        and e["data"].get("node") is None]
            assert max(idx_start) < min(idx_done)
        for pid in (pid1, pid2):
            entry = _wait_history(base, pid)
            assert entry["status"]["status_str"] == "success", entry["status"]
        # The overlapping samplers shared step dispatches (continuous
        # batching actually engaged): 2 concurrent 8-step prompts cost
        # under the 16 dispatches serial execution would need.
        assert q.scheduler is not None
        delta = q.scheduler.total_dispatches() - dispatches_before
        assert 1 <= delta < 16, delta

    def test_queue_delete_cancels_running_prompt(self, server, tmp_path,
                                                 monkeypatch):
        """Stock POST /queue {"delete": [pid]}: per-prompt cancel of the
        RUNNING prompt — stops at the next step boundary via its own scope
        event (not the all-or-nothing /interrupt)."""
        base, _, out_dir = server
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        wf = _stock_graph(paths["ckpt"], out_dir)
        wf["3"]["inputs"]["steps"] = 500
        sock, read_event = TestServer()._ws_connect(base)
        pid = _post(base, "/prompt", {"prompt": wf})["prompt_id"]
        for _ in range(200):
            if read_event()["type"] == "progress":
                break
        else:
            raise AssertionError("sampler never reported progress")
        resp = _post(base, "/queue", {"delete": [pid]})
        assert resp["deleted"] == 1
        sock.close()
        entry = _wait_history(base, pid)
        assert entry["status"]["status_str"] == "interrupted"

    def test_queue_delete_drops_pending_only_target(self, server, tmp_path,
                                                    monkeypatch):
        """Deleting a queued prompt leaves its neighbors to run."""
        base, _, out_dir = server
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        wf = _stock_graph(paths["ckpt"], out_dir)
        wf["3"]["inputs"]["steps"] = 200  # keeps the single worker busy
        pid_busy = _post(base, "/prompt", {"prompt": wf})["prompt_id"]
        wf2 = json.loads(json.dumps(wf))
        wf2["3"]["inputs"].update(seed=9, steps=2)
        wf3 = json.loads(json.dumps(wf))
        wf3["3"]["inputs"].update(seed=10, steps=2)
        pid2 = _post(base, "/prompt", {"prompt": wf2})["prompt_id"]
        pid3 = _post(base, "/prompt", {"prompt": wf3})["prompt_id"]
        assert _post(base, "/queue", {"delete": [pid2]})["deleted"] == 1
        _post(base, "/queue", {"delete": [pid_busy]})  # unblock the worker
        assert _wait_history(base, pid2)["status"]["status_str"] == "interrupted"
        assert _wait_history(base, pid3)["status"]["status_str"] == "success"

    def test_bounded_queue_returns_429(self, tmp_path, monkeypatch):
        base_srv, q = make_server(port=0, output_dir=str(tmp_path / "out"),
                                  max_pending=1)
        thread = __import__("threading").Thread(
            target=base_srv.serve_forever, daemon=True)
        thread.start()
        base = f"http://127.0.0.1:{base_srv.server_address[1]}"
        try:
            paths = _synthetic_stock_env(tmp_path, monkeypatch)
            wf = _stock_graph(paths["ckpt"], str(tmp_path / "out"))
            wf["3"]["inputs"]["steps"] = 300
            pid_busy = _post(base, "/prompt", {"prompt": wf})["prompt_id"]
            _wait_running(base, pid_busy)
            # Worker busy; depth 1 queue takes exactly one more.
            wf2 = json.loads(json.dumps(wf))
            wf2["3"]["inputs"]["seed"] = 8
            _post(base, "/prompt", {"prompt": wf2})
            wf3 = json.loads(json.dumps(wf))
            wf3["3"]["inputs"]["seed"] = 9
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(base, "/prompt", {"prompt": wf3})
            assert err.value.code == 429
        finally:
            _post(base, "/interrupt")
            base_srv.shutdown()
            q.shutdown()

    def test_metrics_endpoint_prometheus_text(self, server):
        base, _, _ = server
        body = _get(base, "/metrics")
        text = body.decode() if isinstance(body, bytes) else body
        assert "pa_server_queue_pending" in text
        assert "# TYPE pa_server_queue_pending gauge" in text


def _wait_running(base, pid, timeout=60):
    t0 = time.time()
    while time.time() - t0 < timeout:
        state = _get(base, "/queue")
        if pid in state["queue_running"]:
            return
        time.sleep(0.05)
    raise TimeoutError(f"{pid} never started running")


class TestLatentPreviews:
    def test_opt_in_preview_frames_arrive_mid_sampling(self, server, tmp_path,
                                                       monkeypatch):
        """extra_data.preview=true → per-step binary WS frames in the stock
        layout (>II event-type 1 PREVIEW_IMAGE + format 2 PNG + PNG bytes),
        decodable and latent-grid-sized; without the flag, zero binary frames
        (previews are opt-in)."""
        import io
        import struct

        from PIL import Image

        base, _, out_dir = server
        paths = _synthetic_stock_env(tmp_path, monkeypatch)
        wf = _stock_graph(paths["ckpt"], out_dir)

        sock, read_frame = TestServer()._ws_connect(base, raw=True)
        pid = _post(
            base, "/prompt", {"prompt": wf, "extra_data": {"preview": True}}
        )["prompt_id"]
        previews, done = [], False
        for _ in range(300):
            opcode, payload = read_frame()
            if opcode == 0x2:
                previews.append(payload)
                continue
            evt = json.loads(payload)
            if (evt["type"] == "executing"
                    and evt["data"].get("node") is None
                    and evt["data"].get("prompt_id") == pid):
                done = True
                break
        sock.close()
        assert done and len(previews) == 2  # one per sampler step
        etype, fmt = struct.unpack(">II", previews[0][:8])
        assert (etype, fmt) == (1, 2)  # PREVIEW_IMAGE, PNG
        img = Image.open(io.BytesIO(previews[0][8:]))
        # 32px request / 8 (EmptyLatentImage grid) = 4px latent, upscaled by
        # an integer factor; mode RGB.
        assert img.mode == "RGB"
        assert img.size[0] == img.size[1] and img.size[0] % 4 == 0

        # Default run: no binary frames.
        sock, read_frame = TestServer()._ws_connect(base, raw=True)
        pid2 = _post(base, "/prompt", {"prompt": {
            **json.loads(json.dumps(wf)),
            "3": {**wf["3"], "inputs": {**wf["3"]["inputs"], "seed": 5}},
        }})["prompt_id"]
        binaries = 0
        for _ in range(300):
            opcode, payload = read_frame()
            if opcode == 0x2:
                binaries += 1
                continue
            evt = json.loads(payload)
            if (evt["type"] == "executing"
                    and evt["data"].get("node") is None
                    and evt["data"].get("prompt_id") == pid2):
                break
        sock.close()
        assert binaries == 0

    def test_latent_to_rgb_shapes(self):
        import numpy as np

        from comfyui_parallelanything_tpu.utils.latent_preview import (
            latent_to_rgb,
            preview_png,
        )

        for shape in [(2, 8, 6, 4), (1, 8, 6, 16), (1, 8, 6, 5),
                      (1, 3, 8, 6, 4)]:
            rgb = latent_to_rgb(np.random.default_rng(0).normal(size=shape))
            assert rgb.shape == (8, 6, 3)
            assert rgb.min() >= 0.0 and rgb.max() <= 1.0
        png = preview_png(np.zeros((1, 4, 4, 4), np.float32))
        assert png[:4] == b"\x89PNG"


class TestUploadImage:
    def _multipart(self, fields):
        boundary = "----patest123"
        parts = []
        for name, (filename, content, ctype) in fields.items():
            head = f'Content-Disposition: form-data; name="{name}"'
            if filename:
                head += f'; filename="{filename}"'
            parts.append(
                f"--{boundary}\r\n{head}\r\n"
                f"Content-Type: {ctype}\r\n\r\n".encode() + content + b"\r\n"
            )
        body = b"".join(parts) + f"--{boundary}--\r\n".encode()
        return body, f"multipart/form-data; boundary={boundary}"

    def _upload(self, base, body, ctype):
        req = urllib.request.Request(
            base + "/upload/image", data=body,
            headers={"Content-Type": ctype}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    def test_upload_roundtrip_and_dedupe(self, server, tmp_path, monkeypatch):
        import numpy as np
        from PIL import Image
        import io

        base, _, _ = server
        in_dir = tmp_path / "input"
        monkeypatch.setenv("PA_INPUT_DIR", str(in_dir))
        buf = io.BytesIO()
        Image.fromarray(np.zeros((4, 4, 3), np.uint8)).save(buf, "PNG")
        png = buf.getvalue()

        body, ctype = self._multipart(
            {"image": ("up.png", png, "image/png")})
        out = self._upload(base, body, ctype)
        assert out == {"name": "up.png", "subfolder": "", "type": "input"}
        assert (in_dir / "up.png").read_bytes() == png

        # Re-upload without overwrite: stock dedupe suffix.
        out2 = self._upload(base, body, ctype)
        assert out2["name"] == "up (1).png"
        # overwrite=true clobbers in place.
        body3, ctype3 = self._multipart({
            "image": ("up.png", png, "image/png"),
            "overwrite": ("", b"true", "text/plain"),
        })
        out3 = self._upload(base, body3, ctype3)
        assert out3["name"] == "up.png"
        # Path components are flattened away.
        body4, ctype4 = self._multipart(
            {"image": ("../../evil.png", png, "image/png")})
        out4 = self._upload(base, body4, ctype4)
        assert "/" not in out4["name"] and out4["name"].endswith("evil.png")
        assert (in_dir / out4["name"]).exists()

    def test_upload_rejects_non_multipart(self, server):
        base, _, _ = server
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, "/upload/image", {"not": "multipart"})
        assert ei.value.code == 400
