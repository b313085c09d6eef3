"""The client's side of HTTP: post a graph, wait for its history entry, fetch
every image back through ``/view``. All end-to-end times are taken here."""

from __future__ import annotations

import dataclasses
import io
import json
import time
import urllib.error
import urllib.request

import numpy as np


def http(base: str, path: str, payload=None, timeout: float = 60.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        base + path, data=data, headers={"Content-Type": "application/json"},
        method="GET" if payload is None else "POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        body, ctype = r.read(), r.headers.get("Content-Type", "")
    return json.loads(body) if "json" in ctype else body


@dataclasses.dataclass
class Result:
    index: int
    due: float            # perf_counter seconds when the request was due
    sent: float
    done: float           # last image back through /view (or the failure)
    ok: bool
    error: str = ""
    prompt_id: str = ""
    exec_s: float | None = None
    images: list = dataclasses.field(default_factory=list)  # PNG bytes

    @property
    def latency(self) -> float:
        return self.done - self.due


def run_request(base: str, graph: dict, output_node: str, index: int,
                due: float, poll_s: float, timeout_s: float) -> Result:
    """One request, timed from when it was due until its last image is back.
    A refusal (429), a timeout and a non-success all come back ``ok=False``."""
    sent = time.perf_counter()
    res = Result(index=index, due=due, sent=sent, done=sent, ok=False)
    try:
        pid = http(base, "/prompt", {"prompt": graph})["prompt_id"]
        res.prompt_id = pid
        while True:
            hist = http(base, f"/history/{pid}")
            if pid in hist:
                break
            if time.perf_counter() - sent > timeout_s:
                raise TimeoutError(f"no history entry after {timeout_s} s")
            time.sleep(poll_s)
        entry = hist[pid]
        status = entry["status"]
        res.exec_s = status.get("exec_s")
        if status.get("status_str") != "success":
            raise RuntimeError(f"status {status}")
        for ref in entry["outputs"][output_node]["images"]:
            res.images.append(http(
                base, f"/view?filename={ref['filename']}"
                      f"&subfolder={ref['subfolder']}"))
        res.ok = True
    except (urllib.error.URLError, OSError, RuntimeError, KeyError,
            TimeoutError, ValueError) as e:
        res.error = f"{type(e).__name__}: {e}"
    res.done = time.perf_counter()
    return res


def decode_png(png: bytes) -> np.ndarray:
    from PIL import Image

    return np.asarray(Image.open(io.BytesIO(png)).convert("RGB"))


def metric_totals(text: str) -> dict[str, float]:
    """Family → sum of its samples, from a Prometheus exposition text."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        name, _, value = line.rpartition(" ")
        family = name.split("{", 1)[0]
        try:
            out[family] = out.get(family, 0.0) + float(value)
        except ValueError:
            continue
    return out
