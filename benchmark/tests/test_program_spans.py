"""The per-layer metrics that read the program's own stage spans (PR 24:
``denoise`` in the sampler loop, ``image-fetch`` / ``png-encode`` in the save
node, ``admission-wait`` in the prompt queue), through the readers that were
there: the traced rehearsal reports them beside everything it reported
before."""

import json
import os

import run
from test_run import _last_line

CELL = "sd15-b8-512.closed"  # the cell the tiny twin stands for
NEW = {"sampler.denoiser_calls_per_request": "calls/request",
       "sampler.dispatch_ms": "ms", "save.fetch_ms": "ms",
       "save.png_encode_ms": "ms", "server.queue_wait_ms": "ms"}
# What the cell's metric list read on a traced rehearsal before this PR: the
# readers of the profiler's device planes find no TPU plane on the CPU.
BEFORE = {"graph.non_sampler_ms", "programs.compiles_in_window",
          "sampler.steps_per_request", "server.overhead_ms"}


def test_traced_rehearsal_reports_the_program_span_metrics(
        restorable, capsys, monkeypatch):
    # A twin is named by no metric's `workloads`: read the list of its cell.
    listed = run.layer_metrics_for
    monkeypatch.setattr(run, "layer_metrics_for",
                        lambda _twin, e2e: listed(CELL, e2e))
    run.main(["--workload", "sd15-tiny.closed", "--seed", "79", "--seconds", "5",
              "--trace", "1", "--rehearse"])
    line, _ = _last_line(capsys)
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    got = line["metrics"]
    assert BEFORE | set(NEW) <= set(got)
    assert {k: got[k]["unit"] for k in NEW} == NEW
    steps = run.load_json("graphs", "sd15-stock")["graph"]["3"]["inputs"]["steps"]
    assert got["sampler.denoiser_calls_per_request"]["value"] == steps
    assert got["sampler.steps_per_request"]["value"] == steps
    # host time: the dispatches and the save stages are part of what their
    # prompts took, the stages of what the other nodes took
    assert 0 < got["sampler.dispatch_ms"]["value"]
    assert 0 < got["save.fetch_ms"]["value"] + got["save.png_encode_ms"]["value"] \
        < got["graph.non_sampler_ms"]["value"]
    assert 0 <= got["server.queue_wait_ms"]["value"] < 1e3


def test_each_new_metric_is_a_file_and_an_entry_that_agree():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        m = run.load_json("layer_metrics", name)
        assert m["reader"] in ("span_count", "span_sum_ms")
        assert {k: m[k] for k in entries[name]} == entries[name]
