"""The per-layer metrics that read the server's own spans of the space between
two prompts (PR 36: ``worker-idle`` and ``prompt-finish`` on the worker's
thread, ``http-prompt`` and ``http-view`` on the handlers'), through the reader
that was there: data files only, in every cell."""

import json
import os

import pytest
import run
from test_run import _last_line
from yardstick import readers

NEW = {"server.between_prompts_ms": ("worker-idle", "images_per_s"),
       "server.finish_ms": ("prompt-finish", "time_to_image_p50_s"),
       "server.submit_ms": ("http-prompt", "time_to_image_p50_s"),
       "server.view_ms": ("http-view", "time_to_image_p50_s")}


def _benchmark() -> dict:
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_is_a_file_and_an_entry_that_agree(name):
    entry = {m["name"]: m for m in _benchmark()["per_layer"]}[name]
    m = run.load_json("layer_metrics", name)
    span, moves = NEW[name]
    assert m["reader"] == "span_sum_ms" in readers.READERS
    assert m["args"] == {"name": span}
    assert {k: m[k] for k in entry} == entry
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": "program_span", "moves": moves,
                     "layer": "HTTP and prompt queue, server.py"}


def test_the_four_apply_to_every_cell():
    doc = _benchmark()
    reported = {m["name"] for m in doc["end_to_end"]}
    assert len(doc["workloads"]) == 5
    for cell in doc["workloads"]:
        applies = {m["name"] for m in run.layer_metrics_for(cell["name"], reported)}
        assert set(NEW) <= applies, cell["name"]


def test_traced_rehearsal_prints_all_four(restorable, capsys):
    run.main(["--workload", "sd15-tiny.closed", "--seed", "83", "--seconds", "5",
              "--trace", "1", "--rehearse"])
    line, _ = _last_line(capsys)
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    got = line["metrics"]
    assert set(NEW) <= set(got)
    assert {got[k]["unit"] for k in NEW} == {"ms"}
    # the server's own finish work and the handlers are parts of what the
    # client sees around a prompt; the worker's wait spans the client's turn,
    # which holds the POST and every /view
    for k in NEW:
        assert 0 < got[k]["value"] < 1e3
    assert got["server.submit_ms"]["value"] + got["server.view_ms"]["value"] \
        < got["server.between_prompts_ms"]["value"]
