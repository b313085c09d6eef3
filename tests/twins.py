"""The benchmark's tiny twins for the tests that hold a family to its plain
reference (``test_*_reference*.py``). A twin's weight files and tokenizer
tables are drawn from a seed by the benchmark's own ``run.synthesize``, which
takes seconds to a minute and draws no compute type: ``twin_files`` writes
them ONCE a file of tests, and what differs from test to test — the program's
presets swapped for the twin's sizes in a compute type, the program's
variables — rides on the test's own ``monkeypatch``. The files are read-only:
a test that needs a changed file writes a copy under its ``tmp_path``."""

import os
import sys
import threading
import time

import numpy as np
import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "benchmark")
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import run  # noqa: E402 — the benchmark's own file loading and preset swap
from yardstick import client, synth, traffic  # noqa: E402


@pytest.fixture(scope="module")
def twin_files(tmp_path_factory):
    """``twin_files(cell_name)`` → (cell, what a reference is built from, its
    keywords, the program's variables), synthesized at the first call of a
    file of tests and kept until its last test."""
    made = {}

    def files(cell_name, seed=11):
        if (cell_name, seed) not in made:
            cell = run.load_cell(cell_name)
            work = tmp_path_factory.mktemp(cell_name.split(".")[0])
            ref_args, ref_kw, env, _ = run.synthesize(cell["config_data"], str(work), seed)
            made[cell_name, seed] = (cell, ref_args, ref_kw, env)
        return made[cell_name, seed]

    return files


def _twin(twin_files, monkeypatch, cell_name, dtype):
    """A twin's files (``twin_files``), the program's presets swapped for the
    twin's sizes in compute type ``dtype`` (None: what the preset says) and
    the program's variables set, all for the length of one test → (cell, what
    a reference is built from, its keywords)."""
    cell, ref_args, ref_kw, env = twin_files(cell_name)
    run.apply_program_presets(cell["config_data"], monkeypatch.setattr, dtype)
    for k, v in {**env, "PA_TOKENIZER_JSON": ""}.items():
        monkeypatch.setenv(k, v)
    return cell, ref_args, ref_kw


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want - want.mean()))


def _counted(name, **labels):
    from comfyui_parallelanything_tpu.utils.metrics import registry

    return registry.get(name, labels) or 0.0


def _twin_file(twin_files, tmp_path, monkeypatch, cell_name, sizes, dtype, home):
    """The one weight file of a twin whose part has the sizes key ``sizes``,
    with that twin's presets swapped in under ``dtype`` for the length of the
    test → the file's path. The twin a file of
    tests is about (``home``) serves it from ``twin_files``; another family's
    file is written alone under ``tmp_path`` — the same bytes, and seconds
    where that family's whole twin takes up to a minute."""
    config = run.load_cell(cell_name)["config_data"]
    specs = synth.checkpoint_files(config)
    index = next(i for i, s in enumerate(specs)
                 if any(p["sizes"] == sizes for p in s["parts"]))
    run.apply_program_presets(config, monkeypatch.setattr, dtype)
    if cell_name == home:
        _, ref_args, ref_kw, _ = twin_files(cell_name)
        return ref_kw.get("files", {}).get(specs[index]["file"], ref_args[0])
    path = str(tmp_path / specs[index]["file"])
    synth.write_checkpoint(path, 11, config, index)
    return path


def _float32_image(twin_files, cell_name, reference):
    """Request 0 of the cell's schedule and its image by the family's float32
    ``reference`` module → (the request, the image): what a file computes
    once (a module-scoped fixture) for the served image to be held to and the
    lower precisions' gaps to be measured from."""
    cell, ref_args, ref_kw, _ = twin_files(cell_name)
    sched = traffic.Schedule(cell["mix"], 5, 10)
    req = reference.describe(
        traffic.fill_graph(cell["template"], cell["mix"], sched.request(0)))
    return req, reference.Reference(cell["config_data"], *ref_args, "float32",
                                    **ref_kw).images(req, [0])


def _serve(cell, graphs):
    """``graphs`` posted one after another to a ``server.py`` of their own
    (tracer on; ``SaveImage``'s files go where the twin's variables send
    them) → (each request's result, the server's spans). The tracer is left
    as it was found: a worker runs the next file of tests in this process."""
    from comfyui_parallelanything_tpu.server import make_server
    from comfyui_parallelanything_tpu.utils import tracing

    was_on = tracing.on()
    srv, q = make_server(port=0, output_dir=os.environ["PA_OUTPUT_DIR"], trace=True)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        res = [client.run_request(base, g, cell["template"]["output_node"], i,
                                  time.perf_counter(), 0.02, 600)
               for i, g in enumerate(graphs)]
        spans = client.http(base, "/trace")
    finally:
        srv.shutdown()
        srv.server_close()
        q.shutdown()
        thread.join(timeout=30)
        if not was_on:
            tracing.disable()
    return res, spans


def _residency_events():
    from comfyui_parallelanything_tpu.utils.metrics import registry

    m = registry._metrics.get("pa_model_residency_total") or {"values": {}}
    return {tuple(sorted(dict(k).items())): v for k, v in m["values"].items()}


def _moved(before):
    """``{"<model>:<event>": count}`` of the residency rule's moves since
    ``before`` (a ``_residency_events()``)."""
    now = _residency_events()
    return {dict(k)["model"] + ":" + dict(k)["event"]: v - before.get(k, 0.0)
            for k, v in now.items() if v != before.get(k, 0.0)}
