#!/usr/bin/env python3
"""Records the small profiler traces kept under ``benchmark/tests/data`` (run on
the chip). ``small`` (PR 23): two tiny jitted programs, a pause, the harness's
marker around them. ``cut`` (PR 30): a program of some milliseconds queued
several times and the profiler stopped while the device is still running them,
as the benchmark's bracket stops it mid-step — the last run is a stump. Writes
``<out>/<name>.xplane.pb``, ``<out>/<name>.expect.json`` (``dump_structure``)
and for ``cut`` the module runs one by one, to write ``cut.by_hand.json``
from."""

import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def _start_trace(log: str) -> None:
    """The profiler as the benchmark's bracket starts it."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log, profiler_options=opts)


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from yardstick import readers, xplane

    @jax.jit
    def small_matmul(a):
        return a @ a

    @jax.jit
    def small_add(a):
        return a + 1.0

    a = jnp.ones((1024, 1024), jnp.bfloat16)
    small_matmul(a).block_until_ready()
    small_add(a).block_until_ready()
    log = os.path.join(out, "small_trace")
    shutil.rmtree(log, ignore_errors=True)
    _start_trace(log)
    with jax.profiler.TraceAnnotation(xplane.MARK):
        for _ in range(3):
            small_matmul(a).block_until_ready()
        time.sleep(0.05)
        small_add(a).block_until_ready()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    path = xplane.find_xplane(log)
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    readers.dump_structure(path, os.path.join(out, "small.expect.json"))
    shutil.rmtree(log, ignore_errors=True)
    print("recorded", os.path.getsize(os.path.join(out, "small.xplane.pb")), "bytes")


def cut(out: str) -> None:
    import json

    import jax
    import jax.numpy as jnp

    from yardstick import readers, xplane

    @jax.jit
    def small_chain(a):
        return jax.lax.fori_loop(0, 24, lambda _, x: (x @ a) * 0.5 + x * 0.5, a)

    a = jnp.full((4096, 4096), 1.0 / 4096, jnp.bfloat16)
    small_chain(a).block_until_ready()
    log = os.path.join(out, "cut_trace")
    shutil.rmtree(log, ignore_errors=True)
    _start_trace(log)
    with jax.profiler.TraceAnnotation(xplane.MARK):
        t = time.perf_counter()
        small_chain(a).block_until_ready()
        one = time.perf_counter() - t
        queued = [small_chain(a) for _ in range(6)]
        time.sleep(3.5 * one)  # the device is inside the fourth queued run
    jax.profiler.stop_trace()
    for q in queued:
        q.block_until_ready()
    path = xplane.find_xplane(log)
    shutil.copy(path, os.path.join(out, "cut.xplane.pb"))
    readers.dump_structure(path, os.path.join(out, "cut.expect.json"))
    tr = xplane.load(path)
    chip = tr.chips[min(tr.chips)]
    runs = {"window_ns": tr.window, "last_device_event_end_ns": xplane.last_ns(chip),
            "modules": [[n, s, e, e - s] for n, s, e in chip.modules],
            "one_run_on_the_host_clock_s": one}
    with open(os.path.join(out, "cut.runs.json"), "w") as f:
        json.dump(runs, f, indent=1)
    shutil.rmtree(log, ignore_errors=True)
    print("recorded", os.path.getsize(os.path.join(out, "cut.xplane.pb")), "bytes")
    print(json.dumps(runs))


if __name__ == "__main__":
    dest = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out"
    os.makedirs(dest, exist_ok=True)
    {"small": main, "cut": cut}[sys.argv[2] if len(sys.argv) > 2 else "small"](dest)
