#!/usr/bin/env python3
"""Records the small profiler trace kept under ``benchmark/tests/data`` (run on
the chip, PR 23): two tiny jitted programs, a pause, the harness's marker
around them. Writes ``<out>/small.xplane.pb`` and ``<out>/small.expect.json``
(what a by-hand reading of the same file gives, via ``dump_structure``)."""

import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


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
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log, profiler_options=opts)
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


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out")
