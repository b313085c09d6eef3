#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the plain reference put in the
program's place and computed below the precision the configuration states.

    python3 benchmark/tools/control.py --workload <cell> --seeds 1,2,3 [--precisions int8,bfloat16]

For each seed it synthesises the cell's weights, picks the request and rows a
run of that seed would check, computes their images in float32 and in each
lower precision, and prints the numbers a run compares (the image's gap to float32 in units of the stated
precision's own gap, with the raw relative L2 and largest absolute gaps beside
it) for the lower precision against the float32 reference. ``int8`` is the control: it has to come out above the
limits. ``bfloat16`` is the precision the configurations state: it shows
where a sound run lies. Runs on the chip at the cell's own size; the
benchmark's own runs never run it."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402 — the harness's own file loading, comparison, work directory


def gaps(got, ref32, ref16) -> dict:
    """What run.py compares, for images put in the program's place."""
    import numpy as np

    served = [np.round(np.clip(g, 0, 1) * 255.0).astype(np.uint8) for g in got]
    _, rows = run.compare_images(served, ref32, ref16, {
        "image_gap_in_stated_precision_units": float("inf")})
    return {"image_gap_in_stated_precision_units": [r["value"] for r in rows],
            "image_rel_l2": [r["image_rel_l2"] for r in rows],
            "unit_rel_l2": [r["unit_rel_l2"] for r in rows],
            "image_max_abs": [r["image_max_abs"] for r in rows]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=45)
    ap.add_argument("--precisions", default="int8")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    cell = run.load_cell(args.workload)
    config, mix, template = cell["config_data"], cell["mix"], cell["template"]
    if args.rehearse and not config.get("rehearsal"):
        run.die("--rehearse is for tiny twins only")
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        run.die(f"JAX found no TPU (devices: {jax.devices()}); nothing was run")
    # the same cache as run.py's: fixed path in the checkout, no size cap
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(run.ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)

    import importlib

    from yardstick import traffic

    reference = importlib.import_module(f"yardstick.{config['reference']}")
    work = os.path.join(run.WORK, config["name"] + "-control")
    for seed in (int(s) for s in args.seeds.split(",")):
        ref_args, ref_kw, _, _ = run.synthesize(config, work, seed)
        schedule = traffic.Schedule(mix, seed, args.seconds)
        reqs, rows = run.pick_checked(mix, schedule, seed)
        for i in reqs:
            req = reference.describe(traffic.fill_graph(
                template, mix, schedule.request(i)))
            images, secs = {}, {}
            stated = config["precision"]
            wanted = [p for p in args.precisions.split(",") if p != stated]
            for p in ["float32", stated, *wanted]:
                t = time.perf_counter()
                images[p] = reference.Reference(
                    config, *ref_args, p, **ref_kw).images(req, rows)
                secs[p] = round(time.perf_counter() - t, 2)
            for p in wanted:
                print(json.dumps({
                    "workload": args.workload, "seed": seed, "request": i,
                    "rows": rows, "precision": p, "against": "float32",
                    "unit": stated,
                    **gaps(images[p], images["float32"], images[stated]),
                    "seconds": secs,
                }), flush=True)


if __name__ == "__main__":
    main()
