#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout, on a machine that holds the chips the cell
asks for. It finds the cell's files by name under ``benchmark/`` (see
``benchmark/README.md``), synthesises weights and tokenizer tables from
``--seed``, runs the plain reference for the requests it will check, starts
``comfyui_parallelanything_tpu.server`` in THIS process (one process owns the
chip), warms up the cell's own requests, offers the mix's load for ``--seconds``
from the client's side of HTTP, compares what came back with the reference and
prints one JSON object as its last line. ``--trace 0`` reports the end-to-end
metrics with the profiler off; ``--trace 1`` turns the program's span tracer on,
brackets part of the window with ``jax.profiler`` and reports the per-layer
metrics and the breakdown. Without a TPU it prints an error and exits 2.
"""

from __future__ import annotations

import time

P_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(HERE, ".work")  # listed in .gitignore
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def die(msg: str, code: int = 2):
    print(f"benchmark/run.py: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def load_json(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    if not os.path.exists(path):
        die(f"no {kind}/{name}.json under benchmark/")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    cell = load_json("workloads", name)
    cell["config_data"] = load_json("configs", cell["config"])
    cell["mix"] = load_json("traffic", cell["traffic"])
    cell["template"] = load_json("graphs", cell["graph"])
    return cell


def layer_metrics_for(cell_name: str, e2e_reported: set) -> list[dict]:
    """Every ``layer_metrics/*.json`` that applies to this cell."""
    out = []
    d = os.path.join(HERE, "layer_metrics")
    for fn in sorted(os.listdir(d)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(d, fn)) as f:
            m = json.load(f)
        cells = m.get("workloads")
        if cells is not None and cell_name not in cells:
            continue
        if cells is None and m["moves"] not in e2e_reported:
            continue
        out.append(m)
    return out


def apply_program_presets(config: dict, set_attr=setattr, dtype=None) -> None:
    """Tiny twins only: swap the program's preset factories for ones with the
    twin's sizes (the tests' recipe), so the whole command can be walked on
    the CPU. A configuration a cell names has no such key. Tests pass
    ``monkeypatch.setattr`` so the originals come back, and a compute
    ``dtype`` to hold the program to float32."""
    for target, over in (config.get("program_presets") or {}).items():
        mod_name, name = target.split(":")
        mod = importlib.import_module(mod_name)
        real = getattr(mod, name)
        over = {k: tuple(v) if isinstance(v, list) else v for k, v in over.items()}
        if dtype is not None:
            over["dtype"] = dtype
        set_attr(mod, name,
                 (lambda real, over: lambda **kw: real(**{**over, **kw}))(real, over))


def write_tokenizers(config: dict, work: str, seed: int) -> tuple[dict, dict]:
    """The tokenizers a configuration names beside CLIP's (``tokenizers``:
    entries ``{name, writer, vocab_size, max_length, env}``): each table is
    drawn from the seed by ``yardstick.<writer>.write`` → the harness's own
    objects by name, and the program's variables (``env``: variable → which of
    the written files)."""
    named, env = {}, {}
    for entry in config.get("tokenizers") or []:
        mod = importlib.import_module(f"yardstick.{entry['writer']}")
        written = mod.write(os.path.join(work, "tokenizer", entry["name"]), seed, entry)
        env.update({var: written[key] for var, key in entry["env"].items()})
        named[entry["name"]] = mod.load(written, entry)
    return named, env


def synthesize(config: dict, work: str, seed: int):
    """All that is drawn from the seed, under ``work``: the weight files,
    CLIP's tables and the tokenizers the configuration names → (what a
    reference is built from: ``Reference(config, *args, precision, **kw)``; the
    program's variables; what was written)."""
    from yardstick import synth
    from yardstick.tokenizer import BPE

    files, info = synth.write_checkpoints(work, seed, config)
    vocab, merges = synth.write_tokenizer(
        os.path.join(work, "tokenizer"), seed, config["text"]["vocab_size"])
    named, named_env = write_tokenizers(config, work, seed)
    env = dict(PA_MODELS_DIR=os.path.join(work, "models"),
               PA_OUTPUT_DIR=os.path.join(work, "output"),
               PA_CLIP_VOCAB=vocab, PA_CLIP_MERGES=merges, **named_env)
    # A multi-file or multi-tokenizer family gets those by keyword; a
    # configuration that lists neither is called as it always was.
    kw = {}
    if named:
        kw["tokenizers"] = named
    if "files" in config["checkpoint"]:
        kw["files"] = files
    info = {**info, "tokenizers": ["clip", *named], "env": sorted(named_env)}
    return (next(iter(files.values())), BPE(vocab, merges)), kw, env, info


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks)) if peaks else 0


class Window:
    """Offers a schedule to the server and collects every result."""

    def __init__(self, base, cell, schedule, seconds):
        from yardstick import traffic

        self.base, self.schedule = base, schedule
        self.seconds = seconds
        self.mix, self.template = cell["mix"], cell["template"]
        self.traffic = traffic
        self.results: list = []
        self._lock = threading.Lock()
        self._next = 0
        self.start = None
        self.late_s: list[float] = []

    def one(self, req, due, timeout_s=None):
        from yardstick import client

        r = client.run_request(
            self.base,
            self.traffic.fill_graph(self.template, self.mix, req),
            self.template["output_node"],
            req.index, due, float(self.mix.get("poll_s", 0.02)),
            timeout_s or float(self.mix.get("request_timeout_s", 120)))
        with self._lock:
            self.results.append(r)
        return r

    def _closed_client(self):
        end = self.start + self.seconds
        while time.perf_counter() < end:
            with self._lock:
                i = self._next
                self._next += 1
            # A closed-loop request is due when its client is free to send it.
            self.one(self.schedule.request(i), time.perf_counter())

    def _open_sender(self, threads):
        for i, off in enumerate(self.schedule.due):
            due = self.start + off
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.late_s.append(max(0.0, time.perf_counter() - due))
            t = threading.Thread(target=self.one,
                                 args=(self.schedule.request(i), due), daemon=True)
            t.start()
            threads.append(t)

    def run(self):
        self.start = time.perf_counter()
        threads: list[threading.Thread] = []
        if self.schedule.loop == "closed":
            threads = [threading.Thread(target=self._closed_client, daemon=True)
                       for _ in range(int(self.mix.get("clients", 1)))]
            for t in threads:
                t.start()
        else:
            sender = threading.Thread(target=self._open_sender, args=(threads,),
                                      daemon=True)
            sender.start()
            sender.join()
        for t in threads:
            t.join()
        last = max((r.done for r in self.results), default=self.start)
        # The window is --seconds, stretched to the completion of the work
        # that was in flight when they ran out: all the work, all the time.
        self.length = max(self.seconds, last - self.start)
        return self


def pick_checked(mix: dict, schedule, seed: int) -> tuple[list[int], list[int]]:
    """The requests (among the window's first few, which every window
    finishes) and the batch rows a run of this seed compares, drawn from it."""
    import numpy as np

    check = mix["check"]
    rng = np.random.default_rng([seed, 20])
    first = int(check.get("among_first", 3))
    horizon = schedule.count() or first
    reqs = sorted(int(i) for i in rng.choice(
        min(horizon, first), size=min(int(check["requests"]), horizon),
        replace=False))
    batch = int(mix["latent"]["batch_size"])
    rows = sorted(int(i) for i in rng.choice(
        batch, size=min(int(check["rows"]), batch), replace=False))
    return reqs, rows


def end_to_end(window, images_per_request: int) -> dict:
    from yardstick import stats

    ok = [r for r in window.results if r.ok]
    out = {"images_per_s": {"value": len(ok) * images_per_request / window.length,
                            "unit": "images/s"}}
    lat = [r.latency for r in ok]
    if lat:
        out["time_to_image_p50_s"] = {"value": stats.median(lat), "unit": "s"}
        if window.schedule.loop == "open":
            out["time_to_image_p90_s"] = {
                "value": stats.percentile_failures_worst(
                    lat, [r.latency for r in window.results if not r.ok], 90),
                "unit": "s"}
    return out


def rel_l2(got, want) -> float:
    """The gap's norm over the norm of ``want`` about its mean."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want - want.mean()))


def compare_images(served: list, ref32, ref16, limits: dict) -> tuple[bool, list]:
    """Each number compared, beside its limit. ``served`` are uint8 images,
    ``ref32`` / ``ref16`` the float32 reference and the reference at the
    stated precision, float images in [0, 1], row for row. The number that
    decides is the served image's gap to float32 in units of the gap the
    stated precision itself opens on this request: the raw gap swings with
    the seed's weights by a factor of two, the unit swings with it."""
    import numpy as np

    rows = []
    for k, (s, r32, r16) in enumerate(zip(served, ref32, ref16)):
        s = np.asarray(s, np.float32) / 255.0
        unit = rel_l2(r16, r32)
        rows.append({"number": f"image_gap_in_stated_precision_units[{k}]",
                     "value": rel_l2(s, r32) / unit,
                     "limit": limits["image_gap_in_stated_precision_units"],
                     "image_rel_l2": rel_l2(s, r32), "unit_rel_l2": unit,
                     "image_max_abs": float(np.abs(s - r32).max())})
    ok = all(r["value"] <= r["limit"] for r in rows)
    return ok, rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="walk a tiny twin's cell on the CPU; refused for any "
                         "configuration that is not marked as a rehearsal twin")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    config, mix, template = cell["config_data"], cell["mix"], cell["template"]
    if args.rehearse and not config.get("rehearsal"):
        die("--rehearse is for tiny twins only; this configuration is real")
    try:
        importlib.import_module("comfyui_parallelanything_tpu")
    except ImportError as e:
        die(f"the program is not in this checkout ({e}); nothing was run")

    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not args.rehearse:
        die(f"JAX found no TPU (devices: {devs}); nothing was run")
    if len(devs) < cell["chips"]:
        die(f"the cell asks for {cell['chips']} chips, JAX found {len(devs)}")
    devs = devs[: cell["chips"]]
    say("device", platform=platform, kind=devs[0].device_kind, count=len(devs),
        jax=jax.__version__)

    from yardstick import client, readers, stats, traffic

    reference = importlib.import_module(f"yardstick.{config['reference']}")
    if args.rehearse:
        apply_program_presets(config)

    # -- set-up: cache, weights, tokenizer --------------------------------
    # The compile cache: at a fixed path inside the checkout, whatever the
    # machine's environment names, and without a size cap — a run writes some
    # hundreds of MiB of executables, and a capped cache (this PR's chip
    # machine came with one of 192 MiB) evicts them before the next run reads
    # them, so every run would compile everything. The program takes the
    # directory from the environment variable the benchmark sets here.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("PA_COMPILE_CACHE_MIN_S", "0")
    from comfyui_parallelanything_tpu.utils import enable_compilation_cache
    from comfyui_parallelanything_tpu.utils.telemetry import compile_snapshot

    cache_dir = enable_compilation_cache()
    jax.config.update("jax_compilation_cache_max_size", -1)
    work = os.path.join(WORK, config["name"])
    shutil.rmtree(os.path.join(work, "output"), ignore_errors=True)
    t = time.perf_counter()
    ref_args, ref_kw, env, info = synthesize(config, work, args.seed)
    for var in ("PA_TOKENIZER_JSON", "PA_T5_TOKENIZER_JSON"):
        os.environ.pop(var, None)  # only what the configuration names
    os.environ.update(env)
    synth_s = time.perf_counter() - t
    say("synthesize", seed=args.seed, seconds=synth_s, **info)

    # -- the plain reference, before the program's state exists -----------
    schedule = traffic.Schedule(mix, args.seed, args.seconds)
    picked, rows = pick_checked(mix, schedule, args.seed)
    batch = int(mix["latent"]["batch_size"])
    t = time.perf_counter()
    ref_images = {i: {} for i in picked}
    for precision in ("float32", config["precision"]):
        ref = reference.Reference(config, *ref_args, precision, **ref_kw)
        for i in picked:
            g = traffic.fill_graph(template, mix, schedule.request(i))
            ref_images[i][precision] = ref.images(reference.describe(g), rows)
        del ref
    gc.collect()  # the reference's device arrays go before the program's come
    reference_s = time.perf_counter() - t
    say("reference", seconds=reference_s, requests=picked, rows=rows,
        peak_bytes_after=peak_bytes(devs))

    # -- the system under test ----------------------------------------------
    from comfyui_parallelanything_tpu.server import make_server

    t = time.perf_counter()
    srv, q = make_server(port=0, trace=bool(args.trace),
                         **{k: v for k, v in mix.get("server", {}).items()
                            if not (k == "workers" and v == 1)})
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    ok_all, compared, failed_why = True, [], []
    try:
        # Warm-up: the cell's own requests (indices the window never uses)
        # until one runs with 0 compiles; the first is then repeated and has
        # to come back byte for byte.
        warm = Window(base, cell, schedule, args.seconds)
        warm_runs, first = [], None
        for k in range(int(mix.get("warmup_max", 6))):
            before = compile_snapshot()
            r = warm.one(schedule.request(10 ** 6 + k), time.perf_counter(),
                         timeout_s=float(mix.get("warmup_timeout_s", 1500)))
            after = compile_snapshot()
            delta = {key: after[key] - before[key] for key in
                     ("compiles", "compile_time_s", "cache_hits", "cache_misses")}
            warm_runs.append({"latency_s": r.latency, "ok": r.ok, **delta})
            if not r.ok:
                die(f"warm-up request failed: {r.error}", 1)
            first = first or r
            if k >= 1 and delta["compiles"] == 0:
                break
        again = warm.one(schedule.request(10 ** 6), time.perf_counter())
        same = again.ok and again.images == first.images
        compared.append({"number": "repeat_probe_bytes_differ",
                         "value": 0 if same else 1, "limit": 0})
        ok_all &= same
        warm_s = time.perf_counter() - t
        say("warmup", seconds=warm_s, runs=warm_runs,
            repeat_latency_s=again.latency)

        metrics0 = client.metric_totals(client.http(base, "/metrics").decode())
        comp0 = compile_snapshot()
        setup_s = (time.perf_counter() - P_PROCESS) - reference_s
        say("setup", setup_s=setup_s, synth_s=synth_s, load_and_warm_s=warm_s,
            compile_cache_dir=cache_dir,
            **{k: comp0[k] for k in ("compiles", "compile_time_s",
                                     "cache_hits", "cache_misses")})

        # -- the measured window -------------------------------------------------
        window = Window(base, cell, schedule, args.seconds)
        tracer = None
        if args.trace:
            tracer = readers.ProfilerBracket(
                os.path.join(work, "trace"), mix.get("trace", {}), window)
            tracer.start()
        window.run()
        if tracer is not None:
            tracer.join()
        metrics1 = client.metric_totals(client.http(base, "/metrics").decode())
        comp1 = compile_snapshot()
        spans = client.http(base, "/trace") if args.trace else None
    finally:
        srv.shutdown()
        srv.server_close()
        q.shutdown()
        thread.join(timeout=30)

    # -- what came back -----------------------------------------------------------
    results = sorted(window.results, key=lambda r: r.index)
    say("window", window_s=window.length,
        latency_s=[round(r.latency, 4) for r in results],
        exec_s=[r.exec_s for r in results])
    attempted = len(results)
    failed = sum(1 for r in results if not r.ok)
    for r in results:
        if not r.ok:
            failed_why.append({"index": r.index, "error": r.error})
    h, w = int(mix["latent"]["height"]), int(mix["latent"]["width"])
    bad_shape = constant = duplicates = 0
    by_index = {r.index: r for r in results}
    for r in results:
        if not r.ok:
            continue
        if len(r.images) != batch:
            bad_shape += 1
            continue
        duplicates += len(r.images) - len(set(r.images))
        if r.index in ref_images or r.index == results[0].index:
            for png in r.images:
                img = client.decode_png(png)
                bad_shape += img.shape != (h, w, 3)
                constant += int(img.min() == img.max())
    counters = {fam: metrics1.get(fam, 0.0) - metrics0.get(fam, 0.0) for fam in
                ("pa_degradation_total", "pa_serving_inline_fallback_total")}
    compiles_in_window = comp1["compiles"] - comp0["compiles"]
    compared += [
        {"number": "failed_requests", "value": failed, "limit": 0},
        {"number": "wrong_image_count_or_size", "value": int(bad_shape), "limit": 0},
        {"number": "constant_images", "value": int(constant), "limit": 0},
        {"number": "duplicate_images_in_a_batch", "value": int(duplicates), "limit": 0},
        {"number": "compiles_in_window", "value": compiles_in_window, "limit": 0},
        *({"number": fam, "value": v, "limit": 0} for fam, v in counters.items()),
    ]
    if args.trace:
        # The span tracer is on: every prompt ran the steps its graph asks for
        # (a skipped denoiser step hardly moves the image; this sees it).
        asked = int(reference.describe(
            traffic.fill_graph(template, mix, schedule.request(0)))["steps"])
        seen = readers.spans_per_prompt(results, spans, "step")
        compared.append({"number": "prompts_whose_sampler_steps_differ_from_the_graph",
                         "value": sum(1 for n in seen.values() if n != asked),
                         "limit": 0, "asked": asked,
                         "seen": sorted(set(seen.values()))})
    for i, want in ref_images.items():
        r = by_index.get(i)
        if r is None or not r.ok or len(r.images) != batch:
            compared.append({"number": f"request[{i}]_not_served", "value": 1, "limit": 0})
            continue
        served = [client.decode_png(r.images[k]) for k in rows]
        ok, nums = compare_images(served, want["float32"],
                                  want[config["precision"]], config["limits"])
        for n in nums:
            n["number"] = f"request[{i}].{n['number']}"
        compared += nums
        ok_all &= ok
    ok_all &= all(c["value"] <= c["limit"] for c in compared)
    say("correct", correct=bool(ok_all), compared=compared, failures=failed_why[:5])

    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes(devs)}
    line = {"correct": bool(ok_all), "attempted": attempted, "failed": failed,
            "device": device, "workload": args.workload, "seed": args.seed,
            "window_s": window.length, "requests_completed": attempted - failed,
            "reference_s": reference_s, "poll_s": float(mix.get("poll_s", 0.02))}
    if not args.trace:
        metrics = end_to_end(window, batch)
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        if window.late_s:
            line["generator_late_p50_ms"] = 1e3 * stats.median(window.late_s)
            line["generator_late_max_ms"] = 1e3 * max(window.late_s)
    else:
        ctx = readers.Context(
            cell=cell, window=window, results=results, spans=spans,
            metrics0=metrics0, metrics1=metrics1, comp0=comp0, comp1=comp1,
            trace=tracer.reduce(), bracket=tracer, chips=len(devs),
            device_kind=devs[0].device_kind, here=HERE, batch=batch)
        metrics = {}
        for m in layer_metrics_for(args.workload, {"images_per_s",
                                                   "time_to_image_p50_s", "setup_s"}
                                   | ({"time_to_image_p90_s"}
                                      if schedule.loop == "open" else set())):
            value = readers.read(m, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(readers.device_busy(ctx))
        line["breakdown"] = readers.breakdown(ctx)
    line["metrics"] = metrics
    # Each number compared beside its limit: last in the line, and as the last
    # lines of standard error.
    line["compared"] = [{k: c[k] for k in ("number", "value", "limit")}
                        for c in compared]
    print(json.dumps(line), flush=True)
    for c in line["compared"]:
        print(f"compared {c['number']} value={c['value']} limit={c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    if failed and failed == attempted:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
