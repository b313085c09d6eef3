"""The trace reduction: on hand-made intervals, and on a small trace recorded
on the chip (``data/small.xplane.pb``, by ``tools/record_small_trace.py``)
whose busy time, module times and gaps are known by hand."""

import json
import os

from yardstick import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_busy_and_gaps_on_hand_made_intervals():
    # a while (0-100) with its body ops nested inside, then a pause, then an op
    chip = xplane.Chip(
        ops=[("while.1", 0, 100), ("fusion.1", 10, 40), ("fusion.2", 50, 90),
             ("copy.3", 150, 170)],
        modules=[("jit_step(1)", 0, 100), ("jit_decode(2)", 150, 170)])
    win = (0, 200)
    assert xplane.busy_ns(chip, win) == 120  # a union, not the sum 190
    assert xplane.gaps(chip, win) == [(100, 150), (170, 200)]
    assert xplane.busy_ns(chip, (20, 160)) == 90  # clipped to the window
    self_t = xplane.self_time_by_name(chip, win)
    assert self_t == {"while.1": 30, "fusion.1": 30, "fusion.2": 40, "copy.3": 20}
    assert sum(self_t.values()) == xplane.busy_ns(chip, win)
    assert xplane.time_by_name(chip.modules, win, "^jit_step") == {"jit_step(1)": 100}
    assert xplane.count_by_pattern(chip.modules, win, "^jit_") == 2
    assert xplane.count_by_pattern(chip.modules, (10, 200), "^jit_") == 1
    # a mean per event is taken over events wholly inside the window
    assert xplane.whole_events(chip.modules, (10, 200), "^jit_") == [20]
    assert xplane.whole_events(chip.modules, win, "^jit_step") == [100]


def test_union_merges_touching_and_ignores_empty():
    assert xplane.union([(5, 7), (0, 5), (9, 9), (6, 8)]) == [(0, 8)]


def _by_fn(mods):
    out = {}
    for name, ns in mods.items():
        fn = name.split("(")[0]
        out[fn] = out.get(fn, 0) + ns
    return out


def test_recorded_trace_reduces_to_what_was_read_by_hand():
    with open(os.path.join(DATA, "small.by_hand.json")) as f:
        want = json.load(f)
    tr = xplane.load(os.path.join(DATA, "small.xplane.pb"))
    assert sorted(tr.chips) == want["chips"]
    assert [int(t) for t in tr.window] == want["window_ns"]
    chip = tr.chips[0]
    inside, whole = want["in_window"], want["whole_trace"]
    assert _by_fn(xplane.time_by_name(chip.modules, tr.window)) == inside["module_ns"]
    assert xplane.count_by_pattern(
        chip.modules, tr.window, "^jit_small_matmul") == inside["matmul_starts"]
    assert xplane.busy_ns(chip, tr.window) == inside["busy_ns"]
    gaps = xplane.gaps(chip, tr.window)
    assert len(gaps) == inside["gaps"]
    assert max(b - a for a, b in gaps) == inside["longest_gap_ns"]
    assert sum(b - a for a, b in gaps) + inside["busy_ns"] == tr.window[1] - tr.window[0]
    assert _by_fn(xplane.time_by_name(chip.modules, None)) == whole["module_ns"]
    assert xplane.count_by_pattern(
        chip.modules, None, "^jit_small_matmul") == whole["matmul_starts"]
    assert xplane.busy_ns(chip, None) == whole["busy_ns"]
    self_t = xplane.self_time_by_name(chip, None)
    assert sum(self_t.values()) == whole["busy_ns"]


def test_operation_time_inside_a_module_group_is_per_run():
    """``trace_op_ms`` with ``within_group``: kernel time inside each whole
    decode run, per run — not spread over a guessed number of requests."""
    from yardstick import readers

    class Ctx:
        trace = xplane.Trace({0: xplane.Chip(
            ops=[("%k = custom-call(), custom_call_target=\"tpu_custom_call\"", 110, 130),
                 ("%fusion.1 = fusion()", 130, 190),
                 ("%k = custom-call(), custom_call_target=\"tpu_custom_call\"", 410, 440),
                 ("%k = custom-call(), custom_call_target=\"tpu_custom_call\"", 905, 950)],
            modules=[("jit__lambda(7)", 100, 200), ("jit__lambda(7)", 400, 500),
                     ("jit__lambda(7)", 900, 1100), ("jit_apply(3)", 200, 400)])}, (0, 1000))

        def win(self):
            return self.trace.window

        def patterns(self, group):
            return {"decode": r"^jit__lambda\("}.get(group)

    per_run = readers._trace_op_ms(
        {"pattern": "tpu_custom_call", "within_group": "decode"}, Ctx())
    assert per_run == (20 + 30) / 2 / 1e6  # the third run is cut by the window
    whole = readers._trace_op_ms({"pattern": "tpu_custom_call"}, Ctx())
    assert whole == (20 + 30 + 45) / 1e6
    assert readers.op_label(Ctx.trace.chips[0].ops[0][0]) == "k:tpu_custom_call"


def test_a_run_cut_by_the_end_of_the_trace_is_not_a_whole_run():
    """``data/cut.xplane.pb`` (``tools/record_small_trace.py cut``, on the
    chip): the profiler was stopped while the device ran, as the benchmark's
    bracket stops it inside a step. The stump ends where the device trace
    stops, inside the marked window; the mean per run leaves it out."""
    with open(os.path.join(DATA, "cut.by_hand.json")) as f:
        want = json.load(f)
    tr = xplane.load(os.path.join(DATA, "cut.xplane.pb"))
    assert [int(t) for t in tr.window] == want["window_ns"]
    chip = tr.chips[0]
    assert [[int(a), int(b)] for _, a, b in chip.modules] == want["runs_ns"]
    end = xplane.last_ns(chip)
    assert end == want["last_device_event_end_ns"] == want["runs_ns"][-1][1] < tr.window[1]
    began = xplane.last_start_ns(chip)
    assert began == want["runs_ns"][-1][0]
    pat = "^jit_small_chain"
    # as the parent read it: the stump counts, and the mean is 4% low here
    old = xplane.whole_events(chip.modules, tr.window, pat)
    assert old == want["whole_runs_ns"] + [want["stump_ns"]]
    assert abs(sum(old) / len(old) / 1e6 - want["mean_ms_with_the_stump"]) < 1e-9
    # a run counts only if it had ended when the chip's last run began
    new = xplane.whole_events(chip.modules, tr.window, pat, began)
    assert new == want["whole_runs_ns"]
    assert abs(sum(new) / len(new) / 1e6 - want["mean_ms_without_it"]) < 1e-9
    assert xplane.whole_runs(chip.modules, tr.window, pat, began) == [
        tuple(r) for r in want["runs_ns"][1:4]]
    # ... whether or not the stump's end is the trace's last device event
    later = xplane.Chip(ops=chip.ops + [("%fusion.9 = fusion()", end, end + 5000)],
                        modules=chip.modules)
    assert xplane.last_ns(later) == end + 5000
    assert xplane.whole_events(later.modules, tr.window, pat,
                               xplane.last_start_ns(later)) == want["whole_runs_ns"]
    # the reader hands that on
    from yardstick import readers

    class Ctx:
        trace = tr

        def win(self):
            return tr.window

        def patterns(self, group):
            return pat

    assert readers._module_events(Ctx(), "denoiser") == want["whole_runs_ns"]


def test_step_mfu_is_the_windows_operations_over_its_length_times_the_peak(monkeypatch):
    """``window_mfu``: every run of the denoiser's module in the window is one
    ``cost`` of operations (a run cut by an edge by the share of it inside);
    the decode and the gaps are time only, so it lies under the roofline."""
    import run
    from yardstick import readers

    class Ctx:
        # 3 whole runs of 100 ns and half of a fourth; decode and gaps between
        trace = xplane.Trace({0: xplane.Chip(
            ops=[("%fusion.1 = fusion()", 0, 1050)],
            modules=[("jit_apply(3)", 100, 200), ("jit_apply(3)", 250, 350),
                     ("jit__lambda(7)", 350, 500), ("jit_apply(3)", 500, 600),
                     ("jit_apply(3)", 950, 1050)])}, (0, 1000))
        here, device_kind, chips = run.HERE, "TPU v5 lite", 1
        cell = {"mix": {}}
        config = {"shape_functions": "shapes_sd"}

        def win(self):
            return self.trace.window

        def patterns(self, group):
            return {"denoiser": r"^jit_apply\("}.get(group)

    monkeypatch.setattr(readers, "_cost", lambda a, ctx: {"flops": 197e12 * 50e-9,
                                                         "bytes": 1.0})
    args = {"group": "denoiser", "cost": "denoiser_step"}
    # one run needs 50 ns at the peak and takes 100: the roofline reads 50% ...
    assert readers._roofline_share(args, Ctx()) == 50.0
    # ... and 3.5 runs' operations in 1000 ns of window are 17.5% of the peak
    assert abs(readers._window_mfu(args, Ctx()) - 17.5) < 1e-9
    assert readers.READERS["window_mfu"] is readers._window_mfu
    m = run.load_json("layer_metrics", "step_mfu")
    assert m["reader"] == "window_mfu" and "workloads" not in m and "mfu" in m["name"]
    Ctx.trace = None
    assert readers._window_mfu(args, Ctx()) is None  # nothing to read: left out, never 0
