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
