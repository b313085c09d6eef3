"""The arrival schedule and the per-request draws: equal for equal seeds,
the same amount of work for every seed."""

import json
import os


import run
from yardstick import stats, traffic

OPEN = {"name": "t", "loop": "open", "rate_per_s": 1.4,
        "latent": {"width": 512, "height": 512, "batch_size": 1},
        "draws": {"noise_seed": "unique", "prompt": {"kind": "unique", "words": 8},
                  "negative": "blurry"}}


def test_equal_seeds_give_equal_schedules_and_other_seeds_other_ones():
    big = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
    a, b = traffic.Schedule(OPEN, big, 45), traffic.Schedule(OPEN, big, 45)
    c = traffic.Schedule(OPEN, big + 1, 45)
    assert a.due == b.due and a.due != c.due
    assert [a.request(i) for i in range(5)] == [b.request(i) for i in range(5)]
    assert a.request(0).noise_seed != c.request(0).noise_seed
    assert a.request(0).positive != a.request(1).positive


def test_open_loop_offers_every_seed_the_same_work():
    for seed in (1, 2, 3 * 10 ** 9):
        s = traffic.Schedule(OPEN, seed, 45)
        assert s.count() == 63 and s.due[0] == 0.0
        assert all(x <= y for x, y in zip(s.due, s.due[1:]))
        assert 45 * 61 / 63 < s.due[-1] < 45


def test_closed_loop_has_no_due_times_and_fixed_text():
    cell = run.load_cell("sd15-b8-512.closed")
    s = traffic.Schedule(cell["mix"], 7, 45)
    assert s.count() is None and s.request(3).due_s is None
    assert s.request(0).positive == s.request(9).positive
    assert s.request(0).noise_seed != s.request(9).noise_seed


def test_zipf_prompts_repeat_and_unique_ones_do_not():
    mix = dict(OPEN, draws={"noise_seed": "unique", "negative": "",
                            "prompt": {"kind": "zipf", "s": 1.1, "texts": 16}})
    s = traffic.Schedule(mix, 5, 45)
    texts = [s.request(i).positive for i in range(63)]
    assert 1 < len(set(texts)) <= 16
    u = traffic.Schedule(OPEN, 5, 45)
    assert len({u.request(i).positive for i in range(63)}) == 63


def test_fill_graph_writes_the_draws_and_leaves_the_template_alone():
    cell = run.load_cell("sd15-b8-512.closed")
    before = json.dumps(cell["template"])
    r = traffic.Schedule(cell["mix"], 1, 45).request(2)
    g = traffic.fill_graph(cell["template"], cell["mix"], r)
    assert g["3"]["inputs"]["seed"] == r.noise_seed
    assert g["5"]["inputs"] == {"width": 512, "height": 512, "batch_size": 8}
    assert g["6"]["inputs"]["text"] == r.positive
    assert json.dumps(cell["template"]) == before


def test_every_cell_of_benchmark_json_has_its_files():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
    names = {m["name"] for m in bench["per_layer"]}
    files = {fn[:-5] for fn in os.listdir(os.path.join(run.HERE, "layer_metrics"))}
    assert names == files


def test_percentiles_and_spread():
    assert stats.nearest_rank(range(1, 11), 90) == 9
    assert stats.nearest_rank(range(1, 71), 90) == 63
    assert stats.nearest_rank([5.0], 90) == 5.0
    # a failed or refused request counts as the worst
    assert stats.percentile_failures_worst([1, 2, 3, 4, 5, 6, 7, 8], [0.1, 30], 90) == 8
    assert stats.percentile_failures_worst([1, 2, 3, 4, 5, 6, 7, 8], [0.1, 30], 100) == 30
    assert abs(stats.iqr_share([10, 10.1, 9.9, 10.05, 9.95, 10]) - 0.0125) < 1e-3
