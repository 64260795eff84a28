"""The seeded traffic generator and discovery of cells by name."""

import json

import numpy as np
import pytest

from perfbench import discover, traffic


@pytest.mark.parametrize("mix_name", ["chat", "decode_batch"])
def test_same_seed_same_requests(mix_name):
    mix = discover.traffic(mix_name)
    a = traffic.generate(mix, 2 ** 31 + 5, 32000)
    b = traffic.generate(mix, 2 ** 31 + 5, 32000)
    assert [(r.prompt, r.max_new, r.due) for r in a] == [(r.prompt, r.max_new, r.due) for r in b]
    c = traffic.generate(mix, 2 ** 31 + 6, 32000)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("mix_name", ["chat", "decode_batch"])
def test_every_seed_gets_the_same_work(mix_name):
    mix = discover.traffic(mix_name)
    runs = [traffic.generate(mix, s, 32000) for s in (1, 2 ** 33 + 1)]
    sched = [[(len(r.prompt), r.max_new, r.due) for r in reqs] for reqs in runs]
    assert sched[0] == sched[1]
    for reqs in runs:
        for r in reqs:
            assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
            assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
            assert 0 <= min(r.prompt) and max(r.prompt) < 32000


def test_lognormal_median_and_clip():
    rng = np.random.RandomState(0)
    x = traffic.draw_lengths({"dist": "lognormal", "median": 128, "sigma": 0.8,
                              "min": 16, "max": 768}, rng, 20000)
    assert 120 <= np.median(x) <= 136 and x.min() >= 16 and x.max() <= 768


def test_open_loop_rate():
    mix = dict(discover.traffic("chat"), requests=4000, rate_rps=2.0)
    due = [r.due for r in traffic.generate(mix, 3, 100)]
    assert np.all(np.diff(due) > 0)
    assert due[-1] / len(due) == pytest.approx(0.5, rel=0.05)


def test_files_added_are_found_without_editing(checkout):
    root, cell = checkout()
    pb = root / "perfbench"
    (pb / "traffic" / "extra_mix.json").write_text(json.dumps({"loop": "closed"}))
    (pb / "metrics" / "extra_metric.py").write_text("def read(run):\n    return 42.0\n")
    cfg = json.loads((pb / "configs" / "tiny.json").read_text())
    (pb / "configs" / "extra.json").write_text(json.dumps(dict(cfg, n_layers=3)))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "extra", "source": "test", "file": "perfbench/configs/extra.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "extra.extra_mix", "config": "extra",
                               "traffic": "extra_mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "extra_metric", "unit": "%", "better": "higher",
                               "source": "device_trace", "layer": "device", "moves": "itl_p95_ms",
                               "workloads": ["extra.extra_mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    bench = discover.load_benchmark(root)
    w = discover.workload(bench, "extra.extra_mix")
    assert discover.config(bench, w["config"], root)["n_layers"] == 3
    assert discover.traffic(w["traffic"], pb) == {"loop": "closed"}
    names = [m["name"] for m in discover.cell_metrics(bench, "extra.extra_mix", "per_layer")]
    assert "extra_metric" in names and "step_mfu" not in names
    assert discover.metric_reader("extra_metric", pb)(None) == 42.0
    with pytest.raises(discover.BenchError):
        discover.workload(bench, "nope.nope")


def test_every_listed_metric_has_a_reader():
    bench = discover.load_benchmark()
    for m in bench["per_layer"]:
        assert callable(discover.metric_reader(m["name"]))
    for w in bench["workloads"]:
        assert discover.config(bench, w["config"])["arch_id"] == w["config"]
        assert discover.traffic(w["traffic"])["serve"]["slots"] >= 1
