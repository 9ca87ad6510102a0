"""The harness finds cells, configurations, traffic and metrics by name,
and ``BENCHMARK.json`` keeps to the benchmark's contract."""

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, HERE)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import minibench  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MAN = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = MAN["end_to_end"] + MAN["per_layer"]
CELLS = [w["name"] for w in MAN["workloads"]]


def test_new_cell_config_traffic_and_metric_are_found_as_files(tmp_path):
    root = minibench.make_tree(str(tmp_path))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny-pool4.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-pool2"
    with open(os.path.join(b, "configs", "tiny-pool2.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "tiny-two.json"), "w") as f:
        json.dump({"tenants": [{"kind": "decode", "batch": 2, "cache_len": 32},
                               {"kind": "train", "batch": 2, "seq": 16}]}, f)
    with open(os.path.join(b, "workloads", "tiny.two.json"), "w") as f:
        json.dump({"config": "tiny-pool2", "traffic": "tiny-two", "entry": "fabric", "chips": 1,
                   "warm_calls": 1, "limits": minibench.LIMITS}, f)
    with open(os.path.join(b, "metrics", "calls_per_s.events.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.n_calls / ctx.window_s\n")
    man_path = os.path.join(root, "BENCHMARK.json")
    with open(man_path) as f:
        man = json.load(f)
    man["workloads"].append({"name": "tiny.two", "config": "tiny-pool2", "traffic": "tiny-two",
                             "chips": 1, "why": "test"})
    man["end_to_end"].append({"name": "calls_per_s.events", "unit": "1/s", "better": "higher",
                              "bound": 0.25, "source": "host_clock", "workloads": ["tiny.two"]})
    with open(man_path, "w") as f:
        json.dump(man, f)
    rc, res, err = minibench.run(root, "tiny.two", seconds=0.5)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    assert res["metrics"]["calls_per_s.events"]["value"] > 0
    assert "setup_s" in res["metrics"]


def test_every_name_and_unit_keeps_to_the_charset():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MAN[group]:
            assert NAME.match(e["name"]), e["name"]
    for m in METRICS:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_its_files_and_reports_enough(cell):
    entry = next(w for w in MAN["workloads"] if w["name"] == cell)
    wl = harness.load_json("workloads", cell + ".json")
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        entry["config"], entry["traffic"], entry["chips"])
    harness.load_json("configs", wl["config"] + ".json")
    harness.load_json("traffic", wl["traffic"] + ".json")
    assert os.path.exists(os.path.join(BENCH, "entries", wl["entry"] + ".py"))
    e2e = [m["name"] for m in harness.cell_metrics(MAN, cell, trace=False)]
    per = harness.cell_metrics(MAN, cell, trace=True)
    assert "setup_s" in e2e and len(e2e) >= 2 and per
    for m in per:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("name", [m["name"] for m in METRICS])
def test_every_metric_has_a_reader(name):
    assert callable(harness.reader(name).read)


def test_metric_workload_lists_name_cells():
    for m in METRICS:
        for cell in m.get("workloads", []):
            assert cell in CELLS, (m["name"], cell)
