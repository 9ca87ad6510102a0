"""A whole run at test size, on the CPU, with the timed path broken
underneath: ``correct`` has to come out false for every fault a cell can
have, and for the control (the bfloat16 reference in the program's
place).  The same run unbroken comes out correct.  The chip check is the
only part of a run skipped (``minibench``)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import minibench  # noqa: E402

CELLS = ["tiny.attach", "tiny.fabric", "tiny.fleet"]

# the report folds nothing: every step returns the simulator's state as it was
UNCHANGED = """
from repro.core import engine
_submit = engine.EngineHandle.submit
engine.EngineHandle.submit = lambda self, traces, scales=None, fold=None: _submit(self, traces, scales, None)
"""

_SCALED_FINISH = """
import dataclasses
import numpy as np
from repro.core import analyzer
_finish = analyzer.PendingBatch.finish
def _scaled(bd, fields, k):
    return dataclasses.replace(bd, **{f: getattr(bd, f) * k for f in fields if getattr(bd, f) is not None})
"""

# half of each batch's epochs priced, the total taken as twice their mean
HALF = _SCALED_FINISH + """
_launch = analyzer.EpochAnalyzer.launch_batch
def launch(self, traces, lat_scales=None, stager=None):
    traces = list(traces)
    keep = max(1, len(traces) // 2)
    pb = _launch(self, traces[:keep], None if lat_scales is None else list(lat_scales)[:keep], stager)
    pb.fault_k = len(traces) / keep
    return pb
def finish(self):
    k = getattr(self, "fault_k", 1.0)
    bd = _finish(self)
    return _scaled(bd, [f.name for f in dataclasses.fields(bd)], k)
analyzer.EpochAnalyzer.launch_batch = launch
analyzer.PendingBatch.finish = finish
"""

# every batch's congestion altered by 0.1 % where it is produced
ALTERED = _SCALED_FINISH + """
analyzer.PendingBatch.finish = lambda self: _scaled(
    _finish(self), ["congestion_ns", "per_switch_congestion_ns", "per_host_congestion_ns",
                    "per_class_congestion_ns"], 1.001)
"""

# the control: the reference carried in bfloat16 prices every batch
CONTROL = """
import json
import numpy as np
from repro.core import analyzer
from reference import oracle
_fabric = {"tiny.attach": "tiny-fig1", "tiny.fabric": "tiny-pool4"}[sys.argv[2]]
with open("bench/configs/" + _fabric + ".json") as f:
    _fab = json.load(f)["fabric"]
def launch(self, traces, lat_scales=None, stager=None):
    flat = oracle.flatten(_fab, self.flat.n_hosts)
    epochs = [{"t": tr.t_ns, "pool": tr.pool, "host": tr.host, "qos": tr.qos, "bytes": tr.bytes_}
              for tr in traces if tr.n]
    bd = oracle.price_batch(flat, epochs, self.n_windows, q=oracle.round_bf16)
    pb = analyzer.PendingBatch(self, None, analyzer.DispatchStats(rows=len(epochs)))
    pb.control = analyzer.DelayBreakdown(
        bd["latency"], bd["congestion"], bd["bandwidth"], bd["per_pool_latency"],
        bd["per_switch_congestion"], bd["per_switch_bandwidth"], bd["per_host_latency"],
        bd["per_host_congestion"], bd["per_host_bandwidth"], bd["per_class_congestion"])
    return pb
analyzer.EpochAnalyzer.launch_batch = launch
analyzer.PendingBatch.finish = lambda self: self.control
"""

FAULTS = {"unchanged": UNCHANGED, "half": HALF, "altered": ALTERED, "control": CONTROL}

_FLEET = """
import dataclasses
import json
from repro.core import fleet as fleet_mod
from repro.core.analyzer import DelayBreakdown
_dispatch = fleet_mod.FleetSim._dispatch
def _scaled(bd, fields, k):
    return dataclasses.replace(bd, **{f: getattr(bd, f) * k for f in fields if getattr(bd, f) is not None})
_ALL = [f.name for f in dataclasses.fields(DelayBreakdown)]
"""

# the fleet's pricing comes back as zeros: nothing was priced
FLEET_ZEROS = _FLEET + """
fleet_mod.FleetSim._dispatch = lambda self, rt, tiles, mesh: [
    _scaled(bd, _ALL, 0.0) for bd in _dispatch(self, rt, tiles, mesh)]
"""

# half of each rack's epochs priced, the total taken as twice their mean
FLEET_HALF = _FLEET + """
def dispatch(self, rack_traces, tiles, mesh):
    half = [rows[: max(1, len(rows) // 2)] for rows in rack_traces]
    k = [len(r) / len(h) for r, h in zip(rack_traces, half)]
    return [_scaled(bd, _ALL, kk) for bd, kk in zip(_dispatch(self, half, tiles, mesh), k)]
fleet_mod.FleetSim._dispatch = dispatch
"""

# congestion altered by 0.1 % where it is produced
FLEET_ALTERED = _FLEET + """
fleet_mod.FleetSim._dispatch = lambda self, rt, tiles, mesh: [
    _scaled(bd, ["congestion_ns", "per_switch_congestion_ns", "per_host_congestion_ns"], 1.001)
    for bd in _dispatch(self, rt, tiles, mesh)]
"""

# the gather from the chips left out: only the first shard's rows come back
FLEET_SHARD = _FLEET + """
def dispatch(self, rack_traces, tiles, mesh):
    out = _dispatch(self, rack_traces, tiles, mesh)
    first = max(1, self.last_dispatch.shard_rows or len(out))
    return [bd if k < first else _scaled(bd, _ALL, 0.0) for k, bd in enumerate(out)]
fleet_mod.FleetSim._dispatch = dispatch
"""

# the control: the reference carried in bfloat16 prices every rack row
FLEET_CONTROL = _FLEET + """
from reference import oracle
with open("bench/configs/tiny-pool4.json") as f:
    _fab = json.load(f)["fabric"]
def dispatch(self, rack_traces, tiles, mesh):
    flat = oracle.flatten(_fab, self.hosts_per_rack)
    out = []
    for rows in rack_traces:
        epochs = [{"t": tr.t_ns, "pool": tr.pool, "host": tr.host, "qos": tr.qos,
                   "bytes": tr.bytes_} for tr in rows if tr.n]
        bd = oracle.price_batch(flat, epochs, self.n_windows, q=oracle.round_bf16)
        out.append(DelayBreakdown(
            bd["latency"], bd["congestion"], bd["bandwidth"], bd["per_pool_latency"],
            bd["per_switch_congestion"], bd["per_switch_bandwidth"], bd["per_host_latency"],
            bd["per_host_congestion"], bd["per_host_bandwidth"], bd["per_class_congestion"]))
    return out
fleet_mod.FleetSim._dispatch = dispatch
"""

FLEET_FAULTS = {"zeros": FLEET_ZEROS, "half": FLEET_HALF, "altered": FLEET_ALTERED,
                "shard_lost": FLEET_SHARD, "control": FLEET_CONTROL}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return minibench.make_tree(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell", CELLS)
def test_unbroken_run_is_correct(tree, cell):
    rc, res, err = minibench.run(tree, cell, seed=2**33 + 5, seconds=0.5)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert err.rstrip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS[:2])
def test_broken_run_is_not_correct(tree, cell, fault):
    rc, res, err = minibench.run(tree, cell, seed=11, seconds=0.5, fault=FAULTS[fault])
    assert rc == 0, err[-3000:]
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("fault", sorted(FLEET_FAULTS))
def test_broken_fleet_run_is_not_correct(tree, fault):
    rc, res, err = minibench.run(tree, "tiny.fleet", seed=13, seconds=0.5,
                                 fault=FLEET_FAULTS[fault])
    assert rc == 0, err[-3000:]
    assert res["correct"] is False, res["checks"]
