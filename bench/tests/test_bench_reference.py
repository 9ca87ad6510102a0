"""The benchmark's copies equal today's program on small inputs: the f64
oracle, the fabric lowering, the tenant phase builder, the tracer's event
synthesis and the host merge.  (The copies must not import the program;
this test is the only place the two meet.)"""

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import adapt  # noqa: E402
import tenants  # noqa: E402
from reference import cell, oracle  # noqa: E402

from repro.core import merge_host_traces, synthesize_step_trace  # noqa: E402
from repro.core.analyzer import analyze_ref  # noqa: E402
from repro.core.events import MemEvents  # noqa: E402


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _wfq_fabric():
    fab = json.loads(json.dumps(_cfg("pool4-starcoder2-3b")["fabric"]))
    fab["qos_classes"] = 3
    fab["switches"][0]["discipline"] = "wfq"
    fab["switches"][0]["class_weights"] = [4.0, 2.0, 1.0]
    return fab


FABRICS = {
    "figure1": (lambda: _cfg("fig1-qwen3-0.6b")["fabric"], 1),
    "pool4": (lambda: _cfg("pool4-starcoder2-3b")["fabric"], 4),
    "pool4_wfq": (_wfq_fabric, 4),
}


@pytest.mark.parametrize("name", sorted(FABRICS))
def test_flatten_matches_program(name):
    fab, hosts = FABRICS[name][0](), FABRICS[name][1]
    mine = oracle.flatten(fab, hosts)
    theirs = adapt.topology(fab, hosts).flatten()
    np.testing.assert_array_equal(mine["pool_latency_ns"], theirs.pool_latency_ns)
    np.testing.assert_array_equal(mine["route"], theirs.route)
    np.testing.assert_array_equal(mine["stt_ns"], theirs.switch_stt_ns)
    np.testing.assert_array_equal(mine["bandwidth_gbps"], theirs.switch_bandwidth_gbps)
    np.testing.assert_array_equal(mine["stage_order"], theirs.stage_order())
    np.testing.assert_array_equal(mine["class_weights"], theirs.class_weight_table())
    assert tuple(mine["discipline"]) == tuple(theirs.switch_discipline)


def _random_epoch(rng, flat, n):
    t = np.sort(rng.uniform(0, 4 * n, n))
    pool = rng.integers(0, flat["P"], n)
    host = rng.integers(0, flat["H"], n)
    qos = rng.integers(0, flat["C"], n)
    nbytes = rng.choice([64.0, 256.0, 4096.0], n)
    return {"t": t, "pool": pool, "host": host, "qos": qos, "bytes": nbytes}


@pytest.mark.parametrize("name", sorted(FABRICS))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_oracle_matches_analyze_ref(name, seed):
    fab, hosts = FABRICS[name][0](), FABRICS[name][1]
    flat = oracle.flatten(fab, hosts)
    pflat = adapt.topology(fab, hosts).flatten()
    rng = np.random.default_rng(seed)
    ev = _random_epoch(rng, flat, 600)
    me = MemEvents(t_ns=ev["t"], pool=ev["pool"].astype(np.int32), bytes_=ev["bytes"],
                   is_write=np.zeros(600, bool), region=np.zeros(600, np.int32),
                   host=ev["host"].astype(np.int32), qos=ev["qos"].astype(np.int32))
    win = 50.0
    mine = oracle.analyze(flat, ev, win, 64)
    theirs = analyze_ref(pflat, me, bw_window_ns=win, n_windows=64)
    assert mine["latency"] == pytest.approx(theirs.latency_ns, rel=1e-12)
    assert mine["congestion"] == pytest.approx(theirs.congestion_ns, rel=1e-12)
    assert mine["bandwidth"] == pytest.approx(theirs.bandwidth_ns, rel=1e-12)
    np.testing.assert_allclose(mine["per_switch_congestion"], theirs.per_switch_congestion_ns, rtol=1e-12)
    np.testing.assert_allclose(mine["per_switch_bandwidth"], theirs.per_switch_bandwidth_ns, rtol=1e-12)
    np.testing.assert_allclose(mine["per_host_congestion"], theirs.per_host_congestion_ns, rtol=1e-12)
    np.testing.assert_allclose(mine["per_host_bandwidth"], theirs.per_host_bandwidth_ns, rtol=1e-12)
    np.testing.assert_allclose(mine["per_class_congestion"], theirs.per_class_congestion_ns, rtol=1e-12)
    assert theirs.congestion_ns > 0


@pytest.mark.parametrize("arch,kind,batch,seq,cache_len", [
    ("qwen3-0.6b", "train", 4, 1024, 0),
    ("starcoder2-3b", "train", 4, 1024, 0),
    ("starcoder2-3b", "decode", 17, 1, 5000),
])
def test_tenants_match_phase_builder(arch, kind, batch, seq, cache_len):
    from repro.configs import get_config
    from repro.models.phases import build_regions_and_phases

    cfg = _cfg("fig1-qwen3-0.6b" if arch.startswith("qwen3") else "pool4-starcoder2-3b")
    regions, phases = tenants.build(cfg["model"], kind, batch, seq, cache_len=cache_len)
    # the copy holds a sliding-window model's cache at the window; the
    # program's builder reads the whole cache_len
    window = cfg["model"].get("sliding_window") or cache_len
    rmap, pphases = build_regions_and_phases(get_config(arch), kind, batch=batch, seq=seq,
                                             cache_len=min(cache_len, window))
    assert [(r.name, r.nbytes, r.tensor_class) for r in rmap] == regions
    assert len(pphases) == len(phases)
    for p, (name, flops, acc) in zip(pphases, phases):
        assert p.name == name
        assert p.flops == pytest.approx(flops, rel=1e-12)
        assert [(a.region, a.bytes_, a.is_write) for a in p.accesses] == [
            (r, pytest.approx(b, rel=1e-12), w) for r, b, w in acc]


def test_synthesis_and_merge_match_tracer():
    cfg = _cfg("pool4-starcoder2-3b")
    mix = [{"kind": "train", "batch": 4, "seq": 1024},
           {"kind": "decode", "batch": 9, "cache_len": 3000}]
    progs = tenants.programs(cfg, mix)
    batch = cell.tenant_batch(cfg, progs)
    flat = adapt.topology(cfg["fabric"], 2).flatten()
    per_host = []
    for regions, phases in progs:
        rmap, ph = adapt.memory_program(regions, phases)
        place = cell.pool_of(regions, cfg["placement"], flat.pool_names)
        for r in rmap:
            r.pool = place[r.name]
        traces, _, _ = synthesize_step_trace(ph, rmap, granularity_bytes=64, epoch_mode="layer")
        per_host.append(traces)
    merged = [merge_host_traces([e[k] for e in per_host if k < len(e)],
                                [h for h, e in enumerate(per_host) if k < len(e)])
              for k in range(max(len(e) for e in per_host))]
    assert len(merged) == len(batch.epochs)
    for want, got in zip(merged, batch.epochs):
        np.testing.assert_array_equal(got["t"], want.t_ns)
        np.testing.assert_array_equal(got["pool"], want.pool)
        np.testing.assert_array_equal(got["host"], want.host)
        np.testing.assert_allclose(got["bytes"], want.bytes_, rtol=1e-15)
