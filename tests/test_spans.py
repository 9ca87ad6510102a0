"""Spans and counters of the dispatch path (``repro.core.spans``): the
DispatchStats split they feed, the process-wide table they fill only while
a profiler session records, the trace annotations an operator sees, and
the compile listener that moves cold-call compiles out of compute time."""

import glob
import os

import jax
import numpy as np
import pytest

from repro.core import (
    Access,
    AnalysisEngine,
    ClassMapPolicy,
    DeviceCacheConfig,
    EpochAnalyzer,
    FabricSession,
    Phase,
    RegionMap,
    Tenant,
    pooled_topology,
    spans,
    synthetic_trace,
)
from repro.core.fleet import FleetSim, synthetic_tenant
from repro.core.policy import InterleavePolicy
from repro.core.scenario import Scenario, ScenarioSuite
from repro.core.topology import TopologyOverride, figure1_topology
from repro.core.units import ns_to_s
from repro.models.phases import build_regions_and_phases
import repro.configs as cfgs

SPLIT = ("stage_s", "transfer_s", "enqueue_s", "wait_s", "d2h_s")
SPAN_OF = {
    "stage_s": "cxlsim.stage",
    "transfer_s": "cxlsim.h2d",
    "enqueue_s": "cxlsim.enqueue",
    "wait_s": "cxlsim.wait",
    "d2h_s": "cxlsim.d2h",
}
COUNTERS = {"cxlsim.slots", "cxlsim.events", spans.BACKEND_COMPILE, spans.H2D_BUFFERS}


def _tenants(n=2, layers=24):
    """Tenants with a real jitted step and layer-many phases, so every span
    of a round lasts far longer than the annotation's own cost."""
    out = []
    for i in range(n):
        rm = RegionMap()
        rm.alloc("w", 1 << 24, "param")
        rm.alloc("kv", 1 << 24, "kvcache")
        phases = [
            Phase(f"layer{j}", flops=5e8,
                  accesses=(Access("w", 1 << 22), Access("kv", 1 << 22, True)))
            for j in range(layers)
        ]
        step = jax.jit(lambda x: (x @ x.T).sum())
        out.append(Tenant(f"t{i}", phases, rm, ClassMapPolicy({"kvcache": "shared_pool"}),
                          step_fn=step, step_args=(np.ones((64, 64), np.float32),)))
    return out


def _split(rep):
    return {f: getattr(rep, f) for f in SPLIT + ("compile_s", "slots", "events")}


@pytest.fixture
def session():
    # a device cache makes every round merge anew (no replay), so the
    # client's merge span is real work too
    with AnalysisEngine() as eng:  # private engine: no cross-test coalescing
        sess = FabricSession(pooled_topology(n_hosts=2), _tenants(), engine=eng,
                             pipeline=True, max_events_per_access=256,
                             cache=DeviceCacheConfig(capacity_bytes=1 << 26))
        sess.run(2)  # warm: every shape compiled before any window
        yield sess
        sess.close()


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("cxlsim."):
                        c, s = out.get(e.name, (0, 0.0))
                        out[e.name] = (c + 1, s + ns_to_s(e.duration_ns))
    return out


def test_no_session_leaves_table_empty_and_stats_filled(session):
    spans.reset()
    before = _split(session.report)
    session.run(2)
    after = _split(session.report)
    assert spans.traced_totals() == {}
    for f in SPLIT:
        assert after[f] > before[f], f
    assert after["events"] > before["events"]


def test_traced_rounds_fill_table_equal_to_dispatch_stats(session, tmp_path):
    before = _split(session.report)
    spans.reset()
    with jax.profiler.trace(str(tmp_path)):
        session.run(4)
    delta = {f: v - before[f] for f, v in _split(session.report).items()}
    table = spans.traced_totals()
    assert delta["compile_s"] == 0.0
    for f, name in SPAN_OF.items():
        count, seconds = table[name]
        assert count == 4, name
        assert seconds == pytest.approx(delta[f], rel=1e-9, abs=1e-12), name
    assert table["cxlsim.slots"][0] == delta["slots"]
    assert table["cxlsim.events"][0] == delta["events"]
    for name in ("cxlsim.merge", "cxlsim.launch", "cxlsim.finish", "cxlsim.fold"):
        assert table[name][0] == 4, name
    assert table["cxlsim.native"][0] == 8  # two tenants a round

    # the same spans, annotated on a host plane of the profiler's trace
    traced = _host_spans(str(tmp_path))
    for name, (count, seconds) in table.items():
        if name in COUNTERS:
            continue
        assert name in traced, name
        assert traced[name][0] == count, name
        # each annotation adds about a microsecond to its span; backpressure,
        # when the client happens to meet it, may last no longer than that
        if name != "cxlsim.submit_wait":
            assert traced[name][1] == pytest.approx(seconds, rel=0.05), name


def test_compute_is_enqueue_wait_d2h_and_counters_are_exact(tmp_path):
    flat = pooled_topology(n_hosts=2).flatten()
    traces = [synthetic_trace(n, flat.n_pools, epoch_ns=1e6, seed=n).with_host(n % 2)
              for n in (100, 200, 300)]
    an = EpochAnalyzer(flat, n_windows=32)
    an.analyze_batch(traces)
    spans.reset()
    with jax.profiler.trace(str(tmp_path)):
        an.analyze_batch(traces)
    st = an.last_dispatch
    assert st.compute_s == st.enqueue_s + st.wait_s + st.d2h_s
    assert (st.slots, st.events) == (4 * 512, 600)  # [B, N] buckets of 3 x 300
    t = spans.traced_totals()
    assert t["cxlsim.slots"][0] == 4 * 512
    assert t["cxlsim.events"][0] == 600


def test_fresh_jit_counts_one_compile_and_warm_window_none(tmp_path):
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = np.arange(7, dtype=np.float32)
    spans.reset()
    with jax.profiler.trace(str(tmp_path / "cold")):
        jax.block_until_ready(f(x))
    assert spans.traced_totals()[spans.BACKEND_COMPILE][0] == 1
    spans.reset()
    with jax.profiler.trace(str(tmp_path / "warm")):
        jax.block_until_ready(f(x))
    assert spans.BACKEND_COMPILE not in spans.traced_totals()


def _sweep():
    cfg = cfgs.get_smoke("starcoder2-3b")
    regions, phases = build_regions_and_phases(cfg, "train", batch=2, seq=64)
    scens = [
        Scenario(InterleavePolicy(["cxl_pool1", "cxl_pool2"]),
                 TopologyOverride(pools={"cxl_pool1": {"latency_ns": 150 + 25 * i}}),
                 name=f"s{i}")
        for i in range(3)
    ]
    # an odd window count: no other test has compiled this sweep shape
    suite = ScenarioSuite(figure1_topology(), regions, phases, n_windows=37)
    return lambda: suite.run(scens), lambda: suite.last_dispatch


def _fleet():
    tenants = [synthetic_tenant(f"t{i}", seed=i, gib=8.0) for i in range(6)]
    fleet = FleetSim(n_racks=2, hosts_per_rack=3, granularity_bytes=65536,
                     max_events_per_access=16, n_windows=37)
    return lambda: fleet.simulate(tenants, offload_fraction=1.0), lambda: fleet.last_dispatch


@pytest.mark.parametrize("make", [_sweep, _fleet], ids=["scenario_suite", "fleet_sim"])
def test_cold_call_compile_moves_out_of_compute(make):
    call, stats = make()
    call()
    cold = stats()
    assert cold.compile_s > 0
    assert cold.compute_s == cold.enqueue_s + cold.wait_s + cold.d2h_s
    call()
    warm = stats()
    assert warm.compile_s == 0.0
    assert warm.enqueue_s > 0 and warm.wait_s >= 0 and warm.d2h_s > 0
