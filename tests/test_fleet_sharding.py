"""Sharded dispatch + FleetSim: parity, observability, and fleet scheduling.

The conftest boots jax with 8 virtual CPU devices, so every test here runs
against a real multi-device ('data',) mesh.  The contract under test:
sharding the leading scenario/session/rack axis over the mesh changes
*where* planes compute, never *what* they compute — bitwise for the
analyzer paths (identical per-plane program), <=1e-6 relative for the
sweep — and every dispatch reports its device/shard/padding observability.
"""

import warnings

import numpy as np
import pytest

import repro.configs as cfgs
from repro.core.analyzer import DispatchStats, EpochAnalyzer
from repro.core.engine import AnalysisEngine
from repro.core.events import synthetic_trace
from repro.core.fleet import FleetSim, synthetic_tenant
from repro.core.policy import ClassMapPolicy, InterleavePolicy
from repro.core.scenario import Scenario, ScenarioSuite
from repro.core.topology import TopologyOverride, figure1_topology, pooled_topology
from repro.distributed.sharding import (
    pad_to_multiple,
    resolve_data_mesh,
)
from repro.models.phases import build_regions_and_phases


def _session_groups(flat, k, b=3, n=300):
    return [
        [
            synthetic_trace(n, flat.n_pools, epoch_ns=1e6, seed=7 * i + j)
            .with_host(i % flat.n_hosts)
            for j in range(b)
        ]
        for i in range(k)
    ]


# --------------------------------------------------------------------------- #
# mesh resolution / fallback / errors
# --------------------------------------------------------------------------- #


def test_conftest_provides_eight_virtual_devices(data_mesh):
    import jax

    assert jax.device_count() == 8
    assert data_mesh.shape == {"data": 8}


def test_resolve_rejects_mesh_without_data_axis():
    import jax

    mesh = jax.make_mesh((2, 4), ("a", "b"))
    with pytest.raises(ValueError, match="data"):
        resolve_data_mesh(mesh, 8)


def test_resolve_falls_back_when_devices_exceed_rows(data_mesh):
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        sub, n = resolve_data_mesh(data_mesh, 5)
    assert n == 5 and sub is not None
    assert any("falling back" in str(x.message) for x in w)
    # one row: nothing to shard at all
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        sub1, n1 = resolve_data_mesh(data_mesh, 1)
    assert sub1 is None and n1 == 1


def test_pad_to_multiple():
    assert pad_to_multiple(16, 8) == 16
    assert pad_to_multiple(17, 8) == 24
    assert pad_to_multiple(5, 1) == 5
    assert pad_to_multiple(5, 0) == 5


# --------------------------------------------------------------------------- #
# analyzer: coalesced multi-session dispatch parity (bitwise)
# --------------------------------------------------------------------------- #


def test_analyze_batch_multi_sharded_bitwise_parity(data_mesh):
    flat = pooled_topology(n_hosts=4).flatten()
    groups = _session_groups(flat, 11)  # uneven: bucket(11)=16 -> 2 rows/device
    plain = EpochAnalyzer(flat, n_windows=64)
    sharded = EpochAnalyzer(flat, n_windows=64, mesh=data_mesh)
    a = plain.analyze_batch_multi(groups)
    b = sharded.analyze_batch_multi(groups)
    for x, y in zip(a, b):
        assert x.latency_ns == y.latency_ns
        assert x.congestion_ns == y.congestion_ns
        assert x.bandwidth_ns == y.bandwidth_ns
        np.testing.assert_array_equal(x.per_host_total_ns, y.per_host_total_ns)
    st = sharded.last_dispatch
    assert isinstance(st, DispatchStats)
    assert (st.devices_used, st.shard_rows, st.rows, st.padded_fraction) == (
        8, 2, 11, 5 / 16
    )
    assert st.slots == 16 * 4 * 512  # [K, B, N] buckets of 11 x 3 x 300
    assert st.events == sum(tr.n for g in groups for tr in g)
    assert st.compute_s == st.enqueue_s + st.wait_s + st.d2h_s
    assert plain.last_dispatch.devices_used == 1
    assert sharded.sharded_dispatches == 1


def test_analyze_batch_multi_per_call_mesh_overrides_constructor(data_mesh):
    flat = pooled_topology(n_hosts=2).flatten()
    groups = _session_groups(flat, 8, b=2, n=128)
    plain = EpochAnalyzer(flat, n_windows=64)
    a = plain.analyze_batch_multi(groups)
    b = plain.analyze_batch_multi(groups, mesh=data_mesh)
    for x, y in zip(a, b):
        assert x.latency_ns == y.latency_ns
    assert plain.last_dispatch.devices_used == 8
    assert plain.sharded_dispatches == 1


def test_analyze_batch_multi_fewer_rows_than_devices_warns_and_matches(data_mesh):
    flat = pooled_topology(n_hosts=2).flatten()
    groups = _session_groups(flat, 5, b=2, n=128)
    plain = EpochAnalyzer(flat, n_windows=64)
    sharded = EpochAnalyzer(flat, n_windows=64, mesh=data_mesh)
    a = plain.analyze_batch_multi(groups)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        b = sharded.analyze_batch_multi(groups)
    assert any("falling back" in str(x.message) for x in w)
    for x, y in zip(a, b):
        assert x.latency_ns == y.latency_ns
    assert sharded.last_dispatch.devices_used == 5


# --------------------------------------------------------------------------- #
# scenario suite: sweep parity (<=1e-6) + observability in table()
# --------------------------------------------------------------------------- #


def _sweep_fixtures(n_scen=8):
    cfg = cfgs.get_smoke("starcoder2-3b")
    regions, phases = build_regions_and_phases(cfg, "train", batch=2, seq=64)
    scens = []
    for i in range(n_scen):
        lat = 150 + 25 * i
        ov = TopologyOverride(pools={"cxl_pool1": {"latency_ns": lat}})
        pol = (
            ClassMapPolicy({"opt_state": "cxl_pool2", "grad": "cxl_pool1"})
            if i % 2
            else InterleavePolicy(["cxl_pool1", "cxl_pool2"])
        )
        scens.append(Scenario(pol, ov, name=f"s{i}"))
    return figure1_topology(), regions, phases, scens


def test_scenario_sweep_sharded_parity(data_mesh):
    topo, regions, phases, scens = _sweep_fixtures(8)
    plain = ScenarioSuite(topo, regions, phases)
    sharded = ScenarioSuite(topo, regions, phases, mesh=data_mesh)
    ra = plain.run(scens)
    rb = sharded.run(scens)
    for a, b in zip(ra.breakdowns, rb.breakdowns):
        assert b.total_ns == pytest.approx(a.total_ns, rel=1e-6)
        assert b.latency_ns == pytest.approx(a.latency_ns, rel=1e-6)
    assert ra.devices_used == 1 and rb.devices_used == 8
    assert rb.shard_rows == 1 and rb.padded_fraction == 0.0
    row = rb.table()[0]
    assert row["devices_used"] == 8
    assert row["shard_rows"] == 1
    assert row["padded_fraction"] == 0.0


def test_scenario_sweep_uneven_k_falls_back(data_mesh):
    topo, regions, phases, scens = _sweep_fixtures(6)
    plain = ScenarioSuite(topo, regions, phases)
    sharded = ScenarioSuite(topo, regions, phases, mesh=data_mesh)
    ra = plain.run(scens)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        rb = sharded.run(scens)
    assert any("falling back" in str(x.message) for x in w)
    for a, b in zip(ra.breakdowns, rb.breakdowns):
        assert b.total_ns == pytest.approx(a.total_ns, rel=1e-6)
    assert rb.devices_used == 6


# --------------------------------------------------------------------------- #
# engine: mesh plumbing + report observability counters
# --------------------------------------------------------------------------- #


def test_engine_mesh_parity_and_handle_stats(data_mesh):
    flat = pooled_topology(n_hosts=4).flatten()
    groups = _session_groups(flat, 4, b=2, n=200)
    ref = EpochAnalyzer(flat, n_windows=64).analyze_batch_multi(groups)
    eng = AnalysisEngine("fleet-test", mesh=data_mesh)
    try:
        handles = [eng.register(EpochAnalyzer(flat, n_windows=64)) for _ in groups]
        futs = [h.submit(g) for h, g in zip(handles, groups)]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # <8 coalesced rows may fall back
            got = [f.result(60) for f in futs]
        for x, y in zip(ref, got):
            assert x.latency_ns == y.latency_ns
            assert x.congestion_ns == y.congestion_ns
            assert x.bandwidth_ns == y.bandwidth_ns
        coalesced = [h for h in handles if h.last_group_size > 1]
        if coalesced:  # timing-dependent, but stats must be coherent
            st = coalesced[0].last_dispatch
            assert st is not None and st.devices_used >= 1
    finally:
        eng.close()


def test_sim_report_summary_carries_dispatch_observability():
    from repro.core.attach import SimReport

    s = SimReport().summary()
    assert s["devices_used"] == 1
    assert s["shard_rows"] == 0
    assert s["padded_waste"] == 0.0
    assert s["coalesced_group_size"] == 1


# --------------------------------------------------------------------------- #
# FleetSim: scheduling, stranding accounting, frontier, sharded parity
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def fleet_tenants():
    return [synthetic_tenant(f"t{i}", seed=i, gib=8.0) for i in range(24)]


def _mini_fleet(**kw):
    kw.setdefault("granularity_bytes", 65536)
    kw.setdefault("max_events_per_access", 16)
    return FleetSim(n_racks=4, hosts_per_rack=4, **kw)


def test_fleet_placement_accounting(fleet_tenants):
    fleet = _mini_fleet()
    placements = fleet.place(fleet_tenants, policy="least_loaded", offload_fraction=1.0)
    assert len(placements) == 24
    for p in placements:
        assert 0 <= p.rack < 4 and 0 <= p.host < 4
        # local + pooled partitions the tenant's demand
        assert p.local_bytes + p.pooled_bytes == pytest.approx(
            p.tenant.demand_bytes()
        )
        # offload_fraction=1.0 moves every offloadable class
        off = sum(
            r.nbytes
            for r in p.tenant.regions.regions
            if r.tensor_class in fleet.offload_classes
        )
        assert p.pooled_bytes == pytest.approx(off)
        # pool_of_region is consistent with the byte split
        pooled = sum(
            r.nbytes
            for r in p.tenant.regions.regions
            if p.pool_of_region[r.rid] == fleet.shared_pool
        )
        assert pooled == pytest.approx(p.pooled_bytes)


def test_fleet_round_robin_spreads_tenants(fleet_tenants):
    fleet = _mini_fleet()
    placements = fleet.place(fleet_tenants[:16], policy="round_robin")
    slots = {(p.rack, p.host) for p in placements}
    assert len(slots) == 16  # 16 tenants over 16 hosts: one each


def test_fleet_rejects_duplicate_names(fleet_tenants):
    with pytest.raises(ValueError, match="unique"):
        _mini_fleet().place([fleet_tenants[0], fleet_tenants[0]])


def test_fleet_overflow_raises_clear_error():
    huge = [synthetic_tenant("huge", seed=1, gib=500.0)]
    with pytest.raises(ValueError, match="local DRAM"):
        FleetSim(n_racks=1, hosts_per_rack=2).place(huge, offload_fraction=0.0)


def test_fleet_simulate_report(fleet_tenants):
    fleet = _mini_fleet()
    rep = fleet.simulate(fleet_tenants, offload_fraction=1.0)
    assert rep.n_hosts == 16 and rep.n_tenants == 24
    assert rep.stranded_recovered_bytes > 0
    assert rep.p99_slowdown() >= rep.mean_slowdown() >= 1.0
    assert rep.tenant_slowdowns().shape == (24,)
    s = rep.summary()
    assert s["stranded_recovered_gb"] > 0
    assert s["devices_used"] == 1


def test_fleet_simulate_sharded_parity(data_mesh, fleet_tenants):
    plain = _mini_fleet()
    sharded = _mini_fleet(mesh=data_mesh)
    a = plain.simulate(fleet_tenants, offload_fraction=1.0)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        b = sharded.simulate(fleet_tenants, offload_fraction=1.0)
    assert any("falling back" in str(x.message) for x in w)  # 4 racks < 8 dev
    np.testing.assert_allclose(b.delay_ns, a.delay_ns, rtol=1e-6)
    np.testing.assert_array_equal(b.native_ns, a.native_ns)
    assert b.devices_used == 4
    assert a.devices_used == 1


def test_fleet_frontier_monotone_and_one_dispatch(data_mesh, fleet_tenants):
    plain = _mini_fleet()
    sharded = _mini_fleet(mesh=data_mesh)
    fracs = (0.0, 0.5, 1.0)
    pts = plain.frontier(fleet_tenants, offload_fractions=fracs)
    assert [p.offload_fraction for p in pts] == list(fracs)
    gb = [p.stranded_recovered_gb for p in pts]
    assert gb[0] == 0.0
    assert all(b >= a for a, b in zip(gb, gb[1:]))
    # F*R = 12 planes stacked into ONE dispatch
    n0 = plain.dispatch_count
    plain.frontier(fleet_tenants, offload_fractions=fracs)
    assert plain.dispatch_count == n0 + 1
    # sharded frontier matches plane for plane (K=12 -> fallback submesh)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        pts_m = sharded.frontier(fleet_tenants, offload_fractions=fracs)
    for a, b in zip(pts, pts_m):
        np.testing.assert_allclose(b.report.delay_ns, a.report.delay_ns, rtol=1e-6)
    # frontier end point == standalone simulate at the same fraction
    rep = plain.simulate(fleet_tenants, offload_fraction=1.0)
    np.testing.assert_allclose(pts[-1].report.delay_ns, rep.delay_ns, rtol=1e-6)


def test_fleet_heterogeneous_rack_overrides(fleet_tenants):
    slow = TopologyOverride(pools={"shared_pool": {"latency_ns": 400.0}})
    uniform = _mini_fleet()
    mixed = FleetSim(
        n_racks=4,
        hosts_per_rack=4,
        rack_overrides=[None, None, slow, slow],
        granularity_bytes=65536,
        max_events_per_access=16,
    )
    # round_robin gives identical placements, so rack deltas isolate topology
    a = uniform.simulate(fleet_tenants, policy="round_robin", offload_fraction=1.0)
    b = mixed.simulate(fleet_tenants, policy="round_robin", offload_fraction=1.0)
    np.testing.assert_allclose(b.delay_ns[:2], a.delay_ns[:2], rtol=1e-6)
    assert (b.delay_ns[2:] > a.delay_ns[2:]).all()


def test_fleet_zero_offload_keeps_everything_local(fleet_tenants):
    fleet = _mini_fleet()
    rep = fleet.simulate(fleet_tenants[:8], offload_fraction=0.0)
    assert rep.stranded_recovered_bytes == 0.0
    for p in rep.placements:
        assert (p.pool_of_region == fleet.local_pool).all()
