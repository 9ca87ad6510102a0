"""Vectorized QoS arbitration cascades: priority / weighted-fair / FIFO
switch queues against the event-by-event DES oracle, dyn-vs-static kernel
parity, ECMP multipath routing, the sweep's ``qos`` axis, and the staging
cap idle-decay.  Exact per-event parity is asserted on tie-free traces
(unique integer timestamps, f32-exact): with tied arrivals the totals are
tie-order-invariant but per-class *attribution* is not, so tied traces are
only checked for conservation (class sums == totals)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QosSpec
from repro.core.analyzer import (
    EpochAnalyzer,
    FineGrainedSimulator,
    analyze_ref,
    plan_cascade,
)
from repro.core.events import EventStager, MemEvents, synthetic_trace
from repro.core.topology import (
    DISCIPLINE_CODES,
    Pool,
    Switch,
    Topology,
    figure1_topology,
    pooled_topology,
)
from repro.core.units import ns_to_s
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.congestion import qos_congestion_cascade as qos_cascade_pallas
from repro.kernels.ref import (
    qos_cascade_dyn,
    qos_serial_queue_cascade,
    serial_queue_cascade,
)

C = 3
WEIGHTS = (4.0, 2.0, 1.0)


def qos_chain(disciplines=("wfq", "priority", "fifo"), weights=WEIGHTS) -> Topology:
    """Depth-3 switch chain with per-switch QoS disciplines."""
    switches = [
        Switch(
            f"sw{d}", 70.0, 64.0 - 8.0 * d, 2.0 + d,
            parent=f"sw{d-1}" if d else None,
            discipline=disc,
            class_weights=weights if disc == "wfq" else None,
        )
        for d, disc in enumerate(disciplines)
    ]
    return Topology(
        pools=[
            Pool("local", 88.9, 76.8, 1 << 36, is_local=True),
            Pool("far1", 180.0, 32.0, 1 << 38, parent=f"sw{len(switches)-1}"),
            Pool("far2", 200.0, 32.0, 1 << 38, parent=f"sw{len(switches)-1}"),
        ],
        switches=switches,
        n_qos_classes=len(weights),
    )


def tie_free_trace(n: int, n_pools: int, seed: int = 0) -> MemEvents:
    """Unique integer timestamps < 2^20: f32-exact and tie-free, so the
    device cascade, the XLA ref, and the DES oracle agree bitwise."""
    rng = np.random.default_rng(seed)
    t = np.sort(rng.choice(np.arange(1, 1 << 20), size=n, replace=False))
    return MemEvents.build(
        t_ns=t.astype(np.float64),
        pool=rng.integers(0, n_pools, n),
        bytes_=np.full(n, 64.0),
        qos=rng.integers(0, C, n),
    )


def _cascade_inputs(flat, ev):
    """(t, bits, stts, qos, disc, weights, names) in the planner's stage
    order — the RC is a stage too, so stages may outnumber the declared
    switches."""
    bits_pool, _, stage_order = plan_cascade(flat)
    order = list(stage_order)
    vpool = ev.host.astype(np.int64) * flat.n_pools + ev.pool.astype(np.int64)
    stage_disc = tuple(flat.switch_discipline[s] for s in order)
    return (
        jnp.asarray(ev.t_ns, jnp.float32),
        jnp.asarray(bits_pool[vpool]),
        jnp.asarray(flat.switch_stt_ns[order], jnp.float32),
        jnp.asarray(ev.qos),
        jnp.asarray(np.asarray(flat.discipline_codes())[order]),
        jnp.asarray(flat.class_weight_table()[order], jnp.float32),
        stage_disc,
    )


# --------------------------------------------------------------------------- #
# kernel-level parity
# --------------------------------------------------------------------------- #


def test_all_fifo_degenerates_bitwise_to_serial_cascade():
    rng = np.random.default_rng(3)
    n, s = 4000, 3
    ts = np.sort(rng.uniform(0, 1e5, n)).astype(np.float32)
    bits = rng.integers(0, 1 << s, n).astype(np.int32)
    stts = jnp.asarray([4.0, 2.0, 0.5], jnp.float32)
    qos = jnp.asarray(rng.integers(0, C, n), jnp.int32)
    w = jnp.ones((s, C), jnp.float32)
    tf_f, idx_f, _ = serial_queue_cascade(jnp.asarray(ts), jnp.asarray(bits), stts)
    tf_q, idx_q, psd = qos_serial_queue_cascade(
        jnp.asarray(ts), jnp.asarray(bits), stts, qos, w, ("fifo",) * s
    )
    np.testing.assert_array_equal(np.asarray(tf_q), np.asarray(tf_f))
    np.testing.assert_array_equal(np.asarray(idx_q), np.asarray(idx_f))
    assert psd.shape == (s, C)  # attribution still per actual class


@pytest.mark.parametrize("disciplines", [
    ("priority", "priority", "priority"),
    ("wfq", "wfq", "wfq"),
    ("wfq", "priority", "fifo"),
])
def test_dyn_matches_static_disciplines(disciplines):
    flat = qos_chain(disciplines).flatten()
    ev = tie_free_trace(3000, flat.n_pools, seed=5)
    t, bits, stts, qos, disc, w, stage_disc = _cascade_inputs(flat, ev)
    tf_s, idx_s, psd_s = qos_serial_queue_cascade(t, bits, stts, qos, w, stage_disc)
    tf_d, idx_d, psd_d = qos_cascade_dyn(t, bits, stts, qos, disc, w)
    np.testing.assert_allclose(np.asarray(tf_d), np.asarray(tf_s), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(psd_d)[:, 0, :], np.asarray(psd_s), rtol=1e-5, atol=1e-3
    )


def test_pallas_interpret_matches_ref():
    flat = qos_chain().flatten()
    ev = tie_free_trace(3000, flat.n_pools, seed=9)
    t, bits, stts, qos, disc, w, _ = _cascade_inputs(flat, ev)
    tf_r, idx_r, psd_r = qos_cascade_dyn(t, bits, stts, qos, disc, w)
    tf_k, idx_k, psd_k = qos_cascade_pallas(
        t, bits, qos, stts, disc, w, block=1024, interpret=True
    )
    np.testing.assert_allclose(np.asarray(tf_k), np.asarray(tf_r), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(idx_k), np.asarray(idx_r))
    np.testing.assert_allclose(
        np.asarray(psd_k), np.asarray(psd_r), rtol=1e-5, atol=1e-3
    )


def test_ops_wrapper_routes_and_shapes():
    flat = qos_chain().flatten()
    ev = tie_free_trace(500, flat.n_pools, seed=2)
    t, bits, stts, qos, disc, w, _ = _cascade_inputs(flat, ev)
    S = stts.shape[0]
    tf, idx, psd = kops.qos_congestion_cascade(
        t, bits, stts, qos, disc, w, impl="ref"
    )
    assert psd.shape == (S, 1, C)
    tf_i, _, psd_i = kops.qos_congestion_cascade(
        t, bits, stts, qos, disc, w, impl="pallas_interpret", block=256
    )
    assert psd_i.shape == (S, 1, C)
    np.testing.assert_allclose(np.asarray(tf_i), np.asarray(tf), rtol=1e-6)


def test_priority_class0_sees_no_lower_class_traffic():
    """Strict priority: class 0's per-event times equal a FIFO run over the
    class-0 subsequence alone — lower classes are invisible to it."""
    rng = np.random.default_rng(11)
    n = 2000
    ts = np.sort(rng.choice(np.arange(1, 1 << 20), size=n, replace=False)).astype(np.float32)
    bits = np.ones(n, np.int32)
    qos = rng.integers(0, C, n).astype(np.int32)
    stts = jnp.asarray([5.0], jnp.float32)
    w = jnp.ones((1, C), jnp.float32)
    tf, idx, _ = qos_serial_queue_cascade(
        jnp.asarray(ts), jnp.asarray(bits), stts, jnp.asarray(qos), w, ("priority",)
    )
    out = np.empty(n, np.float64)
    out[np.asarray(idx)] = np.asarray(tf, np.float64)
    sel = qos == 0
    tf0, idx0, _ = serial_queue_cascade(
        jnp.asarray(ts[sel]), jnp.asarray(bits[sel]), stts
    )
    only0 = np.empty(int(sel.sum()), np.float64)
    only0[np.asarray(idx0)] = np.asarray(tf0, np.float64)
    np.testing.assert_allclose(out[sel], only0, rtol=1e-6)


def test_wfq_weight_shifts_delay_between_classes():
    """Heavier weight => smaller inflated service => less queueing charged."""
    rng = np.random.default_rng(4)
    n = 4000
    ts = np.sort(rng.choice(np.arange(1, 1 << 16), size=n, replace=False)).astype(np.float32)
    bits = np.ones(n, np.int32)
    qos = (np.arange(n) % 2).astype(np.int32)
    stts = jnp.asarray([6.0], jnp.float32)

    def cls_delay(w0, w1):
        w = jnp.asarray([[w0, w1]], jnp.float32)
        _, _, psd = qos_serial_queue_cascade(
            jnp.asarray(ts), jnp.asarray(bits), stts, jnp.asarray(qos), w, ("wfq",)
        )
        return np.asarray(psd)[0]

    heavy0 = cls_delay(8.0, 1.0)
    flipped = cls_delay(1.0, 8.0)
    assert heavy0[0] < flipped[0]  # protected class waits less
    assert heavy0[1] > flipped[1]


# --------------------------------------------------------------------------- #
# DES oracle agreement (tie-free => exact)
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("disciplines", [
    ("wfq", "priority", "fifo"),
    ("priority", "priority", "priority"),
])
def test_des_per_event_final_time_parity(disciplines):
    flat = qos_chain(disciplines).flatten()
    ev = tie_free_trace(4000, flat.n_pools, seed=7)
    t, bits, stts, qos, disc, w, _ = _cascade_inputs(flat, ev)
    tf, idx, _ = qos_cascade_dyn(t, bits, stts, qos, disc, w)
    out = np.empty(ev.n, np.float64)
    out[np.asarray(idx)] = np.asarray(tf, np.float64)
    des = FineGrainedSimulator(flat, bandwidth_mode="stt")
    np.testing.assert_allclose(
        out, des.final_times(ev, presorted=True), rtol=1e-5
    )


def test_analyzer_matches_ref_and_des_per_class():
    flat = qos_chain().flatten()
    ev = tie_free_trace(3000, flat.n_pools, seed=13)
    ref = analyze_ref(flat, ev)
    got = EpochAnalyzer(flat).analyze(ev)
    des = FineGrainedSimulator(flat, bandwidth_mode="stt").simulate(ev)
    assert got.congestion_ns == pytest.approx(ref.congestion_ns, rel=1e-6)
    assert des.congestion_ns == pytest.approx(ref.congestion_ns, rel=1e-6)
    np.testing.assert_allclose(
        got.per_class_congestion_ns, ref.per_class_congestion_ns, rtol=1e-6
    )
    np.testing.assert_allclose(
        des.per_class_congestion_ns, ref.per_class_congestion_ns, rtol=1e-6
    )
    assert float(np.sum(got.per_class_congestion_ns)) == pytest.approx(
        got.congestion_ns, rel=1e-6
    )


def test_qos_off_breakdown_keeps_degenerate_class_axis():
    flat = figure1_topology().flatten()
    ev = synthetic_trace(1500, flat.n_pools, epoch_ns=1e5, seed=1, burstiness=0.6)
    bd = EpochAnalyzer(flat).analyze(ev)
    assert bd.per_class_congestion_ns.shape == (1,)
    assert float(bd.per_class_congestion_ns[0]) == pytest.approx(
        bd.congestion_ns, rel=1e-6
    )


# --------------------------------------------------------------------------- #
# host-segmented attribution (satellite: property test + plain fallback)
# --------------------------------------------------------------------------- #


def _check_host_split(seed: int, n: int, tie_span: int) -> None:
    """Host-segmented per-stage delays must sum (<=1e-5) to the unsegmented
    totals — under tie-HEAVY traces (times drawn with replacement from a
    small span), where per-class order sensitivity is maximal."""
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.integers(0, tie_span, n)).astype(np.float32)
    bits = rng.integers(0, 1 << 3, n).astype(np.int32)
    qos = jnp.asarray(rng.integers(0, C, n), jnp.int32)
    hosts = jnp.asarray(rng.integers(0, 4, n), jnp.int32)
    stts = jnp.asarray([4.0, 2.0, 1.0], jnp.float32)
    disc = jnp.asarray([DISCIPLINE_CODES["wfq"], DISCIPLINE_CODES["priority"],
                        DISCIPLINE_CODES["fifo"]], jnp.int32)
    w = jnp.asarray(np.tile(np.asarray(WEIGHTS), (3, 1)), jnp.float32)
    tf_u, _, psd_u = qos_cascade_dyn(
        jnp.asarray(ts), jnp.asarray(bits), stts, qos, disc, w
    )
    tf_h, _, psd_h = qos_cascade_dyn(
        jnp.asarray(ts), jnp.asarray(bits), stts, qos, disc, w,
        hosts=hosts, n_hosts=4,
    )
    np.testing.assert_allclose(np.asarray(tf_h), np.asarray(tf_u), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(psd_h).sum(axis=1), np.asarray(psd_u).sum(axis=1),
        rtol=1e-5, atol=1e-2,
    )


def test_host_segmented_sums_randomized():
    for seed in range(8):
        _check_host_split(seed, n=500 + 300 * seed, tie_span=64 + 16 * seed)


def test_host_segmented_sums_property():
    pytest.importorskip("hypothesis", reason="optional dev dependency")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(
        seed=st.integers(0, 10_000),
        n=st.integers(2, 1500),
        tie_span=st.integers(2, 200),
    )
    def prop(seed, n, tie_span):
        _check_host_split(seed, n, tie_span)

    prop()


# --------------------------------------------------------------------------- #
# QosSpec + topology threading
# --------------------------------------------------------------------------- #


def test_qos_spec_validation():
    with pytest.raises(ValueError, match="unknown discipline"):
        QosSpec(discipline="strict")
    with pytest.raises(ValueError, match="positive"):
        QosSpec(discipline="wfq", class_weights=(1.0, -2.0))
    with pytest.raises(ValueError, match="unknown switch"):
        QosSpec(switch_disciplines=(("nope", "wfq"),)).apply(
            np.zeros(2, np.int32), np.ones((2, 2)), ["a", "b"]
        )
    assert QosSpec(discipline="wfq", class_weights=(2.0, 1.0)).n_classes() == 2
    assert "wfq" in QosSpec(discipline="wfq").describe()


def test_qos_spec_apply_matches_ecmp_replicas():
    disc = np.zeros(3, np.int32)
    w = np.ones((3, 2))
    QosSpec(
        switch_disciplines=(("sw", "priority"),),
        switch_weights=(("sw", (3.0, 1.0)),),
    ).apply(disc, w, ["sw", "sw@1", "other"])
    assert list(disc) == [DISCIPLINE_CODES["priority"]] * 2 + [0]
    np.testing.assert_allclose(w[:2], [[3.0, 1.0]] * 2)
    np.testing.assert_allclose(w[2], [1.0, 1.0])


def test_topology_derives_qos_classes_and_flags():
    topo = qos_chain()
    flat = topo.flatten()
    assert flat.n_qos_classes == C and flat.has_qos
    codes = np.asarray(flat.discipline_codes())
    assert codes.shape == (flat.n_switches,)  # the RC is a stage too
    assert flat.class_weight_table().shape == (flat.n_switches, C)
    # all-fifo, single-class: qos machinery stays off
    assert not figure1_topology().flatten().has_qos


def test_wfq_weight_length_must_match_classes():
    with pytest.raises(ValueError):
        Topology(
            pools=[Pool("l", 88.9, 76.8, 1 << 30, is_local=True),
                   Pool("p", 180.0, 32.0, 1 << 30, parent="sw")],
            switches=[Switch("sw", 70.0, 64.0, 2.0, discipline="wfq",
                             class_weights=(1.0, 2.0))],
            n_qos_classes=3,
        )


# --------------------------------------------------------------------------- #
# ECMP multipath routing
# --------------------------------------------------------------------------- #


def _multipath_topology(multipath):
    # two remote pools behind one switch: flows vp=1 and vp=2 hash onto
    # different replicas, so multipath=2 genuinely splits the traffic
    return Topology(
        pools=[Pool("l", 88.9, 76.8, 1 << 30, is_local=True),
               Pool("p1", 180.0, 32.0, 1 << 30, parent="sw"),
               Pool("p2", 180.0, 32.0, 1 << 30, parent="sw")],
        switches=[Switch("sw", 70.0, 64.0, 4.0, multipath=multipath)],
    )


def test_multipath_lowers_to_replica_columns():
    flat = _multipath_topology(2).flatten()
    # replica columns first, then the per-host RC pseudo-switch stages
    assert list(flat.switch_names)[:2] == ["sw", "sw@1"]
    # every (host, pool) flow hashes onto exactly one replica
    routed = flat.route[:, :2]
    assert np.all(routed.sum(axis=1) <= 1.0)
    assert routed[:, 0].sum() > 0 and routed[:, 1].sum() > 0


def test_multipath_halves_shared_switch_queueing():
    n = 4000
    t = np.arange(n) * 0.5  # far denser than stt=4.0: heavy queueing
    pool = np.where(np.arange(n) % 2 == 0, 1, 2)
    ev = MemEvents.build(t, pool, np.full(n, 64.0)).with_qos(0)
    c1 = analyze_ref(_multipath_topology(1).flatten(), ev).congestion_ns
    double = _multipath_topology(2).flatten()
    c2 = analyze_ref(double, ev).congestion_ns
    assert c2 < c1  # splitting flows across replicas relieves the queue
    got = EpochAnalyzer(double).analyze(ev)
    assert got.congestion_ns == pytest.approx(c2, rel=1e-5, abs=1e-3)


# --------------------------------------------------------------------------- #
# sweep qos axis
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def qos_suite():
    from repro.core import RegionMap, ScenarioSuite
    from repro.core.tracer import Access, Phase

    rng = np.random.default_rng(0)
    rm = RegionMap()
    for i in range(6):
        r = rm.alloc(f"r{i}", 1 << 20, ("param", "opt_state", "kvcache")[i % 3])
        r.access_count = 10.0
    phases = [
        Phase(f"ph{p}", 1e12, tuple(
            Access(f"r{j}", float(rng.integers(1e5, 6e5)), False)
            for j in rng.choice(6, size=3, replace=False)
        ))
        for p in range(3)
    ]
    suite = ScenarioSuite(
        figure1_topology(), rm, phases,
        region_qos={f"r{i}": i % C for i in range(6)},
    )
    return suite


def test_sweep_qos_axis_one_dispatch_with_dedup(qos_suite):
    from repro.core import ClassMapPolicy, Scenario

    pol = ClassMapPolicy({"opt_state": "cxl_pool2", "kvcache": "cxl_pool1"})
    specs = [
        None,
        QosSpec(discipline="priority"),
        QosSpec(discipline="wfq", class_weights=(8.0, 2.0, 1.0)),
        QosSpec(discipline="wfq", class_weights=(8.0, 2.0, 1.0)),  # duplicate
    ]
    scens = [
        Scenario(policy=pol, name=f"s{i}", qos=q) for i, q in enumerate(specs)
    ]
    d0 = qos_suite.dispatch_count
    res = qos_suite.run(scens)
    assert qos_suite.dispatch_count == d0 + 1  # K scenarios, ONE dispatch
    # duplicated (policy, qos) rows share one cascade plane
    assert qos_suite.last_unique_cascades == 3
    assert res.qos_classes == C
    for row, bd in zip(res.table(), res.breakdowns):
        assert row["qos_classes"] == C
        assert len(row["qos_delay_shares"]) == C
        # attribution conserves the total (tie-invariant even when the
        # synthesized workload has tied timestamps)
        assert float(np.sum(bd.per_class_congestion_ns)) == pytest.approx(
            bd.congestion_ns, rel=1e-5, abs=1e-3
        )
    # the duplicate scenarios are numerically identical
    assert res.breakdowns[2].congestion_ns == res.breakdowns[3].congestion_ns


def test_sweep_qos_fifo_matches_qos_off_totals(qos_suite):
    """A no-op QosSpec under region_qos must reproduce the qos-off totals:
    disciplines/weights are data, FIFO semantics are unchanged."""
    from repro.core import ClassMapPolicy, RegionMap, Scenario, ScenarioSuite

    pol = ClassMapPolicy({"opt_state": "cxl_pool2"})
    on = qos_suite.run([Scenario(policy=pol, name="fifo")]).breakdowns[0]
    off_suite = ScenarioSuite(
        figure1_topology(), qos_suite.regions, qos_suite.phases
    )
    off = off_suite.run([Scenario(policy=pol, name="fifo")]).breakdowns[0]
    # abs covers f32 ulp noise at this trace's time magnitude (~1.5e7 ns):
    # the FIFO path's cummax(t - stt*rank) form can round a start ~1 ulp
    # below its arrival (true congestion here is exactly 0); the QoS path's
    # max(t, horizon) form cannot go negative
    assert on.congestion_ns == pytest.approx(off.congestion_ns, rel=1e-5, abs=4.0)
    assert on.latency_ns == pytest.approx(off.latency_ns, rel=1e-5)
    assert on.bandwidth_ns == pytest.approx(off.bandwidth_ns, rel=1e-4, abs=1.0)


# --------------------------------------------------------------------------- #
# fabric + fleet threading
# --------------------------------------------------------------------------- #


def test_fabric_wfq_weights_shift_tenant_shares():
    from repro.core import FabricSession, InterleavePolicy, RegionMap, Tenant
    from repro.core.tracer import Access, Phase

    def mk_topo(w):
        return Topology(
            pools=[Pool("dram", 100.0, 100.0, 1 << 38, is_local=True),
                   Pool("cxl1", 250.0, 64.0, 1 << 38, parent="sw0"),
                   Pool("cxl2", 300.0, 48.0, 1 << 38, parent="sw0")],
            switches=[Switch("sw0", 70.0, 64.0, 2.0, discipline="wfq",
                             class_weights=w)],
        )

    def mk_tenant(name, seed, qos):
        rng = np.random.default_rng(seed)
        rm = RegionMap()
        for i in range(3):
            rm.alloc(f"{name}/r{i}", 1 << 20, "param")
        phases = [Phase(f"{name}/p{p}", 1e12, tuple(
            Access(f"{name}/r{j}", float(rng.integers(1e5, 8e5)), False)
            for j in range(3)))
            for p in range(2)]
        return Tenant(name=name, phases=phases, regions=rm,
                      policy=InterleavePolicy(["cxl1", "cxl2"]), qos_class=qos)

    reports = {}
    for tag, w in (("protect0", (4.0, 1.0)), ("protect1", (1.0, 8.0))):
        sess = FabricSession(
            mk_topo(w),
            [mk_tenant("lat_crit", 0, 0), mk_tenant("batch", 1, 1)],
            async_analysis=False,
        )
        reports[tag] = sess.run(1)
        sess.close()
    a, b = reports["protect0"], reports["protect1"]
    assert a.summary()["qos_classes"] == 2
    for rep in (a, b):
        assert ns_to_s(float(np.sum(rep.per_class_congestion_ns))) == pytest.approx(
            rep.congestion_s, rel=1e-9, abs=1e-15
        )
    # deprioritizing class 0 raises its share of the queueing delay
    assert b.qos_delay_shares()[0] > a.qos_delay_shares()[0]


def test_fabric_rejects_out_of_range_tenant_class():
    from repro.core import FabricSession, LocalOnlyPolicy, RegionMap, Tenant
    from repro.core.tracer import Phase

    rm = RegionMap()
    rm.alloc("r0", 1 << 20, "param")
    t = Tenant(name="t", phases=[Phase("p", 1e12, ())], regions=rm,
               policy=LocalOnlyPolicy(), qos_class=5)
    with pytest.raises(ValueError, match="qos_class=5"):
        FabricSession(pooled_topology(n_hosts=1), [t], async_analysis=False)


def test_fleet_rack_qos_builds_per_rack_policy_leaves():
    from repro.core.fleet import FleetSim, synthetic_tenant

    rq = [QosSpec(discipline="wfq", class_weights=(8.0, 1.0)),
          QosSpec(discipline="priority", class_weights=(1.0, 1.0))]
    fleet = FleetSim(n_racks=2, hosts_per_rack=2, rack_qos=rq)
    assert fleet.qos_on and fleet.n_qos_classes == 2
    n_stages = fleet._disc_stack.shape[1]  # shared switch + per-host RCs
    assert fleet._disc_stack.shape == (2, n_stages) and n_stages >= 1
    # a blanket QosSpec re-disciplines every stage of its rack
    assert (fleet._disc_stack[0] == DISCIPLINE_CODES["wfq"]).all()
    assert (fleet._disc_stack[1] == DISCIPLINE_CODES["priority"]).all()
    np.testing.assert_allclose(fleet._weights_stack[0, 0], [8.0, 1.0])
    with pytest.raises(ValueError, match="rack_qos"):
        FleetSim(n_racks=3, rack_qos=rq)
    t = dataclasses.replace(synthetic_tenant("t0", seed=0, gib=1.0), qos_class=7)
    with pytest.raises(ValueError, match="qos_class=7"):
        fleet.place([t])


# --------------------------------------------------------------------------- #
# staging cap idle-decay (satellite)
# --------------------------------------------------------------------------- #


def test_stage_packed_caps_decay_after_idle_streak():
    stager = EventStager()
    enter = np.asarray([-1, 0], np.int32)  # pool 0 local, pool 1 -> stage 0

    def stage(n):
        ev = MemEvents.build(
            t_ns=np.arange(1, n + 1, dtype=np.float64),
            pool=np.ones(n, np.int64),
            bytes_=np.full(n, 64.0),
        )
        _, caps = stager.stage_packed([ev], 1, 4096, enter, 1)
        return caps

    burst_caps = stage(2000)
    assert burst_caps[0] >= 2048
    # small steady state: caps stay sticky for CAP_DECAY_CALLS-1 calls...
    for _ in range(EventStager.CAP_DECAY_CALLS - 1):
        assert stage(20) == burst_caps
    # ...then shrink to the streak's peak demand (bucketed), not to zero
    decayed = stage(20)
    assert decayed[0] < burst_caps[0]
    assert decayed[0] >= 32  # still holds the streak's own peak bucket
    # a fresh burst grows the caps right back (hwm semantics keep correctness)
    assert stage(3000)[0] >= 4096


def test_stage_packed_caps_grow_from_the_floor():
    """A stage whose held cap sits at the floor grows when its demand
    outgrows the floor (a routing-driven program can go from no routed
    events in a step to many in the next)."""
    stager = EventStager()
    enter = np.asarray([-1, 0], np.int32)

    def stage(n_local, n_routed):
        n = n_local + n_routed
        ev = MemEvents.build(
            t_ns=np.arange(1, n + 1, dtype=np.float64),
            pool=np.r_[np.zeros(n_local, np.int64), np.ones(n_routed, np.int64)],
            bytes_=np.full(n, 64.0),
        )
        buf, caps = stager.stage_packed([ev], 1, 512, enter, 1)
        return buf, caps

    _, caps = stage(100, 0)
    assert caps == (16,)
    buf, caps = stage(100, 128)
    assert caps == (128,)
    assert int((buf["idx_pack"][0] >= 0).sum()) == 128


def test_stage_packed_oscillating_workload_never_decays():
    stager = EventStager()
    enter = np.asarray([-1, 0], np.int32)

    def stage(n):
        ev = MemEvents.build(
            t_ns=np.arange(1, n + 1, dtype=np.float64),
            pool=np.ones(n, np.int64),
            bytes_=np.full(n, 64.0),
        )
        _, caps = stager.stage_packed([ev], 1, 4096, enter, 1)
        return caps

    big = stage(2000)
    for i in range(3 * EventStager.CAP_DECAY_CALLS):
        # every few calls the workload touches the high caps again: the
        # decay streak resets and the packed width never flaps
        n = 1900 if i % (EventStager.CAP_DECAY_CALLS - 2) == 0 else 30
        assert stage(n) == big
