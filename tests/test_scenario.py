"""Scenario-sweep engine: vectorized-vs-loop policy parity, skeleton/gather
trace split, stacked topology lowering, and batched-vs-sequential oracle
agreement (ISSUE 4)."""

import dataclasses

import numpy as np
import pytest

from repro.core import (
    CACHELINE_BYTES,
    PAGE_BYTES,
    ClassMapPolicy,
    DeviceCacheConfig,
    DeviceCacheModel,
    EpochAnalyzer,
    HotnessTieredPolicy,
    InterleavePolicy,
    LocalOnlyPolicy,
    MemEvents,
    RegionArrays,
    RegionMap,
    Scenario,
    ScenarioSuite,
    Topology,
    TopologyOverride,
    analyze_ref,
    assign_batch,
    concat_events,
    figure1_topology,
    flatten_stack,
    skeleton_to_events,
    synthesize_skeleton,
    synthesize_step_trace,
    two_tier_topology,
)
from repro.core.tracer import Access, Phase, phase_duration_ns, TPU_V5E

FLAT = figure1_topology().flatten()
CLASSES = ["param", "grad", "opt_state", "kvcache", "activation"]


def random_regions(rng, n, max_bytes=1 << 22) -> RegionMap:
    rm = RegionMap()
    for i in range(n):
        r = rm.alloc(
            f"r{i}", int(rng.integers(1, max_bytes)), CLASSES[int(rng.integers(0, 5))]
        )
        r.access_count = float(rng.integers(0, 100))
    return rm


def random_policies(rng, rm):
    total = int(sum(r.nbytes for r in rm))
    return [
        LocalOnlyPolicy(),
        ClassMapPolicy({"opt_state": "cxl_pool2", "kvcache": "cxl_pool1"}),
        ClassMapPolicy({}),
        InterleavePolicy(["cxl_pool2", "cxl_pool3"]),
        InterleavePolicy(
            ["cxl_pool3", "cxl_pool1"],
            weights=[float(rng.integers(1, 5)), float(rng.integers(1, 5))],
            classes=["param", "grad"],
        ),
        HotnessTieredPolicy("cxl_pool1", local_budget_bytes=int(rng.integers(1, total + 1))),
        HotnessTieredPolicy(
            "cxl_pool2",
            hotness={f"r{i}": float(rng.integers(0, 50)) for i in range(0, len(rm), 2)},
            local_budget_bytes=total // 3,
        ),
    ]


# --------------------------------------------------------------------------- #
# policy parity: vectorized assign vs the place() loop oracle
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("seed", range(8))
def test_assign_matches_place_randomized(seed):
    rng = np.random.default_rng(seed)
    rm = random_regions(rng, int(rng.integers(1, 60)))
    ra = RegionArrays.from_regions(rm)
    for pol in random_policies(rng, rm):
        vec = pol.assign(ra, FLAT)
        pol.place(rm, FLAT)
        np.testing.assert_array_equal(
            vec, rm.pool_vector(), err_msg=f"seed={seed} policy={pol.describe()}"
        )


def test_hotness_first_fit_boundary():
    """A region that overflows the budget leaves it untouched, so a later
    colder-but-smaller region still lands local (loop and vector agree)."""
    rm = RegionMap()
    rm.alloc("big", 1000, "param")
    rm.alloc("mid", 800, "param")
    rm.alloc("small", 200, "param")
    hot = {"big": 3000.0, "mid": 1600.0, "small": 200.0}  # density 3.0 / 2.0 / 1.0
    pol = HotnessTieredPolicy("cxl_pool1", hotness=hot, local_budget_bytes=1200)
    vec = pol.assign(RegionArrays.from_regions(rm), FLAT)
    pol.place(rm, FLAT)
    np.testing.assert_array_equal(vec, rm.pool_vector())
    assert rm["big"].pool == 0 and rm["small"].pool == 0  # first-fit skipped mid
    assert rm["mid"].pool == FLAT.pool_names.index("cxl_pool1")


@pytest.mark.parametrize("budget_off", [-1, 0, 1])
def test_hotness_exact_budget_boundary(budget_off):
    rng = np.random.default_rng(3)
    rm = random_regions(rng, 20, max_bytes=1 << 12)
    ra = RegionArrays.from_regions(rm)
    # budget exactly at / just around a prefix sum of the density order
    dens_order = np.argsort(
        -(ra.access_count / np.maximum(ra.nbytes, 1)), kind="stable"
    )
    budget = int(ra.nbytes[dens_order[:7]].sum()) + budget_off
    pol = HotnessTieredPolicy("cxl_pool2", local_budget_bytes=budget)
    vec = pol.assign(ra, FLAT)
    pol.place(rm, FLAT)
    np.testing.assert_array_equal(vec, rm.pool_vector())


def test_interleave_ties_follow_declared_pool_order():
    """Equal weights + equal sizes round-robin exactly in declaration order,
    regardless of which pool indices the names map to."""
    rm = RegionMap()
    for i in range(6):
        rm.alloc(f"r{i}", 1 << 20, "param")
    # declared order deliberately NOT pool-index order
    pol = InterleavePolicy(["cxl_pool3", "cxl_pool1", "cxl_pool2"])
    pol.place(rm, FLAT)
    i3 = FLAT.pool_names.index("cxl_pool3")
    i1 = FLAT.pool_names.index("cxl_pool1")
    i2 = FLAT.pool_names.index("cxl_pool2")
    assert rm.pool_vector().tolist() == [i3, i1, i2, i3, i1, i2]
    vec = pol.assign(RegionArrays.from_regions(rm), FLAT)
    np.testing.assert_array_equal(vec, rm.pool_vector())


def test_assign_batch_dedups_repeated_policies():
    rng = np.random.default_rng(0)
    rm = random_regions(rng, 12)
    ra = RegionArrays.from_regions(rm)
    pol = ClassMapPolicy({"opt_state": "cxl_pool2"})
    mat = assign_batch([pol, LocalOnlyPolicy(), pol], ra, FLAT)
    assert mat.shape == (3, len(rm))
    np.testing.assert_array_equal(mat[0], mat[2])
    assert (mat[1] == 0).all()


def test_assign_batch_dedups_granularity_copies():
    """with_granularity copies change the trace granule, never placement,
    so the sequential interleave recurrence must run once, not per copy."""
    rng = np.random.default_rng(1)
    rm = random_regions(rng, 12)
    ra = RegionArrays.from_regions(rm)
    pol = InterleavePolicy(["cxl_pool2", "cxl_pool3"], weights=[1, 2])
    page = pol.with_granularity(PAGE_BYTES)
    calls = []
    orig = InterleavePolicy.assign
    try:
        InterleavePolicy.assign = lambda self, ra, flat: (
            calls.append(1), orig(self, ra, flat))[1]
        mat = assign_batch([pol, page], ra, FLAT)
    finally:
        InterleavePolicy.assign = orig
    np.testing.assert_array_equal(mat[0], mat[1])
    assert len(calls) == 1


# --------------------------------------------------------------------------- #
# tracer skeleton/gather split
# --------------------------------------------------------------------------- #


def legacy_synthesize(phases, regions, granularity_bytes=64.0,
                      max_events_per_access=64, calibration=1.0, epoch_mode="step"):
    """The pre-split per-access loop (the skeleton's executable spec)."""
    per_phase, durs, cur = [], [], 0.0
    for ph in phases:
        dur = phase_duration_ns(ph, TPU_V5E)
        parts = []
        for a in ph.accesses:
            r = regions[a.region]
            b = a.bytes_ * calibration
            n_ev = int(min(max(np.ceil(b / granularity_bytes), 1), max_events_per_access))
            offs = (np.arange(n_ev, dtype=np.float64) + 0.5) / n_ev * dur
            base = 0.0 if epoch_mode == "layer" else cur
            parts.append(MemEvents(  # simlint: ignore[event-columns] -- built from scenario spec fields, not an event trace; exact weight / host-0 is the reference semantics
                t_ns=base + offs,
                pool=np.full((n_ev,), r.pool, np.int32),
                bytes_=np.full((n_ev,), b / n_ev, np.float64),
                is_write=np.full((n_ev,), a.is_write, bool),
                region=np.full((n_ev,), r.rid, np.int32),
            ))
        per_phase.append(concat_events(parts))
        durs.append(dur)
        cur += dur
    if epoch_mode == "layer":
        return per_phase, durs, [p.name for p in phases]
    return [concat_events(per_phase)], [float(sum(durs))], ["step"]


def _workload(seed=0, n_regions=10, n_phases=4):
    rng = np.random.default_rng(seed)
    rm = random_regions(rng, n_regions)
    phases = [
        Phase(
            f"ph{p}",
            float(rng.integers(1e10, 8e10)),
            tuple(
                Access(f"r{int(j)}", float(rng.integers(1e5, 3e6)), bool(rng.random() < 0.4))
                for j in rng.choice(n_regions, size=4, replace=False)
            ),
        )
        for p in range(n_phases)
    ]
    return rm, phases


@pytest.mark.parametrize("mode", ["step", "layer"])
@pytest.mark.parametrize("gran", [64.0, 4096.0])
def test_skeleton_matches_legacy_loop(mode, gran):
    rm, phases = _workload()
    rm["r3"].pool = 2
    rm["r5"].pool = 1
    got_tr, got_n, got_names = synthesize_step_trace(
        phases, rm, granularity_bytes=gran, epoch_mode=mode
    )
    ref_tr, ref_n, ref_names = legacy_synthesize(
        phases, rm, granularity_bytes=gran, epoch_mode=mode
    )
    assert got_names == ref_names and np.allclose(got_n, ref_n)
    assert len(got_tr) == len(ref_tr)
    for a, b in zip(got_tr, ref_tr):
        np.testing.assert_array_equal(a.t_ns, b.t_ns)
        np.testing.assert_array_equal(a.pool, b.pool)
        np.testing.assert_array_equal(a.bytes_, b.bytes_)
        np.testing.assert_array_equal(a.is_write, b.is_write)
        np.testing.assert_array_equal(a.region, b.region)


def test_skeleton_gather_is_placement_independent():
    rm, phases = _workload(seed=1)
    skel = synthesize_skeleton(phases, rm, granularity_bytes=256.0)
    a = skeleton_to_events(skel, np.zeros((len(rm),), np.int32))[0]
    pv = np.arange(len(rm), dtype=np.int32) % FLAT.n_pools
    b = skeleton_to_events(skel, pv)[0]
    np.testing.assert_array_equal(a.t_ns, b.t_ns)  # structure shared
    np.testing.assert_array_equal(b.pool, pv[skel.region])  # only pools move


def test_skeleton_unknown_region_raises():
    rm = RegionMap()
    rm.alloc("w", 100, "param")
    with pytest.raises(KeyError):
        synthesize_skeleton([Phase("p", 1e9, (Access("nope", 10.0),))], rm)


# --------------------------------------------------------------------------- #
# stacked topology lowering
# --------------------------------------------------------------------------- #


def test_flatten_stack_base_row_matches_flatten():
    t = figure1_topology()
    st = flatten_stack(t, [None, None])
    flat = t.flatten()
    np.testing.assert_allclose(st.pool_latency_ns[0], flat.pool_latency_ns)
    np.testing.assert_allclose(st.pool_bandwidth_gbps[1], flat.pool_bandwidth_gbps)
    np.testing.assert_allclose(st.switch_stt_ns[0], flat.switch_stt_ns)
    np.testing.assert_allclose(st.switch_bandwidth_gbps[0], flat.switch_bandwidth_gbps)
    np.testing.assert_allclose(st.local_latency_ns, flat.local_latency_ns)


def test_flatten_stack_member_matches_rebuilt_tree():
    t = figure1_topology()
    ov = TopologyOverride(
        pools={"cxl_pool1": {"latency_ns": 310.0, "bandwidth_gbps": 12.0}},
        switches={"switch1": {"stt_ns": 9.0, "bandwidth_gbps": 10.0, "latency_ns": 95.0}},
        rc_latency_ns=25.0,
        local_dram_latency_ns=70.0,
    )
    st = flatten_stack(t, [None, ov])
    pools = [
        dataclasses.replace(p, latency_ns=310.0, bandwidth_gbps=12.0)
        if p.name == "cxl_pool1" else p
        for p in t.pools
    ]
    sws = [
        dataclasses.replace(s, stt_ns=9.0, bandwidth_gbps=10.0, latency_ns=95.0)
        if s.name == "switch1" else s
        for s in t.switches
    ]
    ref = Topology(
        pools, sws, rc_latency_ns=25.0, rc_bandwidth_gbps=t.rc_bandwidth_gbps,
        rc_stt_ns=t.rc_stt_ns, local_dram_latency_ns=70.0,
    ).flatten()
    m = st.member(1)
    np.testing.assert_allclose(m.pool_latency_ns, ref.pool_latency_ns)
    np.testing.assert_allclose(m.pool_bandwidth_gbps, ref.pool_bandwidth_gbps)
    np.testing.assert_allclose(m.switch_stt_ns, ref.switch_stt_ns)
    np.testing.assert_allclose(m.switch_bandwidth_gbps, ref.switch_bandwidth_gbps)
    assert m.local_latency_ns == 70.0
    np.testing.assert_array_equal(m.route, ref.route)  # structure untouched


def test_flatten_stack_rejects_structural_overrides():
    t = two_tier_topology()
    with pytest.raises(ValueError):
        flatten_stack(t, [TopologyOverride(pools={"nope": {"latency_ns": 1.0}})])
    with pytest.raises(ValueError):
        flatten_stack(t, [TopologyOverride(pools={"cxl_pool": {"capacity_bytes": 1}})])


# --------------------------------------------------------------------------- #
# scenario batch vs sequential analyze_ref
# --------------------------------------------------------------------------- #


def _suite_and_grid(epoch_mode="step"):
    rm, phases = _workload(seed=2, n_regions=14, n_phases=5)
    topo = figure1_topology()
    suite = ScenarioSuite(topo, rm, phases, epoch_mode=epoch_mode)
    total = int(sum(r.nbytes for r in rm))
    policies = {
        "local": LocalOnlyPolicy(),
        "off": ClassMapPolicy({"opt_state": "cxl_pool2", "kvcache": "cxl_pool1"}),
        "il": InterleavePolicy(["cxl_pool2", "cxl_pool3"], weights=[1, 3]),
        "hot": HotnessTieredPolicy("cxl_pool1", local_budget_bytes=total // 2),
    }
    overrides = {
        "base": None,
        "slow": TopologyOverride(
            pools={"cxl_pool2": {"latency_ns": 420.0}},
            switches={"switch1": {"stt_ns": 30.0}},
        ),
        "thin": TopologyOverride(
            switches={"switch0": {"bandwidth_gbps": 1.0}, "switch1": {"bandwidth_gbps": 0.5}}
        ),
    }
    caches = {
        "nc": None,
        "c": DeviceCacheConfig(capacity_bytes=4 << 20, line_bytes=4096, n_sets=64),
    }
    scens = ScenarioSuite.cartesian(
        policies, overrides, caches, granularities=[CACHELINE_BYTES, PAGE_BYTES]
    )
    return rm, phases, suite, scens


@pytest.mark.parametrize("epoch_mode", ["step", "layer"])
def test_sweep_matches_sequential_analyze_ref(epoch_mode):
    rm, phases, suite, scens = _suite_and_grid(epoch_mode)
    res = suite.run(scens)
    assert suite.dispatch_count == 1  # the whole grid: ONE stacked dispatch
    stack = flatten_stack(suite.topology, [s.topology for s in scens])
    for k, s in enumerate(scens):
        flat_k = stack.member(k)
        s.policy.place(rm, suite.base_flat)
        traces, _, _ = synthesize_step_trace(
            phases, rm, granularity_bytes=s.policy.granularity_bytes,
            epoch_mode=epoch_mode,
        )
        model = (
            DeviceCacheModel(s.cache, flat_k, [rm]) if s.cache is not None else None
        )
        ref = None
        for tr in traces:
            span = max(float(tr.t_ns.max()) + 1.0 if tr.n else 0.0, suite.bw_window_ns)
            bww = max(span / suite.n_windows, 1.0)
            scale = model.observe_scale(tr) if model is not None else None
            bd = analyze_ref(
                flat_k, tr, bw_window_ns=bww, lat_scale=scale,
                n_windows=suite.n_windows,
            )
            ref = bd if ref is None else ref + bd
        got = res.breakdowns[k]
        for f in ("latency_ns", "congestion_ns", "bandwidth_ns"):
            a, b = getattr(got, f), getattr(ref, f)
            assert abs(a - b) / max(abs(b), 1.0) <= 1e-4, (
                f"{s.label()} {f}: {a} vs {b}"
            )
        np.testing.assert_allclose(
            got.per_pool_latency_ns, ref.per_pool_latency_ns, rtol=1e-4, atol=1.0
        )


def test_chain_full_plane_and_sweep_price_latency_alike():
    """One chain-eligible workload that all three graphs take — the chain
    dispatch, the full-plane word-plane dispatch and the scenario sweep —
    gives the same latency leaves: the pricing lives in one helper."""
    rm, phases = _workload(seed=5)
    suite = ScenarioSuite(figure1_topology(), rm, phases, epoch_mode="layer")
    pol = ClassMapPolicy({"opt_state": "cxl_pool2", "kvcache": "cxl_pool1"})
    swept = suite.run([Scenario(policy=pol, name="off")]).breakdowns[0]
    pol.place(rm, suite.base_flat)
    traces, _, _ = synthesize_step_trace(
        phases, rm, granularity_bytes=pol.granularity_bytes, epoch_mode="layer"
    )
    chain = EpochAnalyzer(suite.base_flat, n_windows=suite.n_windows, pipeline=True)
    plane = EpochAnalyzer(suite.base_flat, n_windows=suite.n_windows)
    assert chain._chain_plan is not None and plane._chain_plan is None
    assert swept.latency_ns > 0
    for bd in (chain.analyze_batch(traces), plane.analyze_batch(traces)):
        for leaf in ("latency_ns", "per_pool_latency_ns", "per_host_latency_ns"):
            np.testing.assert_allclose(
                getattr(bd, leaf), getattr(swept, leaf), rtol=1e-6, err_msg=leaf
            )


def test_sweep_reuses_compile_cache_across_runs():
    _, _, suite, scens = _suite_and_grid()
    suite.run(scens)
    # the compile cache is process-global for the sweep kernel, so only
    # the *delta* is meaningful: re-running (even reordered) must not
    # trace or compile anything new — no per-scenario recompiles
    before = suite.compile_cache_size()
    suite.run(list(reversed(scens)))
    assert suite.dispatch_count == 2
    assert suite.compile_cache_size() == before


def test_sweep_dedups_cascades():
    """Latency/bandwidth/cache variants share placement+STT => one cascade."""
    rm, phases = _workload(seed=4)
    suite = ScenarioSuite(figure1_topology(), rm, phases)
    pol = ClassMapPolicy({"opt_state": "cxl_pool2"})
    scens = [
        Scenario(policy=pol, topology=TopologyOverride(
            pools={"cxl_pool2": {"latency_ns": float(l)}}))
        for l in (150.0, 250.0, 350.0, 450.0)
    ]
    suite.run(scens)
    assert suite.last_unique_cascades == 1
    # distinct stt rows break the dedup (worst case U == K, still correct)
    scens2 = [
        Scenario(policy=pol, topology=TopologyOverride(
            switches={"switch1": {"stt_ns": float(s)}}))
        for s in (2.0, 4.0, 8.0)
    ]
    res = suite.run(scens2)
    assert suite.last_unique_cascades == 3
    cong = [b.congestion_ns for b in res.breakdowns]
    assert cong[0] <= cong[1] <= cong[2]


def test_sweep_zero_bandwidth_is_unconstrained_not_nan():
    """bw=0 means an unconstrained component in analyze_ref; the stacked
    path must match (0/0 windows previously produced NaN totals that
    poisoned SweepResult.best())."""
    rm, phases = _workload(seed=6)
    suite = ScenarioSuite(figure1_topology(), rm, phases)
    pol = ClassMapPolicy({"opt_state": "cxl_pool2"})
    scens = [
        Scenario(policy=pol, name="base"),
        Scenario(policy=pol, name="bw0", topology=TopologyOverride(
            switches={"switch1": {"bandwidth_gbps": 0.0}})),
    ]
    res = suite.run(scens)
    totals = res.totals_ns()
    assert np.isfinite(totals).all()
    stack = flatten_stack(suite.topology, [s.topology for s in scens])
    pol.place(rm, suite.base_flat)
    traces, _, _ = synthesize_step_trace(phases, rm)
    span = max(float(traces[0].t_ns.max()) + 1.0, suite.bw_window_ns)
    ref = analyze_ref(
        stack.member(1), traces[0],
        bw_window_ns=max(span / suite.n_windows, 1.0), n_windows=suite.n_windows,
    )
    assert res.breakdowns[1].bandwidth_ns == pytest.approx(
        ref.bandwidth_ns, rel=1e-4, abs=1e-3
    )
    assert res.best() is not None  # frontier stays usable


def test_sweep_capacity_frontier():
    rm = RegionMap()
    rm.alloc("huge", int(FLAT.pool_capacity[1]) + 1, "opt_state")
    rm.alloc("w", 1 << 20, "param")
    phases = [Phase("p", 1e10, (Access("huge", 1e6), Access("w", 1e5)))]
    suite = ScenarioSuite(figure1_topology(), rm, phases)
    over = Scenario(policy=ClassMapPolicy({"opt_state": "cxl_pool1"}), name="over")
    ok = Scenario(policy=ClassMapPolicy({"opt_state": "cxl_pool2"}), name="ok")
    res = suite.run([over, ok])
    assert not res.feasible[0] and res.feasible[1]
    assert res.best() == 1  # infeasible scenario excluded from the frontier
    assert res.best(require_feasible=False) == 0  # ...unless asked not to
    assert res.best(max_slowdown=1.0 + 1e-12) is None
    with pytest.raises(ValueError):
        suite.run([over], on_overflow="raise")


def test_successive_halving_improves():
    rm, phases = _workload(seed=5)
    topo = two_tier_topology()
    suite = ScenarioSuite(topo, rm, phases)
    pol = ClassMapPolicy({"opt_state": "cxl_pool"})

    def mk(bw):
        return Scenario(
            policy=pol,
            topology=TopologyOverride(
                switches={"sw": {"bandwidth_gbps": float(bw)}},
                pools={"cxl_pool": {"bandwidth_gbps": float(bw)}},
            ),
            name=f"bw{bw:.4g}",
        )

    def refine(s, rnd):
        bw = float(s.topology.switches["sw"]["bandwidth_gbps"])
        return [mk(bw * 1.3), mk(bw / 1.3)]

    seeds = [mk(b) for b in (4.0, 16.0, 64.0)]
    res0 = suite.run(seeds)
    res, best = suite.successive_halving(seeds, refine, rounds=2)
    assert res.totals_ns()[best] <= res0.totals_ns().min() + 1e-6
    assert suite.dispatch_count == 4  # seed eval + 1 per round + initial run


# --------------------------------------------------------------------------- #
# satellites
# --------------------------------------------------------------------------- #


def test_hillclimb_module_docstring_survives():
    import repro.launch.hillclimb as hc

    assert hc.__doc__ and "hillclimb" in hc.__doc__


def test_with_granularity_copies():
    pol = ClassMapPolicy({"opt_state": "cxl_pool2"})
    page = pol.with_granularity(PAGE_BYTES)
    assert page.granularity_bytes == PAGE_BYTES
    assert pol.granularity_bytes == CACHELINE_BYTES
    assert page.class_to_pool == pol.class_to_pool
