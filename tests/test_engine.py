"""Shared async analysis engine: cross-session coalescing, lifecycle,
dropped-batch accounting, report-race regression, and the attach/fabric
rewiring on top of it (ISSUE 5)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Access,
    AnalysisEngine,
    CXLMemSim,
    ClassMapPolicy,
    DelayBreakdown,
    DeviceCacheConfig,
    EpochAnalyzer,
    FabricReport,
    FabricSession,
    HostClock,
    MigrationConfig,
    MigrationSimulator,
    Phase,
    RegionMap,
    SimReport,
    Tenant,
    pooled_topology,
    synthetic_trace,
    two_tier_topology,
)
from repro.core.engine import dispatch_key


# --------------------------------------------------------------------------- #
# helpers
# --------------------------------------------------------------------------- #


class _SlowAnalyzer:
    """Non-coalescible stub that parks the dispatcher so later submissions
    from other handles pile up and coalesce."""

    def __init__(self, flat, sleep_s=0.25):
        self.flat = flat
        self.sleep_s = sleep_s

    def simulate(self, tr, lat_scale=None):
        time.sleep(self.sleep_s)
        return DelayBreakdown.zero(
            self.flat.n_pools, self.flat.n_switches, self.flat.n_hosts
        )


class _FlakyAnalyzer(EpochAnalyzer):
    """Raises on one specific analyze_batch call (per-batch failure stub)."""

    def __init__(self, *args, fail_on=2, **kwargs):
        super().__init__(*args, **kwargs)
        self.calls = 0
        self.fail_on = fail_on

    def analyze_batch(self, traces, lat_scales=None, stager=None):
        self.calls += 1
        if self.calls == self.fail_on:
            raise RuntimeError("injected analyzer failure")
        return super().analyze_batch(traces, lat_scales, stager=stager)


def _toy_attach(engine=None, async_mode=True, **sim_kw):
    regions = RegionMap()
    regions.alloc("w", 1 << 22, "param")
    regions.alloc("opt", 1 << 23, "opt_state")
    phases = [
        Phase("fwd", flops=1e8, accesses=(Access("w", 1 << 22),)),
        Phase("opt", flops=1e7, accesses=(Access("opt", 1 << 23, True),)),
    ]
    step = jax.jit(lambda x: (x * x).sum())
    sim = CXLMemSim(
        two_tier_topology(),
        ClassMapPolicy({"opt_state": "cxl_pool"}),
        async_analysis=async_mode,
        engine=engine,
        **sim_kw,
    )
    return sim.attach(step, phases, regions)


def _tenants(n=2, mults=None, step=False):
    out = []
    for i in range(n):
        mult = 1 if mults is None else mults[i]
        rm = RegionMap()
        rm.alloc("w", 1 << 22, "param")
        rm.alloc("kv", 1 << 22, "kvcache")
        phases = [
            Phase(
                "fwd",
                flops=5e8,
                accesses=(
                    Access("w", mult * (1 << 22)),
                    Access("kv", mult * (1 << 22), True),
                ),
            )
        ]
        step_fn = jax.jit(lambda x: (x @ x.T).sum()) if step else None
        args = (jnp.ones((32, 32)),) if step else ()
        out.append(
            Tenant(
                f"t{i}", phases, rm, ClassMapPolicy({"kvcache": "shared_pool"}),
                step_fn=step_fn, step_args=args,
            )
        )
    return out


# --------------------------------------------------------------------------- #
# engine core: futures, coalescing, lifecycle
# --------------------------------------------------------------------------- #


def test_engine_solo_submit_matches_sync_bitwise():
    """A solo submission runs the exact analyze_batch path: identical bits."""
    flat = pooled_topology(n_hosts=1).flatten()
    an = EpochAnalyzer(flat)
    traces = [synthetic_trace(700, flat.n_pools, seed=3, burstiness=0.6)]
    ref = an.analyze_batch(traces)
    with AnalysisEngine() as eng:
        h = eng.register(an)
        got = h.submit(traces).result(timeout=60)
        h.flush()
    assert got.latency_ns == ref.latency_ns
    assert got.congestion_ns == ref.congestion_ns
    assert got.bandwidth_ns == ref.bandwidth_ns
    np.testing.assert_array_equal(got.per_pool_latency_ns, ref.per_pool_latency_ns)


def test_dispatch_key_groups_equal_topologies_only():
    flat = pooled_topology(n_hosts=1).flatten()
    a, b = EpochAnalyzer(flat), EpochAnalyzer(pooled_topology(n_hosts=1).flatten())
    assert dispatch_key(a) == dispatch_key(b)
    c = EpochAnalyzer(pooled_topology(n_hosts=1, cxl_bandwidth_gbps=1.0).flatten())
    assert dispatch_key(a) != dispatch_key(c)
    d = EpochAnalyzer(flat, n_windows=64)
    assert dispatch_key(a) != dispatch_key(d)
    # Pallas impls never coalesce (epoch loop unvalidated under session vmap)
    e = EpochAnalyzer(flat, impl="pallas_interpret")
    assert dispatch_key(e) is None


def test_engine_coalesces_cross_session_not_same_session():
    """While the dispatcher is parked, submissions from K distinct handles
    coalesce into ONE stacked dispatch; two batches of the same handle never
    share a dispatch (bit-stability of the solo path)."""
    flat = pooled_topology(n_hosts=1).flatten()
    analyzers = [EpochAnalyzer(flat) for _ in range(4)]
    traces = [
        [synthetic_trace(300 + 41 * i, flat.n_pools, seed=i, burstiness=0.5)]
        for i in range(4)
    ]
    solo = [a.analyze_batch(tr) for a, tr in zip(analyzers, traces)]
    with AnalysisEngine() as eng:
        park = eng.register(_SlowAnalyzer(flat))
        handles = [eng.register(a) for a in analyzers]
        park.submit([synthetic_trace(8, flat.n_pools)])
        futs = [h.submit(tr) for h, tr in zip(handles, traces)]
        # a second batch on handle 0 must NOT join the same stacked dispatch
        futs.append(handles[0].submit(traces[0]))
        results = [f.result(timeout=60) for f in futs]
        for h in handles:
            h.flush()
        stats = eng.stats()
    assert stats["coalesced_dispatches"] >= 1
    assert stats["max_coalesced_sessions"] == 4
    for ref, got in zip(solo + [solo[0]], results):
        assert got.latency_ns == pytest.approx(ref.latency_ns, rel=1e-6)
        assert got.congestion_ns == pytest.approx(ref.congestion_ns, rel=1e-5, abs=1e-3)
        assert got.bandwidth_ns == pytest.approx(ref.bandwidth_ns, rel=1e-5, abs=1e-3)


def test_analyze_batch_multi_matches_solo():
    """The stacked [K, B, N] entry point returns each session's own totals,
    matching per-session analyze_batch, including host decomposition,
    device-cache scales, ragged batch sizes, and empty groups."""
    flat = pooled_topology(n_hosts=2).flatten()
    an = EpochAnalyzer(flat)
    g0 = [
        synthetic_trace(500, flat.n_pools, seed=0, burstiness=0.7).with_host(0),
        synthetic_trace(200, flat.n_pools, seed=1).with_host(1),
    ]
    g1 = [synthetic_trace(333, flat.n_pools, seed=2).with_host(1)]
    scale = np.full((flat.n_hosts * flat.n_pools,), 0.5)
    groups = [g0, [], g1]
    scales = [[None, scale], None, [scale]]
    multi = an.analyze_batch_multi(groups, scales)
    assert len(multi) == 3
    assert multi[1].total_ns == 0.0
    for got, (tr, sc) in zip(
        (multi[0], multi[2]), ((g0, scales[0]), (g1, scales[2]))
    ):
        ref = an.analyze_batch(tr, sc)
        assert got.latency_ns == pytest.approx(ref.latency_ns, rel=1e-6)
        assert got.congestion_ns == pytest.approx(ref.congestion_ns, rel=1e-5, abs=1e-3)
        assert got.bandwidth_ns == pytest.approx(ref.bandwidth_ns, rel=1e-5, abs=1e-3)
        np.testing.assert_allclose(
            got.per_host_latency_ns, ref.per_host_latency_ns, rtol=1e-5
        )


def test_analyze_batch_multi_rejects_pallas():
    flat = pooled_topology(n_hosts=1).flatten()
    an = EpochAnalyzer(flat, impl="pallas_interpret")
    with pytest.raises(ValueError, match="inline"):
        an.analyze_batch_multi([[synthetic_trace(16, flat.n_pools)]])


def test_invalid_batch_does_not_poison_coalesced_peers():
    """A session submitting an unreachable-route trace into a coalesced
    group drops ONLY its own batch; peers' results and error state are
    untouched."""
    flat = pooled_topology(n_hosts=1).flatten()
    good_an, bad_an = EpochAnalyzer(flat), EpochAnalyzer(flat)
    good_tr = [synthetic_trace(200, flat.n_pools, seed=0)]
    bad_tr = [synthetic_trace(200, flat.n_pools, seed=1).with_host(3)]  # no such host
    ref = good_an.analyze_batch(good_tr)
    with AnalysisEngine() as eng:
        park = eng.register(_SlowAnalyzer(flat))
        good, bad = eng.register(good_an), eng.register(bad_an)
        park.submit([synthetic_trace(8, flat.n_pools)])
        fut_bad = bad.submit(bad_tr)
        fut_good = good.submit(good_tr)
        got = fut_good.result(timeout=60)
        with pytest.raises(ValueError, match="host id 3"):
            fut_bad.result(timeout=60)
        good.flush()  # innocent peer: no error, nothing dropped
        assert good.dropped_batches == 0
        with pytest.raises(ValueError, match="host id 3"):
            bad.flush()
        assert bad.dropped_batches == 1 and bad.dropped_epochs == 1
    assert got.latency_ns == pytest.approx(ref.latency_ns, rel=1e-6)


def test_cancelled_future_does_not_kill_dispatcher():
    """A caller cancelling a pending submission future must not crash the
    dispatcher or corrupt drop accounting — the future is a notification,
    not the work."""
    flat = pooled_topology(n_hosts=1).flatten()
    with AnalysisEngine() as eng:
        park = eng.register(_SlowAnalyzer(flat, sleep_s=0.2))
        h = eng.register(EpochAnalyzer(flat))
        park.submit([synthetic_trace(8, flat.n_pools)])
        fut = h.submit([synthetic_trace(64, flat.n_pools)])
        assert fut.cancel()  # still queued behind the parked batch
        h.flush()  # batch was analyzed + folded regardless; no error
        assert h.dropped_batches == 0
        # the dispatcher survives: later submissions still complete
        bd = h.submit([synthetic_trace(64, flat.n_pools)]).result(timeout=60)
        assert bd.total_ns >= 0
        assert not eng._broken


def test_default_engine_replaced_after_break():
    eng = AnalysisEngine.default()
    assert AnalysisEngine.default() is eng  # stable while healthy
    try:
        eng._broken = True
        fresh = AnalysisEngine.default()
        assert fresh is not eng
        assert AnalysisEngine.default() is fresh
    finally:
        eng._broken = False  # other tests' handles may still point here


def test_engine_lifecycle_and_backpressure():
    flat = pooled_topology(n_hosts=1).flatten()
    eng = AnalysisEngine()
    h = eng.register(EpochAnalyzer(flat), max_inflight=2)
    for _ in range(5):  # more batches than inflight: submit must backpressure
        h.submit([synthetic_trace(64, flat.n_pools)])
    h.flush()
    h.close()
    with pytest.raises(RuntimeError, match="closed"):
        h.submit([synthetic_trace(8, flat.n_pools)])
    with pytest.raises(ValueError, match="max_inflight"):
        eng.register(EpochAnalyzer(flat), max_inflight=0)
    eng.close()
    eng.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        eng.register(EpochAnalyzer(flat))


# --------------------------------------------------------------------------- #
# satellite: report race (attach) — writes under the report lock
# --------------------------------------------------------------------------- #


def test_report_race_step_vs_report_two_threads():
    """Hammer step() and report reads concurrently with migration + cache
    active: every running-statistic write happens under the report lock,
    so totals stay consistent and nothing raises."""
    regions = RegionMap()
    regions.alloc("w", 1 << 22, "param")
    regions.alloc("kv", 1 << 22, "kvcache")
    phases = [
        Phase(
            "fwd",
            flops=1e8,
            accesses=(Access("w", 1 << 22), Access("kv", 1 << 22, True)),
        )
    ]
    topo = two_tier_topology()
    mig = MigrationSimulator(
        MigrationConfig(mode="software", promote_threshold=1, local_budget_bytes=1 << 30),
        regions,
        topo.flatten(),
    )
    sim = CXLMemSim(
        topo,
        ClassMapPolicy({"kvcache": "cxl_pool"}),
        migration=mig,
        cache=DeviceCacheConfig(capacity_bytes=1 << 26),
        check_capacity=False,
    )
    step = jax.jit(lambda x: (x * x).sum())
    x = jnp.ones((64, 64))
    errors = []
    with sim.attach(step, phases, regions) as prog:

        def reader():
            try:
                for _ in range(40):
                    _ = prog.report.migration_moved_bytes
                    _ = prog._report.cache_hit_fraction
            except BaseException as e:  # pragma: no cover - failure path
                errors.append(e)

        t = threading.Thread(target=reader)
        t.start()
        for _ in range(25):
            prog.step(x)
        t.join()
        rep = prog.report
        assert not errors
        assert rep.steps == 25 and rep.epochs == 25
        assert rep.migration_moved_bytes > 0
        assert np.isfinite(rep.cache_hit_fraction)


# --------------------------------------------------------------------------- #
# satellite: lifecycle — no thread growth across attach/close cycles
# --------------------------------------------------------------------------- #


def test_no_thread_growth_across_attach_close_cycles():
    x = jnp.ones((8, 8))
    # warm-up creates the process-default engine's single dispatcher thread
    with _toy_attach() as prog:
        prog.run(1, x)
    base = threading.active_count()
    for _ in range(50):
        with _toy_attach() as prog:
            prog.run(1, x)
    assert threading.active_count() <= base


def test_no_thread_growth_across_fabric_sessions():
    topo = pooled_topology(n_hosts=2)
    with FabricSession(topo, _tenants(2)) as sess:
        sess.run(1)
    base = threading.active_count()
    for _ in range(10):
        with FabricSession(pooled_topology(n_hosts=2), _tenants(2)) as sess:
            sess.run(1)
    assert threading.active_count() <= base


def test_private_engine_thread_joined_on_close():
    base = threading.active_count()
    with AnalysisEngine() as eng:
        prog = _toy_attach(engine=eng)
        prog.run(2, jnp.ones((8, 8)))
        prog.close()
    assert threading.active_count() <= base


# --------------------------------------------------------------------------- #
# satellite: dropped-batch accounting
# --------------------------------------------------------------------------- #


def test_dropped_batches_recorded_and_error_raised_once():
    """Batch 2 of 5 fails: flush raises once, the report records exactly
    the failed batch's epochs as dropped, and the other 4 batches' totals
    are present."""
    prog = _toy_attach()
    flaky = _FlakyAnalyzer(prog.sim.flat, fail_on=2)
    prog._analyzer = prog._handle.analyzer = flaky
    x = jnp.ones((8, 8))
    for _ in range(5):
        prog.step(x)
    with pytest.raises(RuntimeError, match="injected analyzer failure"):
        prog.flush()
    rep = prog.report  # second flush: error already surfaced, no re-raise
    assert rep.steps == 5
    assert rep.dropped_batches == 1
    assert rep.epochs + rep.dropped_epochs == 5  # one epoch per step here
    assert rep.dropped_epochs == 1
    assert rep.latency_s > 0  # surviving batches were folded
    prog.close()


def test_dropped_batches_sync_path():
    prog = _toy_attach(async_mode=False)
    prog._analyzer = _FlakyAnalyzer(prog.sim.flat, fail_on=1)
    with pytest.raises(RuntimeError, match="injected analyzer failure"):
        prog.step(jnp.ones((8, 8)))
    assert prog._report.dropped_batches == 1
    assert prog._report.dropped_epochs == 1


def test_fabric_dropped_round_recorded():
    sess = FabricSession(pooled_topology(n_hosts=2), _tenants(2))
    flaky = _FlakyAnalyzer(sess.flat, fail_on=2)
    sess._analyzer = sess._handle.analyzer = flaky
    for _ in range(4):
        sess.round()
    with pytest.raises(RuntimeError, match="injected analyzer failure"):
        sess.flush()
    rep = sess.report
    assert rep.rounds == 3 and rep.dropped_batches == 1
    assert rep.dropped_epochs == 1
    sess.close()


# --------------------------------------------------------------------------- #
# satellite: summary key sets locked
# --------------------------------------------------------------------------- #


def test_sim_report_summary_keys_locked():
    assert set(SimReport().summary()) == {
        "steps", "epochs", "native_s", "simulated_s", "slowdown",
        "latency_s", "congestion_s", "bandwidth_s", "coherency_s",
        "injected_sleep_s", "analyzer_s", "overhead",
        "migration_moved_bytes", "cache_hit_fraction",
        "dropped_batches", "dropped_epochs",
        "devices_used", "shard_rows", "padded_waste", "coalesced_group_size",
        "stage_s", "transfer_s", "compile_s", "compute_s",
        "enqueue_s", "wait_s", "d2h_s", "slots", "events",
        "donated_dispatches", "aot_cache_hits",
        "qos_classes", "qos_delay_shares",
    }


def test_fabric_report_summary_keys_locked():
    rep = FabricReport(hosts=[HostClock(0, "a"), HostClock(1, "b")])
    base = {
        "rounds", "epochs", "latency_s", "congestion_s", "bandwidth_s",
        "coherency_s", "bi_messages", "analyzer_s",
        "migration_moved_bytes", "cache_hit_fraction",
        "dropped_batches", "dropped_epochs",
        "devices_used", "shard_rows", "padded_waste", "coalesced_group_size",
        "stage_s", "transfer_s", "compile_s", "compute_s",
        "enqueue_s", "wait_s", "d2h_s", "slots", "events",
        "donated_dispatches", "aot_cache_hits",
        "qos_classes", "qos_delay_shares",
    }
    per_host = {
        f"host{h}_{k}" for h in (0, 1)
        for k in ("native_s", "simulated_s", "slowdown")
    }
    assert set(rep.summary()) == base | per_host


# --------------------------------------------------------------------------- #
# satellite: async-vs-sync FabricSession equivalence (bit-equal)
# --------------------------------------------------------------------------- #


_FABRIC_VARIANTS = {
    "replay": {},  # stateless: round replay cache active
    "migration": dict(
        migration=MigrationConfig(
            mode="software", promote_threshold=1, local_budget_bytes=1 << 30
        )
    ),
    "cache": dict(cache=DeviceCacheConfig(capacity_bytes=1 << 26)),
    "migration+cache": dict(
        migration=MigrationConfig(
            mode="software", promote_threshold=1, local_budget_bytes=1 << 30
        ),
        cache=DeviceCacheConfig(capacity_bytes=1 << 26),
    ),
}


@pytest.mark.parametrize("variant", sorted(_FABRIC_VARIANTS))
def test_fabric_async_matches_sync_bit_equal(variant):
    """Overlapped rounds fold the SAME analyses in the SAME order as forced
    synchronous rounds — per-host clocks and fabric totals are bit-equal
    (trace-only tenants: native clocks are roofline-paced, deterministic).
    Stateful transforms (migration remap, cache tags) run on the submitting
    thread in both modes, so statefulness does not break equivalence."""
    kw = _FABRIC_VARIANTS[variant]
    topo = lambda: pooled_topology(n_hosts=2, cxl_bandwidth_gbps=8.0)
    sync = FabricSession(topo(), _tenants(2, mults=(1, 4)), async_analysis=False, **kw)
    sync.run(3)
    with AnalysisEngine() as eng:  # private engine: no cross-test coalescing
        with FabricSession(topo(), _tenants(2, mults=(1, 4)), engine=eng, **kw) as asy:
            asy.run(3)
    a, b = sync.report, asy.report
    for f in (
        "rounds", "epochs", "latency_s", "congestion_s", "bandwidth_s",
        "coherency_s", "bi_messages", "migration_moved_bytes",
    ):
        assert getattr(a, f) == getattr(b, f), f
    if variant in ("cache", "migration+cache"):
        assert a.cache_hit_fraction == b.cache_hit_fraction
    np.testing.assert_array_equal(a.per_pool_latency_ns, b.per_pool_latency_ns)
    np.testing.assert_array_equal(
        a.per_switch_congestion_ns, b.per_switch_congestion_ns
    )
    np.testing.assert_array_equal(a.per_switch_bandwidth_ns, b.per_switch_bandwidth_ns)
    for ha, hb in zip(a.hosts, b.hosts):
        for f in (
            "steps", "native_s", "simulated_s", "latency_s", "congestion_s",
            "bandwidth_s", "coherency_s", "slowdown",
        ):
            assert getattr(ha, f) == getattr(hb, f), f


# --------------------------------------------------------------------------- #
# tentpole: submission precedes native dispatch (the overlap contract)
# --------------------------------------------------------------------------- #


def test_fabric_round_submits_before_native_steps():
    order = []
    with AnalysisEngine() as eng:
        tenants = _tenants(2, step=True)
        for t in tenants:
            jitted = t.step_fn

            def stepper(x, _jitted=jitted, _name=t.name):
                order.append(f"native:{_name}")
                return _jitted(x)

            t.step_fn = stepper
        sess = FabricSession(pooled_topology(n_hosts=2), tenants, engine=eng)
        orig_submit = sess._handle.submit

        def recording_submit(*args, **kwargs):
            order.append("submit")
            return orig_submit(*args, **kwargs)

        sess._handle.submit = recording_submit
        sess.round()
        sess.close()
    assert order == ["submit", "native:t0", "native:t1"]


def test_fabric_round_returns_breakdown_only_in_sync_mode():
    sync = FabricSession(pooled_topology(n_hosts=2), _tenants(2), async_analysis=False)
    assert sync.round() is not None
    with FabricSession(pooled_topology(n_hosts=2), _tenants(2)) as asy:
        assert asy.round() is None
        # the report property flushes pending folds: never a partial read
        assert asy.report.rounds == 1


def test_attach_async_still_matches_sync():
    """The engine-backed attach path preserves the historical async
    semantics: totals match the synchronous pipeline."""
    x = jnp.ones((32, 32))
    reports = {}
    for mode in (False, True):
        with _toy_attach(async_mode=mode) as prog:
            prog.run(3, x)
            reports[mode] = prog.report
    a, b = reports[False], reports[True]
    assert a.epochs == b.epochs == 3
    assert b.latency_s == pytest.approx(a.latency_s, rel=1e-6)
    assert b.congestion_s == pytest.approx(a.congestion_s, rel=1e-6, abs=1e-12)
    assert b.analyzer_s > 0


# --------------------------------------------------------------------------- #
# persistent compilation cache placement
# --------------------------------------------------------------------------- #

_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.core.aot import install_persistent_cache
hits = []
jax.monitoring.register_event_listener(
    lambda e, **kw: hits.append(e) if e.endswith("compilation_cache/cache_hits") else None
)
print(install_persistent_cache())
if COMPILE:
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(8.0)).block_until_ready()
print(len(hits))
"""


def _cache_probe(compile_, cache_dir=None):
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(repo / "src"))
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    out = subprocess.run(
        [sys.executable, "-c", f"COMPILE = {compile_!r}\n" + _CACHE_PROBE],
        env=env, cwd=repo, capture_output=True, text=True, timeout=120, check=True,
    ).stdout.split()
    return out[-2], int(out[-1])


def test_persistent_cache_lives_where_the_environment_says(tmp_path):
    """JAX_COMPILATION_CACHE_DIR places the cache, code sets no other
    directory, and a second identical process hits what the first wrote."""
    from pathlib import Path

    cache = tmp_path / "jax_cache"
    where, hits = _cache_probe(True, cache)
    assert where == str(cache) and hits == 0
    assert any(cache.iterdir())
    where, hits = _cache_probe(True, cache)
    assert where == str(cache) and hits >= 1
    # without the variable: the fixed, gitignored <repo>/.jax_cache
    where, _ = _cache_probe(False)
    assert where == str(Path(__file__).resolve().parents[1] / ".jax_cache")
