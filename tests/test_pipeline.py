"""Device-resident epoch pipeline: on-device staging sort, donated ring
buffers, AOT dispatch cache, and the packed compact cascade.

Covers the PR's contract end to end:

  * the on-device merge kernels (`two_run_merge`, `staging_sort`) are
    **bitwise** equal to the host stable argsort they replace, pads and
    ties included;
  * `chain_cascade` matches the serial full-width cascade oracle;
  * a pipeline analyzer matches the classic jitted path and the numpy
    oracle on chain-eligible *and* ineligible topologies;
  * donated staging planes are actually consumed (reusing one raises);
  * the AOT executable cache reaches zero lowerings in steady state;
  * `presorted=` lets the oracles skip their re-sort without changing
    results;
  * the async engine's overlapped launch/finish dispatcher returns the
    same numbers as synchronous dispatch.
"""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.analyzer import (
    DelayBreakdown,
    DispatchStats,
    EpochAnalyzer,
    FineGrainedSimulator,
    _analyze_pipeline_jax,
    analyze_ref,
    plan_chain,
)
from repro.core.engine import AnalysisEngine
from repro.core.events import (
    EventStager,
    MemEvents,
    merge_host_traces,
    plane_layout,
    plane_words,
    synthetic_trace,
)
from repro.core.topology import (
    chained_topology,
    figure1_topology,
    pooled_topology,
    two_tier_topology,
)
from repro.kernels import ref


# --------------------------------------------------------------------------- #
# kernel oracles
# --------------------------------------------------------------------------- #


def _host_stable(keys, *payloads):
    order = np.argsort(keys, kind="stable")
    return (np.asarray(keys)[order],) + tuple(np.asarray(p)[order] for p in payloads)


def _two_runs(rng, w0, w1, pad0, pad1):
    """Two sorted runs [A | B] with many exact ties and +inf/-1 pad tails."""
    a = np.sort(rng.integers(0, 20, w0)).astype(np.float32)
    b = np.sort(rng.integers(0, 20, w1)).astype(np.float32)
    ids = np.arange(w0 + w1, dtype=np.int32)
    a[w0 - pad0 :] = np.inf
    b[w1 - pad1 :] = np.inf
    ids[w0 - pad0 : w0] = -1
    ids[w0 + w1 - pad1 :] = -1
    return np.concatenate([a, b]), ids


@pytest.mark.parametrize(
    "w0, w1, pad0, pad1",
    [
        (37, 27, 5, 3),  # interleaved ties, both runs padded
        (256, 16, 9, 16),  # long A, short all-pad B: the attach cell's merge
        (16, 256, 2, 40),  # short A
        (300, 300, 20, 7),  # equal widths
        (1, 40, 0, 4),  # one-element A
        (40, 1, 6, 0),  # one-element B
        (300, 24, 300, 5),  # long run all pads
    ],
)
@pytest.mark.parametrize("search", ["compare_all", "binary"])
def test_two_run_merge_bitwise_with_ties_and_pads(
    rng, monkeypatch, search, w0, w1, pad0, pad1
):
    if search == "binary":  # the branch wide runs take past the pair cap
        monkeypatch.setattr(ref, "_COMPARE_ALL_MAX_PAIRS", 0)
    x, ids = _two_runs(rng, w0, w1, pad0, pad1)
    got_x, got_i = ref.two_run_merge(jnp.asarray(x), w0, jnp.asarray(ids))
    # host oracle: stable argsort of the run-major concatenation resolves
    # ties lower-run-first — exactly two_run_merge's tie contract
    exp_x, exp_i = _host_stable(x, ids)
    np.testing.assert_array_equal(np.asarray(got_x), exp_x)
    np.testing.assert_array_equal(np.asarray(got_i), exp_i)


def test_two_run_merge_vmapped_batch(rng):
    w0, w1, B = 64, 16, 4
    rows = [
        _two_runs(rng, w0, w1, int(rng.integers(0, w0)), int(rng.integers(0, w1 + 1)))
        for _ in range(B)
    ]
    x = np.stack([r[0] for r in rows])
    ids = np.stack([r[1] for r in rows])
    f = jax.vmap(lambda xx, ii: ref.two_run_merge(xx, w0, ii))
    got_x, got_i = f(jnp.asarray(x), jnp.asarray(ids))
    for b in range(B):
        exp_x, exp_i = _host_stable(x[b], ids[b])
        np.testing.assert_array_equal(np.asarray(got_x[b]), exp_x)
        np.testing.assert_array_equal(np.asarray(got_i[b]), exp_i)


@pytest.mark.parametrize("caps", [(16,), (16, 16), (8, 16, 4), (8, 8, 8, 8, 8)])
def test_staging_sort_bitwise_vs_host_argsort(rng, caps):
    total = sum(caps)
    xs, ids = [], []
    off = 0
    for c in caps:
        fill = int(rng.integers(0, c + 1))
        run = np.full((c,), np.inf, np.float32)
        run[:fill] = np.sort(rng.integers(0, 12, fill)).astype(np.float32)
        rid = np.full((c,), -1, np.int32)
        rid[:fill] = off + np.arange(fill, dtype=np.int32)
        xs.append(run)
        ids.append(rid)
        off += c
    x = np.concatenate(xs)
    idx = np.concatenate(ids)
    got_x, got_i = ref.staging_sort(jnp.asarray(x), caps, jnp.asarray(idx))
    # -1 pads all carry +inf keys; stable argsort keeps them run-ordered at
    # the tail, matching the merge tree's pad handling
    exp_x, exp_i = _host_stable(x, idx)
    np.testing.assert_array_equal(np.asarray(got_x), exp_x)
    np.testing.assert_array_equal(np.asarray(got_i), exp_i)


def test_staging_sort_vmapped_batch(rng):
    caps = (8, 16, 8)
    B, W = 4, sum(caps)
    x = np.full((B, W), np.inf, np.float32)
    idx = np.full((B, W), -1, np.int32)
    off = 0
    for c in caps:
        for b in range(B):
            fill = int(rng.integers(1, c + 1))
            x[b, off : off + fill] = np.sort(
                rng.uniform(0, 100, fill)
            ).astype(np.float32)
            idx[b, off : off + fill] = off + np.arange(fill, dtype=np.int32)
        off += c
    f = jax.vmap(lambda xx, ii: ref.staging_sort(xx, caps, ii))
    got_x, got_i = f(jnp.asarray(x), jnp.asarray(idx))
    for b in range(B):
        exp_x, exp_i = _host_stable(x[b], idx[b])
        np.testing.assert_array_equal(np.asarray(got_x[b]), exp_x)
        np.testing.assert_array_equal(np.asarray(got_i[b]), exp_i)


def _check_chain_cascade_vs_serial(rng, caps, stts, fills=None):
    """chain_cascade over packed entry segments (``fills[d]`` real events
    in segment ``d``, random when None) against the serial cascade."""
    # tie-free times => per-event finals are bitwise identical
    D = len(caps)  # stages, deepest first; stage d's events traverse d..D-1
    W = sum(caps)
    stts = np.asarray(stts, np.float32)
    t_pack = np.full((W,), np.inf, np.float32)
    idx = np.full((W,), -1, np.int32)
    entry = np.full((W,), -1, np.int32)
    off = 0
    for d, c in enumerate(caps):
        fill = int(rng.integers(1, c + 1)) if fills is None else fills[d]
        t_pack[off : off + fill] = np.sort(
            rng.uniform(0, 400, fill)
        ).astype(np.float32)
        idx[off : off + fill] = off + np.arange(fill, dtype=np.int32)
        entry[off : off + fill] = d
        off += c
    t_fin, i_fin, dsums = ref.chain_cascade(
        jnp.asarray(t_pack), jnp.asarray(idx), jnp.asarray(stts), caps
    )
    # serial oracle: flatten to one sorted timeline, run the full-width
    # cascade with nested masks (stage s serves every event entering at
    # depth <= s in deepest-first order)
    real = idx >= 0
    order = np.argsort(t_pack[real], kind="stable")
    t_sorted = t_pack[real][order]
    ent_sorted = entry[real][order]
    route_bits = np.zeros_like(ent_sorted)
    for s in range(D):
        route_bits |= np.where(ent_sorted <= s, 1 << s, 0)
    tf, slot, ds = ref.serial_queue_cascade(
        jnp.asarray(t_sorted),
        jnp.asarray(route_bits),
        jnp.asarray(stts),
    )
    got = {int(i): float(t) for i, t in zip(np.asarray(i_fin), np.asarray(t_fin)) if i >= 0}
    # tf[k] is the final time of the event at sorted position slot[k]
    exp = {
        int(i): float(t)
        for i, t in zip(idx[real][order][np.asarray(slot)], np.asarray(tf))
    }
    assert got == exp
    np.testing.assert_allclose(np.asarray(dsums), np.asarray(ds), rtol=1e-6)


def test_chain_cascade_matches_serial_cascade(rng):
    _check_chain_cascade_vs_serial(rng, (8, 8, 16, 8), [7.0, 5.0, 3.0, 2.0])


def test_chain_cascade_pad_only_entry_segments(rng):
    # the attach cell's packing: every event enters at the deepest stage and
    # the later entry segments hold only their 16 +inf pads
    _check_chain_cascade_vs_serial(rng, (64, 16, 16), [4.0, 2.0, 3.0], fills=(57, 0, 0))


def test_pipeline_graph_merges_pad_segments_without_a_loop():
    # the attach cell's chain dispatch, scaled down: a wide first segment and
    # two 16-wide entry segments.  Ranking only the short run compares it
    # against the long one directly, so no while loop may run over the
    # packed width (a binary search over it is a 13-round gather loop).
    flat = figure1_topology().flatten()
    plan = plan_chain(flat)
    B, N, caps = 4, 2048, (1024, 16, 16)
    W = sum(caps)
    V, S = np.asarray(flat.route).shape
    layout = plane_layout(B, N, V, W)
    sds = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    args = (
        sds((plane_words(layout),), i32),
        sds((V,), f32), sds((), f32), sds((V, S), f32), sds((S,), f32), sds((S,), f32),
    )
    jitted = jax.jit(
        _analyze_pipeline_jax,
        static_argnames=("layout", "stage_order", "seg_caps", "n_windows"),
        donate_argnums=(0,),
    )
    lowered = jitted.lower(
        *args, layout=layout, stage_order=plan.stage_order, seg_caps=caps,
        n_windows=32,
    )
    hlo = lowered.compile().as_text()
    loops = [ln for ln in hlo.splitlines() if re.search(r"\bwhile\(", ln)]
    wide = [
        ln for ln in loops
        if any(int(d) >= caps[0] for dims in re.findall(r"\[([\d,]+)\]", ln.split(" while(")[0])
               for d in dims.split(","))
    ]
    assert not wide, wide


# --------------------------------------------------------------------------- #
# staging: ring slots and the packed (zero-argsort) path
# --------------------------------------------------------------------------- #


def _trace(flat, n, seed):
    return synthetic_trace(n, flat.n_pools, seed=seed)


def test_stager_ring_slots_do_not_alias():
    flat = two_tier_topology().flatten()
    st = EventStager(slots=2)
    tr = [_trace(flat, 100, 1)]
    b1 = st.stage(tr, 1, 128)
    b2 = st.stage([_trace(flat, 100, 2)], 1, 128)
    assert b1["t"] is not b2["t"]  # double-buffered: fill never clobbers
    b3 = st.stage([_trace(flat, 100, 3)], 1, 128)
    assert b3["t"] is b1["t"]  # ring of 2 wraps around


def test_stage_packed_segments_are_sorted_runs():
    topo = chained_topology(3)
    flat = topo.flatten()
    plan = plan_chain(flat)
    assert plan is not None
    st = EventStager()
    traces = [_trace(flat, 200, s) for s in range(3)]
    buf, caps = st.stage_packed(
        traces, 4, 256, plan.enter_stage, len(plan.stage_order)
    )
    assert sum(caps) == buf["t_pack"].shape[1]
    off = 0
    for c in caps:
        seg = buf["t_pack"][:, off : off + c]
        assert np.all(seg[:, 1:] >= seg[:, :-1])  # per-depth runs sorted free
        off += c
    # pads: -1 idx iff +inf key
    np.testing.assert_array_equal(buf["idx_pack"] < 0, np.isinf(buf["t_pack"]))


def test_memevents_build_avoids_list_roundtrip(rng):
    n = 200_000
    t = np.sort(rng.uniform(0, 1e6, n))
    pool = rng.integers(0, 3, n)
    by = np.full((n,), 64.0)
    import time as _time

    t0 = _time.perf_counter()
    ev = MemEvents.build(t_ns=t, pool=pool, bytes_=by)
    build_s = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    for a in (t, pool, by):
        a.astype(a.dtype, copy=True)
    copy_s = _time.perf_counter() - t0
    assert ev.n == n
    # staging is O(copy): ndarray inputs must not detour through list()
    assert build_s < max(30 * copy_s, 0.05)
    # generators still work (the slow path is for non-arrays only)
    ev2 = MemEvents.build(
        t_ns=(float(x) for x in t[:10]),
        pool=(int(p) for p in pool[:10]),
        bytes_=(float(b) for b in by[:10]),
    )
    assert ev2.n == 10


# --------------------------------------------------------------------------- #
# pipeline analyzer: parity, donation, AOT steady state
# --------------------------------------------------------------------------- #


TOPOS = {
    "figure1": figure1_topology,
    "two_tier": two_tier_topology,
    "chained": lambda: chained_topology(4),
}


@pytest.mark.parametrize("name", sorted(TOPOS))
def test_pipeline_matches_baseline_and_oracle(name, rng):
    flat = TOPOS[name]().flatten()
    traces = [_trace(flat, 300 + 37 * i, 10 + i) for i in range(3)]
    base = EpochAnalyzer(flat, n_windows=32)
    pipe = EpochAnalyzer(flat, n_windows=32, pipeline=True)
    a = base.analyze_batch(traces)
    b = pipe.analyze_batch(traces)
    np.testing.assert_allclose(b.latency_ns, a.latency_ns, rtol=1e-4)
    np.testing.assert_allclose(b.congestion_ns, a.congestion_ns, rtol=1e-4)
    np.testing.assert_allclose(b.bandwidth_ns, a.bandwidth_ns, rtol=1e-4)
    # numpy float64 oracle: f32 accumulation differences stay under 1e-3
    ref_tot = sum(
        analyze_ref(flat, tr, n_windows=32).total_ns for tr in traces
    )
    np.testing.assert_allclose(b.total_ns, ref_tot, rtol=1e-3)


def test_pipeline_on_chain_ineligible_topology_falls_back(rng):
    # pooled: 2 hosts -> plan_chain refuses; pipeline still runs (AOT'd
    # full-plane graph) and matches the baseline bitwise-ish
    flat = pooled_topology(n_hosts=2).flatten()
    assert plan_chain(flat) is None
    traces = [
        _trace(flat, 256, 3).with_host(0),
        _trace(flat, 256, 4).with_host(1),
    ]
    merged = merge_host_traces(traces)
    base = EpochAnalyzer(flat, n_windows=32)
    pipe = EpochAnalyzer(flat, n_windows=32, pipeline=True)
    a = base.analyze_batch([merged])
    b = pipe.analyze_batch([merged])
    np.testing.assert_allclose(b.total_ns, a.total_ns, rtol=1e-4)
    assert pipe.last_dispatch.donated is False  # no donation off-chain
    assert pipe.last_dispatch.compute_s >= 0.0


def test_plan_chain_eligibility():
    assert plan_chain(chained_topology(4).flatten()) is not None
    assert plan_chain(figure1_topology().flatten()) is not None
    assert plan_chain(pooled_topology(n_hosts=2).flatten()) is None


def test_donated_buffer_is_consumed(rng):
    flat = chained_topology(3).flatten()
    pipe = EpochAnalyzer(flat, n_windows=32, pipeline=True)
    traces = [_trace(flat, 200, 7)]
    pend = pipe.launch_batch(traces)
    bd = pend.finish()
    assert bd.total_ns > 0
    st = pipe.last_dispatch
    assert st.donated, "chain dispatch must donate its staging planes"
    assert st.aot_cache_hit is False  # first dispatch lowers
    # the same shape again: donation again, zero new lowerings
    before = pipe._aot.lowerings
    pipe.launch_batch(traces).finish()
    assert pipe.last_dispatch.donated
    assert pipe.last_dispatch.aot_cache_hit
    assert pipe._aot.lowerings == before


def test_aot_cache_zero_lowerings_steady_state(rng):
    flat = chained_topology(3).flatten()
    pipe = EpochAnalyzer(flat, n_windows=32, pipeline=True)
    warm = [_trace(flat, 180, 99)]
    assert pipe.warmup(warm) is True
    assert pipe.warmup(warm) is False  # already warm
    # a short ramp lets the sticky per-stage caps reach their high-water
    # mark; after that the executable key is fixed
    for i in range(5):
        pipe.analyze_batch([_trace(flat, 150 + 10 * i, 1000 + i)])
    base = pipe._aot.lowerings
    for i in range(50):
        pipe.analyze_batch([_trace(flat, 150 + (i % 50), i)])
    assert pipe._aot.lowerings == base, "steady state must not recompile"
    assert pipe._aot.hits >= 50


def test_warmup_noop_for_non_pipeline():
    flat = two_tier_topology().flatten()
    base = EpochAnalyzer(flat, n_windows=32)
    assert base.warmup([_trace(flat, 64, 0)]) is False


def test_dispatch_stats_timing_fields_populated(rng):
    flat = chained_topology(3).flatten()
    pipe = EpochAnalyzer(flat, n_windows=32, pipeline=True)
    pipe.analyze_batch([_trace(flat, 300, 1)])
    st = pipe.last_dispatch
    assert isinstance(st, DispatchStats)
    assert st.stage_s > 0 and st.transfer_s > 0 and st.compute_s > 0
    assert st.compile_s > 0  # first dispatch carries the lowering
    pipe.analyze_batch([_trace(flat, 300, 2)])
    assert pipe.last_dispatch.compile_s == 0.0  # hits are free


# --------------------------------------------------------------------------- #
# one staged word plane per dispatch
# --------------------------------------------------------------------------- #


def _busy_trace(flat, n, seed):
    """Bursty epochs of 16 KiB events: every delay class is well above f32
    time resolution, bandwidth included."""
    tr = synthetic_trace(n, flat.n_pools, epoch_ns=2e5, seed=seed, burstiness=0.8)
    return dataclasses.replace(tr, bytes_=tr.bytes_ * 256)


def _plane_case(name):
    """(flat, pipeline, traces, lat_scales) of a figure-1 chain or a 4-host
    pooled fabric, dispatched by the chain, full-plane or jitted path."""
    if name == "figure1_chain":
        flat = figure1_topology().flatten()
        traces = [_busy_trace(flat, 600 + 37 * i, 20 + i) for i in range(3)]
        pipeline = True
    else:
        flat = pooled_topology(n_hosts=4).flatten()
        traces = [
            merge_host_traces(
                [_busy_trace(flat, 300 + 11 * h + 5 * e, 40 + 4 * e + h) for h in range(4)]
            )
            for e in range(3)
        ]
        pipeline = name == "pooled4_full_plane"
    V = flat.n_hosts * flat.n_pools
    scales = [None, np.linspace(0.5, 1.0, V), None]
    return flat, pipeline, traces, scales


PLANE_CASES = ("figure1_chain", "pooled4_full_plane", "pooled4_jit")


@pytest.mark.parametrize("name", PLANE_CASES)
def test_launch_batch_places_one_buffer_per_dispatch(name, monkeypatch, tmp_path):
    from repro.core import spans
    from repro.distributed import sharding

    flat, pipeline, traces, scales = _plane_case(name)
    an = EpochAnalyzer(flat, n_windows=32, pipeline=pipeline)
    assert (an._chain_plan is not None) == (name == "figure1_chain")
    placed = []
    real_put = sharding.timed_device_put

    def counting_put(tree, *args, **kwargs):
        placed.append(len(jax.tree.leaves(tree)))
        return real_put(tree, *args, **kwargs)

    monkeypatch.setattr(sharding, "timed_device_put", counting_put)
    an.analyze_batch(traces, scales)  # warm
    spans.reset()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            an.analyze_batch(traces, scales)
    assert placed == [1] * 4
    assert spans.traced_totals()[spans.H2D_BUFFERS] == (3, 0.0)
    assert an.last_dispatch.donated == (name == "figure1_chain")


@pytest.mark.parametrize("chain", [True, False], ids=["chain", "full_plane"])
def test_word_plane_unpacks_bit_identical(chain):
    from repro.core.analyzer import unpack_plane

    flat = figure1_topology().flatten()
    plan = plan_chain(flat)
    B, N, V = 4, 512, flat.n_pools
    st = EventStager()
    # three real rows and one empty: +inf/-1 pads, 0/1 valid, zeroed tails
    traces = [_trace(flat, n, 60 + n) for n in (300, 511, 17)]
    if chain:
        buf, caps = st.stage_packed(
            traces, B, N, plan.enter_stage, len(plan.stage_order), scale_width=V
        )
        assert np.isinf(buf["t_pack"]).any() and (buf["idx_pack"] == -1).any()
    else:
        buf, caps = st.stage(traces, B, N, scale_width=V), ()
    layout = plane_layout(B, N, V, sum(caps))
    assert buf["words"].shape == (plane_words(layout),)
    f32max, i32 = np.finfo(np.float32).max, np.iinfo(np.int32)
    buf["bytes"][0, :4] = [-0.0, np.inf, -np.inf, f32max]
    buf["pool"][1, :3] = [i32.min, i32.max, -1]
    buf["weight"][2, :2] = [-0.0, np.finfo(np.float32).smallest_subnormal]
    buf["bw_window"][:] = [1.0, -0.0, 3e38, 1e-30]
    buf["scale"][:] = np.linspace(-2.0, 2.0, B * V, dtype=np.float32).reshape(B, V)
    buf["scale"][3, 0] = -0.0
    got = jax.jit(unpack_plane, static_argnums=1)(jnp.asarray(buf["words"]), layout)
    assert sorted(got) == sorted(name for name, *_ in layout)
    for name, _, shape, kind in layout:
        g, want = np.asarray(got[name]), buf[name]
        assert g.shape == shape, name
        if kind == "b":
            assert g.dtype == np.bool_
            np.testing.assert_array_equal(g, want != 0)
            assert int(want.sum()) == 300 + 511 + 17
        else:
            assert g.dtype == want.dtype, name
            np.testing.assert_array_equal(g.view(np.int32), want.view(np.int32))


def test_word_plane_refuses_a_time_dtype_of_another_width():
    flat = figure1_topology().flatten()
    with pytest.raises(ValueError, match="32-bit"):
        EventStager(np.float16).stage([_trace(flat, 10, 0)], 1, 16)


@pytest.mark.parametrize("name", PLANE_CASES)
def test_word_plane_dispatch_matches_oracle(name):
    flat, pipeline, traces, scales = _plane_case(name)
    an = EpochAnalyzer(flat, n_windows=32, pipeline=pipeline)
    got = an.analyze_batch(traces, scales)
    ref = DelayBreakdown.zero(flat.n_pools, flat.n_switches, flat.n_hosts)
    for tr, sc in zip(traces, scales):
        # the analyzer's effective window: the epoch's span over n_windows
        window = max(float(tr.t_ns.max()) + 1.0, an.bw_window_ns) / 32
        ref = ref + analyze_ref(
            flat, tr, bw_window_ns=window, lat_scale=sc, n_windows=32
        )
    assert ref.bandwidth_ns > 0 and ref.congestion_ns > 0
    assert got.latency_ns == pytest.approx(ref.latency_ns, rel=1e-4)
    assert got.congestion_ns == pytest.approx(ref.congestion_ns, rel=1e-3)
    assert got.bandwidth_ns == pytest.approx(ref.bandwidth_ns, rel=1e-3)
    assert got.total_ns == pytest.approx(ref.total_ns, rel=1e-3)
    np.testing.assert_allclose(got.per_host_latency_ns, ref.per_host_latency_ns, rtol=1e-4)
    np.testing.assert_allclose(
        got.per_host_congestion_ns, ref.per_host_congestion_ns, rtol=5e-3
    )


# --------------------------------------------------------------------------- #
# presorted oracles
# --------------------------------------------------------------------------- #


def test_analyze_ref_presorted_parity(rng):
    flat = pooled_topology(n_hosts=2).flatten()
    merged = merge_host_traces(
        [_trace(flat, 300, 1).with_host(0), _trace(flat, 300, 2).with_host(1)]
    )
    a = analyze_ref(flat, merged, n_windows=32)
    b = analyze_ref(flat, merged, n_windows=32, presorted=True)
    assert a.total_ns == b.total_ns
    np.testing.assert_array_equal(
        a.per_switch_congestion_ns, b.per_switch_congestion_ns
    )


def test_fine_simulator_presorted_parity(rng):
    flat = two_tier_topology().flatten()
    tr = _trace(flat, 200, 5).sorted_by_time()
    sim = FineGrainedSimulator(flat)
    a = sim.simulate(tr)
    b = sim.simulate(tr, presorted=True)
    assert a.total_ns == b.total_ns


# --------------------------------------------------------------------------- #
# engine: overlapped launch/finish dispatcher
# --------------------------------------------------------------------------- #


def test_engine_overlapped_pipeline_matches_sync(rng):
    import threading

    flat = chained_topology(3).flatten()
    batches = [[_trace(flat, 200 + 11 * j, 10 * i + j) for j in range(2)] for i in range(5)]
    sync = EpochAnalyzer(flat, n_windows=32)
    expect = [sync.analyze_batch(b) for b in batches]

    eng = AnalysisEngine()
    try:
        pipe = EpochAnalyzer(flat, n_windows=32, pipeline=True)
        h = eng.register(pipe)
        got = {}
        lock = threading.Lock()
        for i, b in enumerate(batches):
            def fold(bd, elapsed, i=i):
                with lock:
                    got[i] = bd
            h.submit(b, None, fold=fold)
        h.flush()
        assert sorted(got) == list(range(5))
        for i in range(5):
            np.testing.assert_allclose(
                got[i].total_ns, expect[i].total_ns, rtol=1e-4
            )
        h.close()
    finally:
        eng.close()
