"""Chip smoke test: the simulator's served path, once, on a TPU.

    python chip_smoke.py               # one chip: attach, fabric, sweep, kernels
    python chip_smoke.py --four-chips  # four chips: sharded fleet frontier only

Each phase drives the path through the entry points a user calls, ends in
``block_until_ready`` or a report flush, and checks what comes out against
the repo's own oracles; a failed check raises and the process exits
non-zero.  No phase catches its own failure.

  attach   ``CXLMemSim(figure1, opt_state -> cxl_pool2, pipeline, warmup)``
           attached to a jitted qwen3-0.6b train step at its published
           widths (bf16 compute, random weights from ``--seed``); the loss
           is finite, nothing is dropped, and one step's epoch batch
           re-priced by ``analyze_ref`` (f64) agrees with the report.
  fabric   ``FabricSession``: 4 trace-driven qwen3-0.6b tenants on
           ``pooled_topology(n_hosts=4)``, ``pipeline=True``; per-host sums
           equal the fabric totals, and ``analyze_ref`` re-prices a round.
  sweep    one ``ScenarioSuite.run`` of the README's 24-scenario figure-1
           grid: one counted dispatch, two scenarios against ``analyze_ref``.
  kernels  ``impl='pallas'`` against ``impl='inline'`` at N = 65,536 events
           per epoch, B = 8: a depth-3 chain, figure 1 with 4 hosts, and a
           4-host WFQ fabric.

``--four-chips`` runs only the sharded ``[K, B, N]`` dispatch — the README's
``FleetSim(n_racks=32, hosts_per_rack=4, mesh=make_data_mesh()).frontier``
— and the same frontier as one unsharded stacked dispatch.

Epochs are one per layer (``EpochSchedule("layer")``): at published widths
a whole train step spans ~75 ms, where an f32 time's ulp (8 ns) is coarser
than the switches' 2-4 ns service times, and step-long epochs queue on
rounding ties the f64 oracle does not see (+1.6% congestion on figure 1).

Every phase prints events per epoch, B, the dispatch split
(``stage_s``/``transfer_s``/``compile_s``/``compute_s``) and the AOT
lowerings it caused; those times are smoke output, not benchmark numbers.
The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU
the script exits non-zero before any phase and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import math
import os
import sys
import time
import warnings

REPO = os.path.dirname(os.path.abspath(__file__))


def log(*parts) -> None:
    print(*parts, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def rel_err(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1.0)


def lowerings() -> int:
    from repro.core.aot import AotDispatchCache

    return AotDispatchCache.total_lowerings()


def log_split(name: str, summary: dict, n_lowerings: int) -> None:
    log(
        f"[{name}] split stage_s={summary['stage_s']!r} "
        f"transfer_s={summary['transfer_s']!r} compile_s={summary['compile_s']!r} "
        f"compute_s={summary['compute_s']!r} aot_lowerings={n_lowerings}"
    )


def log_epochs(name: str, traces) -> None:
    sizes = [int(t.n) for t in traces]
    log(f"[{name}] B={len(sizes)} events_per_epoch={sizes}")


def check_vs_ref(name: str, rep, ref, repeats: int) -> None:
    """Each delay component of a report, per step or round, against the f64
    oracle's breakdown of one batch — the pipeline tests' 1e-3 tolerance."""
    from repro.core.units import s_to_ns

    for f in ("latency", "congestion", "bandwidth"):
        got = s_to_ns(getattr(rep, f"{f}_s")) / repeats
        want = getattr(ref, f"{f}_ns")
        err = rel_err(got, want)
        log(f"[{name}] {f} per repeat {got!r} ns vs analyze_ref {want!r} ns, rel {err!r}")
        check(err <= 1e-3, f"{name} {f} off analyze_ref by {err}")


def ref_total(flat, traces, n_windows: int, bw_window_ns: float = 10_000.0,
              scales=None):
    """Summed f64 oracle breakdown over one batch of epochs, each priced with
    the analyzer's own window rule: ``n_windows`` windows tile the epoch's
    span (at least ``bw_window_ns``)."""
    from repro.core.analyzer import analyze_ref

    out = None
    for i, tr in enumerate(traces):
        span = max(float(tr.t_ns.max()) + 1.0 if tr.n else 0.0, bw_window_ns)
        bd = analyze_ref(
            flat, tr, bw_window_ns=max(span / n_windows, 1.0), n_windows=n_windows,
            lat_scale=None if scales is None else scales[i],
        )
        out = bd if out is None else out + bd
    return out


# --------------------------------------------------------------------------- #
# attach: a real train step under simulation
# --------------------------------------------------------------------------- #


def phase_attach(cfg, batch: int, seq: int, steps: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import (
        ClassMapPolicy,
        CXLMemSim,
        EpochSchedule,
        figure1_topology,
        synthesize_step_trace,
    )
    from repro.launch.steps import make_train_step
    from repro.models import Model
    from repro.models.phases import build_regions_and_phases
    from repro.optim.adamw import AdamWConfig, adamw_init

    name = "attach"
    opt_cfg = AdamWConfig(lr=1e-4, total_steps=100)
    k_init, k_tok = jax.random.split(jax.random.PRNGKey(seed))
    params = Model(cfg).init(k_init)
    opt_state = {"adam": adamw_init(params, opt_cfg), "ef": {}}
    tokens = jax.random.randint(k_tok, (batch, seq), 0, cfg.vocab_size)
    data = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    t0 = time.perf_counter()
    step = (
        jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0, 1))
        .lower(params, opt_state, data)
        .compile()
    )
    mem = step.memory_analysis()
    need = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    )
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit", 16 * 10**9)
    log(
        f"[{name}] {cfg.name} batch={batch} seq={seq} step compile "
        f"{time.perf_counter() - t0!r} s; memory_analysis argument="
        f"{mem.argument_size_in_bytes} output={mem.output_size_in_bytes} "
        f"alias={mem.alias_size_in_bytes} temp={mem.temp_size_in_bytes} "
        f"need={need} bytes_limit={limit}"
    )
    check(need <= limit, f"train step needs {need} bytes > device limit {limit}")

    regions, phases = build_regions_and_phases(cfg, "train", batch=batch, seq=seq)
    topo = figure1_topology()
    policy = ClassMapPolicy({"opt_state": "cxl_pool2"})
    sim = CXLMemSim(
        topo, policy, epoch=EpochSchedule("layer"), pipeline=True, warmup=True
    )
    low0 = lowerings()
    losses = []
    with sim.attach(step, phases, regions) as prog:
        for _ in range(steps):
            params, opt_state, metrics = prog.step(params, opt_state, data)
            losses.append(float(jax.block_until_ready(metrics["loss"])))
        rep = prog.report  # flushes the engine
    summary = rep.summary()
    log(f"[{name}] losses={losses}")
    log(
        f"[{name}] report steps={rep.steps} epochs={rep.epochs} "
        f"latency_s={rep.latency_s!r} congestion_s={rep.congestion_s!r} "
        f"bandwidth_s={rep.bandwidth_s!r} native_s={rep.native_s!r} "
        f"slowdown={rep.slowdown!r} donated={rep.donated_dispatches} "
        f"aot_cache_hits={rep.aot_cache_hits}"
    )
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(rep.steps == steps, f"{rep.steps} steps reported, {steps} run")
    check(rep.latency_s > 0 and rep.congestion_s > 0, "zero delay totals")
    check(
        rep.dropped_batches == 0 and rep.dropped_epochs == 0,
        f"dropped {rep.dropped_batches} batches / {rep.dropped_epochs} epochs",
    )

    # one staged epoch batch, rebuilt independently and re-priced in f64
    traces, _, _ = synthesize_step_trace(
        phases, regions, hw=sim.hw, granularity_bytes=policy.granularity_bytes,
        max_events_per_access=sim.max_events_per_access, epoch_mode="layer",
    )
    log_epochs(name, traces)
    log_split(name, summary, lowerings() - low0)
    check(rep.epochs == steps * len(traces), "epochs folded != epochs submitted")
    check_vs_ref(name, rep, ref_total(sim.flat, traces, sim.n_windows), steps)


# --------------------------------------------------------------------------- #
# fabric: four tenants on one pooled expander
# --------------------------------------------------------------------------- #


def phase_fabric(cfg, rounds: int) -> None:
    from repro.core import (
        ClassMapPolicy,
        EpochSchedule,
        FabricSession,
        Tenant,
        merge_host_traces,
        pooled_topology,
        synthesize_step_trace,
    )
    from repro.models.phases import build_regions_and_phases

    name = "fabric"
    tenants = []
    for h in range(4):
        kind = ("train", "decode")[h % 2]
        regions, phases = build_regions_and_phases(
            cfg, kind, batch=4 * (h + 1), seq=1024, cache_len=4096
        )
        pol = ClassMapPolicy({"opt_state": "shared_pool", "kvcache": "shared_pool"})
        tenants.append(Tenant(f"{kind}{h}", phases, regions, pol))
    low0 = lowerings()
    n_windows = 128
    with FabricSession(
        pooled_topology(n_hosts=4), tenants, epoch=EpochSchedule("layer"),
        n_windows=n_windows, pipeline=True,
    ) as session:
        rep = session.run(rounds)
        flat = session.flat
    summary = rep.summary()
    per_host = [
        (hc.latency_s, hc.congestion_s, hc.bandwidth_s) for hc in rep.hosts
    ]
    log(
        f"[{name}] rounds={rep.rounds} epochs={rep.epochs} latency_s="
        f"{rep.latency_s!r} congestion_s={rep.congestion_s!r} "
        f"bandwidth_s={rep.bandwidth_s!r} per_host={per_host}"
    )
    check(rep.latency_s > 0 and rep.congestion_s > 0, "zero delay totals")
    check(
        rep.dropped_batches == 0 and rep.dropped_epochs == 0,
        f"dropped {rep.dropped_batches} batches / {rep.dropped_epochs} epochs",
    )
    for i, total in enumerate((rep.latency_s, rep.congestion_s, rep.bandwidth_s)):
        s = sum(p[i] for p in per_host)
        check(
            abs(s - total) <= 1e-5 * max(abs(total), 1e-12),
            f"per-host sum {s} != fabric total {total} (component {i})",
        )

    per_tenant = []
    for h, t in enumerate(tenants):
        traces, _, _ = synthesize_step_trace(
            t.phases, t.regions, granularity_bytes=t.policy.granularity_bytes,
            epoch_mode="layer",
        )
        per_tenant.append([tr.with_host(h) for tr in traces])
    # epoch k of every tenant shares one timeline; shorter tenants sit out
    merged = [
        merge_host_traces([tr for tr in group if tr is not None])
        for group in itertools.zip_longest(*per_tenant)
    ]
    log_epochs(name, merged)
    log_split(name, summary, lowerings() - low0)
    check(rep.epochs == rounds * len(merged), "epochs folded != epochs submitted")
    check_vs_ref(name, rep, ref_total(flat, merged, n_windows), rounds)


# --------------------------------------------------------------------------- #
# sweep: the README's 24-scenario grid in one dispatch
# --------------------------------------------------------------------------- #


def phase_sweep(cfg) -> None:
    from repro.core import (
        ClassMapPolicy,
        DeviceCacheConfig,
        DeviceCacheModel,
        InterleavePolicy,
        ScenarioSuite,
        TopologyOverride,
        figure1_topology,
        flatten_stack,
        synthesize_step_trace,
    )
    from repro.models.phases import build_regions_and_phases

    name = "sweep"
    regions, phases = build_regions_and_phases(cfg, "train", batch=4, seq=1024)
    suite = ScenarioSuite(figure1_topology(), regions, phases, epoch_mode="layer")
    scens = ScenarioSuite.cartesian(
        policies={
            "opt_off": ClassMapPolicy({"opt_state": "cxl_pool2"}),
            "il": InterleavePolicy(["cxl_pool2", "cxl_pool3"]),
        },
        overrides={
            f"lat{lat}": TopologyOverride(pools={"cxl_pool2": {"latency_ns": lat}})
            for lat in (150.0, 250.0, 350.0)
        },
        caches={"nc": None, "c1g": DeviceCacheConfig(capacity_bytes=1 << 30)},
        granularities=[64, 4096],
    )
    check(len(scens) == 24, f"grid has {len(scens)} scenarios, not 24")
    low0 = lowerings()
    t0 = time.perf_counter()
    res = suite.run(scens)
    wall = time.perf_counter() - t0
    log(f"[{name}] K={len(scens)} dispatches={suite.dispatch_count} wall_s={wall!r}")
    check(suite.dispatch_count == 1, f"{suite.dispatch_count} dispatches, not 1")
    stats = suite.last_dispatch
    log_split(name, {f: getattr(stats, f) for f in
                     ("stage_s", "transfer_s", "compile_s", "compute_s")},
              lowerings() - low0)

    stack = flatten_stack(suite.topology, [s.topology for s in scens])
    for k in (0, len(scens) - 1):
        s = scens[k]
        flat_k = stack.member(k)
        s.policy.place(regions, suite.base_flat)
        traces, _, _ = synthesize_step_trace(
            phases, regions, granularity_bytes=s.policy.granularity_bytes,
            epoch_mode="layer",
        )
        if k == 0:
            log_epochs(name, traces)
        model = DeviceCacheModel(s.cache, flat_k, [regions]) if s.cache else None
        scales = None if model is None else [model.observe_scale(tr) for tr in traces]
        ref = ref_total(flat_k, traces, suite.n_windows, suite.bw_window_ns, scales)
        got = res.breakdowns[k]
        for f in ("latency_ns", "congestion_ns", "bandwidth_ns"):
            err = rel_err(getattr(got, f), getattr(ref, f))
            log(f"[{name}] {s.label()} {f} {getattr(got, f)!r} vs {getattr(ref, f)!r} rel {err!r}")
            check(err <= 1e-4, f"{s.label()} {f} off analyze_ref by {err}")


# --------------------------------------------------------------------------- #
# kernels: impl='pallas' against impl='inline'
# --------------------------------------------------------------------------- #


def _kernel_cases(n_events: int, n_epochs: int, seed: int):
    from repro.core import (
        Topology,
        figure1_topology,
        merge_host_traces,
        pooled_topology,
        synthetic_trace,
    )
    from repro.core.topology import chained_topology

    f1 = figure1_topology()
    f1_hosts = Topology(
        f1.pools, f1.switches, rc_latency_ns=f1.rc_latency_ns,
        rc_bandwidth_gbps=f1.rc_bandwidth_gbps, rc_stt_ns=f1.rc_stt_ns, n_hosts=4,
    )
    wfq = pooled_topology(n_hosts=4, discipline="wfq", class_weights=(4.0, 2.0, 1.0))

    def host_epochs(flat, n_classes):
        return [
            merge_host_traces([
                synthetic_trace(
                    n_events // 4, flat.n_pools, epoch_ns=1.0 * n_events,
                    seed=seed + 10 * e + h, burstiness=0.6,
                    n_qos_classes=n_classes,
                ).with_host(h)
                for h in range(4)
            ])
            for e in range(n_epochs)
        ]

    chain = chained_topology(3).flatten()
    yield "chain3", chain, [
        synthetic_trace(n_events, chain.n_pools, epoch_ns=1.0 * n_events, seed=seed + e, burstiness=0.6)
        for e in range(n_epochs)
    ]
    flat = f1_hosts.flatten()
    yield "figure1_4hosts", flat, host_epochs(flat, 1)
    flat = wfq.flatten()
    yield "wfq_4hosts", flat, host_epochs(flat, 3)


def phase_kernels(n_events: int, n_epochs: int, seed: int, impl: str = "pallas") -> None:
    import numpy as np

    from repro.core import EpochAnalyzer

    name = "kernels"
    for case, flat, traces in _kernel_cases(n_events, n_epochs, seed):
        log_epochs(f"{name}:{case}", traces)
        out = {}
        for which in ("inline", impl):
            an = EpochAnalyzer(flat, impl=which)
            low0 = lowerings()
            t0 = time.perf_counter()
            an.analyze_batch(traces)  # jit compile + first run
            t1 = time.perf_counter()
            out[which] = an.analyze_batch(traces)
            # analyze_batch stages, transfers and runs in one call: its
            # split is the cold call (compile included) against a warm one
            log(
                f"[{name}:{case}] {which} analyze_batch cold wall_s={t1 - t0!r} "
                f"warm wall_s={time.perf_counter() - t1!r} "
                f"aot_lowerings={lowerings() - low0}"
            )
        a, b = out[impl], out["inline"]
        for f, rtol, atol in (
            ("latency_ns", 1e-4, 1e-3),
            ("congestion_ns", 1e-3, 1e-2),
            ("bandwidth_ns", 1e-2, 1.0),
        ):
            x, y = getattr(a, f), getattr(b, f)
            log(f"[{name}:{case}] {f} {impl}={x!r} inline={y!r}")
            check(abs(x - y) <= atol + rtol * abs(y), f"{case} {f}: {x} vs {y}")
        check(b.congestion_ns > 0, f"{case}: no congestion to compare")
        np.testing.assert_allclose(
            a.per_switch_congestion_ns, b.per_switch_congestion_ns, rtol=2e-3, atol=0.1
        )
        np.testing.assert_allclose(
            a.per_host_congestion_ns, b.per_host_congestion_ns, rtol=2e-3, atol=0.1
        )
        np.testing.assert_allclose(
            a.per_class_congestion_ns, b.per_class_congestion_ns, rtol=2e-3, atol=0.1
        )


# --------------------------------------------------------------------------- #
# four chips: the sharded fleet frontier against one unsharded dispatch
# --------------------------------------------------------------------------- #


def phase_four_chips(n_racks: int = 32, n_tenants: int = 192) -> None:
    import jax

    from repro.core import FleetSim, synthetic_tenant
    from repro.launch.mesh import make_data_mesh

    name = "four_chips"
    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, need 4")
    tenants = [synthetic_tenant(f"t{i}", seed=i, gib=10.0) for i in range(n_tenants)]
    fracs = (0.0, 0.25, 0.5, 1.0)
    fleets = {
        "sharded": FleetSim(n_racks=n_racks, hosts_per_rack=4, mesh=make_data_mesh()),
        "unsharded": FleetSim(n_racks=n_racks, hosts_per_rack=4),
    }
    points = {}
    for which, fleet in fleets.items():
        low0 = lowerings()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            fleet.frontier(tenants, offload_fractions=fracs)  # compile
            t0 = time.perf_counter()
            points[which] = fleet.frontier(tenants, offload_fractions=fracs)
            wall = time.perf_counter() - t0
        st = fleet.last_dispatch
        log(
            f"[{name}] {which} K={len(fracs) * n_racks} devices_used="
            f"{st.devices_used} shard_rows={st.shard_rows} rows={st.rows} "
            f"padded_fraction={st.padded_fraction!r} warm frontier wall_s={wall!r}"
        )
        split = dataclasses.asdict(st) | {"compute_s": st.compute_s}  # a property
        log_split(f"{name}:{which}", split, lowerings() - low0)
        fallback = [str(w.message) for w in caught if "falling back" in str(w.message)]
        check(not fallback, f"sub-mesh fallback: {fallback}")
    check(
        all(p.report.devices_used == 4 for p in points["sharded"]),
        "sharded frontier did not use 4 devices",
    )
    worst = dict.fromkeys(("latency_ns", "congestion_ns", "bandwidth_ns"), 0.0)
    for ps, pu in zip(points["sharded"], points["unsharded"]):
        log(
            f"[{name}] offload={ps.offload_fraction} stranded_recovered_gb="
            f"{ps.stranded_recovered_gb!r} p99_slowdown={ps.p99_slowdown!r} "
            f"(unsharded {pu.p99_slowdown!r})"
        )
        for a, b in zip(ps.report.breakdowns, pu.report.breakdowns):
            for f in worst:
                worst[f] = max(worst[f], rel_err(getattr(a, f), getattr(b, f)))
    log(f"[{name}] worst per-rack rel err sharded vs unsharded {worst!r}")
    # bitwise on CPU; on a TPU the per-device program (K/4 rows) and the
    # one-device program (K rows) are compiled apart and sum f32 in a
    # different order (1.6e-6 measured on a v5e 2x2)
    check(
        max(worst.values()) <= 1e-5,
        f"sharded frontier off the unsharded dispatch by {worst}",
    )


# --------------------------------------------------------------------------- #


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fleet frontier on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.core.aot import install_persistent_cache

    log(f"compile cache: {install_persistent_cache()}")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")

    t_all = time.perf_counter()
    if args.four_chips:
        phase_four_chips()
    else:
        from repro.configs.qwen3_0_6b import CONFIG

        for name, run in (
            ("attach", lambda: phase_attach(CONFIG, 4, 1024, 5, args.seed)),
            ("fabric", lambda: phase_fabric(CONFIG, 3)),
            ("sweep", lambda: phase_sweep(CONFIG)),
            ("kernels", lambda: phase_kernels(65536, 8, args.seed)),
        ):
            t0 = time.perf_counter()
            run()
            log(f"[{name}] phase wall_s={time.perf_counter() - t0!r}")
    log(f"total wall_s={time.perf_counter() - t_all!r}")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
