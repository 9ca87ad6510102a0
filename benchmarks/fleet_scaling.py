"""Device-sharded fleet dispatch vs single-device -> BENCH_fleet.json.

A 100+-host fleet frontier sweep (F offload fractions x R racks = K >= 256
stacked rack planes) evaluated three ways:

  * **sharded** — :meth:`repro.core.FleetSim.frontier` with an 8-virtual-
    device ``('data',)`` mesh: ONE ``[K, B, N]`` dispatch whose rack axis
    is sharded across devices, per-shard on-device reduction, one ``[K]``
    host transfer.
  * **stacked (1 device)** — the same single stacked dispatch, unsharded:
    isolates what sharding adds over stacking.
  * **sequential per-rack** — the pre-fleet pattern: one
    ``EpochAnalyzer.analyze_batch`` dispatch per rack per fraction (K host
    round-trips), the way K independent sessions would price their racks.

All paths are warmed before timing (compile excluded).  The script runs on
whatever devices JAX finds and records them; for the CPU smoke, set
``JAX_PLATFORMS=cpu`` and
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in the environment.
Virtual CPU devices share the machine's physical cores, so a sharded win
there is scheduling and cache-locality headroom, not extra silicon; the
record includes the physical core count so readers can calibrate.

The capacity-planning output — the paper's stranding question at rack
scale — is the frontier curve: stranded GB recovered (bytes the hosts no
longer provision because they moved to the racks' shared expanders) vs
p99 tenant slowdown, at each offload fraction.

Acceptance gate (ISSUE 6):
  * sharded >= 3x sequential per-rack wall-clock at K >= 256 on a
    100+-host fleet,
  * sharded totals within 1e-6 relative of the single-device stacked
    dispatch on every plane,
  * the frontier curve is reported at >= 100 hosts.

``--quick`` (CI smoke) shrinks the fleet; the throughput gate applies only
at full scale (parity and curve gates always hold).
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

import numpy as np

SPEEDUP_GATE = 3.0
PARITY_GATE = 1e-6
FULL_RACKS = 32
HOSTS_PER_RACK = 4
FULL_FRACTIONS = 8
MIN_HOSTS = 100
MIN_K = 256


def build_fleet(n_racks: int, mesh=None):
    from repro.core.fleet import FleetSim

    # 64 KiB granules with 8-event statistical trains per access: the
    # weight field preserves total bytes, so stranding/slowdown totals
    # match finer trains while each rack plane stays dispatch-bound —
    # the regime a fleet sweep actually runs in
    return FleetSim(
        n_racks=n_racks,
        hosts_per_rack=HOSTS_PER_RACK,
        granularity_bytes=65536.0,
        max_events_per_access=8,
        mesh=mesh,
    )


def build_tenants(n_hosts: int):
    from repro.core.fleet import synthetic_tenant

    # ~1.5 tenants per host keeps every host busy without overflowing DRAM
    return [
        synthetic_tenant(f"t{i}", seed=i, gib=10.0)
        for i in range(int(n_hosts * 1.5))
    ]


def sequential_eval(fleet, per_frac):
    """One per-rack dispatch at a time: K host round-trips."""
    from repro.core.analyzer import EpochAnalyzer

    an = EpochAnalyzer(
        fleet.flat,
        bw_window_ns=fleet.bw_window_ns,
        n_windows=fleet.n_windows,
        dtype=fleet.dtype,
    )
    out = []
    for traces, _ in per_frac:
        for rack_rows in traces:
            out.append(an.analyze_batch(rack_rows))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--racks", type=int, default=FULL_RACKS)
    ap.add_argument("--fractions", type=int, default=FULL_FRACTIONS)
    ap.add_argument("--quick", action="store_true", help="CI smoke: 4 racks x 2 fractions")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="BENCH_fleet.json")
    args = ap.parse_args(argv)
    R = 4 if args.quick else args.racks
    F = 2 if args.quick else args.fractions

    import jax

    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh()
    n_dev = jax.device_count()
    fracs = tuple(np.linspace(0.0, 1.0, F))
    n_hosts = R * HOSTS_PER_RACK
    tenants = build_tenants(n_hosts)
    K = F * R

    fleet_1dev = build_fleet(R)
    fleet_mesh = build_fleet(R, mesh=mesh)

    # the placement/synthesis half is shared by every path; stage it once so
    # the timed region measures dispatch, as the frontier itself does
    per_frac = []
    for f in fracs:
        placements = fleet_1dev.place(tenants, "least_loaded", float(f))
        per_frac.append(fleet_1dev._rack_timelines(placements))
    all_traces = [rows for traces, _ in per_frac for rows in traces]

    # warm every path (compile out of the timed region)
    fleet_1dev._dispatch(all_traces, tiles=F, mesh=None)
    fleet_mesh._dispatch(all_traces, tiles=F, mesh=mesh)
    sequential_eval(fleet_1dev, per_frac[:1])

    def timed(fn, repeats):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = fn()
            best = min(best, time.perf_counter() - t0)
        return best, out

    t_shard, bd_shard = timed(
        lambda: fleet_mesh._dispatch(all_traces, tiles=F, mesh=mesh), args.repeats
    )
    t_stack, bd_stack = timed(
        lambda: fleet_1dev._dispatch(all_traces, tiles=F, mesh=None), args.repeats
    )
    t_seq, bd_seq = timed(
        lambda: sequential_eval(fleet_1dev, per_frac), max(args.repeats // 2, 1)
    )

    # plane-for-plane parity: sharded vs 1-device stacked, and vs sequential
    def worst_rel(a_list, b_list):
        worst = 0.0
        for a, b in zip(a_list, b_list):
            for f in ("latency_ns", "congestion_ns", "bandwidth_ns"):
                x, y = getattr(a, f), getattr(b, f)
                worst = max(worst, abs(x - y) / max(abs(y), 1.0))
        return worst

    parity_shard = worst_rel(bd_shard, bd_stack)
    parity_seq = worst_rel(bd_shard, bd_seq)

    # the capacity-planning curve itself (full frontier path, end to end)
    points = fleet_mesh.frontier(tenants, offload_fractions=fracs)
    stats = fleet_mesh.last_dispatch
    curve = [
        {
            "offload_fraction": p.offload_fraction,
            "stranded_recovered_gb": p.stranded_recovered_gb,
            "p99_slowdown": p.p99_slowdown,
            "mean_slowdown": p.mean_slowdown,
        }
        for p in points
    ]

    speedup_vs_seq = t_seq / t_shard
    speedup_vs_stack = t_stack / t_shard
    full_scale = K >= MIN_K and n_hosts >= MIN_HOSTS
    gates = {
        "sharded_parity_le_1e-6": bool(parity_shard <= PARITY_GATE),
        "curve_at_100plus_hosts": bool(n_hosts >= MIN_HOSTS) if not args.quick else None,
        "throughput_ge_3x_at_8dev": (
            bool(speedup_vs_seq >= SPEEDUP_GATE) if full_scale else None
        ),
    }
    ok = all(v for v in gates.values() if v is not None)

    record = {
        "bench": "fleet_scaling",
        "platform": platform.platform(),
        "python": sys.version.split()[0],
        "physical_cores": os.cpu_count(),
        "jax_devices": n_dev,
        "device": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "racks": R,
        "hosts_per_rack": HOSTS_PER_RACK,
        "n_hosts": n_hosts,
        "n_tenants": len(tenants),
        "offload_fractions": F,
        "k_planes": K,
        "dispatch_stats": {
            "devices_used": stats.devices_used,
            "shard_rows": stats.shard_rows,
            "rows": stats.rows,
            "padded_fraction": stats.padded_fraction,
        },
        "sharded_s": t_shard,
        "stacked_1dev_s": t_stack,
        "sequential_per_rack_s": t_seq,
        "speedup_sharded_vs_sequential": speedup_vs_seq,
        "speedup_sharded_vs_stacked_1dev": speedup_vs_stack,
        "max_rel_err_sharded_vs_stacked": parity_shard,
        "max_rel_err_sharded_vs_sequential": parity_seq,
        "frontier": curve,
        "gates": gates,
        "pass": bool(ok),
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record, indent=1))
    if not ok:
        print("ACCEPTANCE GATE FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
