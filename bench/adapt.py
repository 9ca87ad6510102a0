"""Hand the benchmark's inputs to the program in the program's own types,
and read its reports back as plain arrays in nanoseconds."""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

GIB = 1 << 30


def topology(fabric: Dict, n_hosts: int):
    from repro.core import Pool, Switch, Topology

    pools = [
        Pool(p["name"], p["latency_ns"], p["bandwidth_gbps"], int(p["capacity_gib"] * GIB),
             parent=p.get("parent"), is_local=bool(p.get("is_local", False)))
        for p in fabric["pools"]
    ]
    switches = [
        Switch(s["name"], latency_ns=s["latency_ns"], bandwidth_gbps=s["bandwidth_gbps"],
               stt_ns=s["stt_ns"], parent=s.get("parent"),
               discipline=s.get("discipline", "fifo"),
               class_weights=tuple(s["class_weights"]) if s.get("class_weights") else None)
        for s in fabric["switches"]
    ]
    rc = fabric["rc"]
    return Topology(
        pools, switches, rc_latency_ns=rc["latency_ns"], rc_bandwidth_gbps=rc["bandwidth_gbps"],
        rc_stt_ns=rc["stt_ns"], local_dram_latency_ns=fabric["local_dram_latency_ns"],
        n_hosts=n_hosts, n_qos_classes=fabric.get("qos_classes"),
    )


def memory_program(regions: Sequence, phases: Sequence):
    """(RegionMap, [Phase]) from ``bench.tenants.build``'s tuples."""
    from repro.core import RegionMap
    from repro.core.tracer import Access, Phase

    rmap = RegionMap()
    for name, nbytes, cls in regions:
        rmap.alloc(name, nbytes, cls)
    out: List = [
        Phase(name, flops=flops, accesses=tuple(Access(r, b, is_write=w) for r, b, w in acc))
        for name, flops, acc in phases
    ]
    return rmap, out


def report_ns(rep, hosts: bool) -> Dict[str, np.ndarray]:
    """A SimReport or FabricReport as the reference's keys, in ns."""
    ns = 1e9
    out = {
        "latency": rep.latency_s * ns,
        "congestion": rep.congestion_s * ns,
        "bandwidth": rep.bandwidth_s * ns,
        "per_pool_latency": np.array(rep.per_pool_latency_ns, np.float64),
        "per_switch_congestion": np.array(rep.per_switch_congestion_ns, np.float64),
        "per_switch_bandwidth": np.array(rep.per_switch_bandwidth_ns, np.float64),
    }
    if hosts:
        out["per_host_latency"] = np.array([h.latency_s * ns for h in rep.hosts])
        out["per_host_congestion"] = np.array([h.congestion_s * ns for h in rep.hosts])
        out["per_host_bandwidth"] = np.array([h.bandwidth_s * ns for h in rep.hosts])
    return out

