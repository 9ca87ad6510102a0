"""Entry ``fleet``: ``FleetSim.frontier`` over racks of pooled hosts,
sharded over every chip of the machine.

One client call is one frontier: every tenant is placed for each offload
fraction, each rack's tenants merge onto its timeline, and all fractions
times racks go to the chips as one ``[F*R, B, N]`` dispatch sharded on its
leading axis.  The call returns when every rack's pricing is on the host.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

import adapt
import compare
import harness
import tenants
from reference import oracle, placement


class FleetReference:
    """Every rack row of one frontier, as the reference places and prices
    it; only the rows in ``sample`` are priced."""

    def __init__(self, cfg: Dict, wl: Dict, seed: int):
        tr = harness.load_json("traffic", wl["traffic"] + ".json")
        self.n_racks, self.hosts_per_rack = tr["racks"], tr["hosts_per_rack"]
        self.fractions = [float(f) for f in tr["offload_fractions"]]
        self.mix = tenants.draw({"tenants": tr["rack_tenants"] * self.n_racks}, seed)
        self.programs = tenants.programs(cfg, self.mix)
        sim = cfg["simulator"]
        self.cfg, self.sim = cfg, sim
        self.flat = oracle.flatten(cfg["fabric"], self.hosts_per_rack)
        self.hosts, self.qos_on = self.hosts_per_rack, False
        per_tenant = sum(
            oracle.count_events(ph, sim["granularity_bytes"], sim["max_events_per_access"])
            for _, ph in self.programs
        )
        self.events_per_call = len(self.fractions) * per_tenant
        rows = [(f, r) for f in range(len(self.fractions)) for r in range(self.n_racks)]
        rng = np.random.default_rng(tenants.seed_words(seed) + [len(rows)])
        pick = rng.choice(len(rows), size=min(int(tr["sampled_rows"]), len(rows)), replace=False)
        # the fullest offload's first rack always: the most fabric traffic
        self.sample = sorted({rows[i] for i in pick} | {(len(self.fractions) - 1, 0)})
        self.rows_per_call = len(rows)

    def offload_classes(self):
        return tuple(self.cfg["placement"])

    def row_epochs(self, f: int, r: int) -> List[Dict]:
        pools = self.flat["pool_names"]
        shared = pools.index(next(iter(self.cfg["placement"].values())))
        local = next(i for i, p in enumerate(self.cfg["fabric"]["pools"]) if p.get("is_local"))
        cap = {p["name"]: p["capacity_gib"] * placement.GIB for p in self.cfg["fabric"]["pools"]}
        placed = placement.least_loaded(
            self.programs, self.n_racks, self.hosts_per_rack, cap[pools[local]],
            cap[pools[shared]], self.offload_classes(), self.fractions[f],
        )
        per, hosts = [], []
        for (regions, phases), (rack, host, spilled) in zip(self.programs, placed):
            if rack != r:
                continue
            pool_of = {n: shared if spilled.get(n) else local for n, _, _ in regions}
            per.append(oracle.synthesize(regions, phases, pool_of, self.sim["granularity_bytes"],
                                         self.sim["max_events_per_access"]))
            hosts.append(host)
        return oracle.merge(per, hosts=hosts)

    def expected(self, q=oracle.exact) -> Dict:
        """The sampled rows' pricing, keyed by (fraction index, rack)."""
        return {
            fr: oracle.price_batch(self.flat, self.row_epochs(*fr), self.sim["n_windows"], q=q)
            for fr in self.sample
        }


def reference(cfg: Dict, wl: Dict, seed: int) -> FleetReference:
    return FleetReference(cfg, wl, seed)


def _breakdown(bd) -> Dict:
    return {
        "latency": bd.latency_ns, "congestion": bd.congestion_ns, "bandwidth": bd.bandwidth_ns,
        "per_pool_latency": bd.per_pool_latency_ns,
        "per_switch_congestion": bd.per_switch_congestion_ns,
        "per_switch_bandwidth": bd.per_switch_bandwidth_ns,
        "per_host_latency": bd.per_host_latency_ns,
        "per_host_congestion": bd.per_host_congestion_ns,
        "per_host_bandwidth": bd.per_host_bandwidth_ns,
    }


class Entry:
    CALL_SPAN = "bench.frontier"
    FILLED = ("stage_s", "transfer_s", "compute_s")

    def __init__(self, cfg: Dict, wl: Dict, seed: int):
        import jax

        from repro.core import FleetSim, TenantSpec
        from repro.launch.mesh import make_data_mesh

        self.cfg, self.wl = cfg, wl
        self.ref = FleetReference(cfg, wl, seed)
        ref, sim = self.ref, cfg["simulator"]
        self.specs = []
        for i, (regions, phases) in enumerate(ref.programs):
            rmap, ph = adapt.memory_program(regions, phases)
            self.specs.append(TenantSpec(f"t{i}", tuple(ph), rmap))
        with harness.span("bench.attach"):
            self.fleet = FleetSim(
                n_racks=ref.n_racks, hosts_per_rack=ref.hosts_per_rack,
                rack_topology=adapt.topology(cfg["fabric"], ref.hosts_per_rack),
                epoch_mode=sim["epoch"], granularity_bytes=sim["granularity_bytes"],
                max_events_per_access=sim["max_events_per_access"], n_windows=sim["n_windows"],
                mesh=make_data_mesh(wl["chips"]), offload_classes=ref.offload_classes(),
            )
        self.events_per_call = ref.events_per_call
        self.hosts, self.qos_on = ref.hosts, ref.qos_on
        self.counts = dict(calls=0, rows=0, stage_s=0.0, transfer_s=0.0, compute_s=0.0)
        self.rows: List[Dict] = []
        self.keep = False
        for _ in range(int(wl.get("warm_calls", 1))):
            self.call()
        self.keep = True
        jax.effects_barrier()

    def call(self) -> None:
        points = self.fleet.frontier(self.specs, offload_fractions=self.ref.fractions)
        st = self.fleet.last_dispatch
        c = self.counts
        c["calls"] += 1
        c["rows"] += sum(len(p.report.breakdowns) for p in points)
        c["stage_s"] += st.stage_s
        c["transfer_s"] += st.transfer_s
        c["compute_s"] += st.compute_s
        if self.keep:
            self.rows.append({fr: _breakdown(points[fr[0]].report.breakdowns[fr[1]])
                              for fr in self.ref.sample})

    def flush(self) -> None:
        """Each frontier returns with its pricing on the host."""

    def snapshot(self) -> Dict:
        return dict(self.counts, dropped=0, native_s=None, compile_s=None, n_kept=len(self.rows))

    def expected(self, q=oracle.exact) -> Dict:
        return self.ref.expected(q)

    def readings(self, win, ref: Dict) -> Dict[str, float]:
        kept = self.rows[win.snap0["n_kept"]:win.snap1["n_kept"]]
        out = {"latency_gap": 0.0, "congestion_gap": 0.0, "bandwidth_gap": 0.0}
        for rows in kept:
            for fr, got in rows.items():
                out = compare.worst(compare.class_gaps(got, ref[fr]), out)
        if len(kept) != win.n_calls:
            out = {k: float("inf") for k in out}
        rows = win.snap1["rows"] - win.snap0["rows"]
        out["rows_gap"] = float(abs(rows - win.n_calls * self.ref.rows_per_call))
        return out

    def close(self) -> None:
        self.fleet = None
