"""Entry ``fabric``: trace-driven tenants co-attached on one shared fabric.

One client call is ``FabricSession.round``: every tenant's epoch ``k``
merges onto one timeline, the round is submitted to the analysis engine
as one batch, and its pricing folds into the report asynchronously.
Nothing of the tenants lives on the chip but their events.
"""

from __future__ import annotations

from typing import Dict

import adapt
import compare
import harness
import tenants
from reference import cell, oracle


def reference(cfg: Dict, wl: Dict, seed: int) -> cell.Batch:
    """What each round should fold: every tenant's layer epochs merged on
    the shared fabric."""
    mix = tenants.draw(harness.load_json("traffic", wl["traffic"] + ".json"), seed)
    return cell.tenant_batch(cfg, tenants.programs(cfg, mix))


class Entry:
    CALL_SPAN = "bench.round"
    FILLED = ("stage_s", "transfer_s", "compile_s", "compute_s", "lowerings")

    def __init__(self, cfg: Dict, wl: Dict, seed: int):
        from repro.core import ClassMapPolicy, EpochSchedule, FabricSession, Tenant

        self.cfg, self.wl = cfg, wl
        sim = cfg["simulator"]
        self.mix = tenants.draw(harness.load_json("traffic", wl["traffic"] + ".json"), seed)
        self.programs = tenants.programs(cfg, self.mix)
        H = len(self.programs)
        ts = []
        for h, (regions, phases) in enumerate(self.programs):
            rmap, ph = adapt.memory_program(regions, phases)
            policy = ClassMapPolicy(cfg["placement"], granularity_bytes=sim["granularity_bytes"])
            ts.append(Tenant(f"{self.mix[h]['kind']}{h}", ph, rmap, policy))
        with harness.span("bench.attach"):
            self.session = FabricSession(
                adapt.topology(cfg["fabric"], H), ts, epoch=EpochSchedule(sim["epoch"]),
                n_windows=sim["n_windows"], max_events_per_access=sim["max_events_per_access"],
                pipeline=bool(sim["pipeline"]),
            )
        self.ref = cell.tenant_batch(cfg, self.programs)
        self.events_per_call = self.ref.events_per_call
        self.hosts, self.qos_on = self.ref.hosts, self.ref.qos_on
        for _ in range(int(wl.get("warm_calls", 2))):
            self.call()
        self.flush()

    def call(self) -> None:
        self.session.round()

    def flush(self) -> None:
        self.session.flush()

    def snapshot(self) -> Dict:
        rep = self.session.report
        return {
            "report": adapt.report_ns(rep, hosts=True),
            "calls": rep.rounds,
            "epochs": rep.epochs,
            "dropped": rep.dropped_batches,
            "native_s": None,
            "stage_s": rep.stage_s,
            "transfer_s": rep.transfer_s,
            "compile_s": rep.compile_s,
            "compute_s": rep.compute_s,
        }

    def expected(self, q=oracle.exact) -> Dict:
        return self.ref.expected(q)

    def readings(self, win, ref: Dict) -> Dict[str, float]:
        return compare.window_readings(win, ref, self.ref.epochs_per_call)

    def close(self) -> None:
        self.session.close()
