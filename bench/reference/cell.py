"""What one client call should fold, by the reference: a batch of epochs
priced on one fabric.  Built from the configuration and the traffic alone."""

from __future__ import annotations

from typing import Dict, List

from . import oracle


class Batch:
    def __init__(self, flat: Dict, epochs: List[Dict], n_windows: int, qos_on: bool = False):
        self.flat = flat
        self.epochs = epochs
        self.n_windows = int(n_windows)
        self.hosts = int(flat["H"])
        self.qos_on = bool(qos_on)
        self.events_per_call = int(sum(len(e["t"]) for e in epochs))
        self.epochs_per_call = sum(1 for e in epochs if len(e["t"]))

    def expected(self, q=oracle.exact) -> Dict:
        return oracle.price_batch(self.flat, self.epochs, self.n_windows, q=q)


def pool_of(regions, placement: Dict[str, str], pool_names) -> Dict[str, int]:
    """Region name -> pool index under a class -> pool placement; classes
    not named stay in local DRAM (pool 0)."""
    idx = {n: i for i, n in enumerate(pool_names)}
    return {name: idx[placement[cls]] if cls in placement else 0 for name, _, cls in regions}


def tenant_batch(cfg: Dict, programs) -> Batch:
    """One co-scheduled round of ``programs`` (one per host) on the
    configuration's fabric, priced per layer epoch."""
    sim = cfg["simulator"]
    flat = oracle.flatten(cfg["fabric"], len(programs))
    per_host = [
        oracle.synthesize(
            regions, phases, pool_of(regions, cfg["placement"], flat["pool_names"]),
            sim["granularity_bytes"], sim["max_events_per_access"],
        )
        for regions, phases in programs
    ]
    epochs = per_host[0] if len(per_host) == 1 else oracle.merge(per_host)
    return Batch(flat, epochs, sim["n_windows"])

