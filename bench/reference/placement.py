"""Fleet placement, as the reference sees it: tenants assigned to the host
with the most free local DRAM, their offloadable regions spilled to the
rack's shared expander, largest first, until the offload fraction is met
(and further while the rest would not fit the host)."""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

GIB = 1 << 30


def least_loaded(
    programs: Sequence, n_racks: int, hosts_per_rack: int, local_bytes: float,
    shared_bytes: float, offload_classes: Sequence[str], fraction: float,
) -> List[Tuple[int, int, Dict[str, bool]]]:
    """Per tenant ``(rack, host, spilled)`` where ``spilled`` maps region
    name to whether it lives in the shared expander."""
    free_local = np.full((n_racks, hosts_per_rack), float(local_bytes))
    free_shared = np.full((n_racks,), float(shared_bytes))
    out = []
    for regions, _ in programs:
        regions = [r for r in regions if r[1] > 0]
        pinned = sum(r[1] for r in regions if r[2] not in offload_classes)
        off = sorted((r for r in regions if r[2] in offload_classes), key=lambda r: -r[1])
        target = fraction * sum(r[1] for r in off)
        spill, spill_b = [], 0.0
        for r in off:
            if spill_b >= target:
                break
            spill.append(r)
            spill_b += r[1]
        retained = [r for r in off if r not in spill]
        resident = lambda: pinned + sum(r[1] for r in retained)  # noqa: E731
        rack, host = divmod(int(np.argmax(free_local)), hosts_per_rack)
        while retained and resident() > free_local[rack, host]:
            r = retained.pop(0)
            spill.append(r)
            spill_b += r[1]
        if resident() > free_local[rack, host] or spill_b > free_shared[rack]:
            raise ValueError("the fleet's tenants do not fit its racks")
        free_local[rack, host] -= resident()
        free_shared[rack] -= spill_b
        names = {r[0] for r in spill}
        out.append((rack, host, {r[0]: r[0] in names for r in regions}))
    return out
