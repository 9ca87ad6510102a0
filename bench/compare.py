"""The comparison that decides ``correct``.

Each delay class (latency, congestion, bandwidth) is compared as one
number: the widest gap between what the window folded and the f64
reference, over the class total and every entry of its per-pool,
per-switch and per-host vectors.  A vector entry's gap is taken against
the vector's largest reference entry, so a switch that carries almost no
delay cannot swing the reading; a total's gap is taken against the total.
Gaps below one nanosecond of scale are read against 1 ns.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

CLASSES = {
    "latency": ("latency", "per_pool_latency", "per_host_latency"),
    "congestion": ("congestion", "per_switch_congestion", "per_host_congestion"),
    "bandwidth": ("bandwidth", "per_switch_bandwidth", "per_host_bandwidth"),
}


def gap(got, ref) -> float:
    got = np.atleast_1d(np.asarray(got, np.float64))
    ref = np.atleast_1d(np.asarray(ref, np.float64))
    if got.shape != ref.shape:
        return float("inf")
    if not np.all(np.isfinite(got)):
        return float("inf")
    scale = max(float(np.max(np.abs(ref))), 1.0)
    return float(np.max(np.abs(got - ref)) / scale)


def class_gaps(got: Dict, ref: Dict) -> Dict[str, float]:
    """``got`` and ``ref`` hold the reference's keys; a key ``got`` lacks
    (a single-host report has no per-host vectors) is not compared."""
    out = {}
    for cls, keys in CLASSES.items():
        worst = 0.0
        for k in keys:
            if k in got and got[k] is not None:
                worst = max(worst, gap(got[k], ref[k]))
        out[f"{cls}_gap"] = worst
    return out


def window_readings(win, ref: Dict, epochs_per_call: int) -> Dict[str, float]:
    """The compared numbers of a window whose every call folds the same
    batch: the report's window delta against ``n_calls`` times the
    reference's pricing of one call, and the epochs folded, exactly."""
    got = {k: np.asarray(win.snap1["report"][k], np.float64)
           - np.asarray(win.snap0["report"][k], np.float64) for k in win.snap0["report"]}
    out = class_gaps(got, scaled(ref, win.n_calls))
    folded = win.snap1["epochs"] - win.snap0["epochs"]
    out["epochs_gap"] = float(abs(folded - win.n_calls * epochs_per_call))
    return out


def scaled(bd: Dict, n: float) -> Dict:
    return {k: (np.asarray(v, np.float64) * n if v is not None else None) for k, v in bd.items()}


def checks(readings: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Dict]:
    """Every reading beside its limit; a reading with no limit is an error
    in the workload file, not a pass."""
    out = {}
    for name, value in readings.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the workload file")
        out[name] = {"value": float(value), "limit": float(limits[name])}
    return out


def all_within(checks_: Dict[str, Dict]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks_.values())


def worst(readings: Dict[str, float], other: Optional[Dict[str, float]]) -> Dict[str, float]:
    if other is None:
        return dict(readings)
    return {k: max(v, other.get(k, v)) for k, v in readings.items()}
