"""Plain reference of the three-delay epoch model, in float64 numpy.

An independent copy of the simulator's oracle (``analyze_ref``) together
with what it needs: the lowering of a fabric description to virtual-pool
routes, the tracer's deterministic event synthesis, and the co-scheduled
merge of several hosts' epochs.  It imports nothing of the program; its
inputs are the configuration files and the tenant programs of
``bench/tenants.py``.

``q`` is the rounding applied after every arithmetic step.  The identity
gives the float64 reference; ``round_bf16`` gives the control, the same
computation carried in bfloat16.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

DISCIPLINES = ("fifo", "priority", "wfq")

# the tracer's pacing model: a phase lasts max(flops / peak, bytes / HBM)
PACE_PEAK_FLOPS = 197e12
PACE_HBM_BYTES_PER_NS = 819.0

Q = Callable[[np.ndarray], np.ndarray]


def exact(a):
    return a


def round_bf16(a):
    import ml_dtypes

    return np.asarray(a, np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)


# --------------------------------------------------------------------------- #
# fabric lowering
# --------------------------------------------------------------------------- #


def flatten(fabric: Dict, n_hosts: int) -> Dict:
    """Virtual-pool tables of a fabric description (``fabric`` block of a
    configuration file): row ``vp = host * P + pool``; shared switches keep
    one column each, every host has a private root complex column."""
    pools, switches, rc = fabric["pools"], fabric["switches"], fabric["rc"]
    by_name = {s["name"]: s for s in switches}
    P, H = len(pools), int(n_hosts)
    n_sw = len(switches)
    S = n_sw + H
    col = {s["name"]: i for i, s in enumerate(switches)}

    def path(p):
        out, cur = [], p.get("parent")
        while cur is not None:
            out.append(by_name[cur])
            cur = by_name[cur].get("parent")
        return out

    lat = np.zeros((H * P,))
    route = np.zeros((H * P, S))
    for i, p in enumerate(pools):
        total = p["latency_ns"]
        if not p.get("is_local"):
            total += rc["latency_ns"] + sum(s["latency_ns"] for s in path(p))
        for h in range(H):
            vp = h * P + i
            lat[vp] = total
            if p.get("is_local"):
                continue
            route[vp, n_sw + h] = 1.0
            for s in path(p):
                route[vp, col[s["name"]]] = 1.0

    def depth(s):
        d, cur = 1, s.get("parent")
        while cur is not None:
            d, cur = d + 1, by_name[cur].get("parent")
        return d

    C = int(fabric.get("qos_classes", 1))
    weights = np.ones((S, C))
    for i, s in enumerate(switches):
        if s.get("class_weights"):
            weights[i] = s["class_weights"]
    disc = [s.get("discipline", "fifo") for s in switches] + ["fifo"] * H
    for d in disc:
        if d not in DISCIPLINES:
            raise ValueError(f"unknown discipline {d!r}")
    return {
        "P": P,
        "S": S,
        "H": H,
        "C": C,
        "pool_latency_ns": lat,
        "local_latency_ns": float(fabric["local_dram_latency_ns"]),
        "route": route,
        "stt_ns": np.array([s["stt_ns"] for s in switches] + [rc["stt_ns"]] * H, float),
        "bandwidth_gbps": np.array(
            [s["bandwidth_gbps"] for s in switches] + [rc["bandwidth_gbps"]] * H, float
        ),
        "stage_order": np.argsort(
            -np.array([depth(s) for s in switches] + [0] * H), kind="stable"
        ),
        "discipline": disc,
        "class_weights": weights,
        "pool_names": [p["name"] for p in pools],
    }


# --------------------------------------------------------------------------- #
# event synthesis (the tracer's deterministic spread)
# --------------------------------------------------------------------------- #


def count_events(phases: Sequence, granularity_bytes: float, max_events_per_access: int) -> int:
    """Events of one step: ``synthesize``'s count without the events."""
    return int(sum(
        min(max(np.ceil(nbytes / granularity_bytes), 1), max_events_per_access)
        for _, _, accesses in phases for _, nbytes, _ in accesses
    ))


def synthesize(
    regions: Sequence, phases: Sequence, pool_of: Dict[str, int],
    granularity_bytes: float, max_events_per_access: int, epoch_mode: str = "layer",
) -> List[Dict[str, np.ndarray]]:
    """Per-epoch events of one tenant step.  ``regions``/``phases`` as built
    by ``bench.tenants.build``; ``pool_of`` maps region name to pool index.
    An access of ``b`` bytes becomes ``min(ceil(b / g), max)`` events of
    equal share, spread evenly over the phase's paced duration."""
    if epoch_mode != "layer":
        raise ValueError("the benchmark prices layer epochs only")
    names = {r[0] for r in regions}
    epochs = []
    for _, flops, accesses in phases:
        total_b = sum(a[1] for a in accesses)
        dur = max(flops / PACE_PEAK_FLOPS * 1e9, total_b / PACE_HBM_BYTES_PER_NS, 1.0)
        t, b, pool = [], [], []
        for region, nbytes, _ in accesses:
            if region not in names:
                raise KeyError(region)
            n = int(min(max(np.ceil(nbytes / granularity_bytes), 1), max_events_per_access))
            within = np.arange(n, dtype=np.float64)
            t.append((within + 0.5) / float(n) * dur)
            b.append(np.full((n,), nbytes / n))
            pool.append(np.full((n,), pool_of[region], np.int64))
        epochs.append({
            "t": np.concatenate(t) if t else np.zeros((0,)),
            "bytes": np.concatenate(b) if b else np.zeros((0,)),
            "pool": np.concatenate(pool) if pool else np.zeros((0,), np.int64),
        })
    return epochs


def merge(per_host: Sequence[Sequence[Dict]], qos: Optional[Sequence[int]] = None,
          hosts: Optional[Sequence[int]] = None) -> List[Dict]:
    """Epoch ``k`` of every tenant on one timeline: concatenated in the
    order given, then a stable sort by time (tenants with fewer epochs sit
    out).  Tenant ``i`` runs on host ``hosts[i]`` (default ``i``)."""
    n = max(len(e) for e in per_host)
    hosts = list(range(len(per_host))) if hosts is None else list(hosts)
    out = []
    for k in range(n):
        parts = [(i, e[k]) for i, e in enumerate(per_host) if k < len(e) and len(e[k]["t"])]
        t = np.concatenate([p["t"] for _, p in parts])
        order = np.argsort(t, kind="stable")
        out.append({
            "t": t[order],
            "bytes": np.concatenate([p["bytes"] for _, p in parts])[order],
            "pool": np.concatenate([p["pool"] for _, p in parts])[order],
            "host": np.concatenate([np.full(len(p["t"]), hosts[i], np.int64) for i, p in parts])[order],
            "qos": np.concatenate([
                np.full(len(p["t"]), 0 if qos is None else qos[i], np.int64) for i, p in parts
            ])[order],
        })
    return out


# --------------------------------------------------------------------------- #
# the three delays
# --------------------------------------------------------------------------- #


def _serial_queue(arr: np.ndarray, stt: float, q: Q) -> np.ndarray:
    if len(arr) == 0:
        return arr
    idx = np.arange(len(arr), dtype=np.float64)
    shift = q(idx * stt)
    return q(np.maximum.accumulate(q(arr - shift)) + shift)


def analyze(flat: Dict, ev: Dict, bw_window_ns: float, n_windows: int, q: Q = exact) -> Dict:
    """One epoch's delays.  ``ev`` holds ``t``, ``pool``, ``bytes`` and
    optionally ``host``, ``qos``, ``weight``; bandwidth windows are
    ``n_windows`` static windows of ``bw_window_ns``, overflow clamped into
    the last (the jitted analyzers' window rule)."""
    P, S, H, C = flat["P"], flat["S"], flat["H"], flat["C"]
    n = len(ev["t"])
    z = lambda k: np.zeros((k,))
    if n == 0:
        return {"latency": 0.0, "congestion": 0.0, "bandwidth": 0.0,
                "per_pool_latency": z(P), "per_switch_congestion": z(S),
                "per_switch_bandwidth": z(S), "per_host_latency": z(H),
                "per_host_congestion": z(H), "per_host_bandwidth": z(H),
                "per_class_congestion": z(C)}
    t = q(np.asarray(ev["t"], np.float64).copy())
    pool = np.asarray(ev["pool"], np.int64)
    host = np.asarray(ev.get("host", np.zeros(n, np.int64)), np.int64)
    qcls = np.clip(np.asarray(ev.get("qos", np.zeros(n, np.int64)), np.int64), 0, C - 1)
    weight = np.asarray(ev.get("weight", np.ones(n)), np.float64)
    nbytes = q(np.asarray(ev["bytes"], np.float64))
    if host.max() >= H:
        raise ValueError(f"host {host.max()} on a {H}-host fabric")
    vp = host * P + pool

    lat = q(np.maximum(q(flat["pool_latency_ns"][vp] - flat["local_latency_ns"]), 0.0))
    lat = q(lat * weight)
    per_pool_lat = q(np.bincount(pool, weights=lat, minlength=P)[:P])
    per_host_lat = q(np.bincount(host, weights=lat, minlength=H)[:H])

    qos_on = C > 1 or any(d != "fifo" for d in flat["discipline"])
    per_sw_cong, per_host_cong, per_cls_cong = z(S), z(H), z(C)
    for s in flat["stage_order"]:
        stt = float(flat["stt_ns"][s])
        mask = flat["route"][vp, s] > 0
        if stt <= 0 or not mask.any():
            continue
        order = np.argsort(t, kind="stable")
        sub = order[mask[order]]
        disc = flat["discipline"][s] if qos_on else "fifo"
        if disc == "fifo":
            start = _serial_queue(t[sub], stt, q)
        elif disc == "priority":
            q_sub = qcls[sub]
            start = np.empty((len(sub),))
            for lvl in range(C):
                lv = q_sub <= lvl
                st = _serial_queue(t[sub[lv]], stt, q)
                start[q_sub == lvl] = st[q_sub[lv] == lvl]
        else:
            q_sub = qcls[sub]
            w = flat["class_weights"][s]
            start = np.empty((len(sub),))
            for c in range(C):
                cm = q_sub == c
                start[cm] = _serial_queue(t[sub[cm]], stt * float(w.sum()) / float(w[c]), q)
        delay = q(start - t[sub])
        t[sub] = start
        per_sw_cong[s] = delay.sum()
        per_host_cong += np.bincount(host[sub], weights=delay, minlength=H)[:H]
        per_cls_cong += np.bincount(qcls[sub], weights=delay, minlength=C)[:C]

    t_obs = q(t + lat)
    win = np.minimum((t_obs / bw_window_ns).astype(np.int64), n_windows - 1)
    per_sw_bw, per_host_bw = z(S), z(H)
    for s in range(S):
        bw = float(flat["bandwidth_gbps"][s])
        mask = flat["route"][vp, s] > 0
        if bw <= 0 or not mask.any():
            continue
        key = win[mask] * H + host[mask]
        wb_h = q(np.bincount(key, weights=nbytes[mask], minlength=n_windows * H)).reshape(n_windows, H)
        wbytes = q(wb_h.sum(axis=1))
        stretch = q(np.maximum(q(wbytes / bw) - bw_window_ns, 0.0))
        per_sw_bw[s] = stretch.sum()
        share = np.divide(wb_h, wbytes[:, None], out=np.zeros_like(wb_h), where=wbytes[:, None] > 0)
        per_host_bw += q(stretch[:, None] * q(share)).sum(axis=0)

    return {
        "latency": float(q(np.asarray(lat.sum()))),
        "congestion": float(q(np.asarray(per_sw_cong.sum()))),
        "bandwidth": float(q(np.asarray(per_sw_bw.sum()))),
        "per_pool_latency": per_pool_lat,
        "per_switch_congestion": q(per_sw_cong),
        "per_switch_bandwidth": q(per_sw_bw),
        "per_host_latency": per_host_lat,
        "per_host_congestion": q(per_host_cong),
        "per_host_bandwidth": q(per_host_bw),
        "per_class_congestion": q(per_cls_cong),
    }


def price_batch(
    flat: Dict, epochs: Sequence[Dict], n_windows: int,
    min_window_ns: float = 10_000.0, q: Q = exact,
) -> Dict:
    """Summed delays of a batch of epochs, each with the analyzer's window:
    ``n_windows`` windows tile the epoch's span (last time + 1 ns, at least
    ``min_window_ns``), and no window is shorter than 1 ns."""
    total = None
    for ev in epochs:
        if not len(ev["t"]):
            continue
        span = max(float(np.max(ev["t"])) + 1.0, min_window_ns)
        bd = analyze(flat, ev, max(span / n_windows, 1.0), n_windows, q)
        total = bd if total is None else {k: total[k] + bd[k] for k in bd}
    return total
