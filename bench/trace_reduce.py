"""Profiler trace -> device busy time, idle gaps and time per jitted module.

``extract`` keeps what the reduction reads from an ``.xplane.pb``: on every
TPU plane the ``XLA Ops`` and ``XLA Modules`` lines, and on the host the
benchmark's own spans (names starting ``bench.``).  ``reduce`` works on
that plain form, so a recorded trace can be kept small and tested.

  busy_s      union of the op intervals inside the traced window, averaged
              over the chips that ran anything;
  window_s    the ``bench.window`` span (the whole timed window);
  modules     device seconds per jitted module name, summed over chips;
  ops         device seconds per op name, summed over chips;
  gaps        idle intervals of the first chip inside the window, each
              labelled by the innermost ``bench.*`` span open at its middle.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, found {files}")
    return files[0]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit()


def extract(path: str) -> Dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if is_device_plane(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events
                    ]
            devices.append({"name": plane.name, "lines": lines})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, float(e.start_ns), float(e.duration_ns)])
    return {"devices": devices, "spans": spans}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def module_name(name: str) -> str:
    """``jit_f(12)`` -> ``jit_f``: the program id suffix varies per run."""
    return _SUFFIX.sub("", name)


def window_of(ex: Dict) -> Tuple[float, float]:
    wins = [(s, s + d) for n, s, d in ex["spans"] if n == WINDOW_SPAN]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found {len(wins)}")
    return wins[0]


def label_at(spans: List, t: float) -> str:
    """The innermost (latest-starting) bench span open at ``t``."""
    best: Optional[Tuple[float, str]] = None
    for n, s, d in spans:
        if s <= t <= s + d and n != WINDOW_SPAN and (best is None or s > best[0]):
            best = (s, n)
    return best[1] if best else "bench.window"


def reduce(ex: Dict, top: int = 10) -> Dict:
    lo, hi = window_of(ex)
    busy, modules, ops = [], {}, {}
    first_busy = None
    for dev in ex["devices"]:
        evs = dev["lines"].get(OPS_LINE, [])
        iv = union(clip([(s, s + d) for _, s, d in evs], lo, hi))
        if not iv:
            continue
        b = sum(y - x for x, y in iv)
        busy.append(b)
        if first_busy is None:
            first_busy = iv
        for n, s, d in evs:
            c = clip([(s, s + d)], lo, hi)
            if c:
                ops[n] = ops.get(n, 0.0) + (c[0][1] - c[0][0])
        for n, s, d in dev["lines"].get(MODULES_LINE, []):
            c = clip([(s, s + d)], lo, hi)
            if c:
                key = module_name(n)
                modules[key] = modules.get(key, 0.0) + (c[0][1] - c[0][0])
    gaps = []
    if first_busy is not None:
        edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((label_at(ex["spans"], 0.5 * (a + b)), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": (sum(busy) / len(busy) * ns) if busy else 0.0,
        "chips": len(busy),
        "modules": {k: v * ns for k, v in modules.items()},
        "ops": {k: v * ns for k, v in ops.items()},
        "device_ops": [[k, v * ns] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for n, s in gaps[:top]],
    }


def module_seconds(red: Dict, marker: str) -> Optional[float]:
    """Device seconds of the modules whose name holds ``marker``; None when
    no such module ran in the window."""
    hits = [v for k, v in red["modules"].items() if marker in k]
    return sum(hits) if hits else None
