"""The program's own spans and counters (``repro.core.spans``), summed over
the traced window: the program adds to its table only while a profiler
session records, and the benchmark's session records exactly the window.

A program without that module, or a run in which it recorded nothing,
gives None, and the metric stays out of the line.
"""

from __future__ import annotations

import importlib
from typing import Dict, Optional, Tuple

SLOTS = "cxlsim.slots"
EVENTS = "cxlsim.events"
BACKEND_COMPILE = "cxlsim.compile.backend"


def totals() -> Optional[Dict[str, Tuple[int, float]]]:
    """name -> (count, seconds), or None where there is nothing to read."""
    try:
        spans = importlib.import_module("repro.core.spans")
    except ImportError:
        return None
    return spans.traced_totals() or None


def per_event_ns(ctx, name: str) -> Optional[float]:
    """A span's traced seconds over the events priced in the window."""
    t = totals()
    if t is None or not ctx.events:
        return None
    return t.get(name, (0, 0.0))[1] / ctx.events * 1e9


def slot_fill(ctx) -> Optional[float]:
    """Real events over dispatched plane slots, %."""
    t = totals()
    if t is None or not t.get(SLOTS, (0, 0.0))[0]:
        return None
    return 100.0 * t.get(EVENTS, (0, 0.0))[0] / t[SLOTS][0]


def compiles(ctx) -> Optional[float]:
    """Backend compiles in the window, any thread, any call site."""
    t = totals()
    return None if t is None else float(t.get(BACKEND_COMPILE, (0, 0.0))[0])
