"""Shared arithmetic of the metric readers in ``metrics/``.

A reader gets the run's context and returns a number, or None where the
cell's path has nothing to read (the metric then stays out of the line).
"""

from __future__ import annotations

from typing import Optional

import costs
import trace_reduce

ANALYZER_MODULE = "_analyze_"


def per_event_ns(ctx, field: str) -> Optional[float]:
    """A window total of a ``DispatchStats`` field over the events priced."""
    if field not in ctx.filled or ctx.delta.get(field) is None or not ctx.events:
        return None
    return ctx.delta[field] / ctx.events * 1e9


def lowerings(ctx) -> Optional[float]:
    return float(ctx.lowerings) if "lowerings" in ctx.filled else None


def call_p95_ms(ctx) -> Optional[float]:
    """Nearest-rank 95th percentile of every call in the window."""
    xs = sorted(ctx.call_s)
    if not xs:
        return None
    k = max(0, -(-95 * len(xs) // 100) - 1)
    return xs[k] * 1e3


def analyzer_device_s(ctx) -> Optional[float]:
    if ctx.trace is None:
        return None
    return trace_reduce.module_seconds(ctx.trace, ANALYZER_MODULE)


def analyzer_device_ns_per_event(ctx) -> Optional[float]:
    s = analyzer_device_s(ctx)
    if s is None or not ctx.events:
        return None
    return s / ctx.events * 1e9


def analyzer_roofline(ctx) -> Optional[float]:
    s = analyzer_device_s(ctx)
    if not s or not ctx.events:
        return None
    chips = max(ctx.trace["chips"], 1)
    return costs.roofline_share(ctx.events, ctx.hosts, ctx.qos_on, s,
                                ctx.peaks["hbm_bytes_per_s"] * chips)


def device_idle(ctx) -> Optional[float]:
    if ctx.trace is None or ctx.trace["window_s"] <= 0 or ctx.trace["chips"] == 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
