"""Staging: host seconds packing events into device planes
(DispatchStats.stage_s) per priced event."""
import readers


def read(ctx):
    return readers.per_event_ns(ctx, "stage_s")
