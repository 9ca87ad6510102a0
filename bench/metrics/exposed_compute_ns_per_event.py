"""Engine: host seconds blocked on device results (DispatchStats.compute_s,
the part of device work nothing hid) per priced event."""
import readers


def read(ctx):
    return readers.per_event_ns(ctx, "compute_s")
