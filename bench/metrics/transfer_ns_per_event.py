"""H2D: host seconds placing staged planes on the device
(DispatchStats.transfer_s) per priced event."""
import readers


def read(ctx):
    return readers.per_event_ns(ctx, "transfer_s")
