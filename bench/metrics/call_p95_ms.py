"""95th percentile of the wall time of every client call in the window."""
import readers


def read(ctx):
    return readers.call_p95_ms(ctx)
