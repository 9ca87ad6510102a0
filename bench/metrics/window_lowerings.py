"""AOT lowerings inside the window (AotDispatchCache.total_lowerings()
delta); 0 when set-up warmed every shape."""
import readers


def read(ctx):
    return readers.lowerings(ctx)
