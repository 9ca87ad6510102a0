"""Device: share of the traced window in which no op ran (1 - busy union
/ window), averaged over the chips used."""
import readers


def read(ctx):
    return readers.device_idle(ctx)
