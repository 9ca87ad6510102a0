"""Analyzer graph: least bytes of the priced events (costs.py) at the
chip's peak HBM bandwidth (peaks.json), over the modules' device time."""
import readers


def read(ctx):
    return readers.analyzer_roofline(ctx)
