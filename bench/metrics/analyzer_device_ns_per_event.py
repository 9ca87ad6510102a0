"""Analyzer graph: device seconds of the jitted _analyze_* modules in the
trace, summed over chips, per priced event."""
import readers


def read(ctx):
    return readers.analyzer_device_ns_per_event(ctx)
