"""Analyzer graph: real events over the slots of the dispatched planes
(cxlsim.events / cxlsim.slots counters, both axes of [B, N]), %."""
import program_spans


def read(ctx):
    return program_spans.slot_fill(ctx)
