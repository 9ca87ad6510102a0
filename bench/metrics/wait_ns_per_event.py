"""Engine: host seconds blocked on the analyzer's device outputs (the
cxlsim.wait span) per priced event."""
import program_spans


def read(ctx):
    return program_spans.per_event_ns(ctx, "cxlsim.wait")
