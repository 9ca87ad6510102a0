"""Engine: host seconds copying the analyzer's outputs to the host (the
cxlsim.d2h span) per priced event."""
import program_spans


def read(ctx):
    return program_spans.per_event_ns(ctx, "cxlsim.d2h")
