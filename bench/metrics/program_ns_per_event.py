"""Memory program: host seconds building each step's program from its
routing (the cxlsim.program span) per priced event."""
import program_spans


def read(ctx):
    return program_spans.per_event_ns(ctx, "cxlsim.program")
