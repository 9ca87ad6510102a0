"""Session: dispatcher seconds in the fold callbacks and future
resolution (the cxlsim.fold span) per priced event."""
import program_spans


def read(ctx):
    return program_spans.per_event_ns(ctx, "cxlsim.fold")
