"""Session: client seconds held by the engine's backpressure in submit
(the cxlsim.submit_wait span) per priced event."""
import program_spans


def read(ctx):
    return program_spans.per_event_ns(ctx, "cxlsim.submit_wait")
