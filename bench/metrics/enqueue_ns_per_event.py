"""Engine: host seconds calling the analyzer's executable (the
cxlsim.enqueue span; a compile inside the call counts here too, and in
window_compiles) per priced event."""
import program_spans


def read(ctx):
    return program_spans.per_event_ns(ctx, "cxlsim.enqueue")
