"""Memory program: routed-expert bytes priced over all bytes of the steps'
programs (cxlsim.bytes.expert / cxlsim.bytes.priced counters), %."""
import program_spans


def read(ctx):
    t = program_spans.totals()
    if t is None or not t.get("cxlsim.bytes.priced", (0, 0.0))[0]:
        return None
    return 100.0 * t.get("cxlsim.bytes.expert", (0, 0.0))[0] / t["cxlsim.bytes.priced"][0]
