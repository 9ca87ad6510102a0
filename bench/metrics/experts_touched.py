"""Memory program: held expert-layer slots a step's routing read, over
those held (cxlsim.experts.touched / cxlsim.experts.held counters), %."""
import program_spans


def read(ctx):
    t = program_spans.totals()
    if t is None or not t.get("cxlsim.experts.held", (0, 0.0))[0]:
        return None
    return 100.0 * t.get("cxlsim.experts.touched", (0, 0.0))[0] / t["cxlsim.experts.held"][0]
