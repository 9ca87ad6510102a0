"""Model step: the window's decode steps' least HBM bytes (costs_decode.py,
summed by the decode entry into its counter per step at that step's cache
length) at the chip's peak HBM bandwidth (peaks.json), over their native
time (SimReport.native_s), %."""
import costs_decode
import program_spans


def read(ctx):
    t = program_spans.totals()
    native = ctx.delta.get("native_s")
    least = (t or {}).get(costs_decode.LEAST_BYTES, (0, 0.0))[0]
    if not native or not least:
        return None
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / native
