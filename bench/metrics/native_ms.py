"""Session: native wall time per step of the attached program
(SimReport.native_s over the steps in the window)."""


def read(ctx):
    if ctx.delta.get("native_s") is None or not ctx.n_calls:
        return None
    return ctx.delta["native_s"] / ctx.n_calls * 1e3
