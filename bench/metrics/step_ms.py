"""Wall time per attached-program step with simulation on: the whole
window over the steps completed in it."""


def read(ctx):
    if ctx.entry != "attach" or not ctx.n_calls:
        return None
    return ctx.window_s / ctx.n_calls * 1e3
