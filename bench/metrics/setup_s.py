"""Process start to the first timed call: imports, weights, compiles
(from the persistent cache after a cell's first run) and warm calls."""


def read(ctx):
    return ctx.setup_s
