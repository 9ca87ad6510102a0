"""Simulated memory events priced per second: every real event of every
call in the window, over the whole window (flush included)."""


def read(ctx):
    return ctx.events / ctx.window_s if ctx.events else None
