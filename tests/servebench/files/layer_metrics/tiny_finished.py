"""Tests only: requests that finished inside the window."""


def read(ctx):
    return float(sum(1 for s in ctx.streams
                     if s.finished and ctx.w0 <= s.end < ctx.w1))
