"""Device: idle time of chip 0 while the tick thread was outside
`bf.tick` (waiting for the serving lock, `bf.loop.lock`; idle,
`bf.loop.wait`; the profiler's own start), over the traced window.
(servebench/tickspans.py)"""
from servebench.tickspans import idle_share, in_tick


def read(ctx):
    return idle_share(ctx, lambda name: not in_tick(name))
