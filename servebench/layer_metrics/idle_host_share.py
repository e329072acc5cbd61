"""Device: idle time of chip 0 while the tick thread was inside the
tick doing host work (any `bf.tick` span but the fetch), over the traced
window: the device waiting for the host. (servebench/tickspans.py)"""
from servebench.tickspans import FETCH, idle_share, in_tick


def read(ctx):
    return idle_share(ctx, lambda name: in_tick(name) and name != FETCH)
