"""Device: idle time of chip 0 while the tick thread was inside
`bf.tick.drain.fetch`, over the traced window: host and device waiting
for each other (the transfer, the gap before a launched program starts,
a fetch queued behind a newer block). (servebench/tickspans.py)"""
from servebench.tickspans import FETCH, idle_share


def read(ctx):
    return idle_share(ctx, lambda name: name == FETCH)
