"""Device: 1 less the union of the operation intervals over the traced
window (device trace, averaged over the chips)."""


def read(ctx):
    busy, window = ctx.trace.get("busy_s"), ctx.trace.get("window_s")
    return 100.0 * (1.0 - busy / window) if busy and window else None
