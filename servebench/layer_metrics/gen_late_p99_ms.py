"""How late the generator ran: actual send minus due, 99th percentile,
on the generator's own clock. A starved generator must not read as a
fast server."""
from servebench.metrics import percentile


def read(ctx):
    return percentile(ctx.late_ms, 99) if ctx.late_ms else None
