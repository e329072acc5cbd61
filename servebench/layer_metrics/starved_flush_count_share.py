"""Scheduler: the part of `starved_share` the host spent waiting for a
flush count at a full barrier (/debug/ticks: `starved_by` of the span
`drain.flush_count`)."""
from servebench.starved import by_span, share


def read(ctx):
    return share(ctx, by_span("drain.flush_count"))
