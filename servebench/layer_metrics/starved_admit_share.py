"""Scheduler: the part of `starved_share` the host spent admitting
requests (/debug/ticks: `starved_by` of the span `admit` and of
`admit.seed`, the device edits of an inline admission)."""
from servebench.starved import by_span, share


def read(ctx):
    return share(ctx, by_span("admit", "admit.seed"))
