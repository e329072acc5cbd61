"""Scheduler: the part of `starved_share` the host spent handing drained
tokens to their requests (/debug/ticks: `starved_by` of the spans
`drain.emit` and `spec_emit`)."""
from servebench.starved import by_span, share


def read(ctx):
    return share(ctx, by_span("drain.emit", "spec_emit"))
