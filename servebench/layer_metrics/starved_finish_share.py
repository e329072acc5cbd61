"""Scheduler: the part of `starved_share` whose wait began at a finish
barrier (/debug/ticks: `starved_s` of the ticks whose `starved_cause` is
`finish`)."""
from servebench.starved import by_cause, share


def read(ctx):
    return share(ctx, by_cause("finish"))
