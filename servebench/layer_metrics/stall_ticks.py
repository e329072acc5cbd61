"""Scheduler: the window's untraced ticks that STALLED (/debug/ticks:
`stall` not null: a wall of over ten times the median of the last 64
ticks and over 0.25 s, whatever phase held it, or a block fetch by the
same rule). 0.0 in a sound run. Leaves each one's account (phase, span,
cause, what the process and the machine did meanwhile) in the info line
as `stalled_ticks`."""
from servebench.offcpu import stalled


def read(ctx):
    found = stalled(ctx)
    if found is None:
        return None
    ctx.info["stalled_ticks"] = found
    return float(len(found))
