"""Device: the share of the window's untraced ticks that the device
waited for the host, by the program's starvation clock (/debug/ticks:
`starved_s` over `wall_s + gap_s`, ticks whose record says `profiled`
false). A lower bound, on the host's clock, over the whole window. Leaves
the tables of servebench/starved.py:tables in the info line."""
from servebench.starved import share, tables, whole


def read(ctx):
    v = share(ctx, whole)
    if v is not None:
        ctx.info.update(tables(ctx))
    return v
