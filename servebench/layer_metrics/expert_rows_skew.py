"""Models: the rows routed to the fullest expert of a layer in one step
over the mean rows of an expert (/debug/ticks: `expert_rows_max` over
`expert_rows_mean`, each the mean over the steps and layers of the mixed
blocks a tick drained). 1 is even routing; a grouped dispatch pads every
expert's rows to the fullest one's, so this is what it would compute
over what it needs. The ratio of the two means over the ticks of the
window that drained a block. None on a program whose tick records hold
no such counts."""
from servebench.spans import ticks_in_window


def read(ctx):
    ticks = [t for t in ticks_in_window(ctx)
             if t.get("expert_rows_max") is not None
             and t.get("expert_rows_mean")]
    if not ticks:
        return None
    return sum(t["expert_rows_max"] for t in ticks) \
        / sum(t["expert_rows_mean"] for t in ticks)
