"""Scheduler: the share of tick wall time spent in ticks that
dispatched a mixed block (/debug/ticks: `program` names what a tick
dispatched), over all the ticks of the window: how much of the window
ran at the prefill-width block's pace. None on a program whose tick
records name no program."""
from servebench.spans import ticks_in_window


def read(ctx):
    ticks = [t for t in ticks_in_window(ctx) if "program" in t]
    wall = sum(t["wall_s"] for t in ticks)
    if wall <= 0:
        return None
    mixed = sum(t["wall_s"] for t in ticks
                if "bf_mixed" in (t["program"] or ""))
    return 100.0 * mixed / wall
