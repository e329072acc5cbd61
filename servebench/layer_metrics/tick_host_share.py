"""Scheduler: the share of tick wall time spent in host phases, that is
not waiting for the device's results (/debug/ticks: wall_s less fetch_s),
over the ticks of the window."""
from servebench.spans import ticks_in_window


def read(ctx):
    ticks = ticks_in_window(ctx)
    wall = sum(t["wall_s"] for t in ticks)
    if wall <= 0:
        return None
    return 100.0 * sum(t["wall_s"] - t["fetch_s"] for t in ticks) / wall
