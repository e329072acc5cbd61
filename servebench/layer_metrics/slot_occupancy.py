"""Scheduler: mean decoding batch over `max_batch`, weighted by tick
wall time (/debug/ticks), over the ticks of the window."""
from servebench.spans import ticks_in_window


def read(ctx):
    ticks = ticks_in_window(ctx)
    wall = sum(t["wall_s"] for t in ticks)
    if wall <= 0:
        return None
    mean = sum(t["batch"] * t["wall_s"] for t in ticks) / wall
    return 100.0 * mean / ctx.config["serve"]["max_batch"]
