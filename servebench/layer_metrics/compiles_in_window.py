"""Engine programs: programs compiled inside the window, by the
program's own count (/debug/ticks: `compiles`, the running count of
JAX's compile events, fed whether or not the persistent cache answered):
the last tick record's count less the first's. None on a program that
does not count."""
from servebench.spans import ticks_in_window


def read(ctx):
    counts = [t["compiles"] for t in ticks_in_window(ctx) if "compiles" in t]
    return float(counts[-1] - counts[0]) if counts else None
