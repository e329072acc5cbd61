"""Engine programs: the duration on the device of one dispatched block
program (device trace, `XLA Modules` of chip 0): the median over the
runs of the program that took most of the traced time."""
import statistics

from servebench.spans import block_durations


def read(ctx):
    d = block_durations(ctx)
    return statistics.median(d) * 1e3 if d else None
