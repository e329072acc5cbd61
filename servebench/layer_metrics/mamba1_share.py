"""Kernels: device time of the Mamba-1 mixers (the in-projection, the
causal conv over u, the x- and dt-projections with their norms, the
recurrence over every slot's state with an exponential a state value, a
chunk's positions, the gate) over the time the device was busy (device
trace). The mixers are XLA's own operations, told by the shapes of their
results: servebench/mamba1_peaks.py:mamba1_patterns. None without a
trace, for a configuration without a Mamba-1 layer, or where no such
operation ran (a program that has no such layer kind)."""
from servebench.mamba1_peaks import mamba1_op_seconds


def read(ctx):
    busy = (ctx.trace or {}).get("busy_s")
    sec = mamba1_op_seconds(ctx)
    return 100.0 * sec / busy if busy and sec else None
