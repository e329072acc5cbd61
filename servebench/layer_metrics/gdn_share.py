"""Kernels: device time of the Gated DeltaNet mixers (the projections,
the causal conv over q, k and v, the delta rule over every slot's state,
a chunk's chunkwise solve, the norm a head and its gate) over the time
the device was busy (device trace). The mixers are XLA's own operations,
told by the shapes of their results:
servebench/gdn_peaks.py:gdn_patterns. None without a trace, for a
configuration without a linear-attention layer, or where no such
operation ran (a program that has no such layer kind)."""
from servebench.gdn_peaks import gdn_op_seconds


def read(ctx):
    busy = (ctx.trace or {}).get("busy_s")
    sec = gdn_op_seconds(ctx)
    return 100.0 * sec / busy if busy and sec else None
