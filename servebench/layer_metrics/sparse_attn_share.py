"""Kernels: device time of the sparse-attention path (the index scores,
the top-k, the read of the selected rows and the product over them) over
the time the device was busy (device trace). The path is XLA's own
operations, told by the shapes of their results:
servebench/sparse_peaks.py:sparse_patterns. None without a trace, or for
a configuration without an indexer."""
from servebench.sparse_peaks import sparse_op_seconds


def read(ctx):
    busy = (ctx.trace or {}).get("busy_s")
    sec = sparse_op_seconds(ctx)
    return 100.0 * sec / busy if busy and sec is not None else None
