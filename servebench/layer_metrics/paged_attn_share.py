"""Kernels: time in the paged-attention Mosaic call over the time the
device was busy (device trace)."""
import re

from servebench.spans import op_seconds

PAGED = re.compile(r"paged_att", re.I)


def read(ctx):
    busy = ctx.trace.get("busy_s")
    return 100.0 * op_seconds(ctx, PAGED) / busy if busy else None
