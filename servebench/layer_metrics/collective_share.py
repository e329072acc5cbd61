"""Parallel: time in collective operations over the time the device was
busy (device trace)."""
import re

from servebench.spans import op_seconds

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all", re.I)


def read(ctx):
    busy = ctx.trace.get("busy_s")
    return 100.0 * op_seconds(ctx, COLLECTIVE) / busy if busy else None
