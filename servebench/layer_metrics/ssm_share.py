"""Kernels: device time of the Mamba-2 mixers (the in-projection, the
causal conv, the selective scan over every slot's state, the gate and
its norm) over the time the device was busy (device trace). The mixers
are XLA's own operations, told by the shapes of their results:
servebench/ssm_peaks.py:ssm_patterns. None without a trace, or for a
configuration without a Mamba layer."""
from servebench.ssm_peaks import ssm_op_seconds


def read(ctx):
    busy = (ctx.trace or {}).get("busy_s")
    sec = ssm_op_seconds(ctx)
    return 100.0 * sec / busy if busy and sec is not None else None
