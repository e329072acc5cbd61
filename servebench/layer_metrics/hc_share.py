"""Models: device time of the mixing of a model's n residual streams
(hyper-connections: the norm over a token's n x hidden values, the
projection to n + n + n^2 coefficients, the Sinkhorn rounds, the read
and the write of the streams, in each of a layer's two sublayers) over
the time the device was busy (device trace). The mixing is XLA's own
operations, told by the shapes of their results:
servebench/hc_peaks.py:hc_patterns. None without a trace, or for a
configuration without `hc_mult`."""
from servebench.hc_peaks import hc_op_seconds


def read(ctx):
    busy = (ctx.trace or {}).get("busy_s")
    sec = hc_op_seconds(ctx)
    return 100.0 * sec / busy if busy and sec else None
