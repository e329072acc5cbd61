"""Kernels: the least time the chip could take for the Mamba-1 mixers of
one block, over the time they took in one block. The least time is the
MODEL's bytes over the published memory bandwidth, or its operations
over the bf16 peak if that is longer
(servebench/mamba1_peaks.py:mamba1_least_seconds): per Mamba layer and
decode step the mixer's four projections once and every live stream's
recurrent state read and written once, the live streams read from the
clients' timelines at the middle of the trace, as block_roofline takes
them. The time is the mixers' share of the block programs' device time
(their operations' self seconds over the seconds of all runs of the block
programs in the capture) times the median whole block
(servebench/spans.py:block_durations). A mixed block's chunk columns pass
the mixers too, which the least time does not count, and the
out-projection's result is not told from another layer's
(mamba1_peaks.py): the first reads the share lower, the second higher, by
what PERF.md section 5 gives."""
import statistics

from servebench.mamba1_peaks import mamba1_least_seconds, mamba1_op_seconds
from servebench.metrics import live_contexts
from servebench.spans import DECODE_BLOCKS, MIXED_BLOCKS, block_durations


def read(ctx):
    whole = block_durations(ctx)
    sec = mamba1_op_seconds(ctx)
    if not whole or not sec:
        return None
    runs = sum(d for name, rs in ctx.trace["module_runs"].items()
               if MIXED_BLOCKS in name or DECODE_BLOCKS in name
               for _, d in rs)
    live = len(live_contexts(ctx.streams, ctx.trace_at))
    if not runs or not live:
        return None
    least = mamba1_least_seconds(
        ctx.config, ctx.device["kind"], ctx.chips,
        ctx.config["serve"]["decode_steps_per_tick"], live)
    path_s = statistics.median(whole) * sec / runs
    ctx.info["mamba1_roofline"] = dict(least, path_s=path_s, streams=live)
    return 100.0 * least["least_s"] / path_s
