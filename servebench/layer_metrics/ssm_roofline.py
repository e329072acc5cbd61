"""Kernels: the least time the chip could take for the Mamba-2 mixers of
one block, over the time they took in one block. The least time is the
MODEL's bytes over the published memory bandwidth
(servebench/peaks.py:ssm_least_seconds, from the counts of
servebench/ssm_peaks.py): per Mamba layer and decode step the mixer's
two projections once and every live stream's recurrent state read and
written once, the live streams read from the clients' timelines at the
middle of the trace, as block_roofline takes them. The time is the
mixers' share of the block programs' device time (their operations' self
seconds over the seconds of all runs of the block programs in the
capture) times the median whole block
(servebench/spans.py:block_durations). A mixed block's chunk columns
pass the mixers too, which the least time does not count, and the
out-projection's result is not told from another layer's
(ssm_peaks.py): the first reads the share lower, the second higher, by
what PERF.md section 5 gives."""
import statistics

from servebench.metrics import live_contexts
from servebench.peaks import ssm_least_seconds
from servebench.spans import DECODE_BLOCKS, MIXED_BLOCKS, block_durations
from servebench.ssm_peaks import ssm_op_seconds


def read(ctx):
    whole = block_durations(ctx)
    sec = ssm_op_seconds(ctx)
    if not whole or not sec:
        return None
    runs = sum(d for name, rs in ctx.trace["module_runs"].items()
               if MIXED_BLOCKS in name or DECODE_BLOCKS in name
               for _, d in rs)
    live = len(live_contexts(ctx.streams, ctx.trace_at))
    if not runs or not live:
        return None
    least = ssm_least_seconds(
        ctx.config, ctx.device["kind"], ctx.chips,
        ctx.config["serve"]["decode_steps_per_tick"], live)
    return 100.0 * least["least_s"] / (statistics.median(whole) * sec / runs)
