"""Kernels: the least time the chip could take for the sparse-attention
path of one block, over the time that path took in one block. The least
time is the MODEL's bytes over the published memory bandwidth
(servebench/peaks.py:sparse_least_seconds, from the counts of
servebench/sparse_peaks.py): per layer and decode step the index keys
of the live context, min(context, topk) rows of keys and values a
stream and the indexer's weights, the contexts read from the clients'
timelines at the middle of the trace, as block_roofline takes them. The
time is the path's share of the block programs' device time (its
operations' self seconds over the seconds of all runs of the block
programs in the capture) times the median whole block
(servebench/spans.py:block_durations). A mixed block's chunk rows read
their slot's context whole and masked, which the least time does not
count: the share reads lower for it, never higher."""
import statistics

from servebench.metrics import live_contexts
from servebench.peaks import sparse_least_seconds
from servebench.sparse_peaks import sparse_op_seconds
from servebench.spans import DECODE_BLOCKS, MIXED_BLOCKS, block_durations


def read(ctx):
    whole = block_durations(ctx)
    sec = sparse_op_seconds(ctx)
    if not whole or not sec:
        return None
    runs = sum(d for name, rs in ctx.trace["module_runs"].items()
               if MIXED_BLOCKS in name or DECODE_BLOCKS in name
               for _, d in rs)
    contexts = live_contexts(ctx.streams, ctx.trace_at)
    if not runs or not contexts:
        return None
    least = sparse_least_seconds(
        ctx.config, ctx.device["kind"], ctx.chips,
        ctx.config["serve"]["decode_steps_per_tick"], contexts)
    return 100.0 * least["least_s"] / (statistics.median(whole) * sec / runs)
