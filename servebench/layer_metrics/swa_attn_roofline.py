"""Kernels: the least time the chip could take for the paged-attention
calls of one block, over the time those calls took in one block. The
least time is the MODEL's bytes over the published memory bandwidth, or
its operations over the bf16 peak if that is longer
(servebench/window_peaks.py:paged_least_seconds, a sum of
servebench/peaks.py's own per-layer counts): per layer and decode step
the rows a stream's decode row reads, min(context, window) in a sliding
layer and the context in a full one, 4,096 B a row, the contexts read
one by one from the clients' timelines at the middle of the trace, as
block_roofline takes them. The time is the calls' share of the block
programs' device time (their self seconds over the seconds of all runs
of the block programs in the capture) times the median whole block
(servebench/spans.py:block_durations). A mixed block's chunk columns
read their stream's rows through XLA's gather, outside the call: neither
side counts them. None where the trace holds no such call."""
import statistics

from servebench.metrics import live_contexts
from servebench.spans import DECODE_BLOCKS, MIXED_BLOCKS, block_durations
from servebench.window_peaks import paged_least_seconds, paged_op_seconds


def read(ctx):
    whole = block_durations(ctx)
    sec = paged_op_seconds(ctx)
    if not whole or not sec:
        return None
    runs = sum(d for name, rs in ctx.trace["module_runs"].items()
               if MIXED_BLOCKS in name or DECODE_BLOCKS in name
               for _, d in rs)
    contexts = live_contexts(ctx.streams, ctx.trace_at)
    if not runs or not contexts:
        return None
    least = paged_least_seconds(
        ctx.config, ctx.device["kind"], ctx.chips,
        ctx.config["serve"]["decode_steps_per_tick"], contexts)
    read_s = statistics.median(whole) * sec / runs
    ctx.info["swa_attn_roofline"] = dict(least, read_s=read_s,
                                         streams=len(contexts))
    return 100.0 * least["least_s"] / read_s
