"""Kernels: the least time the chips could take for one block, over the
time the block took (block_ms_p50). The least time is the larger of
bytes over the published memory bandwidth and operations over the
published bf16 peak (servebench/peaks.py): each of the block's steps
streams the weights once and the keys and values of the live context,
which is read from the clients' timelines at the middle of the trace."""
import statistics

from servebench.metrics import live_context
from servebench.peaks import block_least_seconds
from servebench.spans import block_durations


def read(ctx):
    d = block_durations(ctx)
    if not d:
        return None
    live, tokens = live_context(ctx.streams, ctx.trace_at)
    least = block_least_seconds(
        ctx.config, ctx.device["kind"], ctx.chips,
        ctx.config["serve"]["decode_steps_per_tick"], live, tokens)
    return 100.0 * least["least_s"] / statistics.median(d)
