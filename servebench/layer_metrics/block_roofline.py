"""Kernels: the least time the chips could take for one block, over the
time a block took: the median over the whole runs of the block programs,
mixed or decode (servebench/spans.py:block_durations). The least time is
the larger of bytes over the published memory bandwidth and operations
over the published bf16 peak (servebench/peaks.py): each of the block's
steps streams the weights its live streams touch once and, layer by
layer, what that layer's kind reads for each live stream (cached rows up
to its window or its selection, index keys, recurrent state), the
contexts read one by one from the clients' timelines at the middle of
the trace. Leaves the count, its parts, the contexts and the block's
time in the info line (`block_roofline`).

`serve.decode_steps_per_tick` is what the least time of ONE forward of
`decode_width` positions is multiplied by, so for a model that
generates by blocks it counts FORWARDS in one block program, not tokens
and not blocks: S denoising forwards that write no keys and values and
the one forward that commits them count S + 1 for each block generated."""
import statistics

from servebench.metrics import live_contexts
from servebench.peaks import block_least_seconds
from servebench.spans import block_durations


def read(ctx):
    d = block_durations(ctx)
    if not d:
        return None
    contexts = live_contexts(ctx.streams, ctx.trace_at)
    least = block_least_seconds(
        ctx.config, ctx.device["kind"], ctx.chips,
        ctx.config["serve"]["decode_steps_per_tick"], contexts)
    block_s = statistics.median(d)
    ctx.info["block_roofline"] = dict(least, contexts=contexts,
                                      block_s=block_s)
    return 100.0 * least["least_s"] / block_s
