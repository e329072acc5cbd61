"""Kernels: the least time the chip could take for the selecting path of
one block (a lightning indexer over a latent cache), over the time that
path took in one block. The least time is the MODEL's bytes over the
published memory bandwidth, or its operations over the bf16 peak if that
is longer (servebench/dsa_peaks.py:dsa_least_seconds, from the file's
PUBLISHED keys): per layer and decode step every live position's index
key at 256 B, min(context, `index_topk`) latent rows a stream at
1,152 B, the indexer's weights and the two absorbed expansions once, the
contexts read one by one from the clients' timelines at the middle of
the trace, as block_roofline takes them. The time is the path's share of
the block programs' device time (its operations' self seconds over the
seconds of all runs of the block programs in the capture) times the
median whole block (servebench/spans.py:block_durations). A mixed
block's chunk rows score and read their slot's context whole and masked,
which the least time does not count, and a read that walks every LIVE
row moves more than the selected ones: the share reads lower for both,
never higher."""
import statistics

from servebench.dsa_peaks import dsa_least_seconds, dsa_op_seconds
from servebench.metrics import live_contexts
from servebench.spans import DECODE_BLOCKS, MIXED_BLOCKS, block_durations


def read(ctx):
    whole = block_durations(ctx)
    sec = dsa_op_seconds(ctx)
    if not whole or not sec:
        return None
    runs = sum(d for name, rs in ctx.trace["module_runs"].items()
               if MIXED_BLOCKS in name or DECODE_BLOCKS in name
               for _, d in rs)
    contexts = live_contexts(ctx.streams, ctx.trace_at)
    if not runs or not contexts:
        return None
    least = dsa_least_seconds(
        ctx.config, ctx.device["kind"], ctx.chips,
        ctx.config["serve"]["decode_steps_per_tick"], contexts)
    path_s = statistics.median(whole) * sec / runs
    ctx.info["dsa_roofline"] = dict(least, path_s=path_s,
                                    streams=len(contexts))
    return 100.0 * least["least_s"] / path_s
