"""Kernels: the least time the chip could take for the latent read of
one block, over the time the read took in one block. The least time is
the MODEL's bytes over the published memory bandwidth, or its operations
over the bf16 peak if that is longer
(servebench/latent_peaks.py:latent_least_seconds, a sum of
servebench/peaks.py's own per-layer counts): per layer and decode step
every live stream's cached rows once, 1,152 B a row, and the two
absorbed expansions once, the contexts read one by one from the clients'
timelines at the middle of the trace, as block_roofline takes them. The
time is the read's share of the block programs' device time (its
operation's self seconds over the seconds of all runs of the block
programs in the capture) times the median whole block
(servebench/spans.py:block_durations). A mixed block's chunk columns
read their stream's rows through XLA's gather, outside the call: neither
side counts them. The chip holds a row in 1,280 B of lanes: the share
reads 10 % under what the memory system did (latent_peaks.py)."""
import statistics

from servebench.latent_peaks import latent_least_seconds, latent_op_seconds
from servebench.metrics import live_contexts
from servebench.spans import DECODE_BLOCKS, MIXED_BLOCKS, block_durations


def read(ctx):
    whole = block_durations(ctx)
    sec = latent_op_seconds(ctx)
    if not whole or not sec:
        return None
    runs = sum(d for name, rs in ctx.trace["module_runs"].items()
               if MIXED_BLOCKS in name or DECODE_BLOCKS in name
               for _, d in rs)
    contexts = live_contexts(ctx.streams, ctx.trace_at)
    if not runs or not contexts:
        return None
    least = latent_least_seconds(
        ctx.config, ctx.device["kind"], ctx.chips,
        ctx.config["serve"]["decode_steps_per_tick"], contexts)
    read_s = statistics.median(whole) * sec / runs
    ctx.info["latent_attn_roofline"] = dict(least, read_s=read_s,
                                            streams=len(contexts))
    return 100.0 * least["least_s"] / read_s
