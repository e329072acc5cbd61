"""Models: the least time the chip could take for the mixing of the
residual streams in one block, over the time the mixing took in one
block. The least time is the MODEL's bytes over the published memory
bandwidth (servebench/hc_peaks.py:hc_least_seconds): per sublayer and
decode step every live stream's n x hidden residual values read once
and written once in bf16 and the sublayer's mixing projection once, the
live streams read from the clients' timelines at the middle of the
trace, as block_roofline takes them. The time is the mixing's share of
the block programs' device time (its operations' self seconds over the
seconds of all runs of the block programs in the capture) times the
median whole block (servebench/spans.py:block_durations), built as
latent_attn_roofline is. A mixed block's chunk columns are mixed too,
which the least time does not count: the share reads low by their part
(hc_peaks.py)."""
import statistics

from servebench.hc_peaks import hc_least_seconds, hc_op_seconds
from servebench.metrics import live_contexts
from servebench.spans import DECODE_BLOCKS, MIXED_BLOCKS, block_durations


def read(ctx):
    whole = block_durations(ctx)
    sec = hc_op_seconds(ctx)
    if not whole or not sec:
        return None
    runs = sum(d for name, rs in ctx.trace["module_runs"].items()
               if MIXED_BLOCKS in name or DECODE_BLOCKS in name
               for _, d in rs)
    live = len(live_contexts(ctx.streams, ctx.trace_at))
    if not runs or not live:
        return None
    least = hc_least_seconds(
        ctx.config, ctx.device["kind"], ctx.chips,
        ctx.config["serve"]["decode_steps_per_tick"], live)
    mix_s = statistics.median(whole) * sec / runs
    ctx.info["hc_roofline"] = dict(least, mix_s=mix_s)
    return 100.0 * least["least_s"] / mix_s
