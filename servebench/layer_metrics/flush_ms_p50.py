"""Cache manager: the duration on the device of one flush of the
write-combined window into the page pool (device trace, `XLA Modules`
of chip 0, the program named `flush_paged_window`): the median over its
whole runs (`servebench/spans.py:whole_runs` drops the runs the
capture's edges may have cut). A flush follows the blocks on the device
chain that sets `tpot_p50_ms`, and no other per-layer metric sees it:
`mixed_block_ms_p50` is the block alone. None where the trace holds
none, as on a program that keeps no window."""
import statistics

from servebench.spans import whole_runs


def read(ctx):
    runs = whole_runs(ctx, "flush_paged_window")
    return statistics.median(runs) * 1e3 if runs else None
