"""Cache manager: the cached rows the SLIDING layers' decode rows read
over what they would have read with no window (/debug/ticks:
`swa_rows_read` over `swa_rows_whole`, each summed over the sliding
layers, the live decode rows and the steps of the mixed blocks a tick
drained, counted on the device beside the rows and fetched with the
blocks' tokens: a live row at position p reads min(p + 1, window) rows a
sliding layer and would read p + 1). 100 % where no window binds; near
45 % for streams of 9,000 under a window of 4,096. The ratio of the sums
over the ticks of the window that drained a block. None on a program
whose tick records hold no such count (a cache of one kind, or a
program older than the counter)."""
from servebench.spans import ticks_in_window


def read(ctx):
    ticks = [t for t in ticks_in_window(ctx) if t.get("swa_rows_whole")]
    if not ticks:
        return None
    return 100.0 * sum(t.get("swa_rows_read") or 0.0 for t in ticks) \
        / sum(t["swa_rows_whole"] for t in ticks)
