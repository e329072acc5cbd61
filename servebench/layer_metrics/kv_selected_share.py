"""Cache manager: the cached positions a live decode row READ over the
positions it could attend (/debug/ticks: `kv_rows_selected` over
`kv_rows_live`, each the mean over the layers, live decode rows and
steps of the mixed blocks a tick drained, counted on the device where
the rows are selected and fetched with the blocks' tokens). 100 % is a
program that selects nothing; a model whose indexer keeps `topk`
positions reads topk / context once the context is longer. The ratio of
the sums over the ticks of the window that drained a block. None on a
program whose tick records hold no such count (a model without an
indexer, or a program older than the counter)."""
from servebench.spans import ticks_in_window


def read(ctx):
    ticks = [t for t in ticks_in_window(ctx)
             if t.get("kv_rows_selected") is not None
             and t.get("kv_rows_live")]
    if not ticks:
        return None
    return 100.0 * sum(t["kv_rows_selected"] for t in ticks) \
        / sum(t["kv_rows_live"] for t in ticks)
