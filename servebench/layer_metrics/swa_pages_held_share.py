"""Cache manager: the bytes of pages the cache HOLDS, both kinds, over
what one table for every layer would hold for the same streams
(/debug/ticks: `kv_pages_slide`, the sliding layers' ring pages x the
slots that hold a request, and `kv_pages_full`, the full layers' pages in
use; a page of a kind is its layers x page x the row's bytes, the layers
by kind from the file's `sliding_window_layout`). One table would hold
the full kind's pages in EVERY layer: a full layer's pages follow the
context, so they are what a sliding layer would hold too. The mean over
the ticks of the window. Near 50 % for streams of 9,000 under a window of
4,096 in six layers of eight; 100 % is a cache of one kind, whose tick
records hold no such count: None there (or on a program older than the
counter)."""
from servebench.spans import ticks_in_window
from servebench.window_peaks import layers_by_kind


def read(ctx):
    slide, full = layers_by_kind(ctx.config)
    shares = [(slide * t["kv_pages_slide"] + full * t["kv_pages_full"])
              / ((slide + full) * t["kv_pages_full"])
              for t in ticks_in_window(ctx)
              if t.get("kv_pages_slide") is not None
              and t.get("kv_pages_full")]
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
