"""The least time of the paged-attention calls of one decode step for a
model whose sliding layers forget, and the rows and pages its cache
holds by kind. Written for trinity-large-ep8 (servebench/configs/), from
the published keys of the file alone.

A decode row of a stream holding `context` tokens reads, in a layer,
`rows_read(config, layer, context)` cached rows (servebench/peaks.py:
min(context, `sliding_window_size`) where `sliding_window_layout[layer]`
is 1, else all of them), each `cached_row_bytes` wide (keys and values
of `num_key_value_heads` heads), and spends `row_flops` operations on
each. The paged call reads nothing else of any size: the query and its
output are 12 KB a row. So the least time of a step's paged calls is the
larger of those bytes over the memory bandwidth and those operations
over the bf16 peak, a sum of the same per-layer functions
`block_least_seconds` adds up: the part cannot disagree with the whole.

`pages_held` is what the cache holds for the same streams, by kind, and
what ONE table for every layer would hold: a sliding layer's ring keeps
`ring_pages` pages a stream whatever its context (the window, what the
write-combined window may stage, a page more), a full layer
ceil(context / page).

stdlib only.
"""
from __future__ import annotations

import re
from typing import Dict, Sequence

from servebench import peaks

#: the paged-attention Mosaic call, as servebench/layer_metrics/
#: paged_attn_share.py names it
PAGED = re.compile(r"paged_att", re.I)
#: rows a slot's write-combined window may hold: two blocks in flight x
#: the block's steps x a chunk of 32 (butterfly_tpu/cache/paged.py
#: staged_most; the program's defaults, which no cell's file changes)
INFLIGHT, CHUNK = 2, 32


def layers_by_kind(config: Dict):
    """(sliding layers, full layers) of the layers as run."""
    L = config["num_hidden_layers"]
    layout = (config.get("sliding_window_layout") or [])[:L]
    slide = sum(1 for v in layout if v)
    return slide, L - slide


def rows_by_kind(config: Dict, contexts: Sequence[float]) -> Dict[str, float]:
    """Cached rows ONE decode step reads for live streams of `contexts`
    tokens each, over the layers of each kind: `slide` (what the sliding
    layers read), `slide_whole` (what they would read with no window)
    and `full`."""
    L = config["num_hidden_layers"]
    layout = (config.get("sliding_window_layout") or [0] * L)[:L]
    out = {"slide": 0.0, "slide_whole": 0.0, "full": 0.0}
    for c in contexts:
        for l in range(L):
            if layout[l]:
                out["slide"] += peaks.rows_read(config, l, c)
                out["slide_whole"] += c
            else:
                out["full"] += peaks.rows_read(config, l, c)
    return out


def paged_least_seconds(config: Dict, device_kind: str, chips: int,
                        steps: int, contexts: Sequence[float]
                        ) -> Dict[str, float]:
    """The least time `chips` chips could take for the paged-attention
    calls of one block of `steps` decode steps with live streams of
    `contexts` tokens each: the rows read by kind x `cached_row_bytes`
    over the bandwidth, or their products over the bf16 peak."""
    rows = rows_by_kind(config, contexts)
    read = rows["slide"] + rows["full"]
    least = peaks.least_seconds(
        steps * read * peaks.cached_row_bytes(config),
        steps * read * peaks.row_flops(config), device_kind, chips)
    return dict(least, rows=rows)


def ring_pages(config: Dict) -> int:
    """Pages of a sliding layer's ring a stream: the window, the rows a
    slot may have staged, and a page more."""
    serve = config["serve"]
    staged = INFLIGHT * int(serve["decode_steps_per_tick"]) * CHUNK
    return -(-(int(config["sliding_window_size"]) + staged)
             // int(serve["page_size"])) + 1


def pages_held(config: Dict, contexts: Sequence[float]) -> Dict[str, float]:
    """Bytes of cached rows held for live streams of `contexts` tokens
    each: `by_kind` (a ring a stream in each sliding layer, pages up to
    the context in each full one) and `one_table` (pages up to the
    context in every layer). A cache of one kind holds `one_table`."""
    page = int(config["serve"]["page_size"])
    slide, full = layers_by_kind(config)
    row = peaks.cached_row_bytes(config) * page     # a page of one layer
    grown = sum(-(-int(c) // page) for c in contexts)
    ring = ring_pages(config) * len(contexts)
    return {"by_kind": row * (slide * ring + full * grown),
            "one_table": row * (slide + full) * grown}


def paged_op_seconds(ctx):
    """Self seconds of the paged-attention calls in the trace, or None
    where there is no trace."""
    ops = (ctx.trace or {}).get("ops")
    if not ops:
        return None
    return sum(sec for name, sec, _ in ops if PAGED.search(name))
