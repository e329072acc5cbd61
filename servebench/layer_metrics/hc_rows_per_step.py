"""Models: the positions whose n residual streams one step mixed
(/debug/ticks: `hc_rows` over `hc_steps`, the sums over the mixed blocks
a tick drained: a live decode row counts one, a chunk its real columns,
filler and idle rows none; counted on the device from the step's own
row mask and fetched with the blocks' tokens), over the ticks of the
window that drained a block. Beside `slot_occupancy` it says that decode
rows AND chunk columns pass the mixing (live slots plus the chunk
columns a step); the reader leaves the live streams at the window's
middle in the info line (`hc_rows_per_step`: `streams`). None on a
program whose tick records hold no such count (a model of one stream,
or a program older than the counter)."""
from servebench.metrics import live_contexts
from servebench.spans import ticks_in_window


def read(ctx):
    ticks = [t for t in ticks_in_window(ctx)
             if t.get("hc_rows") is not None and t.get("hc_steps")]
    if not ticks:
        return None
    got = sum(t["hc_rows"] for t in ticks) / sum(t["hc_steps"] for t in ticks)
    ctx.info["hc_rows_per_step"] = {
        "counted": got,
        "streams": len(live_contexts(ctx.streams, (ctx.w0 + ctx.w1) / 2))}
    return got
