"""Cache manager: the cached latent rows one step's DECODE rows read,
summed over the layers (/debug/ticks: `latent_rows` over `latent_steps`,
the sums over the mixed blocks a tick drained: a live decode row at
position p counts p + 1 in each layer, itself among them; a chunk's
columns, filler and idle rows none; counted on the device from the
lengths the read itself is handed and fetched with the blocks' tokens),
over the ticks of the window that drained a block. Beside the benchmark's
own count, the live streams' contexts from the clients' timelines times
the configuration's layers (left in the info line as
`latent_rows_per_step`: `expected`), it says that a decode row read its
stream's rows and nothing else: the two agree to within the rows of the
streams that were in prefill phase or between requests at that moment.
None on a program whose tick records hold no such count (a model without
latent attention, or a program older than the counter)."""
from servebench.metrics import live_contexts
from servebench.spans import ticks_in_window


def read(ctx):
    ticks = [t for t in ticks_in_window(ctx)
             if t.get("latent_rows") is not None and t.get("latent_steps")]
    if not ticks:
        return None
    got = sum(t["latent_rows"] for t in ticks) \
        / sum(t["latent_steps"] for t in ticks)
    mid = (ctx.w0 + ctx.w1) / 2
    contexts = live_contexts(ctx.streams, mid)
    ctx.info["latent_rows_per_step"] = {
        "counted": got, "streams": len(contexts),
        "expected": ctx.config["num_hidden_layers"] * sum(contexts)}
    return got
