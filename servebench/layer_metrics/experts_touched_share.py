"""Models: the distinct experts that the rows of one step touched in one
layer, over the layer's experts (E under each name servebench/peaks.py
reads it: `num_experts`, `num_local_experts`, `n_routed_experts`;
/debug/ticks: `experts_touched`, the mean over the steps and layers of
the mixed blocks a tick drained,
counted on the device where the tokens are routed and fetched with the
blocks' tokens). It is the share of a layer's expert bytes that a
dispatch which skips unrouted experts would still stream; the program
computes, and streams, every expert. The mean over the ticks of the
window that drained a block. None on a program whose tick records hold
no such count (a dense model, or a program older than the counter)."""
from servebench.peaks import num_experts
from servebench.spans import ticks_in_window


def read(ctx):
    seen = [t["experts_touched"] for t in ticks_in_window(ctx)
            if t.get("experts_touched") is not None]
    experts = num_experts(ctx.config)
    if not seen or not experts:
        return None
    return 100.0 * sum(seen) / len(seen) / experts
