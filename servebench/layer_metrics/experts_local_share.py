"""Models: of the expert assignments of the window's steps (real rows x
experts a token), those that fell on an expert THIS chip holds
(/debug/ticks: `expert_rows_local` over `expert_rows_routed`, each
summed over the steps of the mixed blocks a tick drained and the mean
over the layers that route, counted on the device where the tokens are
routed and fetched with the blocks' tokens). One chip's share of a
deployment's experts: the router ranges over all the published experts
and the chip computes the ones it holds, 16 of 256 here, 6.25 % under
even routing. The ratio of the sums over the ticks of the window. None
on a program whose tick records hold no such count (every expert held,
a dense model, or a program older than the counter)."""
from servebench.spans import ticks_in_window


def read(ctx):
    ticks = [t for t in ticks_in_window(ctx)
             if t.get("expert_rows_routed")]
    if not ticks:
        return None
    return 100.0 * sum(t.get("expert_rows_local") or 0.0 for t in ticks) \
        / sum(t["expert_rows_routed"] for t in ticks)
