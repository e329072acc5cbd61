"""Cache manager: the positions one step pushed through the delta rule
(/debug/ticks: `ssm_rows` over `ssm_steps`, the sums over the mixed
blocks a tick drained: a live decode row counts one, a prefill chunk its
real columns, filler columns and idle rows none; counted on the device
and fetched with the blocks' tokens), over the ticks of the window that
drained a block, for a configuration with linear-attention layers. Beside
`slot_occupancy` it says that decode rows AND chunks reach the state:
live slots plus the chunk's columns. None for a configuration without
such a layer, or on a program whose tick records hold no such count."""
from servebench.gdn_peaks import linear_layers
from servebench.spans import ticks_in_window


def read(ctx):
    if not linear_layers(ctx.config):
        return None
    ticks = [t for t in ticks_in_window(ctx)
             if t.get("ssm_rows") is not None and t.get("ssm_steps")]
    if not ticks:
        return None
    return sum(t["ssm_rows"] for t in ticks) \
        / sum(t["ssm_steps"] for t in ticks)
