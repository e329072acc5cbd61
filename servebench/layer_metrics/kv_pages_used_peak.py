"""Cache manager: the most pages in use at any tick of the window, over
the pool (`pages_free` of /debug/ticks against the pool's size, which the
server prints when it starts to listen: `pages=<n>x<page>tok`)."""
import re

from servebench.spans import ticks_in_window


def read(ctx):
    m = re.search(r"pages=(\d+)x", ctx.ready_line)
    ticks = ticks_in_window(ctx)
    if not m or not ticks:
        return None
    return 100.0 * (1.0 - min(t["pages_free"] for t in ticks) / int(m.group(1)))
