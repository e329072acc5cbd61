"""Engine programs: prompt tokens of the requests admitted inside the
window (`admit` spans of /debug/requests), over the window."""
from servebench.spans import timelines


def read(ctx):
    tl = timelines(ctx)
    if not tl:
        return None
    n = 0
    for s in ctx.streams:
        ev = tl.get(s.rid, {}).get("events", {})
        if "admit" in ev and ctx.w0 <= ev["admit"]["t"] < ctx.w1:
            n += s.prompt_len
    return n / ctx.seconds
