"""Scheduler: submit to admission (/debug/requests spans), median over
the requests due inside the window."""
import statistics

from servebench.spans import due_in_window, timelines


def read(ctx):
    tl = timelines(ctx)
    v = []
    for s in due_in_window(ctx):
        ev = tl.get(s.rid, {}).get("events", {})
        if "submit" in ev and "admit" in ev:
            v.append((ev["admit"]["t"] - ev["submit"]["t"]) * 1e3)
    return statistics.median(v) if v else None
