"""Front end: a request's wait for the serving lock inside `submit`
(`lock_wait_s` of the `submit` event, /debug/requests), median over the
requests due inside the window. None on a program that does not record
it."""
import statistics

from servebench.spans import due_in_window, timelines


def read(ctx):
    tl = timelines(ctx)
    v = [tl[s.rid]["events"]["submit"]["lock_wait_s"] * 1e3
         for s in due_in_window(ctx)
         if "lock_wait_s" in tl.get(s.rid, {}).get("events", {})
         .get("submit", {})]
    return statistics.median(v) if v else None
