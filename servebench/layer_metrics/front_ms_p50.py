"""Front end: what HTTP, JSON and the SSE hand-over add to the first
token. The client's send-to-first-token time less the server's own
submit-to-first-token time of the same request (/debug/requests, joined
by X-Request-Id); median over the requests due inside the window."""
import statistics

from servebench.spans import due_in_window, timelines


def read(ctx):
    tl = timelines(ctx)
    v = []
    for s in due_in_window(ctx):
        ev = tl.get(s.rid, {}).get("events", {})
        if s.times and "submit" in ev and "first_token" in ev:
            server = ev["first_token"]["t"] - ev["submit"]["t"]
            v.append((s.times[0] - s.sent - server) * 1e3)
    return statistics.median(v) if v else None
