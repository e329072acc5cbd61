"""Cache manager: requests preempted under page pressure inside the
window: the `preempt` events of /debug/requests (the scheduler records
one beside every increment of `preemptions_total`; /metrics itself waits
for the serving lock, which a long tick holds, and answers 503)."""
from servebench.spans import to_client_clock


def read(ctx):
    recs = (ctx.requests or {}).get("requests")
    if recs is None:
        return None
    n = 0
    for rec in recs:
        for ev in rec.get("events", []):
            if ev["name"] == "preempt" and \
                    ctx.w0 <= to_client_clock(ctx, ev["t"]) < ctx.w1:
                n += 1
    return float(n)
