"""Engine programs: the part of `starved_share` the host spent building
and launching the next block (/debug/ticks: `starved_by` of the spans
`assemble`, `mixed`, `dispatch`, `dispatch.put` and `dispatch.launch`)."""
from servebench.starved import by_span, share


def read(ctx):
    return share(ctx, by_span("assemble", "mixed", "dispatch",
                              "dispatch.put", "dispatch.launch"))
