"""The program's starvation clock as the per-layer readers see it: the
device's wait for the host, clocked by the host in every tick record
(`/debug/ticks`: `starved_s`, `starved_cause`, `starved_by`, `gap_s`,
`profiled`; butterfly_tpu/sched/scheduler.py `_starve`, `_fed`).

A share is the starved seconds of the window's ticks over the time those
ticks span, the sum of their `wall_s + gap_s`. The readers take the ticks
whose record says `profiled` false: a live capture multiplies the host's
phases, and the figure a user pays is the untraced one. A tick is charged
the wait that its launch ENDED, wherever it began. The clock is a lower
bound: idle time before the host looked is not in it.

None on tick records without `starved_s` (a program older than the
clock), 0.0 where such records starved nothing.

stdlib only.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

from servebench.spans import ticks_in_window


def clocked(ctx, profiled: bool = False) -> List[Dict]:
    """The window's tick records that carry the clock, captured or not."""
    return [t for t in ticks_in_window(ctx)
            if "starved_s" in t and t["profiled"] == profiled]


def share(ctx, part: Callable[[Dict], float],
          profiled: bool = False) -> Optional[float]:
    """100 x the sum of `part(tick)` seconds over the seconds the ticks
    span; None where no tick of the window carries the clock."""
    ticks = clocked(ctx, profiled)
    span = sum(t["wall_s"] + t["gap_s"] for t in ticks)
    return 100.0 * sum(part(t) for t in ticks) / span if span > 0 else None


def whole(tick: Dict) -> float:
    return tick["starved_s"] or 0.0


def by_cause(cause: str) -> Callable[[Dict], float]:
    return lambda t: whole(t) if t["starved_cause"] == cause else 0.0


def by_span(*names: str) -> Callable[[Dict], float]:
    return lambda t: sum(t["starved_by"].get(n, 0.0) for n in names)


def tables(ctx) -> Dict:
    """What the info line says beside the shares: the starved seconds by
    span and by cause, most first, how many ticks they rest on, and the
    share over the ticks a capture covered, which is the number to lay
    beside that capture's `device_idle_share`."""
    spans: Dict[str, float] = {}
    causes: Dict[str, float] = {}
    ticks = clocked(ctx)
    for t in ticks:
        for name, s in t["starved_by"].items():
            spans[name] = spans.get(name, 0.0) + s
        if whole(t) > 0.0:
            c = t["starved_cause"]
            causes[c] = causes.get(c, 0.0) + whole(t)

    def most_first(d):
        return sorted(([k, v] for k, v in d.items()), key=lambda r: -r[1])
    return {"starved_by_span": most_first(spans),
            "starved_by_cause": most_first(causes),
            "starved_ticks": len(ticks),
            "starved_share_profiled": share(ctx, whole, profiled=True)}
