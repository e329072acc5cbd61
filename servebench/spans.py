"""Helpers the per-layer readers share: the server's spans and ticks
placed on the harness's clock, and cut to the window.

stdlib only.
"""
from __future__ import annotations

from typing import Dict, List


def to_client_clock(ctx, t_server_monotonic: float) -> float:
    """A `time.monotonic()` of the server process as the harness's
    `time.monotonic()`, through the two processes' wall clocks."""
    r = ctx.requests
    wall = t_server_monotonic - r["t0_monotonic"] + r["t0_wall"]
    return wall - ctx.wall_minus_mono


def timelines(ctx) -> Dict[str, Dict]:
    """rid -> {"events": {name: first event}, "preempts": n}, times on
    the harness's clock, from /debug/requests (joined by X-Request-Id)."""
    out = {}
    for rec in (ctx.requests or {}).get("requests", []):
        rid = rec.get("request_id")
        if rid is None:
            continue
        evs, pre = {}, 0
        for ev in rec.get("events", []):
            if ev["name"] == "preempt":
                pre += 1
            if ev["name"] not in evs:
                evs[ev["name"]] = dict(ev, t=to_client_clock(ctx, ev["t"]))
        out[rid] = {"events": evs, "preempts": pre}
    return out


def ticks_in_window(ctx) -> List[Dict]:
    """The scheduler's tick records whose wall time falls in the window."""
    lo, hi = ctx.w0 + ctx.wall_minus_mono, ctx.w1 + ctx.wall_minus_mono
    seen, out = set(), []
    for t in ctx.ticks:
        if lo <= t["t_wall"] < hi and t["seq"] not in seen:
            seen.add(t["seq"])
            out.append(t)
    return out


def due_in_window(ctx):
    return [s for s in ctx.streams
            if s.due is not None and ctx.w0 <= s.due < ctx.w1]


def op_seconds(ctx, pattern) -> float:
    """Self seconds of the device operations whose name matches."""
    return sum(sec for name, sec, _ in ctx.trace.get("ops", [])
               if pattern.search(name))


def block_durations(ctx):
    """Durations (seconds, chip 0) of the runs of the block program: the
    program of `XLA Modules` that took most of the traced time."""
    mods = ctx.trace.get("modules") or {}
    return max(mods.values(), key=sum) if mods else None
