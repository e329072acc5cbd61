#!/usr/bin/env python3
"""Who owns the device's idle time: each idle gap of a profiler trace
laid beside the tick's own spans.

    JAX_PLATFORMS=cpu python3 servebench/tickspans.py <dir-or-file> --out idle.json

The program writes its tick as spans into the profiler's trace
(`bf.tick`, `bf.tick.<phase>`, `bf.tick.drain.fetch`, `bf.loop.lock`,
...: PERF.md section 3), on the thread that dispatches to the device.
For each idle gap of chip 0 (the same gaps as servebench/xplane.py:
between the merged `XLA Ops` intervals, `MIN_GAP_S` and longer) this
names the innermost `bf.` span of that thread that covers the gap's
middle, or `(outside tick)` where none does. The summary (one JSON
object):

  idle_by_span   [[span name, idle seconds of chip 0], ...], most first
  tick_thread    the host line that holds the `bf.tick` spans
  ticks          number of `bf.tick` spans in the trace

Run like xplane.py as a child of the harness after the server has
exited (reading a trace imports JAX). A trace of a program that writes
no `bf.` span gives `idle_by_span: null`. `idle_by_span(ctx)` is the
readers' way in: the first one that needs the table runs the child and
keeps the result in `ctx.info["idle_by_span"]`, which run.py prints
with its info line.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from servebench.xplane import (DEVICE_PLANE, MIN_GAP_S, OPS_LINE,  # noqa: E402
                               _events, find_trace, owners, union)

SPAN_PREFIX = "bf."
TICK = "bf.tick"
FETCH = "bf.tick.drain.fetch"
OUTSIDE = "(outside tick)"


def tick_thread(host_planes):
    """The host line that holds the most `bf.tick` spans, or None."""
    best, score = None, 0
    for plane in host_planes:
        for line in plane.lines:
            n = sum(1 for e in line.events if e.name == TICK)
            if n > score:
                best, score = line, n
    return best, score


def reduce_spans(data) -> dict:
    planes = list(data.planes)
    host, n_ticks = tick_thread([p for p in planes
                                 if p.name.startswith("/host:")])
    summary = {"idle_by_span": None, "ticks": n_ticks,
               "tick_thread": host.name if host is not None else None}
    devs = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                  key=lambda p: p.name)
    ops = next((ln for ln in devs[0].lines if ln.name == OPS_LINE), None) \
        if devs else None
    if host is None or ops is None:
        return summary
    merged = union(_events(ops))
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
            if b[0] - a[1] >= MIN_GAP_S]
    spans = [e for e in _events(host) if e[2].startswith(SPAN_PREFIX)]
    own = owners(gaps, spans)
    if "(no host event)" in own:
        own[OUTSIDE] = own.pop("(no host event)")
    summary["idle_by_span"] = sorted(([k, v] for k, v in own.items()),
                                     key=lambda r: -r[1])
    return summary


def idle_by_span(ctx):
    """[[span name, seconds], ...] of this run's trace, or None (no
    trace, or a program without `bf.` spans). Runs the child once."""
    if "idle_by_span" not in ctx.info:
        ctx.info["idle_by_span"] = None
        out_dir = ROOT / "chiprun_out" / "servebench" / \
            f"{ctx.info['workload']}-s{ctx.info['seed']}-t1"
        out = out_dir / "idle_by_span.json"
        try:
            r = subprocess.run(
                [sys.executable, str(Path(__file__).resolve()),
                 str(out_dir / "trace"), "--out", str(out)],
                env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
                capture_output=True, text=True, timeout=300)
            if r.returncode == 0:
                ctx.info["idle_by_span"] = \
                    json.loads(out.read_text())["idle_by_span"]
            else:
                print("tickspans: " + r.stderr[-2000:], file=sys.stderr)
        except (OSError, ValueError, subprocess.TimeoutExpired) as e:
            print(f"tickspans: {type(e).__name__}: {e}", file=sys.stderr)
    return ctx.info["idle_by_span"]


def idle_share(ctx, owns) -> "float | None":
    """Idle seconds of the gaps whose owner `owns(name)` accepts, as a
    percentage of the traced window (device_idle_share's denominator)."""
    window = ctx.trace.get("window_s")
    table = idle_by_span(ctx) if window else None
    if table is None:
        return None
    return 100.0 * sum(s for name, s in table if owns(name)) / window


def in_tick(name: str) -> bool:
    return name == TICK or name.startswith(TICK + ".")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb, or a directory holding one")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from jax.profiler import ProfileData
    summary = reduce_spans(ProfileData.from_file(find_trace(args.trace)))
    Path(args.out).write_text(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
