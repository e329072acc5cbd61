#!/usr/bin/env python3
"""Reduce a profiler trace (`.xplane.pb`) to the numbers the per-layer
readers take.

    JAX_PLATFORMS=cpu python3 servebench/xplane.py <dir-or-file> --out summary.json

Run as a child of the harness after the server has exited: reading a
trace needs `jax.profiler.ProfileData`, and the harness's parent never
imports JAX. The summary (one JSON object):

  devices        number of device planes
  window_s       first to last device event, the longest over devices
  busy_s         union of the operation intervals, averaged over devices
  ops            [[name, self seconds averaged over devices, count], ...]
                 (self time: an operation's duration less the operations
                 nested in it, so a `while` does not hide its body)
  modules        {program name: [durations in seconds on device 0, ...]}
  idle_gaps      [[what the host's tick thread was doing, seconds], ...]
  planes         the names of the planes and their lines (for a reader)
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: idle gaps shorter than this are the pauses between two operations of
#: one program, not the host's doing
MIN_GAP_S = 5e-6


def clean(name: str, width: int = 64) -> str:
    """A name made of letters, digits, `_`, `.`, `:` and `-` only."""
    return re.sub(r"[^A-Za-z0-9_.:-]", "_", name)[:width]


def find_trace(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def _events(line):
    """[(start_s, end_s, name)] of a line, by start, longest first."""
    out = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
           for e in line.events]
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def union(events):
    """Merged [start, end] intervals of events sorted by start."""
    out = []
    for s, e, _ in events:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def self_times(events):
    """{name: [self seconds, count]}: duration less nested events."""
    acc = defaultdict(lambda: [0.0, 0])
    stack = []   # [end, name, self]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, own = stack.pop()
            acc[name][0] += own
            acc[name][1] += 1
    for s, e, name in events:
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return acc


def owners(gaps, host_events):
    """For each idle gap, the innermost event of the host thread that
    covers the gap's middle; {name: seconds}."""
    acc = defaultdict(float)
    stack, i = [], 0
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        m = (a + b) / 2
        while i < len(host_events) and host_events[i][0] <= m:
            ev = host_events[i]
            while stack and stack[-1][1] <= ev[0]:
                stack.pop()
            stack.append(ev)
            i += 1
        while stack and stack[-1][1] <= m:
            stack.pop()
        acc[stack[-1][2] if stack else "(no host event)"] += b - a
    return acc


def tick_thread(host_planes):
    """The host line that dispatches to the device: the one with most
    events from the scheduler's file."""
    best, score = None, 0
    for plane in host_planes:
        for line in plane.lines:
            n = sum(1 for e in line.events if "scheduler.py" in e.name)
            if n > score:
                best, score = line, n
    return best


def reduce_trace(data) -> dict:
    planes = list(data.planes)
    devs = [p for p in planes if DEVICE_PLANE.match(p.name)]
    summary = {"devices": len(devs),
               "planes": {p.name: [ln.name for ln in p.lines]
                          for p in planes}}
    if not devs:
        return summary
    busy, window, ops = [], [], defaultdict(lambda: [0.0, 0])
    gaps0, modules = [], defaultdict(list)
    for k, plane in enumerate(sorted(devs, key=lambda p: p.name)):
        lines = {ln.name: ln for ln in plane.lines}
        ev = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
        spans = [x for ln in plane.lines for x in _events(ln)]
        if not ev or not spans:
            continue
        merged = union(ev)
        busy.append(sum(e - s for s, e in merged))
        window.append(max(e for _, e, _ in spans) - min(s for s, _, _ in spans))
        for name, (sec, n) in self_times(ev).items():
            ops[name][0] += sec
            ops[name][1] += n
        if k == 0:
            gaps0 = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
                     if b[0] - a[1] >= MIN_GAP_S]
            if MODULES_LINE in lines:
                for s, e, name in _events(lines[MODULES_LINE]):
                    modules[clean(re.sub(r"\(\d+\)$", "", name))].append(e - s)
    n = max(1, len(busy))
    summary["busy_s"] = sum(busy) / n
    summary["window_s"] = max(window) if window else 0.0
    summary["ops"] = sorted(([clean(k), v[0] / n, v[1]] for k, v in ops.items()),
                            key=lambda r: -r[1])
    summary["modules"] = modules
    host = tick_thread([p for p in planes if p.name.startswith("/host:")])
    if host is not None and gaps0:
        own = owners(gaps0, _events(host))
        summary["idle_gaps"] = sorted(([clean(k), v] for k, v in own.items()),
                                      key=lambda r: -r[1])
        summary["tick_thread"] = host.name
    else:
        summary["idle_gaps"] = [["(no host thread found)",
                                 sum(b - a for a, b in gaps0)]] if gaps0 else []
    return summary


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb, or a directory holding one")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from jax.profiler import ProfileData
    summary = reduce_trace(ProfileData.from_file(find_trace(args.trace)))
    Path(args.out).write_text(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
