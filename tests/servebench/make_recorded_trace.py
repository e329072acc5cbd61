#!/usr/bin/env python3
"""Cut a small recorded trace out of a real one, for the tests.

    JAX_PLATFORMS=cpu python3 tests/servebench/make_recorded_trace.py <trace dir> <out.xplane.pb> [--ms 120]

Keeps, from a profiler trace of `butterfly serve` on the chip, a slice of
`--ms` milliseconds of every device plane's `XLA Ops` and `XLA Modules`
lines and of the host thread that runs the scheduler's tick, with the
events' own names, starts and durations, and nothing else. The result is
a valid `.xplane.pb` of some tens of kilobytes that
`servebench/xplane.py` reduces like the whole.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from servebench.xplane import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,  # noqa: E402
                               find_trace, tick_thread)


def esc(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", " ")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--ms", type=float, default=120.0)
    ap.add_argument("--skip-ms", type=float, default=500.0)
    args = ap.parse_args()
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_trace(args.trace))
    planes = list(data.planes)
    devs = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                  key=lambda p: p.name)
    host = tick_thread([p for p in planes if p.name.startswith("/host:")])
    t0 = min(e.start_ns for p in devs for ln in p.lines for e in ln.events)
    lo = t0 + args.skip_ms * 1e6
    hi = lo + args.ms * 1e6
    out = []
    keep = [(p.name, [ln for ln in p.lines if ln.name in (OPS_LINE, MODULES_LINE)])
            for p in devs]
    if host is not None:
        keep.append(("/host:CPU", [host]))
    for pid, (pname, lines) in enumerate(keep, 1):
        names, body = {}, []
        for lid, ln in enumerate(lines, 1):
            evs = [e for e in ln.events
                   if e.start_ns >= lo and e.start_ns + e.duration_ns <= hi]
            body.append(f'  lines {{ id: {lid} name: "{esc(ln.name)}" '
                        f'timestamp_ns: {int(lo)}')
            for e in evs:
                mid = names.setdefault(e.name, len(names) + 1)
                body.append(f'    events {{ metadata_id: {mid} offset_ps: '
                            f'{int((e.start_ns - lo) * 1000)} duration_ps: '
                            f'{int(e.duration_ns * 1000)} }}')
            body.append("  }")
        out.append(f'planes {{ id: {pid} name: "{esc(pname)}"')
        out.extend(body)
        for name, mid in names.items():
            out.append(f'  event_metadata {{ key: {mid} value {{ id: {mid} '
                       f'name: "{esc(name)}" }} }}')
        out.append("}")
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(out))
    Path(args.out).write_bytes(blob)
    print(f"{args.out}: {len(blob)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
