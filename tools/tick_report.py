#!/usr/bin/env python
"""Render a dumped GET /debug/ticks body: the tick-anatomy report.

The software answer to "what are the top host terms in a serving tick"
(ROADMAP item 1) — a top-terms table of the structural tick phases
(total seconds, share of tick wall, p50/p95), the host/device wall
split, the per-cause barrier counts beside the finishes taken at a lazy
drain without a barrier, and a reconciliation line proving the phase
sums account for the measured tick wall time.

stdlib-only (no jax, no numpy): runs anywhere, like trace_report.py.

Usage:  curl -s host:8000/debug/ticks > ticks.json
        python tools/tick_report.py ticks.json [--json]
        python tools/tick_report.py http://host:8000 --follow

``--follow`` polls ``GET /debug/ticks?since=<seq>`` incrementally —
each poll fetches only the ticks recorded since the last one (the
seq-paged ring contract) and renders them one line per tick, so a live
TPU sitting watches the tick anatomy without repeated full dumps.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request
from typing import Dict, List


#: one-line glossary for the structural phase vocabulary — the table's
#: top terms should be self-explaining in a report pasted into an issue
PHASE_NOTES = {
    "expire": "deadline scrub over waiting + running",
    "drain_oldest": "lazy drain of the oldest in-flight block",
    "drain_barrier": "FULL drain (membership change forced it)",
    "admit": "admission: slot grant + prompt staging",
    "assemble": "per-tick operand assembly for the batch",
    "dispatch": "alternating-path prefill/decode dispatch (seeing "
                "this with mixed_dispatch requested = the engine "
                "gated mixed off — stateful draft source or tree "
                "speculation; spec_mixed_fallback_total counts it "
                "and metrics() carries the reason line)",
    "mixed": "ONE fused dispatch: prefill chunks + decode/spec "
             "blocks together (mixed_dispatch, the default)",
    "spec_emit": "host accept/emit walk over drafted tokens",
    "flush": "write-combined KV window flush",
    "other": "unattributed residual of the tick wall",
}


def load_dump(path: str) -> dict:
    with open(path) as f:
        dump = json.load(f)
    if not isinstance(dump, dict) or "ticks" not in dump:
        raise ValueError(
            f"{path} is not a /debug/ticks dump (expected a JSON object "
            f"with a 'ticks' list)")
    return dump


def percentile(values: List[float], q: float) -> float:
    if not values:
        return 0.0
    s = sorted(values)
    idx = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return float(s[idx])


def phase_stats(dump: dict) -> dict:
    """Aggregate the dump: per-phase totals/percentiles (sorted by
    total, descending — the top-terms order), wall/fetch totals, and
    barrier-cause counts."""
    ticks = dump.get("ticks", [])
    series: Dict[str, List[float]] = {}
    wall_total = 0.0
    fetch_total = 0.0
    causes: Dict[str, int] = {}
    finishes_inline = 0
    for t in ticks:
        finishes_inline += t.get("finishes_inline", 0)
        wall_total += t.get("wall_s", 0.0)
        fetch_total += t.get("fetch_s", 0.0)
        for name, v in t.get("phases", {}).items():
            series.setdefault(name, []).append(v)
        for c in t.get("barrier_causes", ()):
            causes[c] = causes.get(c, 0) + 1
    phases = [{"phase": name,
               "total_s": sum(vals),
               "share": (sum(vals) / wall_total) if wall_total else 0.0,
               "p50_s": percentile(vals, 50),
               "p95_s": percentile(vals, 95)}
              for name, vals in series.items()]
    phases.sort(key=lambda p: -p["total_s"])
    phase_sum = sum(p["total_s"] for p in phases)
    return {
        "ticks": len(ticks),
        "wall_total_s": wall_total,
        "phase_total_s": phase_sum,
        # phase sums / tick wall: ~1.0 means the attribution accounts
        # for the measured time (the acceptance property, +-10%)
        "reconciliation": (phase_sum / wall_total) if wall_total else 1.0,
        "host_frac": ((wall_total - fetch_total) / wall_total)
        if wall_total else 0.0,
        "device_frac": (fetch_total / wall_total) if wall_total else 0.0,
        "phases": phases,
        "barrier_causes": causes,
        # finishes a lazy drain took with the newer blocks in flight
        # (mixed dispatch without speculation): no barrier ran for them
        "finishes_inline": finishes_inline,
    }


def render(dump: dict) -> str:
    s = phase_stats(dump)
    lines = []
    lines.append(f"{s['ticks']} tick(s), {s['wall_total_s']:.4f}s wall "
                 f"(next_seq={dump.get('next_seq', '?')}, "
                 f"ring capacity {dump.get('capacity', '?')})")
    lines.append(f"host {100 * s['host_frac']:.1f}% / device-fetch "
                 f"{100 * s['device_frac']:.1f}% of tick wall")
    lines.append("")
    lines.append(f"{'phase':>14} {'total_s':>10} {'share':>7} "
                 f"{'p50_s':>10} {'p95_s':>10}  note")
    for p in s["phases"]:
        lines.append(f"{p['phase']:>14} {p['total_s']:>10.4f} "
                     f"{100 * p['share']:>6.1f}% "
                     f"{p['p50_s']:>10.5f} {p['p95_s']:>10.5f}  "
                     f"{PHASE_NOTES.get(p['phase'], '')}")
    lines.append("")
    lines.append(f"phase sums account for "
                 f"{100 * s['reconciliation']:.1f}% of tick wall")
    if s["barrier_causes"]:
        lines.append("")
        lines.append("full drain barriers by cause:")
        for cause, n in sorted(s["barrier_causes"].items(),
                               key=lambda kv: -kv[1]):
            lines.append(f"  {cause:>14} {n}")
    else:
        lines.append("no full drain barriers in the window")
    lines.append(f"finishes taken at a lazy drain, no barrier: "
                 f"{s['finishes_inline']}")
    return "\n".join(lines)


def tick_line(t: dict) -> str:
    """One incremental --follow line per tick: seq, wall, the dominant
    phase of THIS tick, pipeline depth, occupancy, page headroom."""
    phases = t.get("phases", {})
    timed = {k: v for k, v in phases.items() if k != "other"}
    dom = max(timed, key=timed.get) if timed else "-"
    causes = ",".join(t.get("barrier_causes", ())) or "-"
    return (f"tick {t.get('seq', '?'):>7} {t.get('wall_s', 0.0):>9.4f}s "
            f"dom={dom}:{timed.get(dom, 0.0):.4f}s "
            f"fetch={t.get('fetch_s', 0.0):.4f}s "
            f"batch={t.get('batch', 0)} wait={t.get('waiting', 0)} "
            f"inflight={t.get('inflight', 0)} "
            f"pages={t.get('pages_free', 0)} "
            f"gen={t.get('generated', 0)} barriers={causes} "
            f"finishes_inline={t.get('finishes_inline', 0)}")


def follow(url: str, interval: float, timeout: float,
           max_polls: int = 0) -> int:
    """Poll GET /debug/ticks?since=<seq> and render new ticks as they
    land. `max_polls` bounds the loop for scripted runs (0 = forever).
    """
    base = url.rstrip("/")
    since = 0
    polls = 0
    while True:
        try:
            with urllib.request.urlopen(
                    f"{base}/debug/ticks?since={since}",
                    timeout=timeout) as resp:
                dump = json.loads(resp.read() or b"{}")
        except Exception as e:  # server restarting: report, keep polling
            print(f"poll error: {type(e).__name__}: {e}",
                  file=sys.stderr)
            dump = {}
        for t in dump.get("ticks", ()):
            print(tick_line(t), flush=True)
        since = max(since, int(dump.get("next_seq", since)))
        polls += 1
        if max_polls and polls >= max_polls:
            return 0
        time.sleep(interval)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="render a dumped GET /debug/ticks body")
    ap.add_argument("dump", help="JSON file (the /debug/ticks body), "
                                 "or the server base URL with --follow")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable aggregate instead of the table")
    ap.add_argument("--follow", action="store_true",
                    help="poll /debug/ticks?since=seq incrementally "
                         "(dump is the base URL, e.g. http://host:8000)")
    ap.add_argument("--interval", type=float, default=1.0,
                    help="--follow poll interval in seconds")
    ap.add_argument("--timeout", type=float, default=5.0,
                    help="--follow per-poll HTTP timeout in seconds")
    ap.add_argument("--max-polls", type=int, default=0,
                    help="--follow: stop after N polls (0 = forever)")
    args = ap.parse_args(argv)
    if args.follow:
        return follow(args.dump, args.interval, args.timeout,
                      max_polls=args.max_polls)
    try:
        dump = load_dump(args.dump)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(phase_stats(dump)))
    else:
        print(render(dump))
    return 0


if __name__ == "__main__":
    sys.exit(main())
