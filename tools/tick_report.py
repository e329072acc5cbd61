#!/usr/bin/env python
"""Render a dumped GET /debug/ticks body: the tick-anatomy report.

The software answer to "what are the top host terms in a serving tick"
(ROADMAP item 1) — a top-terms table of the structural tick phases
(total seconds, share of tick wall, p50/p95), the host/device wall
split, the per-cause barrier counts beside the finishes taken at a lazy
drain without a barrier, a reconciliation line proving the phase
sums account for the measured tick wall time, and the CPU clock: the
tick thread's CPU seconds, where it was off a CPU by span (the two
device waits apart from the host's own waiting), what the process's
other threads, its collections and the machine did meanwhile, and the
account of every tick that stalled.

stdlib-only (no jax, no numpy): runs anywhere, like trace_report.py.

Usage:  curl -s host:8000/debug/ticks > ticks.json
        python tools/tick_report.py ticks.json [--json]
        python tools/tick_report.py chiprun_out/servebench/<run>/ticks.json
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
    "dispatch": "always 0: a key the record keeps from before the "
                "fused block (its put and launch are under `mixed`)",
    "mixed": "ONE fused dispatch: prefill chunks + decode/spec "
             "blocks together",
    "spec_emit": "host accept/emit walk over drafted tokens",
    "flush": "write-combined KV window flush",
    "other": "unattributed residual of the tick wall",
}


def load_dump(path: str) -> dict:
    with open(path) as f:
        dump = json.load(f)
    if isinstance(dump, list) and dump and all(
            isinstance(t, dict) and "wall_s" in t for t in dump):
        # the bare records, as the benchmark keeps them (`ticks.json`
        # in the directory of every run)
        dump = {"ticks": dump}
    if not isinstance(dump, dict) or "ticks" not in dump:
        raise ValueError(
            f"{path} is not a /debug/ticks dump (expected a JSON object "
            f"with a 'ticks' list, or the list itself)")
    return dump


def percentile(values: List[float], q: float) -> float:
    if not values:
        return 0.0
    s = sorted(values)
    idx = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return float(s[idx])


#: the spans in which the tick thread waits for the device
DEVICE_WAITS = ("drain.fetch", "drain.flush_count")
#: a stalled tick's account, as the tick record holds it
ACCOUNT = ("wall_s", "fetch_s", "cpu_s", "proc_cpu_s", "gc_s",
           "gc_collections", "gc_generation", "run_delay_s")


def cpu_stats(ticks: List[dict]) -> dict:
    """The CPU clock of the ticks that carry it (`cpu_s` not null):
    totals, the off-CPU seconds by span (most first; over the ticks that
    read the CPU clock at every span boundary, `off_cpu_by` not null:
    one in a few where a read is costly) and every stalled tick's
    account. The process's own clock is read in those ticks too: what
    its other threads burned is over them. Empty for the records of an
    older program."""
    ticks = [t for t in ticks if t.get("cpu_s") is not None]
    if not ticks:
        return {}
    sampled = [t for t in ticks if t["off_cpu_by"] is not None]
    off: Dict[str, float] = {}
    for t in sampled:
        for name, v in t["off_cpu_by"].items():
            off[name] = off.get(name, 0.0) + v
    delays = [t["run_delay_s"] for t in ticks]
    return {
        "ticks": len(ticks),
        "wall_s": sum(t["wall_s"] for t in ticks),
        "cpu_s": sum(t["cpu_s"] for t in ticks),
        "cpu_mean_s": sum(t["cpu_s"] for t in ticks) / len(ticks),
        "off_cpu_ticks": len(sampled),
        "off_cpu_wall_s": sum(t["wall_s"] for t in sampled),
        "off_cpu_by": sorted(off.items(), key=lambda kv: -kv[1]),
        "off_cpu_device_s": sum(v for k, v in off.items()
                                if k in DEVICE_WAITS),
        "off_cpu_host_s": sum(v for k, v in off.items()
                              if k not in DEVICE_WAITS),
        "other_threads_cpu_s": sum(t["proc_cpu_s"] - t["cpu_s"]
                                   for t in sampled),
        "gc_s": sum(t["gc_s"] for t in ticks),
        "gc_collections": sum(t["gc_collections"] for t in ticks),
        "run_delay_s": None if None in delays else sum(delays),
        "stalls": [dict({k: t.get(k) for k in ("seq",) + ACCOUNT},
                        **t["stall"]) for t in ticks if t.get("stall")],
    }


def cpu_lines(c: dict) -> List[str]:
    """`cpu_stats` as the report's lines."""
    if not c:
        return []
    wall = c["wall_s"] or 1.0
    off_wall = c["off_cpu_wall_s"] or 1.0
    delay = c["run_delay_s"]
    out = ["", f"tick thread on a CPU {c['cpu_s']:.4f}s "
           f"({100 * c['cpu_s'] / wall:.1f}% of tick wall, "
           f"{1e3 * c['cpu_mean_s']:.3f} ms a tick)",
           f"meanwhile: {c['gc_collections']} collection(s) "
           f"{c['gc_s']:.4f}s, runnable with no CPU "
           + ("n/a" if delay is None else f"{delay:.4f}s"),
           f"in the {c['off_cpu_ticks']} of {c['ticks']} tick(s) "
           f"that clocked every span ({c['off_cpu_wall_s']:.4f}s wall): "
           f"other threads' CPU {c['other_threads_cpu_s']:.4f}s; off a CPU "
           f"{c['off_cpu_device_s']:.4f}s "
           f"({100 * c['off_cpu_device_s'] / off_wall:.1f}%) waiting for "
           f"the device, {c['off_cpu_host_s']:.4f}s "
           f"({100 * c['off_cpu_host_s'] / off_wall:.1f}%) elsewhere",
           "off-CPU seconds by span:"]
    out += [f"  {name:>18} {v:>10.4f} {100 * v / off_wall:>6.1f}%"
            + ("  (device wait)" if name in DEVICE_WAITS else "")
            for name, v in c["off_cpu_by"]]
    out.append(f"stalled ticks: {len(c['stalls'])}")
    for st in c["stalls"]:
        out.append(f"  tick {st['seq']}: {st['cause']} in {st['phase']} / "
                   f"{st['span']}, {st['excess_s']:.3f}s over the usual; "
                   + " ".join(f"{k}={st[k]}" for k in ACCOUNT))
    return out


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
        "cpu": cpu_stats(ticks),
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
    lines.extend(cpu_lines(s["cpu"]))
    return "\n".join(lines)


def tick_line(t: dict) -> str:
    """One incremental --follow line per tick: seq, wall, the dominant
    phase of THIS tick, pipeline depth, occupancy, page headroom."""
    phases = t.get("phases", {})
    timed = {k: v for k, v in phases.items() if k != "other"}
    dom = max(timed, key=timed.get) if timed else "-"
    causes = ",".join(t.get("barrier_causes", ())) or "-"
    cpu, stall = t.get("cpu_s"), t.get("stall")
    return (f"tick {t.get('seq', '?'):>7} {t.get('wall_s', 0.0):>9.4f}s "
            f"dom={dom}:{timed.get(dom, 0.0):.4f}s "
            f"fetch={t.get('fetch_s', 0.0):.4f}s "
            + (f"cpu={cpu:.4f}s " if cpu is not None else "")
            + (f"STALL={stall['cause']}@{stall['span']} " if stall else "") +
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
