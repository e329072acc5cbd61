"""The tick's CPU clock as the per-layer readers see it: what the tick
thread WORKED and where it WAITED, clocked by the program in every tick
record (`/debug/ticks`: `cpu_s`, `off_cpu_by`, `proc_cpu_s`, `gc_s`,
`gc_collections`, `run_delay_s`, `stall`;
butterfly_tpu/sched/scheduler.py `_lap`, `_account`, `_note_stall`).

`cpu_s` is the tick thread's CPU seconds inside the tick; `off_cpu_by`
is, by the innermost span's own name, the wall less the CPU seconds of
its laps, signed, so its values sum to `wall_s - cpu_s`. Two of those
spans wait for the DEVICE (`drain.fetch`, `drain.flush_count`: `fetch_s`
clocks the same wait on the wall clock alone); off-CPU time in any other
span is the host waiting for itself: a lock, the interpreter lock, the
runtime, a CPU of the machine. The readers take the ticks whose record
says `profiled` false, as servebench/starved.py does and for its reason.

Two things the chip's host does to the CPU clock shape the readers (PR 54:
a read of it is a system call of 5.8 us there, and it moves in steps of
10 ms). The program reads it at every span boundary in one tick of a few
(`off_cpu_by` is null in the others): the tables and `tick_off_cpu_share`
are sums over the ticks that have it. And one tick's `cpu_s` reads 20, 30
or 40 ms where the truth is 31: only means over many ticks say, so the
"median" tick is the median of the MEANS of runs of GROUP ticks.

None on tick records without `cpu_s` (a program older than the clock),
0.0 where such records waited for nothing.

stdlib only.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional

from servebench.spans import ticks_in_window

#: the spans in which the tick thread waits for the device
DEVICE_WAITS = ("drain.fetch", "drain.flush_count")
#: consecutive ticks whose mean `cpu_s` is one sample of the median
GROUP = 16
#: what the info line keeps of a stalled tick: its record's account
ACCOUNT = ("seq", "wall_s", "stall", "fetch_s", "cpu_s", "proc_cpu_s",
           "gc_s", "gc_collections", "gc_generation", "run_delay_s",
           "off_cpu_by", "program", "barrier_causes")


def clocked(ctx) -> List[Dict]:
    """The window's untraced tick records that carry the CPU clock."""
    return [t for t in ticks_in_window(ctx)
            if t.get("cpu_s") is not None and not t["profiled"]]


def spanned(ticks: List[Dict]) -> List[Dict]:
    """Those of `ticks` that read the CPU clock at every span boundary."""
    return [t for t in ticks if t["off_cpu_by"] is not None]


def cpu_ms_p50(ticks: List[Dict]) -> Optional[float]:
    """The median, in ms, of the mean `cpu_s` of each run of GROUP
    consecutive ticks (a last run of half a GROUP or more counts; fewer
    ticks than a GROUP are one run); None of no ticks."""
    cpu = [t["cpu_s"] for t in ticks]
    runs = [cpu[i:i + GROUP] for i in range(0, len(cpu), GROUP)]
    if len(runs) > 1 and 2 * len(runs[-1]) < GROUP:
        runs.pop()
    return 1e3 * statistics.median(
        statistics.fmean(r) for r in runs) if runs else None


def off_cpu(tick: Dict, device: bool) -> float:
    """The tick's off-CPU seconds inside the two device waits, or
    outside them."""
    return sum(s for name, s in tick["off_cpu_by"].items()
               if (name in DEVICE_WAITS) == device)


def share(ticks: List[Dict], seconds: float) -> Optional[float]:
    """100 x `seconds` over the ticks' wall; None where they have none."""
    wall = sum(t["wall_s"] for t in ticks)
    return 100.0 * seconds / wall if wall > 0 else None


def tables(ctx) -> Dict:
    """What the info line says beside `tick_off_cpu_share`: the off-CPU
    seconds by span (the device waits among them), most first, of the
    `off_cpu_ticks` that carry the table, over whose `off_cpu_wall_s` the
    share is taken, as is what the process's other threads burned (the
    process's clock is read in those ticks: it passes 100 where more than
    one other thread ran throughout); the tick thread's own CPU, what it
    spent runnable with no CPU and what collections took, each over the
    wall of ALL the clocked ticks; and the clock's own check: the
    off-CPU seconds of the
    two device waits over `fetch_s`, near 1 where the device wait is
    off-CPU."""
    ticks = clocked(ctx)
    sampled = spanned(ticks)
    spans: Dict[str, float] = {}
    for t in sampled:
        for name, s in t["off_cpu_by"].items():
            spans[name] = spans.get(name, 0.0) + s
    fetch = sum(t["fetch_s"] for t in sampled)
    delays = [t["run_delay_s"] for t in ticks]
    return {"off_cpu_by_span": sorted(([k, v] for k, v in spans.items()),
                                      key=lambda r: -r[1]),
            "off_cpu_ticks": len(sampled),
            "off_cpu_wall_s": sum(t["wall_s"] for t in sampled),
            "tick_cpu_share": share(ticks, sum(t["cpu_s"] for t in ticks)),
            "other_threads_cpu_share": share(sampled, sum(
                t["proc_cpu_s"] - t["cpu_s"] for t in sampled)),
            "run_delay_share": None if None in delays
            else share(ticks, sum(delays)),
            "gc_share": share(ticks, sum(t["gc_s"] for t in ticks)),
            "gc_collections": sum(t["gc_collections"] for t in ticks),
            "fetch_off_cpu_agrees": sum(off_cpu(t, True) for t in sampled)
            / fetch if fetch > 0 else None}


def stalled(ctx) -> Optional[List[Dict]]:
    """The account of each of the window's untraced ticks whose record
    holds a `stall`; None where no record carries the clock."""
    ticks = clocked(ctx)
    if not ticks:
        return None
    return [{k: t.get(k) for k in ACCOUNT} for t in ticks if t["stall"]]
