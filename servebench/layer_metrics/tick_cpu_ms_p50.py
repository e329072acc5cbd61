"""Scheduler: the tick thread's CPU time inside one tick that launched a
block (/debug/ticks: `cpu_s`, by `time.thread_time()` at the tick's two
ends), over the window's untraced ticks: the median of the means of runs
of 16 consecutive such ticks (servebench/offcpu.py:cpu_ms_p50: the CPU
clock of the chip's host moves in steps of 10 ms, so one tick reads 20, 30
or 40 where the truth is 31). The host's WORK a tick, whatever the
machine and the process's other threads did meanwhile: the figure to lay
beside `mixed_block_ms_p50`, behind which it must fit. None on a program
without the clock."""
from servebench.offcpu import clocked, cpu_ms_p50


def read(ctx):
    return cpu_ms_p50([t for t in clocked(ctx) if t["program"]])
