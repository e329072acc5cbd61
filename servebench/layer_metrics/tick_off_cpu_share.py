"""Scheduler: the share of tick wall time that the tick thread spent OFF
a CPU outside its two waits for the device (/debug/ticks: `off_cpu_by` of
every span but `drain.fetch` and `drain.flush_count`, over `wall_s`, the
window's untraced ticks that carry the table: the program reads the CPU
clock at every span boundary in one tick of a few where a read is
costly): the host WAITING for itself inside a tick, for a lock, the
interpreter lock, the runtime or a CPU. Leaves the tables of
servebench/offcpu.py:tables in the info line."""
from servebench.offcpu import clocked, off_cpu, share, spanned, tables


def read(ctx):
    ticks = clocked(ctx)
    if not ticks:
        return None
    ctx.info.update(tables(ctx))
    sampled = spanned(ticks)
    return share(sampled, sum(off_cpu(t, False) for t in sampled))
