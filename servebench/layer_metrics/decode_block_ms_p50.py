"""Engine programs: the duration on the device of one decode block, a
block program in which no lane prefills (device trace, `XLA Modules` of
chip 0, the programs named `bf_decode_block*`): the median over their
runs. None where none ran inside the trace: while a request waits, every
block is a mixed block. That is the whole window of both batch cells, so
like `lock_wait_p50_ms` this reader has no entry in the manifest until a
cell runs decode blocks."""
import statistics


def read(ctx):
    runs = [d for name, ds in (ctx.trace.get("modules") or {}).items()
            if "bf_decode_block" in name for d in ds]
    return statistics.median(runs) * 1e3 if runs else None
