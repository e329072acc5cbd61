"""Engine programs: the duration on the device of one mixed block, a
block program that carries prompt chunks beside its decode lanes
(device trace, `XLA Modules` of chip 0, the programs named `bf_mixed*`):
the median over their runs. None where the trace holds none, as on a
program that does not name its blocks."""
import statistics


def read(ctx):
    runs = [d for name, ds in (ctx.trace.get("modules") or {}).items()
            if "bf_mixed" in name for d in ds]
    return statistics.median(runs) * 1e3 if runs else None
