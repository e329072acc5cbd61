"""Kernels: device time of a latent-attention model's read of its cached
rows (the Mosaic call `latent_attention`: a decode row's 32 heads against
each live row of its stream, once) over the time the device was busy
(device trace). The call is told by its name and its result's shape:
servebench/latent_peaks.py:latent_patterns. None without a trace, for a
configuration without `kv_lora_rank`, or where no such call ran."""
from servebench.latent_peaks import latent_op_seconds


def read(ctx):
    busy = (ctx.trace or {}).get("busy_s")
    sec = latent_op_seconds(ctx)
    return 100.0 * sec / busy if busy and sec else None
