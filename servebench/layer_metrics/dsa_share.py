"""Kernels: device time of the path by which a lightning indexer selects
rows of a latent cache (DSA over MLA: the index scores of a stream's
whole table, the top-k, the selection as a mask, a decode row's read
call `latent_select_attention`, a chunk's masked read, the index
queries) over the time the device was busy (device trace). The
operations are told by the call's name and by shapes made from the
file's sizes: servebench/dsa_peaks.py:dsa_patterns. None without a
trace, for a configuration without `index_topk` beside `kv_lora_rank`,
or where no such operation ran (a program that has no such path)."""
from servebench.dsa_peaks import dsa_op_seconds


def read(ctx):
    busy = (ctx.trace or {}).get("busy_s")
    sec = dsa_op_seconds(ctx)
    if busy and sec:
        ctx.info["dsa_share"] = {
            "path_s": sec, "call_s": dsa_op_seconds(ctx, call_only=True)}
    return 100.0 * sec / busy if busy and sec else None
