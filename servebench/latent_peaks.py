"""A decode row's read of its cached latent rows, counted from a
configuration file: what a latent-attention model (a file with
`kv_lora_rank`) must move and do for that read alone, and how a device
trace tells the read's operation.

It counts the work of the MODEL, not of an implementation, and it counts
nothing of its own: per attention layer and decode step,

* every live stream's cached rows, each read ONCE: `peaks.rows_read` x
  `peaks.cached_row_bytes` (one row of `kv_lora_rank` +
  `qk_rope_head_dim` bf16 values a token: 1,152 B for JoyAI-LLM-Flash);
* the two expansions the absorbed form multiplies by, once a step: a
  head's keys folded into its query and the weighted sum of latents
  taken to its values, `kv_lora_rank` x heads x (`qk_nope_head_dim` +
  `v_head_dim`) parameters (`absorbed_params`: the part of
  `peaks.attention_params` that stands between the query and the cached
  row; under 2 % of the rows' bytes at the cell's contexts);
* the scores over a row and the weighted sum over its latent,
  `peaks.row_flops` a row read.

Whatever serves the read moves at least that: a kernel that fetched a
row once for the scores and again as values would move twice the rows'
bytes, and the share would say so (under 50 %). The rows' bytes are
`peaks.step_parts`' `rows` part to the byte (tests/servebench/), so the
part cannot disagree with the whole (`block_roofline`). The chip holds a
row of 576 values in five lane tiles, 1,280 B (the configuration file's
`assumed`: `pool_lanes`): the share reads 10 % under what the memory
system did, by the model's count and not the layout's.

stdlib only.
"""
from __future__ import annotations

import re
from typing import Dict, Sequence


def is_latent(config: Dict) -> bool:
    return bool(config.get("kv_lora_rank"))


def absorbed_params(config: Dict) -> int:
    """Parameters of one layer's key and value expansions (the source's
    `kv_b_proj`), which the absorbed read multiplies its queries and its
    weighted sums by."""
    return config["kv_lora_rank"] * config["num_attention_heads"] \
        * (config["qk_nope_head_dim"] + config["v_head_dim"])


def latent_least_seconds(config: Dict, device_kind: str, chips: int,
                         steps: int, contexts: Sequence[float]
                         ) -> Dict[str, float]:
    """The least time `chips` chips could take for the latent read of one
    block of `steps` decode steps with live streams of `contexts` tokens
    each. Returns the bytes, the operations, both bounds, which one
    binds, and one step's bytes by part (`parts`: `rows`, `expansions`)."""
    from servebench import peaks
    L, contexts = config["num_hidden_layers"], list(contexts)
    read = peaks.total_rows_read(config, contexts)
    parts = {"rows": read * peaks.cached_row_bytes(config),
             "expansions": L * absorbed_params(config)
             * peaks._bytes_a_weight(config)}
    fl = read * peaks.row_flops(config) \
        + 2.0 * len(contexts) * L * absorbed_params(config)
    return dict(peaks.least_seconds(
        steps * (parts["rows"] + parts["expansions"]), steps * fl,
        device_kind, chips), parts=parts, rows_read=read)


# -- the read's operation in a device trace -----------------------------------
#
# On the chip the read is ONE Mosaic call a layer and step, and a trace
# names a call by its HLO text: the instruction's name, which is the
# jitted wrapper's (butterfly_tpu/ops/latent_attention.py:
# `latent_attention`), and the shape of its result [slots, heads,
# kv_lora_rank] (servebench/xplane.py keeps the first 64 characters,
# every character outside [A-Za-z0-9_.:-] as `_`):
#
#     _latent_attention.12___bf16_96_32_512__2_1_0
#
# The pattern holds the name AND the result's two minor dims from the
# file, so that neither another kernel (`_paged_attention.12`, whose
# share `paged_attn_share` reads) nor an XLA operation that happens to
# produce [.., 32, 512] is taken for it. The projections around the
# read (the query's and the joint latent's, the two expansions, the
# output) are XLA's own operations whose results are [rows, hidden] or
# [rows, heads, ..] like every other layer's: they are not caught, and
# `latent_attn_share` is the read's alone. A program that serves the
# read with XLA's gather (kernels off) matches nothing: the readers
# return None there and the configuration's `kernels_must_hold` makes
# such a run not correct.

def latent_patterns(config: Dict):
    """A compiled pattern over a trace's cleaned operation names."""
    nh, r = config["num_attention_heads"], config["kv_lora_rank"]
    return re.compile(rf"(?<![A-Za-z])latent_attention[._0-9]*_[a-z]+[0-9]+"
                      rf"(?:_[0-9]+)*_{nh}_{r}_")


def latent_op_seconds(ctx):
    """Self seconds of the read's operation in the trace, or None where
    there is no trace or the configuration has no latent attention."""
    ops = (ctx.trace or {}).get("ops")
    if not ops or not is_latent(ctx.config):
        return None
    pat = latent_patterns(ctx.config)
    return sum(sec for name, sec, _ in ops if pat.search(name))
