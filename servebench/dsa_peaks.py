"""A layer whose lightning indexer SELECTS rows of a LATENT cache
(DeepSeek Sparse Attention over latent attention: a configuration file
with `index_topk` beside `kv_lora_rank`; GLM-5), counted from the file's
PUBLISHED keys: what that path must move and do in a decode step, and
how a device trace tells its operations.

It counts the work of the MODEL, not of an implementation: per layer and
decode step,

* the index key of every live position of every live stream, since a
  query scores all of them: `index_head_dim` bf16 values, 256 B
  (`index_key_bytes`);
* the selected rows: min(context, `index_topk`) a stream
  (`rows_selected`), each ONE latent row of `kv_lora_rank` +
  `qk_rope_head_dim` bf16 values, 1,152 B (`row_bytes`), read once;
* the indexer's weights, once, in bf16: `q_lora_rank` x `index_n_heads`
  x `index_head_dim` (its queries read the query latent) + hidden x
  `index_head_dim` + hidden x `index_n_heads` (`indexer_params`:
  9.37 M, 18.7 MB);
* the two expansions the absorbed read multiplies by, once, at the
  bytes a weight of the file's `serve.quant` takes: `kv_lora_rank` x
  heads x (`qk_nope_head_dim` + `v_head_dim`) (`absorbed_params`);
* the index scores, 2 x `index_n_heads` x `index_head_dim` operations
  a scored position (`index_score_flops`), and a row's read over all
  heads, 2 x heads x (2 x `kv_lora_rank` + `qk_rope_head_dim`)
  (`row_flops`).

Whatever serves the path moves at least that: a read that walks every
LIVE row to attend 2,048 of them moves more and the share says so, and
no implementation can read over 100 %. servebench/peaks.py knows no
`index_topk` (it reads `sa_config`): its `block_roofline` counts every
live latent row and no index key or indexer for such a file (PERF.md,
section 7); what this file shares with it are its counts of a latent
ROW and of the two expansions, imported, not repeated.

stdlib only.
"""
from __future__ import annotations

import re
from typing import Dict, Sequence

from servebench import latent_peaks, peaks

#: bf16: the cached rows, the index keys and the indexer's weights
BYTES = 2.0


def is_dsa(config: Dict) -> bool:
    return bool(config.get("index_topk")) and bool(config.get("kv_lora_rank"))


def indexer_params(config: Dict) -> int:
    """Parameters of one layer's indexer."""
    ni, hi = config["index_n_heads"], config["index_head_dim"]
    return config["q_lora_rank"] * ni * hi \
        + config["hidden_size"] * (hi + ni)


def index_key_bytes(config: Dict) -> float:
    return config["index_head_dim"] * BYTES


def index_score_flops(config: Dict) -> float:
    return 2.0 * config["index_n_heads"] * config["index_head_dim"]


#: a latent row's bytes and operations and the two expansions are the
#: benchmark's own counts of a latent file (the parts cannot disagree
#: with the whole): 1,152 B; 2 x heads x (2 x rank + rope); rank x heads
#: x (nope + v)
row_bytes = peaks.cached_row_bytes
row_flops = peaks.row_flops
absorbed_params = latent_peaks.absorbed_params


def rows_selected(config: Dict, rows: float) -> float:
    """Of `rows` cached rows a query could attend, those it reads."""
    return min(rows, config["index_topk"])


def dsa_least_seconds(config: Dict, device_kind: str, chips: int,
                      steps: int, contexts: Sequence[float]
                      ) -> Dict[str, float]:
    """The least time `chips` chips could take for the selecting path of
    one block of `steps` decode steps with live streams of `contexts`
    tokens each. Returns the bytes, the operations, both bounds, which
    one binds and one step's bytes by part (`parts`: `index_keys`,
    `rows`, `indexers`, `expansions`)."""
    L, contexts = config["num_hidden_layers"], list(contexts)
    live = sum(contexts)
    read = sum(rows_selected(config, c) for c in contexts)
    parts = {"index_keys": L * live * index_key_bytes(config),
             "rows": L * read * row_bytes(config),
             "indexers": L * indexer_params(config) * BYTES,
             # int8 codes a byte a weight (the scales a thousandth of
             # that, left out), else bf16; the indexers bf16 either way
             "expansions": L * absorbed_params(config)
             * (1.0 if config["serve"].get("quant") == "int8" else BYTES)}
    fl = L * (live * index_score_flops(config) + read * row_flops(config)
              + 2.0 * len(contexts) * (indexer_params(config)
                                       + absorbed_params(config)))
    return dict(peaks.least_seconds(steps * sum(parts.values()), steps * fl,
                                    device_kind, chips),
                parts=parts, live_tokens=live, selected_tokens=read)


# -- the path's operations in a device trace ---------------------------------
#
# A trace names an operation by its HLO text: the instruction's name and
# the shape of its result (servebench/xplane.py keeps the first 64
# characters, every character outside [A-Za-z0-9_.:-] as `_`). The path
# is ONE Mosaic call a layer and step, named for its jitted wrapper
# (butterfly_tpu/ops/latent_attention.py: `latent_select_attention`)
# with a result [slots, heads, kv_lora_rank], beside XLA's own
# operations, told by the shapes only they produce, computed from the
# file (S slots, M max_seq, `page`, mp = M / page, Hi `index_head_dim`,
# Ni `index_n_heads`, Rp the latent row in whole lanes of 128):
#
#   the call: `latent_select_attention` AND its result's [.., heads,
#     kv_lora_rank]; NOT `latent_attention`, the unselected read of a
#     latent model without an indexer (servebench/latent_peaks.py);
#   a dim of M, S x M or S x mp: the index scores of a stream's whole
#     table and their top-k (a sort), the selection as a mask and as the
#     call's int32 block, a chunk's masks and its masked read over its
#     slot's whole view, the table's index keys as one view;
#   [.., mp, (1,) page, Hi or Rp]: a table row's pages of index keys,
#     and of latent rows (a chunk's masked read);
#   a result that ends in [M / 128, 128]: the running count that breaks
#     a tie at the k-th score, and its relayouts;
#   [.., Ni, Hi]: the index queries (projected from the query latent,
#     rotated); a 3-D [S, n, Hi]: a slot's index keys of the window.
#
# The latent projections, the absorbed queries, the two expansions and
# the output projection are every latent model's [rows, heads, ..] and
# [rows, hidden]: they are NOT caught, and the share is the selecting
# path's alone. PERF.md (section 5) lists the names a traced run showed.

def dsa_patterns(config: Dict):
    """Compiled patterns over a trace's cleaned operation names."""
    serve = config["serve"]
    S, M, page = serve["max_batch"], serve["max_seq"], serve["page_size"]
    mp = -(-M // page)
    nh, r = config["num_attention_heads"], config["kv_lora_rank"]
    hi, ni = config["index_head_dim"], config["index_n_heads"]
    rp = -(-(r + config["qk_rope_head_dim"]) // 128) * 128
    dims = "|".join(str(n) for n in (M, S * M, S * mp))
    return {
        "call": re.compile(
            rf"(?<![A-Za-z])latent_select_attention[._0-9]*_[a-z]+[0-9]+"
            rf"(?:_[0-9]+)*_{nh}_{r}_"),
        "shapes": re.compile(
            rf"(?<![0-9])(?:{dims})(?![0-9])"
            rf"|_{mp}_(?:1_)?{page}_(?:{hi}|{rp})_"
            rf"|_{M // 128}_128__"
            rf"|_\d+_(?:1_)?{ni}_{hi}__"
            rf"|_{S}_\d+_{hi}__"),
    }


def is_dsa_op(name: str, patterns) -> bool:
    return bool(patterns["call"].search(name)
                or patterns["shapes"].search(name))


def dsa_op_seconds(ctx, call_only: bool = False):
    """Self seconds of the selecting path's operations in the trace (the
    Mosaic call alone with `call_only`), or None where there is no trace
    or the configuration selects no latent rows."""
    ops = (ctx.trace or {}).get("ops")
    if not ops or not is_dsa(ctx.config):
        return None
    pats = dsa_patterns(ctx.config)
    if call_only:
        return sum(sec for name, sec, _ in ops if pats["call"].search(name))
    return sum(sec for name, sec, _ in ops if is_dsa_op(name, pats))
