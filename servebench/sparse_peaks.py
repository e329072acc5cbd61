"""A layer with a learned sparse-attention indexer (`sa_config` in the
configuration file), counted from the file: what such a layer holds and
reads beside an attention layer's own rows, and how a device trace tells
the path's operations.

It counts the work of the MODEL, not of an implementation: per layer
and decode step,

* the index keys of the live context: `indexer_num_kv_heads` x
  `indexer_head_dim` values a position (128 B in bf16), every position
  of every live stream, since a query scores all of them
  (`index_key_bytes`);
* the selected rows: min(rows, `topk`) a stream (`rows_selected`), each
  what a cached row of the model holds (servebench/peaks.py:
  `cached_row_bytes`);
* the indexer's weights, once: hidden x (`indexer_num_heads` x
  `indexer_head_dim` + `indexer_num_kv_heads` x `indexer_head_dim` +
  `indexer_num_heads`) values, in bf16 (`indexer_params`);
* the index scores: 2 x heads x dim operations a position and query
  (`index_score_flops`).

Whatever kernel serves it reads at least that. These are the ONLY
formulas of the indexer in the benchmark: `servebench/peaks.py` adds them
up, for the path alone (`sparse_least_seconds`, read by
`sparse_attn_roofline`) and for the whole step (`block_least_seconds`,
read by `block_roofline`).

stdlib only.
"""
from __future__ import annotations

import re
from typing import Dict

#: bf16: the index keys and the indexer's weights
BYTES = 2.0


def indexer_params(config: Dict) -> int:
    """Parameters of one layer's indexer."""
    sa = config["sa_config"]
    per_head = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    key = sa["indexer_num_kv_heads"] * sa["indexer_head_dim"]
    return config["hidden_size"] * (per_head + key + sa["indexer_num_heads"])


def index_key_bytes(config: Dict) -> float:
    """Bytes of index key a cached position holds in one layer."""
    sa = config["sa_config"]
    return sa["indexer_num_kv_heads"] * sa["indexer_head_dim"] * BYTES


def index_score_flops(config: Dict) -> float:
    """Operations one query spends scoring one cached position."""
    sa = config["sa_config"]
    return 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"]


def rows_selected(config: Dict, rows: float) -> float:
    """Of `rows` cached rows a query could attend, those it reads."""
    return min(rows, config["sa_config"]["topk"])


# -- the path's operations in a device trace ---------------------------------
#
# The sparse path is XLA's own operations (an index-score product, a sort,
# gathers, a dense product over the selected rows), not a kernel with a
# name of its own: a trace names an operation by its HLO text, which
# starts with the instruction's name and the shape of its result
# (servebench/xplane.py keeps the first 64 characters, every character
# outside [A-Za-z0-9_.:-] as `_`). So the path's operations are told by
# the shapes only they produce, computed from the configuration file
# (S slots, M max_seq, K topk, Kv KV heads of H, G queries a KV head,
# pages of `page`, Hi the index key's width, `steps` a block's steps):
#
#   a dim of M, S x M, S x K, S x K x Kv or S x M / page: the index
#     scores of a stream's whole table and their sort (lax.top_k), the
#     masks of a chunk's rows and its masked product over its slot's whole
#     view; the table's index keys as one view; the (page, offset) of the
#     selected rows; the rows of the pool seen flat;
#   [S, n, Kv, H], [S, Kv, G, n] and, a KV head at a time, [S, 1 | Kv, 1,
#     G, n] with n >= K: the selected rows beside the window's, and the
#     scores and the softmax over them; [S, K, Kv]: their indices;
#   [.., M / page, (Kv,) page, Hi, H or Kv x H]: a table row's pages of
#     index keys, and of keys and values a head or a token a row (a
#     chunk's masked read);
#   a 3-D [S, n, Kv x H] or [S, n, Hi]: a slot's rows of the window (keys,
#     values, index keys: a token a row) and the index queries;
#   a 5-D [.., .., 1, G, H]: the output of one KV head's queries, a decode
#     row's and a chunk's; a 3-D f32 [S, Kv, G] or [Kv, n, G]: their
#     softmax's running maximum and sum;
#   a result that ends in [M / 128, 128]: the running count that breaks a
#     tie at the k-th score, and its relayouts; a 2-D [S, K] result that
#     is not bf16: the selection itself (hidden_size == topk makes
#     bf16[32, 2048] an activation);
#   a 1-D pred of a whole number of S x steps: the selection's mask over
#     the rows every slot staged in the window (a slot stages a whole
#     number of rows a step).
#
# PERF.md (section 5) lists the names a traced run showed.

def sparse_patterns(config: Dict):
    """Compiled patterns over a trace's cleaned operation names, and the
    two sizes a matched dim is held against."""
    serve, sa = config["serve"], config["sa_config"]
    S, M, page = serve["max_batch"], serve["max_seq"], serve["page_size"]
    K, mp = sa["topk"], -(-M // page)
    Kv, H, Hi = (config["num_key_value_heads"], config["head_dim"],
                 sa["indexer_head_dim"])
    G = config["num_attention_heads"] // Kv
    dims = "|".join(str(n) for n in (M, S * M, S * K, S * K * Kv, S * mp))
    return {
        "always": re.compile(
            rf"(?<![0-9])(?:{dims})(?![0-9])"
            rf"|_{mp}_(?:{Kv}_)?{page}_(?:{Hi}|{H}|{Kv * H})_"
            rf"|_{S}_{K}_{Kv}__|_{M // 128}_128__"
            rf"|_(?:f32|s32|u32|pred)_{S}_{K}__"
            rf"|_{S}_\d+_(?:{Kv * H}|{Hi})__"
            rf"|_\d+_\d+_1_{G}_{H}__"
            rf"|_f32_(?:{S}_{Kv}|{Kv}_\d+)_{G}__"),
        "at_least_topk": re.compile(
            rf"_{S}_(\d+)_{Kv}_{H}_|_{S}_(?:1|{Kv})_(?:1_)?{G}_(\d+)_"),
        "topk": K,
        "window_mask": re.compile(r"_pred_(\d+)__0:T"),
        "window_unit": S * serve["decode_steps_per_tick"],
    }


def is_sparse_op(name: str, patterns) -> bool:
    if patterns["always"].search(name):
        return True
    m = patterns["at_least_topk"].search(name)
    if m and int(m.group(1) or m.group(2)) >= patterns["topk"]:
        return True
    m = patterns["window_mask"].search(name)
    return bool(m) and int(m.group(1)) > 0 \
        and int(m.group(1)) % patterns["window_unit"] == 0


def sparse_op_seconds(ctx):
    """Self seconds of the sparse path's operations in the trace, or None
    where there is no trace or the configuration has no indexer."""
    ops = (ctx.trace or {}).get("ops")
    if not ops or "sa_config" not in ctx.config:
        return None
    pats = sparse_patterns(ctx.config)
    return sum(sec for name, sec, _ in ops if is_sparse_op(name, pats))
