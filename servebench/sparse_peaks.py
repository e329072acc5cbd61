"""The least time of the sparse-attention path of a decode block: the
bytes that a model with a learned sparse-attention indexer (`sa_config`
in its configuration file) must read to select and to attend, over the
published memory bandwidth (servebench/peaks.py).

It counts the work of the MODEL, not of an implementation: per layer
and decode step,

* the index keys of the live context: `indexer_num_kv_heads` x
  `indexer_head_dim` values a position (128 B in bf16), every position
  of every live stream, since a query scores all of them;
* the selected rows of keys and values: min(context, `topk`) a stream,
  2 x `num_key_value_heads` x `head_dim` values each (2,048 B in bf16);
* the indexer's weights, once: hidden x (`indexer_num_heads` x
  `indexer_head_dim` + `indexer_num_kv_heads` x `indexer_head_dim` +
  `indexer_num_heads`) values, in bf16.

Whatever kernel serves it reads at least that. The operations (the index
scores are 2 x heads x dim a position and query) are three orders under
the chip's peak at these sizes and bound nothing; they are returned
beside the bytes.

stdlib only.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable

from servebench.peaks import peaks_of

#: bf16: cached rows and the indexer's weights
BYTES = 2.0


def indexer_params(config: Dict) -> float:
    """Parameters of one layer's indexer."""
    sa = config["sa_config"]
    per_head = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    key = sa["indexer_num_kv_heads"] * sa["indexer_head_dim"]
    return config["hidden_size"] * (per_head + key + sa["indexer_num_heads"])


def index_key_bytes(config: Dict) -> float:
    """Bytes of index key a cached position holds in one layer."""
    sa = config["sa_config"]
    return sa["indexer_num_kv_heads"] * sa["indexer_head_dim"] * BYTES


def kv_row_bytes(config: Dict) -> float:
    """Bytes of keys and values a cached position holds in one layer."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * BYTES


def sparse_least_seconds(config: Dict, device_kind: str, chips: int,
                         steps: int, contexts: Iterable[float]
                         ) -> Dict[str, float]:
    """The least time `chips` chips could take for the sparse-attention
    path of one block of `steps` decode steps: `contexts` holds the
    tokens of context of each live stream. Returns the bytes, the
    operations and both bounds."""
    pk = peaks_of(device_kind)
    sa = config["sa_config"]
    contexts = list(contexts)
    L = config["num_hidden_layers"]
    live = sum(contexts)
    selected = sum(min(c, sa["topk"]) for c in contexts)
    by = steps * L * (live * index_key_bytes(config)
                      + selected * kv_row_bytes(config)
                      + indexer_params(config) * BYTES)
    heads = config["num_attention_heads"]
    fl = steps * L * 2.0 * (
        live * sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + 2 * selected * heads * config["head_dim"]
        + len(contexts) * indexer_params(config))
    t_mem = by / (chips * pk["hbm_bytes_per_s"])
    t_cmp = fl / (chips * pk["bf16_flops_per_s"])
    return {"bytes": by, "flops": fl, "memory_s": t_mem, "compute_s": t_cmp,
            "least_s": max(t_mem, t_cmp), "live_tokens": live,
            "selected_tokens": selected}


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
# pages of `page`, Hi the index key's width):
#
#   a dim of M, S x M, S x K, S x K x Kv or S x M / page: the index
#     scores of a stream's whole table and their sort (lax.top_k), the
#     masks of a chunk's rows and its masked product over its slot's whole
#     view; the table's index keys as one view; the (page, offset) of the
#     selected rows; the rows of the pool seen flat;
#   [S, n, Kv, H] and [S, Kv, G, n] with n >= K: the selected rows beside
#     the window's, and the product over them; [S, K, Kv]: their indices;
#   [.., M / page, (Kv,) page, Hi or H]: a table row's pages of index
#     keys, and of keys and values (a chunk's masked read);
#   [S, M / 128, 128]: the running count that breaks a tie at the k-th
#     score; a 2-D [S, K] result that is not bf16: the selection itself
#     (hidden_size == topk makes bf16[32, 2048] an activation).
#
# PERF.md (section 5) lists the names a traced run showed.

def sparse_patterns(config: Dict):
    """Compiled patterns over a trace's cleaned operation names."""
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
            rf"|_{mp}_(?:{Kv}_)?{page}_(?:{Hi}|{H})_"
            rf"|_{S}_{K}_{Kv}__|_{S}_{M // 128}_128__"
            rf"|_(?:f32|s32|u32|pred)_{S}_{K}__"),
        "at_least_topk": re.compile(
            rf"_{S}_(\d+)_{Kv}_{H}_|_{S}_{Kv}_{G}_(\d+)_"),
    }


def is_sparse_op(name: str, patterns, topk: int) -> bool:
    if patterns["always"].search(name):
        return True
    m = patterns["at_least_topk"].search(name)
    return bool(m) and int(m.group(1) or m.group(2)) >= topk


def sparse_op_seconds(ctx):
    """Self seconds of the sparse path's operations in the trace, or None
    where there is no trace or the configuration has no indexer."""
    ops = (ctx.trace or {}).get("ops")
    if not ops or "sa_config" not in ctx.config:
        return None
    pats = sparse_patterns(ctx.config)
    topk = ctx.config["sa_config"]["topk"]
    return sum(sec for name, sec, _ in ops if is_sparse_op(name, pats, topk))


def live_contexts(streams, t: float):
    """Tokens of context of each stream generating at time t: prompt plus
    the tokens it had received by t (servebench/metrics.py:live_context,
    a stream at a time)."""
    return [s.prompt_len + sum(1 for x in s.times if x <= t)
            for s in streams
            if s.times and s.times[0] <= t and (s.end is None or t < s.end)]
