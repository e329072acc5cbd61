"""The table of peaks, and the bytes and operations a decode block needs.

Peaks are published figures of one chip, keyed by the `device_kind` JAX
reports; a kind that is not here is an error, never a default.

The count is of the MODEL's work, from the published keys of its
configuration file, layer by layer over the layers AS RUN
(`num_hidden_layers` of the file): never from the program's own
configuration and never from how a kernel happens to read. Three
questions are answered per layer, independent of each other:

1. What kind of mixer the layer is, and its parameters (`mixer_params`):
   a Mamba-2 mixer where `layer_types[l]` is "mamba"
   (servebench/ssm_peaks.py), else attention: grouped-query projections,
   or, where the file has `kv_lora_rank`, the five latent projections
   (`attention_params`); a file with `sa_config` adds an indexer a layer,
   in bf16 (servebench/sparse_peaks.py).
2. What a cached row holds (`cached_row_bytes`): keys and values of
   `num_key_value_heads` heads, or ONE latent of `kv_lora_rank` +
   `qk_rope_head_dim` values a token and layer; a file with `sa_config`
   holds an index key beside it. A Mamba layer caches no row: every live
   stream's state is read and written once.
3. How many rows of a stream of context c a step reads in this layer
   (`rows_read`): all c; min(c, `sliding_window_size`) where
   `sliding_window_layout[l]` is 1; min(that, `sa_config.topk`), and c
   index keys, where the file has `sa_config`. So the count takes the live
   streams' contexts one by one, not their sum.

The feed-forward half is dense, or a router, the shared experts and the
routed experts a step is expected to touch (`_feed_forward`).

`ssm_least_seconds` (the Mamba-2 mixers alone) and `sparse_least_seconds`
(the selecting path alone) are sums of the same per-layer functions that
`block_least_seconds` (the whole step) adds up: the parts cannot disagree
with the whole.

stdlib only.
"""
from __future__ import annotations

from typing import Dict, Sequence

from servebench import sparse_peaks, ssm_peaks
from servebench.manifest import decode_width

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
#: int8, 16 GB of HBM at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9},
}


def peaks_of(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}: "
                       "add it to servebench/peaks.py with its source")
    return PEAKS[device_kind]


def least_seconds(by: float, fl: float, device_kind: str,
                  chips: int) -> Dict[str, float]:
    """The least time `chips` chips could take to move `by` bytes and do
    `fl` operations: both bounds, and which one binds."""
    pk = peaks_of(device_kind)
    t_mem = by / (chips * pk["hbm_bytes_per_s"])
    t_cmp = fl / (chips * pk["bf16_flops_per_s"])
    return {"bytes": by, "flops": fl, "memory_s": t_mem, "compute_s": t_cmp,
            "least_s": max(t_mem, t_cmp),
            "bound": "memory" if t_mem >= t_cmp else "compute"}


# -- one layer ----------------------------------------------------------------

def num_experts(config: Dict) -> int:
    """E, the routed experts of a layer, under each name a family
    publishes it; 0 for a dense model."""
    return int(config.get("num_experts") or config.get("num_local_experts")
               or config.get("n_routed_experts") or 0)


def attention_params(config: Dict) -> int:
    """Parameters of one attention layer's projections. Grouped-query:
    h x nh x hd x 2 (query, output) + h x nkv x hd x 2 (key, value).
    Latent (a file with `kv_lora_rank`): the query through its latent of
    `q_lora_rank` (directly where that is null), the joint latent of keys
    and values with the shared rotary key, its expansion to each head's
    keys (`qk_nope_head_dim`) and values (`v_head_dim`), and the output;
    `head_dim` and `num_key_value_heads` are not read for it."""
    h, nh = config["hidden_size"], config["num_attention_heads"]
    r = config.get("kv_lora_rank")
    if not r:
        hd, nkv = config["head_dim"], config["num_key_value_heads"]
        return h * nh * hd * 2 + h * nkv * hd * 2
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    ql = config.get("q_lora_rank")
    query = h * ql + ql * nh * (nope + rope) if ql else h * nh * (nope + rope)
    return query + h * (r + rope) + r * nh * (nope + v) + nh * v * h


def mixer_params(config: Dict, layer: int) -> int:
    """Parameters of the mixer of layer `layer`, by its kind."""
    if ssm_peaks.is_mamba(config, layer):
        return ssm_peaks.proj_params(config)
    return attention_params(config)


def cached_row_bytes(config: Dict) -> float:
    """Bytes one token's cached row holds in one attention layer. Keys
    and values: int8 codes plus one float32 scale per vector, or bf16. A
    latent row (a file with `kv_lora_rank`): the latent and the rotary
    key, bf16, no heads and no separate values."""
    r = config.get("kv_lora_rank")
    if r:
        return (r + config["qk_rope_head_dim"]) * 2.0
    hd = config["head_dim"]
    per_vec = hd + 4.0 if config["serve"].get("kv_quant") == "int8" \
        else 2.0 * hd
    return 2 * config["num_key_value_heads"] * per_vec


def rows_read(config: Dict, layer: int, context: float) -> float:
    """Cached rows that one step reads in layer `layer` for a stream
    holding `context` tokens."""
    if ssm_peaks.is_mamba(config, layer):
        return 0
    rows = context
    layout = config.get("sliding_window_layout") or []
    if layer < len(layout) and layout[layer]:
        rows = min(rows, config["sliding_window_size"])
    if "sa_config" in config:
        rows = sparse_peaks.rows_selected(config, rows)
    return rows


def row_flops(config: Dict) -> float:
    """Operations one query position spends on one cached row it reads,
    over all its heads: scores and the weighted sum over keys and values
    of `head_dim`, or, over a latent row with the expansions absorbed
    into the query and the output, scores over the latent and the rotary
    key and the weighted sum over the latent."""
    nh, r = config["num_attention_heads"], config.get("kv_lora_rank")
    if r:
        return 2.0 * nh * (2 * r + config["qk_rope_head_dim"])
    return 4.0 * nh * config["head_dim"]


# -- the whole model ----------------------------------------------------------

def _feed_forward(config: Dict, routed_read) -> float:
    """Every layer's feed-forward half, in parameters, where each expert
    layer reads `routed_read(E, k)` of its E routed experts (k
    `num_experts_per_tok`) beside its router and its shared experts. An
    expert is 3 x hidden x `moe_intermediate_size`, else
    `intermediate_size`; `first_k_dense_replace` leading layers are
    dense."""
    h, L = config["hidden_size"], config["num_hidden_layers"]
    dense = 3 * h * config["intermediate_size"]
    E = num_experts(config)
    if not E:
        return L * dense
    D = int(config.get("first_k_dense_replace", 0))
    expert = 3 * h * (config.get("moe_intermediate_size")
                      or config["intermediate_size"])
    read = int(config.get("n_shared_experts") or 0) + \
        routed_read(E, int(config["num_experts_per_tok"]))
    return D * dense + (L - D) * (h * E + read * expert)


def _indexers(config: Dict) -> int:
    """Parameters of every layer's indexer; 0 for a file without one."""
    if "sa_config" not in config:
        return 0
    return config["num_hidden_layers"] * sparse_peaks.indexer_params(config)


def _params(config: Dict, routed_read) -> float:
    """Mixers, feed-forward and output head, less the indexers (held in
    bf16 whatever the weights are: `weight_bytes`)."""
    mixers = sum(mixer_params(config, l)
                 for l in range(config["num_hidden_layers"]))
    return mixers + _feed_forward(config, routed_read) \
        + config["vocab_size"] * config["hidden_size"]


def matmul_params(config: Dict) -> float:
    """Parameters one token is MULTIPLIED by in a decode step: every
    layer's mixer (and indexer), its feed-forward and the output head
    (the embedding is a lookup of a few rows). With experts a token meets
    its k routed experts and the shared ones:

        mixers + D x dense + (L - D) x (h x E + (k + shared) x expert) + V x h

    A dense model is D = L."""
    return _params(config, lambda E, k: k) + _indexers(config)


def _touched(live_streams: float):
    """Of E experts, those that n streams' k even draws each are expected
    to touch: E x (1 - (1 - k/E)^n), k at n = 1 and all E once n x k >> E."""
    n = max(1.0, live_streams)
    return lambda E, k: E * (1.0 - (1.0 - k / E) ** n)


def streamed_params(config: Dict, live_streams: float = 1.0) -> float:
    """Parameters one decode step STREAMS from memory for n live
    streams. Dense: `matmul_params`. With experts, a step reads every
    expert that some stream's token was routed to (`_touched`):

        mixers + D x dense
        + (L - D) x (h x E + (shared + E x (1 - (1 - k/E)^n)) x expert) + V x h"""
    return _params(config, _touched(live_streams)) + _indexers(config)


def _bytes_a_weight(config: Dict) -> float:
    """int8 codes (scales are a thousandth of that and left out), or bf16."""
    return 1.0 if config["serve"].get("quant") == "int8" else 2.0


def weight_bytes(config: Dict, live_streams: float = 1.0) -> float:
    """Bytes of weights one step streams (`streamed_params`), the
    indexers in bf16 whatever the others are."""
    return _params(config, _touched(live_streams)) * _bytes_a_weight(config) \
        + _indexers(config) * sparse_peaks.BYTES


def total_rows_read(config: Dict, contexts: Sequence[float]) -> float:
    """Cached rows one step reads, over every layer and live stream."""
    return sum(rows_read(config, l, c) for c in contexts
               for l in range(config["num_hidden_layers"]))


def step_parts(config: Dict, contexts: Sequence[float]):
    """What ONE decode step moves and does for live streams holding
    `contexts` tokens each, w = `decode_width` positions a stream: the
    bytes by part, and the operations.

    * `weights`: the weights that the positions of the live streams touch,
      once (`weight_bytes` at live streams x w draws; an idle step counts
      as one stream's);
    * `rows`: the cached rows each stream's positions read in each layer
      (`rows_read` x `cached_row_bytes`), once whatever the width;
    * `index_keys`: a selecting file's index keys of the whole context;
    * `state`: every live stream's recurrent state in each Mamba layer,
      read and written;
    * the operations: two a position and parameter it is multiplied by
      (`matmul_params`), the attention's own products over the rows read
      (`row_flops`), the index scores and a Mamba layer's six a state
      value."""
    w = decode_width(config)
    live = max(1.0, len(contexts))
    read = total_rows_read(config, contexts)
    parts = {"weights": weight_bytes(config, live * w),
             "rows": read * cached_row_bytes(config),
             "index_keys": 0.0, "state": 0.0}
    fl = 2.0 * matmul_params(config) * live * w + w * read * row_flops(config)
    mixers = ssm_peaks.mamba_layers(config) * len(contexts)
    if mixers:
        parts["state"] = mixers * ssm_peaks.state_bytes(config)
        fl += w * mixers * ssm_peaks.state_flops(config)
    if "sa_config" in config:
        scored = config["num_hidden_layers"] * sum(contexts)
        parts["index_keys"] = scored * sparse_peaks.index_key_bytes(config)
        fl += w * scored * sparse_peaks.index_score_flops(config)
    return parts, fl


def block_least_seconds(config: Dict, device_kind: str, chips: int,
                        steps: int, contexts: Sequence[float]
                        ) -> Dict[str, float]:
    """The least time `chips` chips could take for one block of `steps`
    decode steps with live streams of `contexts` tokens each: `steps`
    times what `step_parts` counts. Returns both bounds, which one
    binds, and the bytes of one step by part (`parts`)."""
    parts, fl = step_parts(config, contexts)
    by = steps * (parts["weights"] + parts["rows"] + parts["index_keys"]
                  + parts["state"])
    return dict(least_seconds(by, steps * fl, device_kind, chips),
                parts=parts)


# -- the parts a kernel of its own serves -------------------------------------

def ssm_least_seconds(config: Dict, device_kind: str, chips: int,
                      steps: int, live_streams: float) -> Dict[str, float]:
    """The least time `chips` chips could take for the Mamba-2 mixers of
    one block of `steps` decode steps with `live_streams` live streams:
    per Mamba layer and step the two projections once (one byte a
    parameter where the configuration serves int8 codes, else two) and
    every live stream's state read and written."""
    Lm = ssm_peaks.mamba_layers(config)
    proj = ssm_peaks.proj_params(config)
    by = steps * Lm * (proj * _bytes_a_weight(config)
                       + live_streams * ssm_peaks.state_bytes(config))
    fl = steps * Lm * live_streams * (2.0 * proj
                                      + ssm_peaks.state_flops(config))
    return least_seconds(by, fl, device_kind, chips)


def sparse_least_seconds(config: Dict, device_kind: str, chips: int,
                         steps: int, contexts: Sequence[float]
                         ) -> Dict[str, float]:
    """The least time `chips` chips could take for the sparse-attention
    path of one block of `steps` decode steps: per layer and step the
    index keys of the live context, the rows each stream reads
    (`rows_read`) and the indexer's weights. Returns the bytes, the
    operations, both bounds and the two token counts (a layer's)."""
    L, contexts = config["num_hidden_layers"], list(contexts)
    live, read = sum(contexts), total_rows_read(config, contexts)
    idx = _indexers(config)
    by = steps * (L * live * sparse_peaks.index_key_bytes(config)
                  + read * cached_row_bytes(config)
                  + idx * sparse_peaks.BYTES)
    fl = steps * (L * live * sparse_peaks.index_score_flops(config)
                  + read * row_flops(config)
                  + 2.0 * len(contexts) * idx)
    return dict(least_seconds(by, fl, device_kind, chips), live_tokens=live,
                selected_tokens=read / L)
