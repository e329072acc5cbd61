"""The table of peaks, and the bytes and operations a decode block needs.

Peaks are published figures of one chip, keyed by the `device_kind` JAX
reports; a kind that is not here is an error, never a default.

stdlib only.
"""
from __future__ import annotations

from typing import Dict

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


def _params(config: Dict, routed_read) -> float:
    """Attention, feed-forward and output head of a configuration file,
    in parameters, where each expert layer reads `routed_read(E, k)` of
    its E routed experts (k `num_experts_per_tok`) beside its router and
    its shared experts. E is `num_experts` or `num_local_experts`; an
    expert is 3 x hidden x `moe_intermediate_size`, else
    `intermediate_size`; `first_k_dense_replace` leading layers are dense."""
    h, hd = config["hidden_size"], config["head_dim"]
    nh, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    L = config["num_hidden_layers"]
    attn = h * nh * hd * 2 + h * nkv * hd * 2
    dense = 3 * h * config["intermediate_size"]
    head = config["vocab_size"] * h
    E = int(config.get("num_experts") or config.get("num_local_experts") or 0)
    if not E:
        return L * (attn + dense) + head
    D = int(config.get("first_k_dense_replace", 0))
    expert = 3 * h * (config.get("moe_intermediate_size")
                      or config["intermediate_size"])
    read = int(config.get("n_shared_experts") or 0) + \
        routed_read(E, int(config["num_experts_per_tok"]))
    return L * attn + D * dense + (L - D) * (h * E + read * expert) + head


def matmul_params(config: Dict) -> float:
    """Parameters one token is MULTIPLIED by in a decode step: every
    layer's attention projections, its feed-forward and the output head
    (the embedding is a lookup of a few rows). With experts a token meets
    its k routed experts and the shared ones:

        L x attention + D x dense + (L - D) x (h x E + (k + shared) x expert) + V x h

    A dense model is D = L."""
    return _params(config, lambda E, k: k)


def streamed_params(config: Dict, live_streams: float = 1.0) -> float:
    """Parameters one decode step STREAMS from memory for n live
    streams. Dense: `matmul_params`. With experts, a step reads every
    expert that some stream's token was routed to: under even routing
    the expected number of distinct experts touched by n x k draws,

        L x attention + D x dense
        + (L - D) x (h x E + (shared + E x (1 - (1 - k/E)^n)) x expert) + V x h

    which is k experts a layer at n = 1 and all E once n x k >> E."""
    n = max(1.0, live_streams)
    return _params(config, lambda E, k: E * (1.0 - (1.0 - k / E) ** n))


def weight_bytes(config: Dict, live_streams: float = 1.0) -> float:
    """Bytes of weights one step streams: int8 codes (scales are a
    thousandth of that and left out), or bf16."""
    per = 1.0 if config["serve"].get("quant") == "int8" else 2.0
    return streamed_params(config, live_streams) * per


def kv_bytes_per_token(config: Dict) -> float:
    """Bytes of cached keys and values one step reads per token of live
    context: int8 codes plus one float32 scale per vector, or bf16."""
    hd = config["head_dim"]
    per_vec = hd + 4.0 if config["serve"].get("kv_quant") == "int8" else 2.0 * hd
    return config["num_hidden_layers"] * 2 * config["num_key_value_heads"] * per_vec


def block_least_seconds(config: Dict, device_kind: str, chips: int,
                        steps: int, live_streams: float,
                        live_context_tokens: float) -> Dict[str, float]:
    """The least time `chips` chips could take for one block of `steps`
    decode steps of w = `decode_width` positions a stream: each step
    streams the weights that the positions of its live streams touch
    once (`streamed_params` at live streams x w draws) and the live
    context's keys and values once, and does two operations per
    position and parameter the position is multiplied by
    (`matmul_params`). Returns both bounds and which one binds."""
    pk = peaks_of(device_kind)
    w = decode_width(config)
    live = max(1.0, live_streams)
    by = steps * (weight_bytes(config, live * w)
                  + live_context_tokens * kv_bytes_per_token(config))
    fl = steps * 2.0 * matmul_params(config) * live * w
    t_mem = by / (chips * pk["hbm_bytes_per_s"])
    t_cmp = fl / (chips * pk["bf16_flops_per_s"])
    return {"bytes": by, "flops": fl, "memory_s": t_mem, "compute_s": t_cmp,
            "least_s": max(t_mem, t_cmp),
            "bound": "memory" if t_mem >= t_cmp else "compute"}
