"""The table of peaks, and the bytes and operations a decode block needs.

Peaks are published figures of one chip, keyed by the `device_kind` JAX
reports; a kind that is not here is an error, never a default.

stdlib only.
"""
from __future__ import annotations

from typing import Dict

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


def matmul_params(config: Dict) -> int:
    """Parameters of the matrices one decode step multiplies by: every
    layer's attention and feed-forward projections and the output head
    (the embedding is a lookup of a few rows)."""
    h, hd = config["hidden_size"], config["head_dim"]
    nh, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    attn = h * nh * hd * 2 + h * nkv * hd * 2
    mlp = 3 * h * config["intermediate_size"]
    return config["num_hidden_layers"] * (attn + mlp) + config["vocab_size"] * h


def weight_bytes(config: Dict) -> float:
    """Bytes of weights one step streams: int8 codes (scales are a
    thousandth of that and left out), or bf16."""
    per = 1.0 if config["serve"].get("quant") == "int8" else 2.0
    return matmul_params(config) * per


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
    decode steps: each step streams the weights once and the live
    context's keys and values, and does two operations per parameter
    and live stream. Returns both bounds and which one binds."""
    pk = peaks_of(device_kind)
    by = steps * (weight_bytes(config)
                  + live_context_tokens * kv_bytes_per_token(config))
    fl = steps * 2.0 * matmul_params(config) * max(1.0, live_streams)
    t_mem = by / (chips * pk["hbm_bytes_per_s"])
    t_cmp = fl / (chips * pk["bf16_flops_per_s"])
    return {"bytes": by, "flops": fl, "memory_s": t_mem, "compute_s": t_cmp,
            "least_s": max(t_mem, t_cmp),
            "bound": "memory" if t_mem >= t_cmp else "compute"}
