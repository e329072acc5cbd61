"""Checkpoint import: HF safetensors/torch-bin directories -> our pytrees.

Covers the north-star requirement of loading HF weights into sharded
arrays (SURVEY.md §2.2 C10). Sharded orbax save/load lives in
butterfly_tpu.ckpt.sharded (slice 7).
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path
from typing import Any, Dict

from butterfly_tpu.core.config import ModelConfig


def _load_hf_state_dict(path: Path) -> Dict[str, Any]:
    """Read every *.safetensors (preferred) or pytorch_model*.bin in a dir."""
    sd: Dict[str, Any] = {}
    st_files = sorted(path.glob("*.safetensors"))
    if st_files:
        from safetensors import safe_open
        for f in st_files:
            with safe_open(str(f), framework="np") as h:
                for k in h.keys():
                    sd[k] = h.get_tensor(k)
        return sd
    bin_files = sorted(path.glob("pytorch_model*.bin")) + sorted(path.glob("*.pt"))
    if bin_files:
        import torch
        for f in bin_files:
            sd.update(torch.load(str(f), map_location="cpu",
                                 weights_only=True))
        return sd
    raise FileNotFoundError(
        f"no *.safetensors or pytorch_model*.bin found under {path}")


#: arch -> the module of butterfly_tpu.models that converts its tensors
_CONVERTERS = {"gpt2": "gpt2", "llama": "llama", "mixtral": "mixtral",
               "smallthinker": "smallthinker"}


def load_checkpoint(path: str, cfg: ModelConfig):
    """Load model weights from `path` (HF-format dir) into our param pytree."""
    # the family first: one without a converter is refused by name
    # before a byte is read
    if cfg.arch not in _CONVERTERS:
        raise ValueError(
            f"no checkpoint converter for arch {cfg.arch!r}: the loader "
            f"knows {sorted(_CONVERTERS)}; the other families are served "
            "from seeded weights only")
    params_from_hf_state_dict = importlib.import_module(
        "butterfly_tpu.models." + _CONVERTERS[cfg.arch]
    ).params_from_hf_state_dict
    p = Path(path)
    if not p.is_dir():
        raise FileNotFoundError(f"checkpoint dir not found: {path}")
    sd = _load_hf_state_dict(p)
    return params_from_hf_state_dict(sd, cfg)


def config_from_hf_dir(path: str) -> ModelConfig:
    """Best-effort ModelConfig from a HF config.json next to the weights."""
    cj = json.loads((Path(path) / "config.json").read_text())
    mt = cj.get("model_type", "llama")
    if mt == "gpt2":
        return ModelConfig(
            arch="gpt2", vocab_size=cj["vocab_size"], hidden_size=cj["n_embd"],
            num_layers=cj["n_layer"], num_heads=cj["n_head"],
            num_kv_heads=cj["n_head"], head_dim=cj["n_embd"] // cj["n_head"],
            intermediate_size=cj.get("n_inner") or 4 * cj["n_embd"],
            max_seq_len=cj["n_positions"], use_bias=True, tie_embeddings=True,
            act="gelu_new", pos_embedding="learned",
            norm_eps=cj.get("layer_norm_epsilon", 1e-5),
        )
    common = dict(
        vocab_size=cj["vocab_size"], hidden_size=cj["hidden_size"],
        num_layers=cj["num_hidden_layers"], num_heads=cj["num_attention_heads"],
        num_kv_heads=cj.get("num_key_value_heads", cj["num_attention_heads"]),
        head_dim=cj.get("head_dim",
                        cj["hidden_size"] // cj["num_attention_heads"]),
        # SmallThinker has experts only, and its own key for their width
        intermediate_size=cj["moe_ffn_hidden_size" if mt == "smallthinker"
                             else "intermediate_size"],
        max_seq_len=cj.get("max_position_embeddings", 8192),
        norm_eps=cj.get("rms_norm_eps", 1e-5),
        rope_theta=cj.get("rope_theta", 500000.0),
        tie_embeddings=cj.get("tie_word_embeddings", False),
    )
    if mt == "smallthinker":
        common.update(
            num_experts=cj["moe_num_primary_experts"],
            num_experts_per_tok=cj["moe_num_active_primary_experts"],
            sliding_window=cj["sliding_window_size"],
            sliding_window_layout=cj["sliding_window_layout"],
            rope_layout=cj["rope_layout"])
        return ModelConfig(arch="smallthinker", act="relu",
                           router_input="attn", **common)
    if mt == "mixtral":
        return ModelConfig(arch="mixtral",
                           num_experts=cj.get("num_local_experts", 8),
                           num_experts_per_tok=cj.get("num_experts_per_tok", 2),
                           **common)
    return ModelConfig(arch="llama", **common)
