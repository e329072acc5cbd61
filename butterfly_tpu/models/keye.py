"""Keye-VL-2.0 MoE family (Kwai-Keye/Keye-VL-2.0-30B-A3B), the language
model alone.

Grouped-query attention with an RMSNorm on each head's queries and keys
and a learned sparse-attention indexer (DeepSeek Sparse Attention's
lightning indexer: 16 index heads of 64 and one index key a token pick
the 2,048 cached positions a query attends) over 128 SiLU-gated experts,
8 a token; expressed via ModelConfig (qk_norm, index_heads,
index_head_dim, index_topk) over models/common.py. Text only: the vision
tower is not here. The plain float32 reference is beside this file
(keye_f32.py). There is no checkpoint converter: the source's weight
names are not known here, and a guessed table would fail at the first
real checkpoint (ckpt/load.py refuses the family by name).
"""
from __future__ import annotations

from butterfly_tpu.core.config import ModelConfig, keye_vl2_30b_a3b
from butterfly_tpu.models.common import Model


def model(cfg: ModelConfig | None = None) -> Model:
    return Model(cfg or keye_vl2_30b_a3b())
