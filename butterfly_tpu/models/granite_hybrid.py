"""Granite-4.0-H family (ibm-granite/granite-4.0-h-small,
`granitemoehybrid`): Mamba-2 mixers beside grouped-query attention.

Of every ten layers nine are Mamba-2 mixers (128 heads of 64, d_state
128, one group, a causal conv of 4) and one is attention without
positional encoding; every layer is followed by 72 SiLU experts of 768,
10 a token, and one shared expert of 1,536; Granite's four multipliers
(embedding, residual, attention, logits) and a tied head. Expressed via
ModelConfig (layer_types, ssm_*, shared_intermediate_size, the
multipliers) over models/common.py: the two kinds of layer have unlike
parameter shapes, so each kind's mixer is stacked apart
(params["mamba"], params["attn"]) beside what every layer has
(params["layers"]), and the layers run as scans over runs of one kind
(layer_runs). A stream's memory of a Mamba layer is a fixed-size state a
SLOT (cache/ssm_state.py), beside a paged pool that holds the attention
layers alone. The plain float32 reference is beside this file
(granite_hybrid_f32.py). There is no checkpoint converter (ckpt/load.py
refuses the family by name).
"""
from __future__ import annotations

from butterfly_tpu.core.config import ModelConfig, granite_4_h_small
from butterfly_tpu.models.common import Model


def model(cfg: ModelConfig | None = None) -> Model:
    return Model(cfg or granite_4_h_small())
