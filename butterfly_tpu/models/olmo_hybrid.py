"""Olmo-Hybrid family (allenai/Olmo-Hybrid-7B, `olmo_hybrid`): Gated
DeltaNet mixers beside full attention, three layers in four.

A Gated DeltaNet layer (arXiv:2412.06464; the `linear_*` keys) keeps ONE
matrix a head as its memory of a stream, 30 heads of [192, 96], and
rewrites it by the DELTA RULE: the state is decayed, READ for the
incoming key, and the difference between the incoming value and what it
held is written back (S <- a S + b (v - a S k) k^T), with beta in (0, 2)
(`linear_allow_neg_eigval`); q, k and v pass one causal conv of 4 taps,
and the readout is normed a head and then gated. Every fourth layer is
full attention of 30 heads over 30 KV heads of 128 (a group of ONE query
a KV head) with an RMSNorm over the WHOLE query and key projections and
NO rotation: the recurrent layers carry order. Every layer has a dense
SwiGLU of 11,008, and the norm of each sublayer sits on its OUTPUT,
x + norm(F(x)) (OLMo 2's block); untied head. Expressed via ModelConfig
(layer_types "linear_attention", gdn_*, post_norm, qk_norm_wide) over
models/common.py: the two kinds of layer have unlike parameter shapes,
so each kind's mixer is stacked apart (params["gdn"], params["attn"])
beside what every layer has (params["layers"]), and the layers run as
scans over runs of one kind (layer_runs). A stream's memory of a Gated
DeltaNet layer is a fixed-size state a SLOT (cache/ssm_state.py: the
second recurrent kind beside Mamba-2, held with two heads' values in one
row of 384 lanes), beside a paged pool that holds the 8 attention layers
alone. The plain float32 reference is
butterfly_tpu/models/olmo_hybrid_f32.py (the benchmark's copy:
servebench/references/olmo_hybrid_f32.py). There is no checkpoint
converter (ckpt/load.py refuses the family by name).
"""
from __future__ import annotations

from butterfly_tpu.core.config import ModelConfig, olmo_hybrid_7b
from butterfly_tpu.models.common import Model


def model(cfg: ModelConfig | None = None) -> Model:
    return Model(cfg or olmo_hybrid_7b())
