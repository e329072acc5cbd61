"""Jamba family (ai21labs/AI21-Jamba2-3B, `jamba`): Mamba-1 mixers
beside attention over ONE key-value head.

A Mamba-1 layer (arXiv:2312.00752; the `mamba_*` keys) keeps N = 16
numbers a CHANNEL as its memory of a stream, 5,120 channels a layer, and
decays each at a rate of its own: h[c, n] <- exp(dt[c] A[c, n]) h[c, n]
+ dt[c] B[n] u[c], where a Mamba-2 head's whole state shares one scalar.
dt reaches the channels through a bottleneck of 160; dt, B and C are
projected from the causal conv's OUTPUT (4 taps with a bias, over the
inner stream alone), each through an RMSNorm of its own (the family's);
the readout is gated by silu(z) with NO norm behind the gate. Layers 7
and 21 of 28 (`attn_layer_period` 14, `attn_layer_offset` 7) are
attention of 20 heads over one key-value head of 128 with NO rotation:
the recurrent layers carry order. Every layer has a dense SwiGLU of
8,192 (`num_experts` 1: no router); tied head. Expressed via ModelConfig
(layer_types "mamba1", mamba1_*) over models/common.py: the two kinds of
layer have unlike parameter shapes, so each kind's mixer is stacked
apart (params["mamba1"], params["attn"]) beside what every layer has
(params["layers"]), and the layers run as scans over runs of one kind
(layer_runs). A stream's memory of a Mamba-1 layer is a fixed-size state
a SLOT (cache/ssm_state.py: the third recurrent kind, held [16, 5120]
with the channels on the lanes), beside a paged pool that holds the 2
attention layers alone. The plain float32 reference is
butterfly_tpu/models/jamba_f32.py (the benchmark's copy:
servebench/references/jamba_f32.py). There is no checkpoint converter
(ckpt/load.py refuses the family by name).
"""
from __future__ import annotations

from butterfly_tpu.core.config import ModelConfig, jamba2_3b
from butterfly_tpu.models.common import Model


def model(cfg: ModelConfig | None = None) -> Model:
    return Model(cfg or jamba2_3b())
