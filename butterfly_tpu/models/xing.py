"""Xing4.0-29B-A4B's family (XingChen-AGI/Xing4.0-29B-A4B, `xing4_0`):
a residual path of FOUR streams mixed by manifold-constrained
hyper-connections, around latent attention and sigmoid-routed experts.

What the family changes is the residual path, which no other family
here does: a token carries n = `hc_mult` = 4 streams of 3,584 in place
of one. Every sublayer (a layer's attention, its feed-forward) reads a
learned mix of the streams, H_pre . X, under its own pre-norm, writes
its output back onto all four, outer(H_post, y), and the streams mix
among themselves through H_res, a 4 x 4 matrix made doubly stochastic
by 20 Sinkhorn rounds a token and sublayer (mHC, arXiv:2512.24880 over
hyper-connections, arXiv:2409.19606); the three come from ONE
projection [14336, 24] of the token's normed streams. That is one pair
of functions, models/common.py stream_read and stream_write, which
every layer body of every family calls (for one stream they are the
norm and x + y, to the bit); the carry of every layer scan is
[n, rows, D], embed_tokens copies the embedding n times and stream_fold
sums the streams before the final norm. The mixing's arithmetic is
float32 whatever the compute dtype is.

Around it: JoyAI-LLM-Flash's latent attention (models/joyai.py: one
cached row of 512 + 64 a token, read absorbed, ops/latent_attention.py
on the chip) with the query through a latent of 768 and a YaRN rotation
(factor 64 over 4,096: core/config.py yarn_inv_freq; m^2 = 2.0047 on the
softmax scale, attn_scale); TWO leading dense layers of 9,216, then 64
experts of 1,024, 4 a token by sigmoid scores with a selection bias,
weights times 2, plus one shared expert (layer_runs: the dense layers
are one run). The published prediction layer
(`num_nextn_predict_layers` 1) is not held. The plain float32 reference
is servebench/references/xing_f32.py, which has the equations and every
assumption at its head. There is no checkpoint converter (ckpt/load.py
refuses the family by name). What a path that hands [rows, D] between
its layers itself cannot carry refuses the model by name
(streams_unsupported: pipeline stages, the sequence-parallel lanes).
"""
from __future__ import annotations

from butterfly_tpu.core.config import ModelConfig, xing4_29b_a4b
from butterfly_tpu.models.common import Model


def model(cfg: ModelConfig | None = None) -> Model:
    return Model(cfg or xing4_29b_a4b())
