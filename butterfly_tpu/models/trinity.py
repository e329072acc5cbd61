"""Trinity family (arcee-ai/Trinity-Large-Preview, `afmoe`): sliding
layers that forget beside full ones, gated attention under sandwich
norms, sigmoid-routed experts.

Of every four layers three attend the last 4,096 positions and rotate
their queries and keys; the fourth attends everything and rotates
nothing (`layer_types`, `global_attn_every_n_layers` 4: layer i is full
where (i + 1) % 4 == 0). Attention is grouped-query, 48 heads over 8
key-value heads of 128, with a norm on each head's queries and keys and
an OUTPUT GATE: the heads' output times sigmoid(h W_g), elementwise,
before the output projection. Every sublayer is normed on BOTH sides, x +
norm_post(F(norm_in(x))), and the embedding is scaled by sqrt(hidden)
(`mup_enabled`). Layers 0-5 have a dense SwiGLU of 12,288; every other
layer 256 experts of 3,072, 4 a token chosen by sigmoid scores with a
stored selection bias, weighted by their normalised scores times 2.448,
plus one shared expert; untied head. Expressed via ModelConfig
(sliding_window + layouts, qk_norm, attn_gate, sandwich_norm, mup_embed,
first_k_dense, router_score "sigmoid", router_bias,
routed_scaling_factor, experts_held) over models/common.py: the gate is
attn_gate / attn_output, the second norm rides stream_read /
stream_write, and the two shapes of feed-forward run as layer runs
(layer_runs; _runs_forward on the contiguous path). No chip holds one
expert layer (7.25 G parameters): a deployment splits the experts
(experts_held). A stream that outlives the window keeps a sliding
layer's rows in a RING of its own beside the full layers' pages
(cache/paged.py ring_pages). The plain float32 reference is
butterfly_tpu/models/trinity_f32.py (the benchmark's copy:
servebench/references/trinity_f32.py). There is no checkpoint converter
(ckpt/load.py refuses the family by name).
"""
from __future__ import annotations

from butterfly_tpu.core.config import ModelConfig, trinity_large
from butterfly_tpu.models.common import Model


def model(cfg: ModelConfig | None = None) -> Model:
    return Model(cfg or trinity_large())
