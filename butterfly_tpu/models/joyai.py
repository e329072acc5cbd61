"""JoyAI-LLM-Flash family (jdopensource/JoyAI-LLM-Flash,
`joyai_llm_flash`, 48B-A2.7B): latent attention over one cached row a
token, a leading dense layer, then sigmoid-routed experts.

Every layer's attention is latent (MLA): the query through a latent of
1,536, keys and values through ONE joint latent of 512 a token beside
ONE rotary key of 64 shared by the 32 heads (interleaved rotation), so a
token caches a single row of 576 values: no heads, no values of its own
(cache/paged.py pool_row). A call that reads cached rows takes the
ABSORBED form, multi-query attention of the 32 heads over that row
(models/common.py latent_attend; ops/latent_attention.py on the chip),
and never materialises a head's keys or values of the context; a fresh
prefill may take the expanded form. Layer 0's feed-forward is dense
(7,168); every other layer has 256 experts of 768, 8 a token: scores
are sigmoids, the choice is by score plus a stored bias, the weights
are the chosen scores over their sum, times 2.5, and one shared expert
is added (route_tokens, moe_block). The two kinds of feed-forward have
unlike shapes, so they are stacked apart (params["dense"],
params["sparse"]) beside what every layer has (params["layers"]), and
the layers run as scans over runs (layer_runs). The published
prediction layer (`num_nextn_predict_layers` 1) is not held: it takes
no part in the model's own next-token distribution. The plain float32
reference is beside this file (joyai_f32.py). There is no checkpoint
converter (ckpt/load.py refuses the family by name).
"""
from __future__ import annotations

from butterfly_tpu.core.config import ModelConfig, joyai_llm_flash
from butterfly_tpu.models.common import Model


def model(cfg: ModelConfig | None = None) -> Model:
    return Model(cfg or joyai_llm_flash())
