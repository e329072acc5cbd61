"""GLM-5 family (zai-org/GLM-5, `glm_moe_dsa`, 744B-A40B): latent
attention whose cached rows a lightning indexer SELECTS (DeepSeek Sparse
Attention over MLA), three leading dense layers, then sigmoid-routed
experts of which one chip holds a share.

Every layer's attention is latent (MLA): the query through a latent c_q
of 2,048, keys and values through ONE joint latent of 512 a token beside
ONE rotary key of 64 shared by the 64 heads (interleaved rotation), so a
token caches a single row of 576 values (cache/paged.py pool_row); a
head's key part is 192 wide and its value 256. Beside the row a token
caches ONE index key of 128: 32 index queries a token, projected from
c_q (models/common.py index_proj: Keye's indexer reads the layer's
input, this one the query latent), score every cached position, only
the first 64 dims of an index head rotating, and a query attends the
2,048 positions that score highest (select_mask). A call that reads
cached rows takes the ABSORBED form under that selection
(latent_attend; on the chip ops/latent_attention.py
latent_select_attention walks the slot's live pages with the selection
as a mask); a fresh prefill may take the expanded form. Layers 0-2 have
a dense feed-forward (12,288); every other layer has 256 experts of
2,048, 8 a token by sigmoid scores plus a stored selection bias,
weights normalised over the chosen and times 2.5, plus one shared
expert (route_tokens, moe_block).

One expert layer is 9.66 G parameters: no chip holds one, and a
deployment splits each layer's experts over chips. ModelConfig's
`experts_held` / `experts_first` describe ONE chip of it: the router
ranges over all 256, the expert leaves hold the chip's share, and the
layer computes its held experts' part of the result plus the shared
expert; what the absent experts would add is left out and nothing
stands in for the other chips or their exchange. The published
prediction layer (`num_nextn_predict_layers` 1) is not held. The plain
float32 reference is servebench/references/glm5_f32.py, given the same
share. There is no checkpoint converter (ckpt/load.py refuses the
family by name).
"""
from __future__ import annotations

from butterfly_tpu.core.config import ModelConfig, glm5
from butterfly_tpu.models.common import Model


def model(cfg: ModelConfig | None = None) -> Model:
    return Model(cfg or glm5())
