"""SmallThinker MoE family (PowerInfer/SmallThinker-21BA3B-Instruct).

Grouped-query attention whose layers are of two kinds (of every four,
one full layer without positional encoding and three that slide over
4,096 tokens and rotate) over 64 ReGLU experts, 6 a token, whose router
reads the ATTENTION's normed input; expressed via ModelConfig
(router_input, sliding_window, the two layouts) over models/common.py.
The plain float32 reference is beside this file (smallthinker_f32.py).
"""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp
import numpy as np

from butterfly_tpu.core.config import ModelConfig, smallthinker_21b_a3b  # noqa: F401
from butterfly_tpu.models.common import Model


def model(cfg: ModelConfig | None = None) -> Model:
    return Model(cfg or smallthinker_21b_a3b())


def params_from_hf_state_dict(sd: Dict[str, Any], cfg: ModelConfig) -> Dict:
    """Convert HF SmallThinkerForCausalLM weights to our pytree.

    The names are those of the source's modelling code as remembered,
    NOT checked against a checkpoint (this sandbox has none): the
    attention and the norms are Llama's; the experts live at
    model.layers.{l}.block_sparse_moe.experts.{e}.gate|up|down.weight
    ([F,D], [F,D], [D,F]) and the router at
    block_sparse_moe.primary_router.weight [E,D]. Our layout stacks
    layers AND experts: w_gate/w_up [L,E,D,F], w_down [L,E,F,D],
    router [L,D,E]. A checkpoint that names them otherwise fails here
    with the missing key.
    """
    def g(name):
        t = sd[name]
        return np.asarray(
            t.detach().cpu().numpy() if hasattr(t, "detach") else t,
            dtype=np.float32)

    L, D = cfg.num_layers, cfg.hidden_size
    Nq, Kv, H, E = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.num_experts
    moe = "model.layers.{}.block_sparse_moe."

    def stack(fmt, post=lambda a: a):
        return jnp.asarray(np.stack([post(g(fmt.format(i)))
                                     for i in range(L)]))

    def proj(n_heads):
        return lambda a: a.T.reshape(D, n_heads, H)

    def experts(which):  # gate|up|down -> [L,E,...] transposed to [in,out]
        return jnp.asarray(np.stack([
            np.stack([g(moe.format(l) + f"experts.{e}.{which}.weight").T
                      for e in range(E)]) for l in range(L)]))

    return {
        "embed": {"tok": jnp.asarray(g("model.embed_tokens.weight"))},
        "layers": {
            "ln1": {"scale": stack("model.layers.{}.input_layernorm.weight")},
            "ln2": {"scale": stack(
                "model.layers.{}.post_attention_layernorm.weight")},
            "attn": {
                "wq": stack("model.layers.{}.self_attn.q_proj.weight",
                            proj(Nq)),
                "wk": stack("model.layers.{}.self_attn.k_proj.weight",
                            proj(Kv)),
                "wv": stack("model.layers.{}.self_attn.v_proj.weight",
                            proj(Kv)),
                "wo": stack("model.layers.{}.self_attn.o_proj.weight",
                            post=lambda a: a.T.reshape(Nq, H, D)),
            },
            "moe": {
                "router": stack(moe + "primary_router.weight",
                                post=lambda a: a.T),          # [D,E]
                "w_gate": experts("gate"),
                "w_up": experts("up"),
                "w_down": experts("down"),
            },
        },
        "final_norm": {"scale": jnp.asarray(g("model.norm.weight"))},
        "lm_head": jnp.asarray(g("lm_head.weight").T),
    }
