"""The plain reference of JoyAI-LLM-Flash (`joyai_llm_flash`): the
forward pass in straightforward `jax.numpy` and float32, with no kernels,
no cache, no absorbed products and no batching.

Written from the published configuration
(huggingface.co/jdopensource/JoyAI-LLM-Flash `config.json`) and the
family's description ("MLA - 40L; 256 experts, top-8, 1 shared; MTP 1").
It shares no code with the program. Tokens to x = E[tok]; for each layer

    x = x + attention(RMSNorm(x; g1))
    x = x + feed_forward(RMSNorm(x; g2))

then a final RMSNorm and logits = x W_head (untied). eps `rms_norm_eps`.

attention (latent, `num_attention_heads` heads), the EXPANDED form:
    c_q = RMSNorm(a W_dq; g_q)                         `q_lora_rank`
    q = c_q W_uq, a head's `qk_nope_head_dim` | `qk_rope_head_dim`
    [c_kv | k_r] = a W_dkv                    `kv_lora_rank` | rope dim
    c_kv = RMSNorm(c_kv; g_kv);  k_r is ONE rotary key a token, shared
    by every head
    q_rope and k_r are rotated at the token's position: theta
    `rope_theta`, `rope_scaling` null (no mscale), `rope_interleave`
    true: the pair i of a head is its dims (2i, 2i+1), rotated by
    pos x theta^(-2i/rope dim), and stays where it was
    k_nope,h = c_kv W_uk,h;  v_h = c_kv W_uv,h       `v_head_dim`
    score_h[t,s] = (q_nope,h[t] . k_nope,h[s] + q_rope,h[t] . k_r[s])
                   x (nope + rope)^-1/2, causal softmax
    out = concat_h(sum_s p v_h[s]) W_o
feed-forward: the first `first_k_dense_replace` layers a SwiGLU of
  `intermediate_size`; every other layer
    s = sigmoid(h W_g)                      over `n_routed_experts`
    the top `num_experts_per_tok` of s + b (b the stored `noaux_tc`
    correction; `n_group` 1 and `topk_group` 1: one group, no group
    step), an equal score to the lower index
    w = s of the chosen (WITHOUT b) / their sum (`norm_topk_prob`)
        x `routed_scaling_factor`
    y = sum_i w_i E_i(h) + S(h): experts and the `n_shared_experts`
    shared expert SwiGLU of `moe_intermediate_size`

Departures from the source, and what is assumed (the configuration
file's `assumed` has each with its reason):
- The prediction layer (`num_nextn_predict_layers` 1) takes no part in
  the next-token distribution and is left out.
- The source fuses `kv_b_proj` (W_uk and W_uv of a head side by side)
  and an expert's gate and up; the splits are layout.
- The shared expert is ONE SwiGLU of `n_shared_experts` x
  `moe_intermediate_size`.

It follows the contract of servebench/refcheck.py: `leaf(path, layer)`
hands over one leaf of the program's parameter tree as float32, and the
sizes come from the configuration FILE's published keys. It holds ONE
layer's and ONE expert's weights at a time. On a TPU a float32 matrix
multiplication runs in lower precision unless told otherwise, so
everything runs under `jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotate_pairs(x, theta: float):
    """x [T, ..., R] at positions 0..T-1: pair i is dims (2i, 2i+1)."""
    T, R = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq        # [T, R/2]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (R // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     odd * jnp.cos(ang) + even * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


#: query rows whose scores are held at once: a stream of 4,500 tokens
#: (tools/latent_parity.py) is 0.6 GB of float32 scores a block of 1,024
ROWS = 1024


def attention(a, w: Dict, config: Dict):
    """a [T, D] normed; causal latent attention, expanded."""
    eps = float(config["rms_norm_eps"])
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    R, theta = config["kv_lora_rank"], float(config["rope_theta"])
    q = jnp.einsum("tr,rnh->tnh", rms_norm(a @ w["w_dq"], w["q_norm"], eps),
                   w["w_uq"])
    q_nope, q_rope = q[..., :nope], rotate_pairs(q[..., nope:], theta)
    ckv = a @ w["w_dkv"]
    c = rms_norm(ckv[:, :R], w["kv_norm"], eps)
    k_r = rotate_pairs(ckv[:, R:], theta)                          # [T, rope]
    k_nope = jnp.einsum("sr,rnh->snh", c, w["w_uk"])
    v = jnp.einsum("sr,rnh->snh", c, w["w_uv"])
    T = a.shape[0]
    out = []
    for lo in range(0, T, ROWS):    # the queries in blocks: [n, ROWS, T]
        at = jnp.arange(lo, min(lo + ROWS, T))
        see = jnp.arange(T)[None, :] <= at[:, None]
        sc = (jnp.einsum("tnh,snh->nts", q_nope[at], k_nope)
              + jnp.einsum("tnh,sh->nts", q_rope[at], k_r)) \
            * (nope + rope) ** -0.5
        pr = jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("nts,snh->tnh", pr, v))
    return jnp.einsum("tnh,nhd->td", jnp.concatenate(out), w["wo"])


def route(h, router, bias, top_k: int, scale: float):
    """[T, E]: each row's weights over the experts, 0 off its top_k."""
    s = jax.nn.sigmoid(h @ router)
    _, idx = jax.lax.top_k(s + bias, top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    wts = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scale
    return jnp.sum(jax.nn.one_hot(idx, s.shape[-1]) * wts[..., None], axis=1)


def expert(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


_expert = jax.jit(expert)

#: this family's names in the program's parameter tree
ATTN_LEAVES = {"w_dq": "w_dq", "q_norm": "q_norm/scale", "w_uq": "w_uq",
               "w_dkv": "w_dkv", "kv_norm": "kv_norm/scale", "w_uk": "w_uk",
               "w_uv": "w_uv", "wo": "wo"}
FFN = ("w_gate", "w_up", "w_down")


def logits(tokens, leaf, config: Dict, rows=None, hidden: bool = False):
    """Logits [T, V] of one sequence of token ids [T] (with `rows`, a
    list of positions, only those rows of the head: [len(rows), V]).
    hidden: the final norm's rows [.., D] in place of the logits (what
    the head reads: tools/latent_parity.py)."""
    eps = float(config["rms_norm_eps"])
    top_k = int(config["num_experts_per_tok"])
    dense = int(config["first_k_dense_replace"])
    scale = float(config["routed_scaling_factor"])
    with jax.default_matmul_precision("highest"):
        x = leaf("embed/tok")[jnp.asarray(tokens)]
        for i in range(config["num_hidden_layers"]):
            a = rms_norm(x, leaf("layers/ln1/scale", i), eps)
            w = {k: leaf("layers/attn/" + p, i)
                 for k, p in ATTN_LEAVES.items()}
            x = x + attention(a, w, config)
            h = rms_norm(x, leaf("layers/ln2/scale", i), eps)
            if i < dense:
                x = x + _expert(h, *(leaf("dense/mlp/" + n, i) for n in FFN))
                continue
            j = i - dense
            mix = route(h, leaf("sparse/moe/router", j),
                        leaf("sparse/moe/router_bias", j), top_k, scale)
            ffn = _expert(h, *(leaf("sparse/shared/" + n, j) for n in FFN))
            # an expert no row chose has weight 0 in every row: left out
            for e in np.flatnonzero(np.asarray(mix).any(axis=0)):
                ffn = ffn + mix[:, e:e + 1] * _expert(
                    h, *(leaf("sparse/moe/" + n, (j, int(e))) for n in FFN))
            x = x + ffn
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, leaf("final_norm/scale"), eps)
        return x if hidden else x @ leaf("lm_head")
