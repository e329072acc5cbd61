"""The plain reference of GLM-5 (`glm_moe_dsa`): the forward pass in
straightforward `jax.numpy` and float32, with no kernels, no cache, no
absorbed products and no batching.

Written from the published configuration
(huggingface.co/zai-org/GLM-5 `config.json`: the DeepSeek key names one
for one) and the family's description ("MLA (nope 192); DSA indexer 32h
top-2048 - 78L; 256 experts, top-8, 1 shared; 3 dense first; MTP 1").
It shares no code with the program. Tokens to x = E[tok]; for each
layer, x the layer's input, h = RMSNorm_w(x) (eps `rms_norm_eps`), t a
query position, s <= t a cached one:

    c_q    = RMSNorm_w(h W_qa)                              `q_lora_rank`
    q[j]   = c_q W_qb[j] -> [q_nope `qk_nope_head_dim` | q_rope
             `qk_rope_head_dim`], j < `num_attention_heads`;
             q_rope = RoPE_pairs(q_rope, t)
    [c|kr] = h W_kva -> [`kv_lora_rank` | rope];  c = RMSNorm_w(c);
             kr = RoPE_pairs(kr, t): ONE rotary key a token, shared by
             every head; what a token caches is [c | kr]
    qI[i]  = c_q W_Iq[i]  [`index_head_dim`], i < `index_n_heads`;
    kI     = LayerNorm_{w,b}(h W_Ik) [`index_head_dim`];  w = h W_Iw
             the FIRST `qk_rope_head_dim` dims of each qI[i] and of kI
             rotated by pairs (2m, 2m+1) at theta^(-2m/rope); the rest
             pass
    I[t,s] = sum_i w[t,i] relu(qI[t,i] . kI[s])
    S_t    = the `index_topk` positions s <= t of highest I[t,s] (all
             while t + 1 <= `index_topk`; a tie to the lower position)
    a[j,s] = (q_nope[t,j] . (c[s] W_uk[j]) + q_rope[t,j] . kr[s])
             x (nope + rope)^-1/2,  s in S_t
    o[j]   = sum_{s in S_t} softmax_s(a[j,.]) (c[s] W_uv[j])  `v_head_dim`
    x      = x + concat_j o[j] W_o;   h' = RMSNorm_w(x)
    dense (layers < `first_k_dense_replace`): y = SwiGLU(h') of
             `intermediate_size`
    else:  sc = sigmoid(h' W_r) over ALL the router's experts
           C = top `num_experts_per_tok` of sc + b (b the stored
               `noaux_tc` correction; `n_group` 1, `topk_group` 1: no
               group step), an equal score to the lower index
           g_e = `routed_scaling_factor` sc_e / (sum_{e' in C} sc_e'
               + 1e-20), e in C (`norm_topk_prob`)
           y = sum_{e in C, e HELD} g_e SwiGLU_e(h') + SwiGLU_shared(h')
    x      = x + y

then a final RMSNorm and logits = x W_head (untied). RoPE_pairs: pair m
of the rotary dims is dims (2m, 2m+1), rotated by pos x theta^(-2m/rope)
and left where it was (`rope_interleave`, `indexer_rope_interleave`
true); theta is `rope_parameters.rope_theta`, no scaling.

THE SHARE. A deployment splits each layer's 256 experts over chips, and
the file this reference is handed describes ONE chip: its `model` group
states `experts_held` experts from `experts_first` on (0 or absent: all
of them). The router's matrix still has every expert's column, the
gates are normalised over the chosen 8 wherever they live, and y sums
the chosen experts that are HELD: what the absent ones would add is
left out, here as in the program, and that partial result goes on to
the next layer. The expert leaves hold the held experts alone, expert e
at index e - `experts_first`. `feed_forward` is the layer's
feed-forward alone, for the test that the shares add up to the whole.

Departures from the source, and what is assumed (the configuration
file's `assumed` has each with its reason):
- The prediction layer (`num_nextn_predict_layers` 1) takes no part in
  the next-token distribution and is left out.
- The published kernels rotate the index queries and keys by a Hadamard
  matrix and hold them in FP8: an orthogonal map of both sides changes
  no score, and FP8 is a storage precision; neither is here.
- The constant `index_n_heads`^-1/2 `index_head_dim`^-1/2 on I changes
  no selection and is left out.
- Which `qk_rope_head_dim` dims of an index head rotate: the first.
- The index key's LayerNorm takes eps `rms_norm_eps`.
- The indexer stands in every layer, the leading dense ones too.
- The source fuses `kv_b_proj` (W_uk and W_uv of a head side by side)
  and an expert's gate and up; the splits are layout.
- The shared expert is ONE SwiGLU of `n_shared_experts` x
  `moe_intermediate_size`.
- `head_dim` 64 and `num_key_value_heads` 64 are published and unread:
  latent attention has neither.

It follows the contract of servebench/refcheck.py: `leaf(path, layer)`
hands over one leaf of the program's parameter tree as float32, and the
sizes come from the configuration FILE's published keys. It holds ONE
layer's and ONE expert's weights at a time. On a TPU a float32 matrix
multiplication runs in lower precision unless told otherwise, so
everything runs under `jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


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


def rotate_first(x, rope: int, theta: float):
    """The first `rope` dims of the last axis rotated by pairs; the
    rest pass."""
    return jnp.concatenate([rotate_pairs(x[..., :rope], theta),
                            x[..., rope:]], axis=-1)


#: query rows whose scores are held at once: a stream of 4,500 tokens
#: (tools/latent_parity.py) is 0.3 GB of float32 scores a block of 256
#: rows and 64 heads
ROWS = 256


def selection(qi, ki, w, topk: int, s: int, e: int):
    """Rows s..e-1 of the selection as a mask [e - s, e]: the index
    scores of each query against the keys up to it, and an exact top-k a
    row (lax.top_k takes equal scores from the lower position up)."""
    score = jnp.einsum("tn,tns->ts", w[s:e], jax.nn.relu(
        jnp.einsum("tnh,sh->tns", qi[s:e], ki[:e])))
    p, j = jnp.arange(s, e)[:, None], jnp.arange(e)[None, :]
    see = j <= p
    if e <= topk:
        return see
    _, idx = jax.lax.top_k(jnp.where(see, score, -jnp.inf), topk)
    chosen = jnp.zeros((e - s, e), bool).at[
        jnp.arange(e - s)[:, None], idx].set(True)
    return chosen & see


def attention(a, w: Dict, config: Dict):
    """a [T, D] normed; causal latent attention over each query's
    selection, expanded."""
    eps = float(config["rms_norm_eps"])
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    R = config["kv_lora_rank"]
    theta = float(config["rope_parameters"]["rope_theta"])
    topk = int(config["index_topk"])
    c_q = rms_norm(a @ w["w_dq"], w["q_norm"], eps)
    q = jnp.einsum("tr,rnh->tnh", c_q, w["w_uq"])
    q_nope, q_rope = q[..., :nope], rotate_pairs(q[..., nope:], theta)
    ckv = a @ w["w_dkv"]
    c = rms_norm(ckv[:, :R], w["kv_norm"], eps)
    k_r = rotate_pairs(ckv[:, R:], theta)                          # [T, rope]
    k_nope = jnp.einsum("sr,rnh->snh", c, w["w_uk"])
    v = jnp.einsum("sr,rnh->snh", c, w["w_uv"])
    # the indexer: its queries from the QUERY LATENT, key and weights
    # from the layer's normed input
    qi = rotate_first(jnp.einsum("tr,rnh->tnh", c_q, w["w_qi"]), rope, theta)
    ki = rotate_first(layer_norm(a @ w["w_ki"], w["ki_scale"], w["ki_bias"],
                                 eps), rope, theta)
    wi = a @ w["w_w"]
    T = a.shape[0]
    out = []
    for lo in range(0, T, ROWS):    # the queries in blocks: [n, ROWS, hi]
        hi = min(lo + ROWS, T)
        sc = (jnp.einsum("tnh,snh->nts", q_nope[lo:hi], k_nope[:hi])
              + jnp.einsum("tnh,sh->nts", q_rope[lo:hi], k_r[:hi])) \
            * (nope + rope) ** -0.5
        see = selection(qi, ki, wi, topk, lo, hi)
        pr = jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("nts,snh->tnh", pr, v[:hi]))
    return jnp.einsum("tnh,nhd->td", jnp.concatenate(out), w["wo"])


def route(h, router, bias, top_k: int, scale: float):
    """[T, E]: each row's weights over ALL the experts, 0 off its top_k."""
    s = jax.nn.sigmoid(h @ router)
    _, idx = jax.lax.top_k(s + bias, top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    wts = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scale
    return jnp.sum(jax.nn.one_hot(idx, s.shape[-1]) * wts[..., None], axis=1)


def expert(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


_expert = jax.jit(expert)

#: this family's names in the program's parameter tree, under "layers"
ATTN_LEAVES = {"w_dq": "attn/w_dq", "q_norm": "attn/q_norm/scale",
               "w_uq": "attn/w_uq", "w_dkv": "attn/w_dkv",
               "kv_norm": "attn/kv_norm/scale", "w_uk": "attn/w_uk",
               "w_uv": "attn/w_uv", "wo": "attn/wo",
               "w_qi": "index/w_qi", "w_ki": "index/w_ki",
               "w_w": "index/w_w", "ki_scale": "index/k_norm/scale",
               "ki_bias": "index/k_norm/bias"}
FFN = ("w_gate", "w_up", "w_down")


def share(config: Dict, experts: int) -> Tuple[int, int]:
    """(first, held) of the chip the file describes: its `model` group's
    `experts_first` and `experts_held`; all `experts` where it states
    no share."""
    model = config.get("model", {})
    held = int(model.get("experts_held", 0))
    return (int(model.get("experts_first", 0)), held) if held \
        else (0, experts)


def feed_forward(h, leaf, j: int, config: Dict, shared: bool = True):
    """An expert layer's feed-forward of h [T, D], layer j of the
    sparse stack: the chosen experts that are HELD (share), and the
    shared expert where `shared`."""
    mix = route(h, leaf("sparse/moe/router", j),
                leaf("sparse/moe/router_bias", j),
                int(config["num_experts_per_tok"]),
                float(config["routed_scaling_factor"]))
    first, held = share(config, mix.shape[-1])
    y = _expert(h, *(leaf("sparse/shared/" + n, j) for n in FFN)) \
        if shared else jnp.zeros_like(h)
    # an expert no row chose has weight 0 in every row: left out
    for e in np.flatnonzero(np.asarray(mix).any(axis=0)):
        if first <= e < first + held:
            y = y + mix[:, e:e + 1] * _expert(
                h, *(leaf("sparse/moe/" + n, (j, int(e) - first))
                     for n in FFN))
    return y


def logits(tokens, leaf, config: Dict, rows=None, hidden: bool = False):
    """Logits [T, V] of one sequence of token ids [T] (with `rows`, a
    list of positions, only those rows of the head: [len(rows), V]).
    hidden: the final norm's rows [.., D] in place of the logits (what
    the head reads: tools/latent_parity.py)."""
    eps = float(config["rms_norm_eps"])
    dense = int(config["first_k_dense_replace"])
    with jax.default_matmul_precision("highest"):
        x = leaf("embed/tok")[jnp.asarray(tokens)]
        for i in range(config["num_hidden_layers"]):
            a = rms_norm(x, leaf("layers/ln1/scale", i), eps)
            w = {k: leaf("layers/" + p, i) for k, p in ATTN_LEAVES.items()}
            x = x + attention(a, w, config)
            h = rms_norm(x, leaf("layers/ln2/scale", i), eps)
            if i < dense:
                x = x + _expert(h, *(leaf("dense/mlp/" + n, i) for n in FFN))
            else:
                x = x + feed_forward(h, leaf, i - dense, config)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, leaf("final_norm/scale"), eps)
        return x if hidden else x @ leaf("lm_head")
