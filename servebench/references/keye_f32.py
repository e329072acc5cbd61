"""The plain reference of Keye-VL-2.0-30B-A3B's language model: the forward
pass in straightforward `jax.numpy` and float32, with no kernels, no cache
and no batching.

Written from the published configuration
(huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B `config.json`) and the
family's description ("GQA 32Q/4KV with DeepSeek-Sparse-Attention indexer
(sa_config topk 2048); 128 experts, top-8, 0 shared"). It shares no code
with the program. One layer, x [T, D]:

    a = RMSNorm(x; g1)
    q, k, v = a W_q, a W_k, a W_v        (no bias; 8 query heads a KV head)
    q, k = RMSNorm over each head's 128 (learned weights), then rotate-half RoPE
    indexer, from the same a:
      qI = a W_qI [16 x 64]   kI = LayerNorm(a W_kI) [64]   w = a W_w [16]
      rotate-half RoPE (the same theta) on all 64 dims of qI and kI
      I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])            for s <= t
    S_t = the `topk` positions s <= t with the largest I[t, s] (all of them
          while t + 1 <= topk; a tie goes to the lower position)
    x' = x + (softmax over S_t of q_t k_s / sqrt(128)) v W_o
    b = RMSNorm(x'; g2);  p = softmax(b W_r) over 128, its top 8 renormalised
    x'' = x' + sum_e p_e W_down,e (silu(b W_gate,e) * (b W_up,e))

then a final RMSNorm and an untied output head. The full I[t, s], an exact
top-k a row and a masked softmax: the selection is one a token and layer,
shared by all 32 heads.

Departures from the source, and what is assumed (the configuration file's
`assumed` has each with its reason):
- TEXT ONLY. The vision tower is not here. A text sequence gives the three
  position ids of `mrope_section` [16, 24, 24] one value, which is plain
  RoPE over the 64 pairs of a head.
- (A) q and k are RMS-normed over a head's 128 with a learned weight
  before RoPE, as the Qwen3-MoE family whose sizes these are does.
- (A) The indexer reads the layer's normed input `a` on both sides
  (DeepSeek-V3.2 feeds its query side from a query latent this model does
  not have).
- (A) The index key passes a LayerNorm (weight and bias, eps 1e-6), as
  DeepSeek-V3.2's does.
- (A) qI and kI are rotated over all 64 dims with the model's theta.
- Any positive constant on I (DeepSeek's 64^-1/2 16^-1/2) changes no
  selection and is left out. `q_chunk_size` and `kv_chunk_size` tile the
  source's own computation and define nothing.
- `norm_topk_prob`: a softmax over all 128 logits whose top 8 are
  renormalised is the softmax over the chosen 8 computed here.

It follows the contract of servebench/refcheck.py: `leaf(path, layer)`
hands over one leaf of the program's parameter tree as float32, and the
sizes come from the configuration FILE's published keys (`sa_config`
among them). It holds ONE layer's and ONE expert's weights at a time (a
layer's 128 experts are 2.4 GB in float32) and computes a layer in blocks
of ROWS queries. On a TPU a float32 matrix multiplication runs in lower
precision unless told otherwise, so everything runs under
`jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

#: queries (and rows of the expert layer) computed at a time
ROWS = 256
#: LayerNorm on the index key
LN_EPS = 1e-6


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def rope(x, theta):
    """x [T, N, H]: rotate the pairs (i, i + H/2) by position * theta^(-2i/H)."""
    T, _, H = x.shape
    half = H // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


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


def attention(q, k, v, qi, ki, w, topk: int):
    """q [T, Nq, H], k/v [T, Kv, H]; qi [T, Ni, Hi], ki [T, Hi], w [T, Ni]:
    causal attention over each query's selection, ROWS queries at a time."""
    T, group = q.shape[0], q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    out = []
    for s in range(0, T, ROWS):
        e = min(s + ROWS, T)
        sc = jnp.einsum("tnh,snh->nts", q[s:e], k[:e]) \
            / jnp.sqrt(jnp.float32(q.shape[-1]))
        see = selection(qi, ki, w, topk, s, e)
        pr = jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("nts,snh->tnh", pr, v[:e]))
    return jnp.concatenate(out, axis=0)


def route(h, router, top_k: int):
    """[T, E]: each row's weights over the experts, 0 off its top_k."""
    r = h @ router
    top, idx = jax.lax.top_k(r, top_k)
    wts = jax.nn.softmax(top, axis=-1)
    return jnp.sum(jax.nn.one_hot(idx, r.shape[-1]) * wts[..., None], axis=1)


def expert(h, mix_e, w_gate, w_up, w_down):
    """One expert's share of the feed-forward: h [T, D], mix_e [T]."""
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down * mix_e[:, None]


def attend_layer(x, w: Dict, eps: float, theta: float, topk: int):
    """x + attention: w holds ln1 [D], wq [D,Nq,H], wk/wv [D,Kv,H],
    q_norm/k_norm [H], wo [Nq,H,D], w_qi [D,Ni,Hi], w_ki [D,Hi],
    w_w [D,Ni], ki_scale/ki_bias [Hi]."""
    a = rms_norm(x, w["ln1"], eps)
    q = rope(rms_norm(jnp.einsum("td,dnh->tnh", a, w["wq"]), w["q_norm"],
                      eps), theta)
    k = rope(rms_norm(jnp.einsum("td,dkh->tkh", a, w["wk"]), w["k_norm"],
                      eps), theta)
    v = jnp.einsum("td,dkh->tkh", a, w["wv"])
    qi = rope(jnp.einsum("td,dnh->tnh", a, w["w_qi"]), theta)
    ki = rope(layer_norm(a @ w["w_ki"], w["ki_scale"], w["ki_bias"],
                         LN_EPS)[:, None], theta)[:, 0]
    o = attention(q, k, v, qi, ki, a @ w["w_w"], topk)
    return x + jnp.einsum("tnh,nhd->td", o, w["wo"])


_attend_layer = jax.jit(attend_layer, static_argnums=(2, 3, 4))
_route = jax.jit(route, static_argnums=(2,))
_expert = jax.jit(expert)

#: this family's names in the program's parameter tree, under "layers"
ATTN_LEAVES = {"ln1": "ln1/scale", "wq": "attn/wq", "wk": "attn/wk",
               "wv": "attn/wv", "wo": "attn/wo",
               "q_norm": "attn/q_norm/scale", "k_norm": "attn/k_norm/scale",
               "w_qi": "index/w_qi", "w_ki": "index/w_ki",
               "w_w": "index/w_w", "ki_scale": "index/k_norm/scale",
               "ki_bias": "index/k_norm/bias"}


def logits(tokens, leaf, config: Dict, rows=None):
    """Logits [T, V] of one sequence of token ids [T] (with `rows`, a
    list of positions, only those rows of the head: [len(rows), V])."""
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    top_k, E = int(config["num_experts_per_tok"]), int(config["num_experts"])
    topk = int(config["sa_config"]["topk"])
    with jax.default_matmul_precision("highest"):
        x = leaf("embed/tok")[jnp.asarray(tokens)]
        for i in range(config["num_hidden_layers"]):
            w = {k: leaf("layers/" + path, i)
                 for k, path in ATTN_LEAVES.items()}
            x = _attend_layer(x, w, eps, theta, topk)
            h = rms_norm(x, leaf("layers/ln2/scale", i), eps)
            mix = _route(h, leaf("layers/moe/router", i), top_k)
            for e in range(E):
                x = x + _expert(h, mix[:, e],
                                *(leaf("layers/moe/" + n, (i, e))
                                  for n in ("w_gate", "w_up", "w_down")))
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, leaf("final_norm/scale"), eps)
        return x @ leaf("lm_head")
