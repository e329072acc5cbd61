"""The plain reference of SmallThinker-21BA3B-Instruct: the forward pass
in straightforward `jax.numpy` and float32, with no kernels, no cache and
no batching.

Written from the published configuration
(huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct `config.json`) and
the family's description ("SWA(4096); NoPE global; 64 experts, top-6, 0
shared; sparse ReGLU; router placed before attention"). It shares no code
with the program. One layer, x [T, D]:

    h  = RMSNorm(x; g1)             r = h W_r            (router logits, HERE)
    q, k, v = h W_q, h W_k, h W_v   (no bias; 7 query heads a KV head)
    rope_layout[l] == 1: rotate-half RoPE on q and k; 0: no positions at all
    scores q k^T / sqrt(H), causal; sliding_window_layout[l] == 1: p - j < window
    x' = x + softmax(.) v W_o
    h2 = RMSNorm(x'; g2)
    the 6 largest of r choose experts, weights = softmax over those 6 logits
    x'' = x' + sum_i w_i W_down,e_i (relu(h2 W_gate,e_i) * (h2 W_up,e_i))

then a final RMSNorm and an untied output head.

Departures from the source, and what is inferred:
- That the router reads the NORMED input of the attention (`h`, not `x`)
  is an inference from "router placed before attention": the config has
  no key for it.
- `moe_primary_router_apply_softmax` and `norm_topk_prob` are both true
  in the source: a softmax over all 64 logits whose top 6 are
  renormalised, which is the softmax over the chosen 6 computed here.
- The family's "secondary experts" have no key in this model's config;
  none are computed. No shared expert, no leading dense layer.
- Both layouts are read from the configuration FILE and cut to the depth
  the file holds (`num_hidden_layers`).

It follows the contract of servebench/refcheck.py: `leaf(path, layer)`
hands over one leaf of the program's parameter tree as float32, and the
sizes come from the configuration FILE's published keys. It holds ONE
layer's weights at a time (a layer's 64 experts are 1.5 GB in float32)
and computes a layer in blocks of ROWS queries, so that a sequence of
several thousand tokens fits beside the program's own weights. On a TPU a
float32 matrix multiplication runs in lower precision unless told
otherwise, so everything runs under
`jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

#: queries (and rows of the expert layer) computed at a time
ROWS = 512


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [T, N, H]: rotate the pairs (i, i + H/2) by position * theta^(-2i/H)."""
    T, _, H = x.shape
    half = H // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, window: int):
    """q [T, Nq, H], k/v [T, Kv, H]: causal, and where `window` > 0 the
    query at p sees j only if p - j < window. Computed ROWS queries at
    a time against the keys up to them."""
    T, group = q.shape[0], q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    out = []
    for s in range(0, T, ROWS):
        e = min(s + ROWS, T)
        sc = jnp.einsum("tnh,snh->nts", q[s:e], k[:e]) \
            / jnp.sqrt(jnp.float32(q.shape[-1]))
        p, j = jnp.arange(s, e)[:, None], jnp.arange(e)[None, :]
        see = j <= p
        if window > 0:
            see = see & (p - j < window)
        pr = jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("nts,snh->tnh", pr, v[:e]))
    return jnp.concatenate(out, axis=0)


def experts(h, r, w: Dict, top_k: int):
    """h [T, D] the feed-forward's input, r [T, E] the router's logits:
    sum over the top_k experts of softmax(their logits) x ReGLU."""
    E = r.shape[-1]
    out = []
    for s in range(0, h.shape[0], ROWS):
        hb, rb = h[s:s + ROWS], r[s:s + ROWS]
        top, idx = jax.lax.top_k(rb, top_k)
        wts = jax.nn.softmax(top, axis=-1)                        # [t, k]
        mix = jnp.sum(jax.nn.one_hot(idx, E) * wts[..., None], axis=1)
        f = jax.nn.relu(jnp.einsum("td,edf->etf", hb, w["w_gate"])) \
            * jnp.einsum("td,edf->etf", hb, w["w_up"])
        y = jnp.einsum("etf,efd->etd", f, w["w_down"])
        out.append(jnp.einsum("etd,te->td", y, mix))
    return jnp.concatenate(out, axis=0)


def layer(x, w: Dict, eps: float, theta: float, rotate: bool, window: int,
          top_k: int):
    """One decoder layer. x [T, D]; w: wq [D,Nq,H], wk/wv [D,Kv,H],
    wo [Nq,H,D], router [D,E], w_gate/w_up [E,D,F], w_down [E,F,D],
    ln1/ln2 [D]."""
    h = rms_norm(x, w["ln1"], eps)
    r = h @ w["router"]
    q = jnp.einsum("td,dnh->tnh", h, w["wq"])
    k = jnp.einsum("td,dkh->tkh", h, w["wk"])
    v = jnp.einsum("td,dkh->tkh", h, w["wv"])
    if rotate:
        q, k = rope(q, theta), rope(k, theta)
    x = x + jnp.einsum("tnh,nhd->td", attention(q, k, v, window), w["wo"])
    h = rms_norm(x, w["ln2"], eps)
    return x + experts(h, r, w, top_k)


_layer = jax.jit(layer, static_argnums=(2, 3, 4, 5, 6))


#: this family's names in the program's parameter tree, under "layers"
LAYER_LEAVES = {"ln1": "ln1/scale", "ln2": "ln2/scale",
                "wq": "attn/wq", "wk": "attn/wk", "wv": "attn/wv",
                "wo": "attn/wo", "router": "moe/router",
                "w_gate": "moe/w_gate", "w_up": "moe/w_up",
                "w_down": "moe/w_down"}


def logits(tokens, leaf, config: Dict, rows=None):
    """Logits [T, V] of one sequence of token ids [T] (with `rows`, a
    list of positions, only those rows of the head: [len(rows), V])."""
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    top_k = int(config["moe_num_active_primary_experts"])
    window = int(config["sliding_window_size"])
    with jax.default_matmul_precision("highest"):
        x = leaf("embed/tok")[jnp.asarray(tokens)]
        for i in range(config["num_hidden_layers"]):
            w = {k: leaf("layers/" + path, i)
                 for k, path in LAYER_LEAVES.items()}
            x = _layer(x, w, eps, theta, bool(config["rope_layout"][i]),
                       window * int(config["sliding_window_layout"][i]),
                       top_k)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, leaf("final_norm/scale"), eps)
        return x @ leaf("lm_head")
