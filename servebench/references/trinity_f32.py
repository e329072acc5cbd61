"""The plain reference of Trinity-Large-Preview (`afmoe`): the forward
pass in straightforward `jax.numpy` and float32, with no kernels, no
cache, no ring, no chunks and no batching.

Written from the published configuration
(huggingface.co/arcee-ai/Trinity-Large-Preview `config.json`) and the
family's description ("SWA(4096) gated; global every 4th", "sigmoid
routing, SMEBU bias", "depth-scaled sandwich norm"). It shares no code
with the program. Tokens to x = E[tok] x sqrt(`hidden_size`)
(`mup_enabled`); for each layer i of `layer_types`:

    a = x + RMSNorm(Attn(RMSNorm(x; g1)); g1')
    x = a + RMSNorm(FF(RMSNorm(a; g2)); g2')       four weights a layer

then a final RMSNorm and logits = x W_head (untied, no scale). eps
`rms_norm_eps` everywhere; a plain RMSNorm (weight x normalised input).

Attention: q = h W_q (`num_attention_heads` heads of `head_dim`), k = h
  W_k, v = h W_v (`num_key_value_heads`), g = h W_g (as wide as q), no
  bias; q and k pass an RMSNorm over each head's `head_dim` with a
  learned weight. Where `layer_types[i]` is "sliding_attention" q and k
  are rotated (pairs (i, i + head_dim/2) by position x
  `rope_theta`^(-2i/head_dim), no scaling) and position p attends j
  where 0 <= p - j < the window; where it is "full_attention" NOTHING is
  rotated and p attends every j <= p. Softmax at head_dim^-0.5, the
  query heads of a group against their one key-value head. The heads'
  output times sigmoid(g), elementwise, then W_o. The mask is written
  out row by row from the layer's kind and the window.
Dense feed-forward (the first `num_dense_layers` layers):
  W_down(silu(h W_gate) * h W_up), `intermediate_size`.
Expert layer: s = sigmoid(h W_r), W_r [hidden, 256]; the
  `num_experts_per_tok` experts are the top of s + b (b a stored bias an
  expert, in the CHOICE alone); weights s[chosen] / (their sum + 1e-20)
  x `route_scale`; result = Shared(h) + sum over the chosen w_e
  Expert_e(h), each a SwiGLU of `moe_intermediate_size`, the shared one
  added unweighted. Of the chosen experts only those this chip HOLDS
  (the file's `model` group: `experts_first`, `experts_held`) are
  summed: what the absent ones would add is left out, here as in the
  program, and the partial result goes on.

What the configuration does not state, and what is assumed (the file's
`assumed` has each with its reason):
- the norm on each head's queries and keys (the family's modelling
  code has it; no key of the config names it).
- that sliding layers alone rotate (the family's code builds rotary
  embeddings for its local layers only).
- the gate: sigmoid of a projection of the sublayer's normed input, as
  wide as the heads' output, multiplied on before W_o.
- "depth-scaled": how the norms behind the sublayers are INITIALISED,
  not an equation; here they are seeded.
- `route_norm` true: the division by the chosen scores' sum; `n_group`
  1 / `topk_group` 1: no group step.
- the shared expert is ONE SwiGLU of `num_shared_experts` x
  `moe_intermediate_size`.
- the window: the source's key is `sliding_window` 4096; the benchmark's
  file states it as `sliding_window_size` (servebench/launcher.py
  refuses the source's key), and this file reads whichever is there.

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

#: query rows whose scores are held at once: a stream of 9,000 tokens
#: (tools/window_parity.py) is 0.9 GB of float32 scores a block of 512
#: rows and 48 heads
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


def window_of(config: Dict) -> int:
    return int(config.get("sliding_window") or config["sliding_window_size"])


def attention(h, w: Dict, eps: float, theta: float, slides: bool,
              window: int):
    """h [T, D] normed -> [T, D]: the gated attention of one layer."""
    q = rms_norm(jnp.einsum("td,dnh->tnh", h, w["wq"]), w["q_norm"], eps)
    k = rms_norm(jnp.einsum("td,dkh->tkh", h, w["wk"]), w["k_norm"], eps)
    v = jnp.einsum("td,dkh->tkh", h, w["wv"])
    g = jax.nn.sigmoid(jnp.einsum("td,dnh->tnh", h, w["wg"]))
    if slides:
        q, k = rope(q, theta), rope(k, theta)
    T, group = q.shape[0], q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    out = []
    for s in range(0, T, ROWS):     # the queries in blocks
        e = min(s + ROWS, T)
        # a sliding layer's block sees no key before its first query's
        # window: the keys are cut there, the mask does the rest
        lo = max(0, s - window + 1) if slides else 0
        sc = jnp.einsum("tnh,snh->nts", q[s:e], k[lo:e]) \
            / jnp.sqrt(jnp.float32(q.shape[-1]))
        p, j = jnp.arange(s, e)[:, None], jnp.arange(lo, e)[None, :]
        see = j <= p
        if slides:
            see = see & (p - j < window)
        pr = jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("nts,snh->tnh", pr, v[lo:e]))
    return jnp.einsum("tnh,nhd->td", jnp.concatenate(out) * g, w["wo"])


_attention = jax.jit(attention, static_argnums=(2, 3, 4, 5))


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
ATTN_LEAVES = {"wq": "attn/wq", "wk": "attn/wk", "wv": "attn/wv",
               "wg": "attn/wg", "wo": "attn/wo",
               "q_norm": "attn/q_norm/scale", "k_norm": "attn/k_norm/scale"}
FFN = ("w_gate", "w_up", "w_down")


def share(config: Dict, experts: int) -> Tuple[int, int]:
    """(first, held) of the chip the file describes: its `model` group's
    `experts_first` and `experts_held`; all `experts` where it states
    no share."""
    model = config.get("model", {})
    held = int(model.get("experts_held", 0))
    return (int(model.get("experts_first", 0)), held) if held \
        else (0, experts)


def feed_forward(h, leaf, stack: str, j: int, config: Dict,
                 shared: bool = True):
    """An expert layer's feed-forward of h [T, D], layer j of the stack
    `stack`: the chosen experts that are HELD (share), and the shared
    expert where `shared`."""
    mix = route(h, leaf(stack + "moe/router", j),
                leaf(stack + "moe/router_bias", j),
                int(config["num_experts_per_tok"]),
                float(config["route_scale"]))
    first, held = share(config, mix.shape[-1])
    y = _expert(h, *(leaf(stack + "shared/" + n, j) for n in FFN)) \
        if shared else jnp.zeros_like(h)
    # an expert no row chose has weight 0 in every row: left out
    for e in np.flatnonzero(np.asarray(mix).any(axis=0)):
        if first <= e < first + held:
            y = y + mix[:, e:e + 1] * _expert(
                h, *(leaf(stack + "moe/" + n, (j, int(e) - first))
                     for n in FFN))
    return y


def logits(tokens, leaf, config: Dict, rows=None):
    """Logits [T, V] of one sequence of token ids [T] (with `rows`, a
    list of positions, only those rows of the head: [len(rows), V])."""
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    dense = int(config["num_dense_layers"])
    window = window_of(config)
    # feed-forwards of two shapes are stacked apart; of one, in "layers"
    sparse = "sparse/" if dense else "layers/"
    with jax.default_matmul_precision("highest"):
        x = leaf("embed/tok")[jnp.asarray(tokens)] \
            * jnp.sqrt(jnp.float32(config["hidden_size"]))
        for i in range(config["num_hidden_layers"]):
            slides = config["layer_types"][i] == "sliding_attention"
            a = rms_norm(x, leaf("layers/ln1/scale", i), eps)
            w = {k: leaf("layers/" + p, i) for k, p in ATTN_LEAVES.items()}
            x = x + rms_norm(_attention(a, w, eps, theta, slides, window),
                             leaf("layers/ln1_post/scale", i), eps)
            h = rms_norm(x, leaf("layers/ln2/scale", i), eps)
            if i < dense:
                y = _expert(h, *(leaf("dense/mlp/" + n, i) for n in FFN))
            else:
                y = feed_forward(h, leaf, sparse, i - dense, config)
            x = x + rms_norm(y, leaf("layers/ln2_post/scale", i), eps)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, leaf("final_norm/scale"), eps)
        return x @ leaf("lm_head")
