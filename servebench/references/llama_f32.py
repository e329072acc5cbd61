"""The plain reference of the Llama family (Llama, Mistral without a
sliding window): the forward pass in straightforward `jax.numpy` and
float32, with no kernels, no cache and no batching.

It follows the published description: pre-norm decoder layers of
RMSNorm, grouped-query attention with rotary embeddings (rotate-half
pairing, as in the Hugging Face checkpoints), a causal mask, and a
SwiGLU feed-forward; a final RMSNorm and an untied output head. It is
written from that description and shares no code with the program.

It holds ONE layer's weights at a time (`layer_weights(i)` hands them
over), because the float32 tree of a 7B model is 29 GB and fits no
chip. On a TPU a float32 matrix multiplication runs in lower precision
unless told otherwise, so everything runs under
`jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp


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


def layer(x, w: Dict, eps: float, theta: float):
    """One decoder layer. x [T, D]; w: wq [D,Nq,H], wk/wv [D,Kv,H],
    wo [Nq,H,D], w_gate/w_up [D,F], w_down [F,D], ln1/ln2 [D]."""
    T = x.shape[0]
    h = rms_norm(x, w["ln1"], eps)
    q = rope(jnp.einsum("td,dnh->tnh", h, w["wq"]), theta)
    k = rope(jnp.einsum("td,dkh->tkh", h, w["wk"]), theta)
    v = jnp.einsum("td,dkh->tkh", h, w["wv"])
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("tnh,snh->nts", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("nts,snh->tnh", p, v)
    x = x + jnp.einsum("tnh,nhd->td", a, w["wo"])
    h = rms_norm(x, w["ln2"], eps)
    f = jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])
    return x + f @ w["w_down"]


_layer = jax.jit(layer, static_argnums=(2, 3))


def logits(tokens, embed, layer_weights: Callable[[int], Dict], n_layers: int,
           final_norm, lm_head, eps: float, theta: float):
    """Logits [T, V] of one sequence of token ids [T]."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(embed, jnp.float32)[jnp.asarray(tokens)]
        for i in range(n_layers):
            x = _layer(x, layer_weights(i), eps, theta)
        x = rms_norm(x, jnp.asarray(final_norm, jnp.float32), eps)
        return x @ jnp.asarray(lm_head, jnp.float32)
