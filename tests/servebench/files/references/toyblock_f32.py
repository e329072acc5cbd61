"""Tests only, and thrown away by design: the Llama family's plain
forward in float32 under the mask of generation by blocks. The sequence
is cut in blocks of B positions (B is the configuration file's
`decode_width`); a position p attends j where j // B <= p // B: causal
between blocks, full inside one.

It exists to show that the reference check tells masks apart: the
program's causal model, fed B positions a call, must NOT agree with it
(tests/servebench/test_servebench_refcheck.py). No configuration of the
benchmark names it. Written from the description; it shares no code with
the program, nor with the benchmark's other references. It follows the
contract of servebench/refcheck.py: ONE full forward of the whole
sequence, under the configuration's own mask.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LEAVES = {"ln1": "ln1/scale", "ln2": "ln2/scale", "wq": "attn/wq",
          "wk": "attn/wk", "wv": "attn/wv", "wo": "attn/wo",
          "w_gate": "mlp/w_gate", "w_up": "mlp/w_up", "w_down": "mlp/w_down"}


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [T, N, H]: rotate the pairs (i, i + H/2) by position * theta^(-2i/H)."""
    T, _, H = x.shape
    inv = 1.0 / (theta ** (jnp.arange(H // 2, dtype=jnp.float32) / (H // 2)))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :H // 2], x[..., H // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def block_mask(T: int, B: int):
    """[T, T], True where the query (row) may attend the key (column)."""
    block = jnp.arange(T) // B
    return block[None, :] <= block[:, None]


def layer(x, w, eps, theta, B):
    h = rms_norm(x, w["ln1"], eps)
    q = rope(jnp.einsum("td,dnh->tnh", h, w["wq"]), theta)
    k = rope(jnp.einsum("td,dkh->tkh", h, w["wk"]), theta)
    v = jnp.einsum("td,dkh->tkh", h, w["wv"])
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("tnh,snh->nts", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    p = jax.nn.softmax(jnp.where(block_mask(x.shape[0], B)[None], s, -jnp.inf),
                       axis=-1)
    x = x + jnp.einsum("tnh,nhd->td", jnp.einsum("nts,snh->tnh", p, v), w["wo"])
    h = rms_norm(x, w["ln2"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def logits(tokens, leaf, config):
    """Logits [T, V] of one sequence of token ids [T]."""
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    with jax.default_matmul_precision("highest"):
        x = leaf("embed/tok")[jnp.asarray(tokens)]
        for i in range(config["num_hidden_layers"]):
            w = {k: leaf("layers/" + path, i) for k, path in LEAVES.items()}
            x = layer(x, w, eps, theta, int(config["decode_width"]))
        return rms_norm(x, leaf("final_norm/scale"), eps) @ leaf("lm_head")
