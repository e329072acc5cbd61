"""The plain reference of Olmo-Hybrid-7B (`olmo_hybrid`): the forward pass
in straightforward `jax.numpy` and float32, with no kernels, no cache, no
chunkwise form and no batching.

Written from the published configuration
(huggingface.co/allenai/Olmo-Hybrid-7B `config.json`), the family's
description ("linear_attention x24 + full x8; dense") and the paper whose
layer the `linear_*` keys size (Yang, Kautz, Hatamizadeh, "Gated Delta
Networks", ICLR 2025). It shares no code with the program. Tokens to
x = E[tok]; for each layer, by `layer_types`:

    h = x + RMSNorm(mixer(x); g1)           the norm on the sublayer's OUTPUT,
    x = h + RMSNorm(swiglu(h); g2)          none on its input (OLMo 2's block)

then a final RMSNorm and logits = x W_head (untied). eps `rms_norm_eps`.

`linear_attention` (Gated DeltaNet), H = `linear_num_value_heads` heads,
keys of dk = `linear_key_head_dim`, values of dv = `linear_value_head_dim`,
a position at a time (the recurrence is a Python loop):
    q, k = x W_q, x W_k [H dk];  v, z = x W_v, x W_g [H dv];  a, b = x W_a,
    x W_b [H];  no bias
    [q|k|v]_t = silu(sum_j w_j [q|k|v]_{t-K+1+j})   causal depthwise conv of
            `linear_conv_kernel_dim` taps, no bias; positions before 0 zero
    q = q / sqrt(|q|^2 + 1e-6) * dk^-0.5;  k = k / sqrt(|k|^2 + 1e-6)  (a head)
    beta = 2 sigmoid(b)  (`linear_allow_neg_eigval`; sigmoid(b) without)
    alpha = exp(-exp(A_log) softplus(a + dt_bias))                 (a head)
    S' = alpha S;  u = beta (v - S' k);  S = S' + u k^T;  o = S q
            S [dv, dk] a head, S_-1 = 0: the DELTA RULE (read, then write
            the difference)
    out = (RMSNorm_dv(o; w) * silu(z)) W_o     norm a head (one weight of dv
            for all heads), THEN gate
`full_attention`: q, k, v = x W_q, x W_k, x W_v (`num_attention_heads`
  heads over `num_key_value_heads` KV heads of `head_dim`, no bias);
  q = RMSNorm(q; g_q), k = RMSNorm(k; g_k) over the WHOLE projection,
  before the heads are split; NO rotation and no other positional term
  (`rope_parameters.rope_theta` is null); causal softmax at
  head_dim^-0.5; W_o.

Departures from the source, and what is assumed (the configuration file's
`assumed` has each with its reason):
- `head_dim` 128 = 3,840 / 30: the source has no key for it.
- The norm on sublayer outputs and the projection-wide query and key
  norms are the family's convention (`olmo2`, `olmo3`), not keys of
  `config.json`; so are the mixer's defaults that the paper's layer
  fixes (output gate, no conv bias, SiLU after the conv, L2-normalised q
  and k with 1e-6 under the root, the dk^-0.5 scale, norm before gate).
- The program holds the four wide projections as ONE leaf (q | k | v | z)
  and a and b as one, and the three convolutions as one over 2 H dk + H dv
  channels: layout only; this file cuts them apart.
- The state here is float32 from the first position to the last. The
  program keeps a stream's state and conv tail in the model's dtype
  BETWEEN calls (bfloat16 in the benchmark's configuration): the check's
  limit is set with that rounding in force.

It follows the contract of servebench/refcheck.py: `leaf(path, layer)`
hands over one leaf of the program's parameter tree as float32, and the
sizes come from the configuration FILE's published keys. It holds ONE
layer's weights at a time. On a TPU a float32 matrix multiplication runs
in lower precision unless told otherwise, so everything runs under
`jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def attention(x, w: Dict, eps: float):
    """x [T, D]; causal attention, one norm over each whole projection,
    no rotation."""
    T = x.shape[0]
    nq, hd = w["wq"].shape[1:]
    nkv = w["wk"].shape[1]
    q = rms_norm(jnp.einsum("td,dnh->tnh", x, w["wq"]).reshape(T, -1),
                 w["q_norm"].reshape(-1), eps).reshape(T, nq, hd)
    k = rms_norm(jnp.einsum("td,dkh->tkh", x, w["wk"]).reshape(T, -1),
                 w["k_norm"].reshape(-1), eps).reshape(T, nkv, hd)
    v = jnp.einsum("td,dkh->tkh", x, w["wv"])
    k, v = jnp.repeat(k, nq // nkv, axis=1), jnp.repeat(v, nq // nkv, axis=1)
    see = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    sc = jnp.einsum("tnh,snh->nts", q, k) * hd ** -0.5
    pr = jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("tnh,nhd->td", jnp.einsum("nts,snh->tnh", pr, v),
                      w["wo"])


@jax.jit
def delta_step(S, q, k, v, alpha, beta):
    """One position of the gated delta rule, a head at a time: S
    [H, dv, dk], q and k [H, dk], v [H, dv], alpha and beta [H]. Returns
    (S after, o [H, dv])."""
    S = alpha[:, None, None] * S
    u = beta[:, None] * (v - jnp.einsum("hvk,hk->hv", S, k))
    S = S + u[:, :, None] * k[:, None, :]
    return S, jnp.einsum("hvk,hk->hv", S, q)


def gated_delta_net(x, w: Dict, sizes: Dict, eps: float, states=None,
                    keep=None):
    """x [T, D]; the mixer, one position at a time. states (a list):
    gains what a stream holds after its last position, (S [H, dv, dk],
    the conv's last K-1 inputs [K-1, 2 H dk + H dv]). keep (a dtype): S
    is rounded to it after every position, as a server that keeps a
    stream's state in that dtype between steps rounds it (a control for
    tools/state_parity.py; the reference itself keeps float32)."""
    H, dk, dv, K = (sizes[n] for n in ("H", "dk", "dv", "K"))
    Dk, Dv = H * dk, H * dv
    T = x.shape[0]
    qkvz = x @ w["in_proj"]                   # q | k | v | z
    qkv, z = qkvz[:, :2 * Dk + Dv], qkvz[:, 2 * Dk + Dv:]
    ab = x @ w["ab_proj"]
    a, b = ab[:, :H], ab[:, H:]
    beta = jax.nn.sigmoid(b) * (2.0 if sizes["neg_eigval"] else 1.0)
    alpha = jnp.exp(-jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"]))
    padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1])), qkv])

    def unit(y):
        return y / jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

    S = jnp.zeros((H, dv, dk), jnp.float32)
    ys = []
    for t in range(T):
        u = jax.nn.silu(jnp.sum(padded[t:t + K] * w["conv_w"], axis=0))
        q = unit(u[:Dk].reshape(H, dk)) * dk ** -0.5
        k = unit(u[Dk:2 * Dk].reshape(H, dk))
        S, o = delta_step(S, q, k, u[2 * Dk:].reshape(H, dv), alpha[t],
                          beta[t])
        if keep is not None:
            S = S.astype(keep).astype(jnp.float32)
        ys.append(rms_norm(o, w["norm"], eps).reshape(Dv))
    if states is not None:
        states.append((S, padded[T:]))
    return (jnp.stack(ys) * jax.nn.silu(z)) @ w["out_proj"]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


#: this family's names in the program's parameter tree: what every layer
#: has under "layers", each kind's mixer under a stack of its own
ATTN_LEAVES = {"wq": "attn/wq", "wk": "attn/wk", "wv": "attn/wv",
               "wo": "attn/wo", "q_norm": "attn/q_norm/scale",
               "k_norm": "attn/k_norm/scale"}
GDN_LEAVES = {"in_proj": "gdn/in_proj", "ab_proj": "gdn/ab_proj",
              "conv_w": "gdn/conv_w", "dt_bias": "gdn/dt_bias",
              "A_log": "gdn/A_log", "norm": "gdn/norm/scale",
              "out_proj": "gdn/out_proj"}


def logits(tokens, leaf, config: Dict, rows=None, states=None, keep=None):
    """Logits [T, V] of one sequence of token ids [T] (with `rows`, a
    list of positions, only those rows of the head: [len(rows), V]).
    states (a list): gains each linear-attention layer's state after the
    last position, in layer order; keep: `gated_delta_net`'s."""
    eps = float(config["rms_norm_eps"])
    sizes = {"H": config["linear_num_value_heads"],
             "dk": config["linear_key_head_dim"],
             "dv": config["linear_value_head_dim"],
             "K": config["linear_conv_kernel_dim"],
             "neg_eigval": bool(config["linear_allow_neg_eigval"])}
    if config["linear_num_key_heads"] != sizes["H"]:
        raise ValueError("key heads unlike value heads: not this layer")
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    seen = {"linear_attention": 0, "full_attention": 0}
    with jax.default_matmul_precision("highest"):
        x = leaf("embed/tok")[jnp.asarray(tokens)]
        for i, kind in enumerate(kinds):
            at = seen[kind]
            seen[kind] += 1
            if kind == "full_attention":
                w = {k: leaf(p, at) for k, p in ATTN_LEAVES.items()}
                out = attention(x, w, eps)
            else:
                w = {k: leaf(p, at) for k, p in GDN_LEAVES.items()}
                out = gated_delta_net(x, w, sizes, eps, states, keep)
            x = x + rms_norm(out, leaf("layers/ln1/scale", i), eps)
            ffn = swiglu(x, *(leaf("layers/mlp/" + n, i)
                              for n in ("w_gate", "w_up", "w_down")))
            x = x + rms_norm(ffn, leaf("layers/ln2/scale", i), eps)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, leaf("final_norm/scale"), eps)
        return x @ leaf("lm_head")
