"""The plain reference of granite-4.0-h-small (`granitemoehybrid`): the
forward pass in straightforward `jax.numpy` and float32, with no kernels,
no cache, no chunked scan and no batching.

Written from the published configuration
(huggingface.co/ibm-granite/granite-4.0-h-small `config.json`) and the
family's description ("Mamba-2 (128 heads, d_state 128); GQA NoPE - 40L:
36 mamba + 4 attention; 72 experts, top-10, 1 shared"). It shares no code
with the program. Tokens to x = E[tok] * embedding_multiplier; for each
layer, by `layer_types`:

    x = x + residual_multiplier * mixer(RMSNorm(x; g1))
    h = RMSNorm(x; g2)
    x = x + residual_multiplier * (moe(h) + shared(h))

then a final RMSNorm and logits = x E^T / logits_scaling (tied).

attention layer: q, k, v, o without bias, `num_attention_heads` queries
  over `num_key_value_heads` KV heads, causal, NO rotation
  (`position_embedding_type` "nope"), scores scaled by
  `attention_multiplier` (1/128, not 128^-1/2).
Mamba-2 layer, a position at a time (the recurrence is a Python loop):
    [z | xBC | dt] = a W_in          widths Di | Di + 2 G N | Nh, no bias
    xBC_t = silu(b + sum_k w_k xBC_{t-K+1+k})   causal depthwise conv of
            `mamba_d_conv` taps with bias; positions before 0 are zero
    xBC_t -> x_t [Nh, Hd], B_t [G, N], C_t [G, N]
    dt = softplus(dt + dt_bias);  A = -exp(A_log)            (a head)
    H_t = exp(dt A) H_{t-1} + dt x_t (outer) B_t   [Hd, N] a head, H_-1 = 0
    y_t = H_t C_t + D x_t
    out = (RMSNorm over all Di of (y * silu(z))) * w) W_out  (gate first,
          then ONE norm: `mamba_n_groups` 1)
experts: router logits r = h W_r, top `num_experts_per_tok` of
  `num_local_experts`, softmax over the chosen; an expert is
  W_down (silu(h W_gate) * (h W_up)) of width `intermediate_size`; the
  shared expert the same at `shared_intermediate_size`, every token,
  added unweighted.

Departures from the source, and what is assumed (the configuration file's
`assumed` has each with its reason):
- The source fuses an expert's gate and up in one `input_linear`; the
  split is layout.
- `mamba_chunk_size` tiles the source's own scan and defines nothing; the
  source's `time_step_limit` is (0, inf): no clamp.
- The state here is float32 from the first position to the last. The
  program keeps a stream's state and conv tail in the model's dtype
  BETWEEN calls (bfloat16 in the benchmark's configuration, as the
  family's public serving stacks do): the check's limit is set with that
  rounding in force.

It follows the contract of servebench/refcheck.py: `leaf(path, layer)`
hands over one leaf of the program's parameter tree as float32, and the
sizes come from the configuration FILE's published keys. It holds ONE
layer's and ONE expert's weights at a time (a layer's 72 experts are 2.7
GB in float32). On a TPU a float32 matrix multiplication runs in lower
precision unless told otherwise, so everything runs under
`jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def attention(a, w: Dict, scale: float):
    """a [T, D] normed; causal grouped-query attention, no rotation."""
    q = jnp.einsum("td,dnh->tnh", a, w["wq"])
    k = jnp.einsum("td,dkh->tkh", a, w["wk"])
    v = jnp.einsum("td,dkh->tkh", a, w["wv"])
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    T = a.shape[0]
    see = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    sc = jnp.einsum("tnh,snh->nts", q, k) * scale
    pr = jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("tnh,nhd->td", jnp.einsum("nts,snh->tnh", pr, v),
                      w["wo"])


def mamba(a, w: Dict, sizes: Dict, eps: float, states=None, keep=None):
    """a [T, D] normed; the mixer, one position at a time. states (a
    list): gains what a stream holds after its last position, (H
    [Nh, Hd, N], the conv's last K-1 inputs [K-1, Dc]). keep (a dtype):
    H is rounded to it after every position, as a server that keeps a
    stream's state in that dtype between steps rounds it (a control for
    tools/state_parity.py; the reference itself keeps float32)."""
    Nh, Hd, N, G, K = (sizes[k] for k in ("Nh", "Hd", "N", "G", "K"))
    Di = Nh * Hd
    T = a.shape[0]
    zxd = a @ w["in_proj"]
    z, xbc, dt = zxd[:, :Di], zxd[:, Di:Di + Di + 2 * G * N], \
        zxd[:, Di + Di + 2 * G * N:]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    A = -jnp.exp(w["A_log"])
    H = jnp.zeros((Nh, Hd, N), jnp.float32)
    ys = []
    for t in range(T):
        u = jax.nn.silu(w["conv_b"] + jnp.sum(padded[t:t + K] * w["conv_w"],
                                              axis=0))
        x = u[:Di].reshape(Nh, Hd)
        B = jnp.repeat(u[Di:Di + G * N].reshape(G, N), Nh // G, axis=0)
        C = jnp.repeat(u[Di + G * N:].reshape(G, N), Nh // G, axis=0)
        step = jax.nn.softplus(dt[t] + w["dt_bias"])               # [Nh]
        H = jnp.exp(step * A)[:, None, None] * H \
            + (step[:, None] * x)[:, :, None] * B[:, None, :]
        ys.append(jnp.einsum("nhs,ns->nh", H, C) + w["D"][:, None] * x)
        if keep is not None:
            H = H.astype(keep).astype(jnp.float32)
    if states is not None:
        states.append((H, padded[T:]))
    y = jnp.stack(ys).reshape(T, Di)
    return rms_norm(y * jax.nn.silu(z), w["norm"], eps) @ w["out_proj"]


def route(h, router, top_k: int):
    """[T, E]: each row's weights over the experts, 0 off its top_k."""
    r = h @ router
    top, idx = jax.lax.top_k(r, top_k)
    wts = jax.nn.softmax(top, axis=-1)
    return jnp.sum(jax.nn.one_hot(idx, r.shape[-1]) * wts[..., None], axis=1)


def expert(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


_expert = jax.jit(expert)

#: this family's names in the program's parameter tree: what every layer
#: has under "layers", each kind's mixer under a stack of its own
ATTN_LEAVES = {k: "attn/" + k for k in ("wq", "wk", "wv", "wo")}
MAMBA_LEAVES = {"in_proj": "mamba/in_proj", "conv_w": "mamba/conv_w",
                "conv_b": "mamba/conv_b", "dt_bias": "mamba/dt_bias",
                "A_log": "mamba/A_log", "D": "mamba/D",
                "norm": "mamba/norm/scale", "out_proj": "mamba/out_proj"}


def logits(tokens, leaf, config: Dict, rows=None, states=None, keep=None):
    """Logits [T, V] of one sequence of token ids [T] (with `rows`, a
    list of positions, only those rows of the head: [len(rows), V]).
    states (a list): gains each Mamba layer's state after the last
    position, in layer order; keep: `mamba`'s."""
    eps = float(config["rms_norm_eps"])
    res = float(config["residual_multiplier"])
    top_k, E = int(config["num_experts_per_tok"]), int(config["num_local_experts"])
    sizes = {"Nh": config["mamba_n_heads"], "Hd": config["mamba_d_head"],
             "N": config["mamba_d_state"], "G": config["mamba_n_groups"],
             "K": config["mamba_d_conv"]}
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    seen = {"mamba": 0, "attention": 0}
    with jax.default_matmul_precision("highest"):
        E_tok = leaf("embed/tok")
        x = E_tok[jnp.asarray(tokens)] * float(config["embedding_multiplier"])
        for i, kind in enumerate(kinds):
            a = rms_norm(x, leaf("layers/ln1/scale", i), eps)
            at = seen[kind]
            seen[kind] += 1
            if kind == "attention":
                w = {k: leaf(p, at) for k, p in ATTN_LEAVES.items()}
                out = attention(a, w, float(config["attention_multiplier"]))
            else:
                w = {k: leaf(p, at) for k, p in MAMBA_LEAVES.items()}
                out = mamba(a, w, sizes, eps, states, keep)
            x = x + res * out
            h = rms_norm(x, leaf("layers/ln2/scale", i), eps)
            mix = route(h, leaf("layers/moe/router", i), top_k)
            ffn = _expert(h, *(leaf("layers/shared/" + n, i)
                               for n in ("w_gate", "w_up", "w_down")))
            for e in range(E):
                ffn = ffn + mix[:, e:e + 1] * _expert(
                    h, *(leaf("layers/moe/" + n, (i, e))
                         for n in ("w_gate", "w_up", "w_down")))
            x = x + res * ffn
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, leaf("final_norm/scale"), eps)
        return jnp.einsum("td,vd->tv", x, E_tok) / float(config["logits_scaling"])
