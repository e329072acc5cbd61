"""The plain reference of AI21-Jamba2-3B (`jamba`): the forward pass in
straightforward `jax.numpy` and float32, with no kernels, no cache, no
chunks and no batching.

Written from the published configuration
(huggingface.co/ai21labs/AI21-Jamba2-3B `config.json`), the family's
description ("Mamba-1 + attention; dense") and the paper whose layer the
`mamba_*` keys size (Gu and Dao, "Mamba: Linear-Time Sequence Modeling
with Selective State Spaces", arXiv:2312.00752). It shares no code with
the program. Tokens to x = E[tok]; for each layer i, attention where
i % `attn_layer_period` == `attn_layer_offset`, else Mamba-1:

    h = x + mixer(RMSNorm(x; g1))
    x = h + swiglu(RMSNorm(h; g2))          `intermediate_size`, no bias

then a final RMSNorm and logits = x E^T (`tie_word_embeddings`). eps
`rms_norm_eps` everywhere.

Mamba-1, Di = `mamba_expand` x `hidden_size` channels, a state of N =
`mamba_d_state` a channel, R = `mamba_dt_rank`, K = `mamba_d_conv`, a
position at a time (the recurrence is a Python loop):
    [u | z] = a W_in                     Di | Di, no bias (`mamba_proj_bias`)
    u_t = silu(sum_j w_j u_{t-K+1+j} + b_conv)   causal depthwise conv with a
            bias (`mamba_conv_bias`) over u ALONE; positions before 0 zero
    [r | B | C] = u W_x                  R | N | N, no bias: from the conv's
            OUTPUT
    r = RMSNorm_R(r; w_dt), B = RMSNorm_N(B; w_B), C = RMSNorm_N(C; w_C)
    dt = softplus(r W_dt + b_dt)         [Di]: a step size a CHANNEL
    A = -exp(A_log)                      [Di, N]
    h[c, n] = exp(dt[c] A[c, n]) h[c, n] + dt[c] B[n] u[c];  h_-1 = 0
    y[c] = sum_n h[c, n] C[n] + D[c] u[c]
    out = (y * silu(z)) W_out            NO norm behind the gate
Attention: q = a W_q (`num_attention_heads` heads of head_dim), k = a W_k,
  v = a W_v (`num_key_value_heads` heads: ONE), no bias; NO rotation and
  no other positional term (the family has none: the recurrent layers
  carry order); causal softmax at head_dim^-0.5, every query head
  against the one key-value head; W_o.

Departures from the source, and what is assumed (the configuration file's
`assumed` has each with its reason):
- the order of the layers: the family's modelling code reads
  `attn_layer_period` / `attn_layer_offset` as above (layers 7 and 21).
- `head_dim` 128 = 2,560 / 20: the source has no key for it.
- `num_experts` 1: the family builds its plain feed-forward there, so
  every layer is one dense SwiGLU and there is no router.
- the three inner norms and the dt-projection's bias are the family's
  (Mamba-1 as published has no norms); u is the first half of W_in.
- The program holds `A_log` as [N, Di], channels on the lanes as its
  state is: layout only; this file turns it to the published [Di, N].
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


def attention(a, w: Dict):
    """a [T, D] normed; causal attention, no rotation, the key-value
    heads repeated over their query heads."""
    T = a.shape[0]
    nq, hd = w["wq"].shape[1:]
    nkv = w["wk"].shape[1]
    q = jnp.einsum("td,dnh->tnh", a, w["wq"])
    k = jnp.repeat(jnp.einsum("td,dkh->tkh", a, w["wk"]), nq // nkv, axis=1)
    v = jnp.repeat(jnp.einsum("td,dkh->tkh", a, w["wv"]), nq // nkv, axis=1)
    see = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    sc = jnp.einsum("tnh,snh->nts", q, k) * hd ** -0.5
    pr = jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("tnh,nhd->td", jnp.einsum("nts,snh->tnh", pr, v),
                      w["wo"])


@jax.jit
def position(h, taps, w: Dict, eps):
    """One position of the mixer behind its in-projection: h [Di, N] the
    state before, taps [K, Di] the position's u and the K-1 before it.
    Returns (h after, y [Di] with the skip term)."""
    R, N = w["dt_norm"].shape[0], w["b_norm"].shape[0]
    c = jax.nn.silu(jnp.sum(taps * w["conv_w"], axis=0) + w["conv_b"])
    rbc = c @ w["x_proj"]                           # from the conv's OUTPUT
    r = rms_norm(rbc[:R], w["dt_norm"], eps)
    B = rms_norm(rbc[R:R + N], w["b_norm"], eps)
    C = rms_norm(rbc[R + N:], w["c_norm"], eps)
    dt = jax.nn.softplus(r @ w["dt_proj"] + w["dt_bias"])       # [Di]
    A = -jnp.exp(w["A_log"].T)                      # [Di, N]
    h = jnp.exp(dt[:, None] * A) * h + (dt * c)[:, None] * B[None, :]
    return h, h @ C + w["D"] * c


def mamba(a, w: Dict, sizes: Dict, eps: float, states=None, keep=None):
    """a [T, D] normed; the mixer, one position at a time. states (a
    list): gains what a stream holds after its last position, (h
    [Di, N], the conv's last K-1 inputs [K-1, Di]). keep (a dtype): h is
    rounded to it after every position, as a server that keeps a
    stream's state in that dtype between steps rounds it (a control for
    tools/state_parity.py; the reference itself keeps float32)."""
    Di, N, K = (sizes[n] for n in ("Di", "N", "K"))
    if w["x_proj"].shape != (Di, sizes["R"] + 2 * N):
        raise ValueError("x_proj is not [Di, R + 2 N]")
    T = a.shape[0]
    uz = a @ w["in_proj"]
    u, z = uz[:, :Di], uz[:, Di:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Di)), u])
    h = jnp.zeros((Di, N), jnp.float32)
    ys = []
    for t in range(T):
        h, y = position(h, padded[t:t + K], w, eps)
        if keep is not None:
            h = h.astype(keep).astype(jnp.float32)
        ys.append(y)
    if states is not None:
        states.append((h, padded[T:]))
    return (jnp.stack(ys) * jax.nn.silu(z)) @ w["out_proj"]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


#: this family's names in the program's parameter tree: what every layer
#: has under "layers", each kind's mixer under a stack of its own
ATTN_LEAVES = {"wq": "attn/wq", "wk": "attn/wk", "wv": "attn/wv",
               "wo": "attn/wo"}
MAMBA_LEAVES = {"in_proj": "mamba1/in_proj", "conv_w": "mamba1/conv_w",
                "conv_b": "mamba1/conv_b", "x_proj": "mamba1/x_proj",
                "dt_norm": "mamba1/dt_norm/scale",
                "b_norm": "mamba1/b_norm/scale",
                "c_norm": "mamba1/c_norm/scale",
                "dt_proj": "mamba1/dt_proj", "dt_bias": "mamba1/dt_bias",
                "A_log": "mamba1/A_log", "D": "mamba1/D",
                "out_proj": "mamba1/out_proj"}


def is_attention(i: int, config: Dict) -> bool:
    """Layer i is attention (else Mamba-1), as the family's modelling
    code reads the two keys."""
    return i % config["attn_layer_period"] == config["attn_layer_offset"]


def logits(tokens, leaf, config: Dict, rows=None, states=None, keep=None):
    """Logits [T, V] of one sequence of token ids [T] (with `rows`, a
    list of positions, only those rows of the head: [len(rows), V]).
    states (a list): gains each Mamba layer's state after the last
    position, in layer order; keep: `mamba`'s."""
    eps = float(config["rms_norm_eps"])
    if config["num_experts"] != 1 or config.get("mamba_proj_bias") \
            or not config.get("mamba_conv_bias", True):
        raise ValueError("experts, a projection bias or a conv without "
                         "bias: not this file's layer")
    sizes = {"Di": config["mamba_expand"] * config["hidden_size"],
             "N": config["mamba_d_state"], "R": config["mamba_dt_rank"],
             "K": config["mamba_d_conv"]}
    seen = {True: 0, False: 0}
    with jax.default_matmul_precision("highest"):
        E_tok = leaf("embed/tok")
        x = E_tok[jnp.asarray(tokens)]
        for i in range(config["num_hidden_layers"]):
            attn = is_attention(i, config)
            at = seen[attn]
            seen[attn] += 1
            a = rms_norm(x, leaf("layers/ln1/scale", i), eps)
            if attn:
                out = attention(a, {k: leaf(p, at)
                                    for k, p in ATTN_LEAVES.items()})
            else:
                out = mamba(a, {k: leaf(p, at)
                                for k, p in MAMBA_LEAVES.items()},
                            sizes, eps, states, keep)
            x = x + out
            x = x + swiglu(rms_norm(x, leaf("layers/ln2/scale", i), eps),
                           *(leaf("layers/mlp/" + n, i)
                             for n in ("w_gate", "w_up", "w_down")))
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, leaf("final_norm/scale"), eps)
        return x @ E_tok.T
