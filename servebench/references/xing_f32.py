"""The plain reference of Xing4.0-29B-A4B (`xing4_0`): the forward pass
in straightforward `jax.numpy` and float32, with no kernels, no cache,
no absorbed products and no batching.

Written from the published configuration
(huggingface.co/XingChen-AGI/Xing4.0-29B-A4B `config.json`), the
family's description ("MLA - 40L; 64 experts, top-4, 1 shared; scaling
2; MTP 1") and the papers its keys name one for one: manifold-constrained
hyper-connections (mHC, arXiv:2512.24880) over hyper-connections
(arXiv:2409.19606) for `hc_mult`, `hc_sinkhorn_iters`, `hc_eps`,
`mhc_h_res_clamp_min/max`; DeepSeek-V2/V3 for the attention's key names
and for `rope_scaling` of type `yarn`. It shares no code with the
program.

THE RESIDUAL PATH. n = `hc_mult` streams of C = `hidden_size` a token:
X_0 = [e, e, .., e], the embedding n times. Each layer has two
sublayers F (attention; feed-forward), each with its own mixing leaves
phi [nC, n + n + n^2], b [n + n + n^2] and three scalars alpha, and a
token's streams X [n, C] pass a sublayer so (float32):

    v     = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)     all nC values, no weight
    pre~  = alpha_pre  * (v @ phi[:, 0:n])     + b[0:n]
    post~ = alpha_post * (v @ phi[:, n:2n])    + b[n:2n]
    res~  = alpha_res  * (v @ phi[:, 2n:])     + b[2n:]   -> [n, n] row-major,
            clipped to [mhc_h_res_clamp_min, mhc_h_res_clamp_max]
    H_pre = sigmoid(pre~)   H_post = 2 sigmoid(post~)
    H_res = M after `hc_sinkhorn_iters` rounds of
            { M /= colsum(M) + hc_eps;  M /= rowsum(M) + hc_eps },  M_0 = exp(res~)
    h     = sum_i H_pre[i] X[i]
    y     = F(RMSNorm(h; g))                the sublayer's own pre-norm
    X'    = H_res @ X + outer(H_post, y)

and after the last layer x = sum_i X[i], a final RMSNorm and logits =
x W_head (untied). The Sinkhorn loop is a Python loop. eps of every
RMSNorm with a weight is `rms_norm_eps`.

attention (latent, `num_attention_heads` heads), the EXPANDED form:
    c_q = RMSNorm(a W_dq; g_q)                         `q_lora_rank`
    q = c_q W_uq, a head's `qk_nope_head_dim` | `qk_rope_head_dim`
    [c_kv | k_r] = a W_dkv                    `kv_lora_rank` | rope dim
    c_kv = RMSNorm(c_kv; g_kv);  k_r is ONE rotary key a token
    q_rope and k_r rotate at the token's position, pair i = dims
    (2i, 2i+1) of the rope dim R, at the YaRN rate
        f_i = theta^(-2i/R);  g_i = f_i / factor
        lo = floor(c(beta_fast)), hi = ceil(c(beta_slow)), clipped to
        [0, R - 1], c(t) = (R/2) ln(orig / (2 pi t)) / ln(theta)
        r_i = clip((i - lo) / (hi - lo), 0, 1)
        rate_i = g_i r_i + f_i (1 - r_i)
    k_nope,h = c_kv W_uk,h;  v_h = c_kv W_uv,h       `v_head_dim`
    score_h[t,s] = (q_nope,h[t] . k_nope,h[s] + q_rope,h[t] . k_r[s])
                   x (nope + rope)^-1/2 x m^2,   m = 0.1 mscale_all_dim
                   ln(factor) + 1;  causal softmax
    out = concat_h(sum_s p v_h[s]) W_o
feed-forward: the first `first_k_dense_replace` layers a SwiGLU of
  `intermediate_size`; every other layer
    s = sigmoid(h W_g) over `n_routed_experts`; the top
    `num_experts_per_tok` of s + b_e (the stored `noaux_tc` correction;
    one group), an equal score to the lower index
    w = s of the chosen (WITHOUT b_e) / (their sum + 1e-20)
        x `routed_scaling_factor`
    y = sum_i w_i E_i(h) + S(h): experts and the shared expert SwiGLU of
    `moe_intermediate_size`

Departures from the source, and what is ASSUMED (the configuration
file's `assumed` has each with its reason):
- X_0 copies the embedding and the fold sums the streams
  (hyper-connections' own, arXiv:2409.19606).
- The order of phi's n + n + n^2 columns (pre, post, res row-major),
  `hc_eps` in the mixing's norm and in BOTH Sinkhorn denominators,
  columns before rows within a round (the paper's M(t) = T_r(T_c(M(t-1)))),
  a mixing of its own for each of a layer's two sublayers, H_post's
  factor 2.
- Seeded alpha 0.01 and b ~ N(0, 1): a seeded H_res is a generic doubly
  stochastic matrix, not the identity a trained model starts from.
- YaRN in the DeepSeek-V2/V3 convention; cos and sin are NOT scaled,
  because `mscale` equals `mscale_all_dim`.
- The prediction layer (`num_nextn_predict_layers` 1) takes no part in
  the next-token distribution and is left out.
- The source fuses `kv_b_proj` (W_uk and W_uv of a head side by side)
  and an expert's gate and up; the splits are layout.

It follows the contract of servebench/refcheck.py: `leaf(path, layer)`
hands over one leaf of the program's parameter tree as float32, and the
sizes come from the configuration FILE's published keys. It holds ONE
layer's and ONE expert's weights at a time. On a TPU a float32 matrix
multiplication runs in lower precision unless told otherwise, so
everything runs under `jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def yarn_rates(config: Dict):
    """The rotation rate of each pair of the rope dim, float64 [R/2]."""
    R, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    f = theta ** (-np.arange(0, R, 2, dtype=np.float64) / R)
    rs = config.get("rope_scaling")
    if not rs:
        return f
    orig = rs["original_max_position_embeddings"]

    def c(turns):
        return (R / 2) * math.log(orig / (2 * math.pi * turns)) \
            / math.log(theta)

    lo = max(math.floor(c(rs["beta_fast"])), 0)
    hi = min(math.ceil(c(rs["beta_slow"])), R - 1)
    r = np.clip((np.arange(R // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return f / rs["factor"] * r + f * (1 - r)


def score_scale(config: Dict) -> float:
    """(nope + rope)^-1/2 x m^2."""
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    rs = config.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        scale *= m * m
    return scale


def rotate_pairs(x, rates):
    """x [T, ..., R] at positions 0..T-1: pair i is dims (2i, 2i+1)."""
    T, R = x.shape[0], x.shape[-1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(rates, jnp.float32)                         # [T, R/2]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (R // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     odd * jnp.cos(ang) + even * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


#: query rows whose scores are held at once (a long stream's parity run)
ROWS = 1024


def attention(a, w: Dict, config: Dict):
    """a [T, D] normed; causal latent attention, expanded."""
    eps = float(config["rms_norm_eps"])
    nope, R = config["qk_nope_head_dim"], config["kv_lora_rank"]
    rates, scale = yarn_rates(config), score_scale(config)
    q = jnp.einsum("tr,rnh->tnh", rms_norm(a @ w["w_dq"], w["q_norm"], eps),
                   w["w_uq"])
    q_nope, q_rope = q[..., :nope], rotate_pairs(q[..., nope:], rates)
    ckv = a @ w["w_dkv"]
    c = rms_norm(ckv[:, :R], w["kv_norm"], eps)
    k_r = rotate_pairs(ckv[:, R:], rates)                          # [T, rope]
    k_nope = jnp.einsum("sr,rnh->snh", c, w["w_uk"])
    v = jnp.einsum("sr,rnh->snh", c, w["w_uv"])
    T = a.shape[0]
    out = []
    for lo in range(0, T, ROWS):    # the queries in blocks: [n, ROWS, T]
        at = jnp.arange(lo, min(lo + ROWS, T))
        see = jnp.arange(T)[None, :] <= at[:, None]
        sc = (jnp.einsum("tnh,snh->nts", q_nope[at], k_nope)
              + jnp.einsum("tnh,sh->nts", q_rope[at], k_r)) * scale
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


def mixing(X, phi, b, alpha, config: Dict):
    """X [T, n, C] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n])."""
    n, eps = config["hc_mult"], float(config["hc_eps"])
    v = X.reshape(X.shape[0], -1)
    v = v / jnp.sqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    pre = alpha[0] * (v @ phi[:, :n]) + b[:n]
    post = alpha[1] * (v @ phi[:, n:2 * n]) + b[n:2 * n]
    res = alpha[2] * (v @ phi[:, 2 * n:]) + b[2 * n:]
    M = jnp.exp(jnp.clip(res.reshape(-1, n, n),
                         float(config["mhc_h_res_clamp_min"]),
                         float(config["mhc_h_res_clamp_max"])))
    for _ in range(int(config["hc_sinkhorn_iters"])):
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)      # columns
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + eps)      # rows
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), M


def sublayer(X, F, norm, hc: Dict, config: Dict):
    """One sublayer over the streams X [T, n, C]."""
    pre, post, res = mixing(X, hc["phi"], hc["b"], hc["alpha"], config)
    h = jnp.einsum("tn,tnc->tc", pre, X)
    y = F(rms_norm(h, norm, float(config["rms_norm_eps"])))
    return jnp.einsum("tij,tjc->tic", res, X) + post[:, :, None] * y[:, None]


#: this family's names in the program's parameter tree
ATTN_LEAVES = {"w_dq": "w_dq", "q_norm": "q_norm/scale", "w_uq": "w_uq",
               "w_dkv": "w_dkv", "kv_norm": "kv_norm/scale", "w_uk": "w_uk",
               "w_uv": "w_uv", "wo": "wo"}
FFN = ("w_gate", "w_up", "w_down")
HC = ("phi", "b", "alpha")


def logits(tokens, leaf, config: Dict, rows=None, hidden: bool = False):
    """Logits [T, V] of one sequence of token ids [T] (with `rows`, a
    list of positions, only those rows of the head: [len(rows), V]).
    hidden: the final norm's rows [.., D] in place of the logits."""
    eps = float(config["rms_norm_eps"])
    top_k = int(config["num_experts_per_tok"])
    dense = int(config["first_k_dense_replace"])
    scale = float(config["routed_scaling_factor"])
    n = int(config["hc_mult"])
    with jax.default_matmul_precision("highest"):
        e = leaf("embed/tok")[jnp.asarray(tokens)]
        X = jnp.stack([e] * n, axis=1)                          # [T, n, C]
        for i in range(config["num_hidden_layers"]):
            w = {k: leaf("layers/attn/" + p, i)
                 for k, p in ATTN_LEAVES.items()}
            X = sublayer(X, lambda a: attention(a, w, config),
                         leaf("layers/ln1/scale", i),
                         {k: leaf("layers/hc1/" + k, i) for k in HC}, config)

            def feed_forward(h, i=i):
                if i < dense:
                    return _expert(h, *(leaf("dense/mlp/" + m, i)
                                        for m in FFN))
                j = i - dense
                mix = route(h, leaf("sparse/moe/router", j),
                            leaf("sparse/moe/router_bias", j), top_k, scale)
                y = _expert(h, *(leaf("sparse/shared/" + m, j) for m in FFN))
                # an expert no row chose has weight 0 in every row
                for x in np.flatnonzero(np.asarray(mix).any(axis=0)):
                    y = y + mix[:, x:x + 1] * _expert(
                        h, *(leaf("sparse/moe/" + m, (j, int(x)))
                             for m in FFN))
                return y

            X = sublayer(X, feed_forward, leaf("layers/ln2/scale", i),
                         {k: leaf("layers/hc2/" + k, i) for k in HC}, config)
        x = jnp.sum(X, axis=1)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, leaf("final_norm/scale"), eps)
        return x if hidden else x @ leaf("lm_head")
