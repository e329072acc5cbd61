"""Functional transformer core shared by GPT-2 / Llama-3 / Mixtral.

Design (TPU-first, not a torch translation):

* Params are plain pytrees (nested dicts of jnp arrays). No Module system —
  pure functions keep every transform (jit, grad, shard_map, scan) trivially
  applicable, and sharding is attached by the partitioner
  (butterfly_tpu.parallel.partition) as PartitionSpecs over leaf paths.
* Per-layer weights are STACKED on a leading layer axis and the forward pass
  is `lax.scan` over layers: one traced layer body regardless of depth, so a
  70B/80-layer model compiles as fast as a 2-layer one, and pipeline
  parallelism can slice the same stacked leaves into stages.
* The KV cache is a pytree of [L, B, S, Kv, H] arrays updated in-place via
  vmapped `lax.dynamic_update_slice` (XLA DynamicUpdateSlice keeps it
  HBM-resident, per the north star in BASELINE.json).

Capability parity note: this realizes the reference's planned "Distributed
Inference Engine" model side (/root/reference/CLAUDE.md:19,21) for which no
implementation exists (see SURVEY.md §0).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from butterfly_tpu.core.config import ModelConfig
# Module-level, deliberately: attention_block runs INSIDE traced code and
# a lazy in-function import executes on every trace — the same per-trace
# tax PR 12's quantize_kv hoist removed from cache/paged.py. No cycle:
# ops.flash_attention imports nothing project-local at module level.
from butterfly_tpu.ops import moe_experts as moe_kernel
from butterfly_tpu.ops import note_kernel
from butterfly_tpu.ops.flash_attention import flash_attention_sharded
from butterfly_tpu.quant.int8 import maybe_dequant, qeinsum

Params = Dict[str, Any]


def _cast_float(a: jax.Array, dtype) -> jax.Array:
    """Cast to the compute dtype, leaving integer (e.g. int8) leaves alone."""
    return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a


class KVCache(NamedTuple):
    """Contiguous KV cache: [num_layers, batch, max_seq, num_kv_heads, head_dim].

    `length[b]` = number of tokens already written for sequence b.

    int8 mode (init_cache(quant="int8")): k/v hold int8 codes in
    [L, B, Kv, S, H] order and k_scale/v_scale [L,B,Kv,S] hold one f32
    scale per stored vector (absmax over head_dim / 127). Decode streams
    half the cache bytes from HBM — the dominant term of the
    bandwidth-bound decode loop at serving batch sizes; dequantization
    is fused into the attention dots (scores scale output-side, value
    scale folded into the probs), so no bf16 copy of the cache ever
    materializes. The dim order differs from the float cache
    deliberately: TPU tiles pad the two minor dims ((32,128) for int8,
    (8,128) for f32), so Kv=8 minor would inflate physical HBM 4x for
    the codes and 16x for the scales; with (S,H) and (Kv,S) minor there
    is no padding and each (b,kv) attention read is one contiguous
    [S,H] tile run.
    """

    k: jax.Array
    v: Optional[jax.Array]  # None for a latent-attention model: k holds
                            # ONE row a token, [L, B, S, 1, latent_row]
                            # (the latent, which is also the values, and
                            # the rotated key), and nothing else is cached
    length: jax.Array  # [B] int32
    k_scale: Optional[jax.Array] = None  # [L,B,Kv,S] f32 iff k is int8
    v_scale: Optional[jax.Array] = None
    ki: Optional[jax.Array] = None  # [L,B,S,Hi] index keys iff the model
                                    # has a sparse-attention indexer
    # a model with Mamba-2 layers (cfg.layer_types): k/v hold its
    # ATTENTION layers only ([La, ...]) and each row's recurrent state
    # is a slot of `ssm` (cache/ssm_state.py SSMState of B slots)
    ssm: Optional[Any] = None

    @property
    def max_seq(self) -> int:
        return self.k.shape[3] if self.quantized else self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype: Optional[jnp.dtype] = None,
               quant: str = "none") -> KVCache:
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.is_latent:
        if quant != "none":
            latent_unsupported(cfg, "the int8 contiguous KV cache")
        return KVCache(
            k=jnp.zeros((cfg.num_layers, batch, max_seq, 1, cfg.latent_row),
                        dtype),
            v=None, length=jnp.zeros((batch,), jnp.int32),
            ki=jnp.zeros((cfg.num_layers, batch, max_seq,
                          cfg.index_head_dim), dtype)
            if cfg.has_indexer else None)
    if cfg.has_ssm:
        if quant != "none":
            ssm_unsupported(cfg, "the int8 contiguous KV cache")
        from butterfly_tpu.cache.ssm_state import init_ssm_state
        kv = (cfg.num_attn_layers, batch, max_seq, cfg.num_kv_heads,
              cfg.head_dim)
        return KVCache(k=jnp.zeros(kv, dtype), v=jnp.zeros(kv, dtype),
                       length=jnp.zeros((batch,), jnp.int32),
                       ssm=init_ssm_state(cfg, batch))
    shape = (cfg.num_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
    ki = jnp.zeros((cfg.num_layers, batch, max_seq, cfg.index_head_dim),
                   dtype) if cfg.has_indexer else None
    if quant == "int8":
        qshape = (cfg.num_layers, batch, cfg.num_kv_heads, max_seq,
                  cfg.head_dim)
        return KVCache(
            k=jnp.zeros(qshape, jnp.int8),
            v=jnp.zeros(qshape, jnp.int8),
            length=jnp.zeros((batch,), jnp.int32),
            k_scale=jnp.zeros(qshape[:-1], jnp.float32),
            v_scale=jnp.zeros(qshape[:-1], jnp.float32), ki=ki,
        )
    if quant != "none":
        raise ValueError(f"unknown kv quant {quant!r}")
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        length=jnp.zeros((batch,), jnp.int32), ki=ki,
    )


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-vector int8 quantization over the last (head_dim) axis.

    x [..., H] float -> (codes [..., H] int8, scale [...] f32) with
    x ~= codes * scale. Zero vectors get scale 1 (codes all 0).
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    codes = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return jnp.clip(codes, -127, 127).astype(jnp.int8), scale


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float,
             axis=-1) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=axis, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def wide_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the two minor dims of x [..., N, H] at once (a
    whole projection, before its heads are split) with a learned weight
    [N, H]."""
    return rms_norm(x, scale, eps, axis=(-2, -1))


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def gelu_new(x: jax.Array) -> jax.Array:
    """GPT-2's tanh-approximated GELU."""
    c = jnp.sqrt(2.0 / jnp.pi).astype(x.dtype)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


ACTIVATIONS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "gelu_new": gelu_new,
    "relu": jax.nn.relu,
}


def rope_freqs(cfg: ModelConfig, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for positions [..., T] -> [..., T, rope_dim/2], f32
    (rope_dim: head_dim, or a latent model's rotary part)."""
    half = cfg.rope_dim // 2
    if cfg.rope_scaling:    # "yarn": the blended rates, constants of cfg
        inv_freq = jnp.asarray(cfg.yarn_inv_freq())
    else:
        inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., T, half]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate-half convention (matches HF Llama so imported weights agree).

    x: [B, T, N, H]; cos/sin: [B, T, half] (or [T, half]).
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[..., None, :].astype(x.dtype)  # broadcast over heads
    sin = sin[..., None, :].astype(x.dtype)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    return jnp.concatenate([r1, r2], axis=-1)


def apply_rope_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array
                     ) -> jax.Array:
    """The rotation over PAIRS (2i, 2i+1) of the last dim (cfg.
    rope_interleave), pair i by frequency i. x: [B, T, N, H]; cos/sin
    [B, T, H/2]. The rotated pair (2i, 2i+1) comes back at (i, i + H/2):
    queries and keys are both laid so, and a score, a sum over the
    dims, is the same whatever order both share. The even and the odd
    dims are each one strided read; putting them back in pairs would be
    a shuffle of every row for nothing."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    cos = cos[..., None, :].astype(x.dtype)
    sin = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def update_cache_layer(ck: jax.Array, cv: jax.Array, k: jax.Array, v: jax.Array,
                       start: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Write k/v [B,T,Kv,H] into cache [B,S,Kv,H] at per-sequence offsets.

    vmapped DynamicUpdateSlice over the batch — stays HBM-resident, no
    host round trip (north-star requirement, BASELINE.json).
    """
    def upd(cache_b, new_b, start_b):
        return lax.dynamic_update_slice(cache_b, new_b, (start_b, 0, 0))

    ck = jax.vmap(upd)(ck, k.astype(ck.dtype), start)
    cv = jax.vmap(upd)(cv, v.astype(cv.dtype), start)
    return ck, cv


def update_cache_layer_q(ck, cv, k_s, v_s, k, v, start):
    """int8 twin of update_cache_layer: quantize then write codes +
    scales. Cache layout is [B,Kv,S,H] / scales [B,Kv,S] (see KVCache);
    k/v arrive as [B,T,Kv,H]. Returns (ck, cv, k_s, v_s)."""
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)

    def upd(cache_b, new_b, start_b):  # [Kv,S,H] <- [Kv,T,H] at (0,s,0)
        return lax.dynamic_update_slice(cache_b, new_b, (0, start_b, 0))

    def upd_s(s_b, new_b, start_b):    # [Kv,S] <- [Kv,T] at (0,s)
        return lax.dynamic_update_slice(s_b, new_b, (0, start_b))

    ck = jax.vmap(upd)(ck, kq.transpose(0, 2, 1, 3), start)
    cv = jax.vmap(upd)(cv, vq.transpose(0, 2, 1, 3), start)
    k_s = jax.vmap(upd_s)(k_s, ks.transpose(0, 2, 1), start)
    v_s = jax.vmap(upd_s)(v_s, vs.transpose(0, 2, 1), start)
    return ck, cv, k_s, v_s


@jax.named_scope("attn")
def attend(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
           cfg: ModelConfig, k_scale: Optional[jax.Array] = None,
           v_scale: Optional[jax.Array] = None) -> jax.Array:
    """Grouped-query attention over the (cached) key/value sequence.

    q: [B, T, Nq, H]; k/v: [B, S, Kv, H]; mask: [B, T, S] bool (True=attend).
    Returns [B, T, Nq, H]. Softmax in f32 for stability.

    int8 cache: k/v are codes in [B,Kv,S,H] order and k_scale/v_scale
    [B,Kv,S] their per-vector scales. The convert feeds the dot
    directly (only int8 bytes stream from HBM); the K scale is constant
    over the contracted head_dim so it applies to the scores
    output-side, and the V scale varies along the contracted S so it
    folds into the probs.
    """
    B, T, Nq, H = q.shape
    quant = k_scale is not None
    S = k.shape[2] if quant else k.shape[1]
    Kv = k.shape[1] if quant else k.shape[2]
    G = Nq // Kv
    q = q.reshape(B, T, Kv, G, H)
    compute = q.dtype
    scale = 1.0 / jnp.sqrt(jnp.asarray(H, jnp.float32))
    k_eq = "bksh" if quant else "bskh"
    scores = jnp.einsum(f"btkgh,{k_eq}->bktgs", q, _cast_float(k, compute),
                        preferred_element_type=jnp.float32)
    if quant:
        scores = scores * k_scale[:, :, None, None, :]
    scores = scores * scale
    scores = jnp.where(mask[:, None, :, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if quant:
        probs = probs * v_scale[:, :, None, None, :]
    out = jnp.einsum(f"bktgs,{k_eq}->btkgh", probs.astype(compute),
                     _cast_float(v, compute))
    return out.reshape(B, T, Nq, H)


@jax.named_scope("attn")
def attend_token_rows(q: jax.Array, k: jax.Array, v: jax.Array,
                      mask: jax.Array) -> jax.Array:
    """attend over TOKEN-major rows (cache/paged.py pool_row): q
    [B, T, Nq, H]; k/v [B, S, Kv*H], a token's KV heads contiguous in
    its row; mask [B, T, S]. Returns [B, T, Nq, H]. A KV head is read
    as a lane-aligned slice of H of the row, one product a head: the
    [B, S, Kv, H] view attend takes is a relayout of every row on the
    chip (a tile row holds H values), which cost a fifth of the whole
    read there (PERF.md PR 37). The same products, scale, mask and
    float32 softmax as attend's, head by head: the same bits."""
    B, T, Nq, H = q.shape
    Kv = k.shape[-1] // H
    q = q.reshape(B, T, Kv, Nq // Kv, H)
    scale = 1.0 / jnp.sqrt(jnp.asarray(H, jnp.float32))

    def head(rows, i):
        return rows[..., i * H:(i + 1) * H]

    scores = jnp.stack(
        [jnp.einsum("btgh,bsh->btgs", q[:, :, i], head(k, i),
                    preferred_element_type=jnp.float32)
         for i in range(Kv)], axis=1)                      # [B,Kv,T,G,S]
    scores = jnp.where(mask[:, None, :, None, :], scores * scale, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.stack([jnp.einsum("btgs,bsh->btgh", probs[:, i], head(v, i))
                     for i in range(Kv)], axis=2)          # [B,T,Kv,G,H]
    return out.reshape(B, T, Nq, H)


# ---------------------------------------------------------------------------
# Layer bodies
# ---------------------------------------------------------------------------

@jax.named_scope("attn")
def qkv_proj(x: jax.Array, p: Params, cfg: ModelConfig,
             cos: jax.Array, sin: jax.Array, rope=None
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """QKV projections (+bias, +norm on heads, +rope). x: [B,T,D] ->
    q [B,T,Nq,H], k/v [B,T,Kv,H]. Shared by the contiguous and paged
    attention paths of every family the program has (GPT-2, Llama,
    Mixtral, SmallThinker, Keye).
    cfg.qk_norm: an RMSNorm with a learned weight [H] over each head's
    queries and keys, before the rotation (the Qwen3 families, Keye).
    cfg.qk_norm_wide: ONE RMSNorm over the whole projection, all heads,
    with a learned weight [N, H] (OLMo 2's family: wide_norm).
    rope: the layer's flag out of its pattern (layer_stack), a traced
    scalar: 0 = this layer has no positional encoding. It turns the
    rotation into the identity (cos 1, sin 0), exactly: x*1 - y*0."""
    dt = x.dtype
    q = qeinsum("btd,dnh->btnh", x, p["wq"], dt)
    k = qeinsum("btd,dkh->btkh", x, p["wk"], dt)
    v = qeinsum("btd,dkh->btkh", x, p["wv"], dt)
    if cfg.use_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"]["scale"], cfg.norm_eps)
    if cfg.qk_norm_wide:
        q = wide_norm(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = wide_norm(k, p["k_norm"]["scale"], cfg.norm_eps)
    if cfg.attention_multiplier:
        # the family's score scale in place of H ** -0.5, which every
        # attend and kernel behind this applies: the queries carry the
        # ratio of the two
        q = q * jnp.asarray(cfg.attention_multiplier * cfg.head_dim ** 0.5,
                            q.dtype)
    if cfg.pos_embedding == "rope":
        if rope is not None:
            cos = jnp.where(rope > 0, cos, 1.0)
            sin = jnp.where(rope > 0, sin, 0.0)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


# ---------------------------------------------------------------------------
# Learned sparse attention: the indexer (cfg.index_heads/_head_dim/_topk)
#
# Beside its keys and values a token caches ONE index key kI [Hi]; a
# query brings index_heads index queries qI [Ni,Hi] and a weight a head
# w [Ni]. The index score of query t for position s <= t is
#     I[t,s] = sum_j w[t,j] * relu(qI[t,j] . kI[s])
# and the query attends the index_topk positions that score highest
# (every position while there are no more; a tie goes to the lower
# position). One selection a token and layer, shared by all heads. The
# selection is discrete, as a routing is: projections and scores run in
# float32 at full precision (router_logits says why), and the indexer's
# weights stay out of the int8 quantiser. These functions are the ONE
# definition that the contiguous path, the paged path and the packed
# step share.
# ---------------------------------------------------------------------------

@jax.named_scope("attn_index")
def index_proj(x: jax.Array, lp: Params, cfg: ModelConfig,
               cos: jax.Array, sin: jax.Array,
               cq: Optional[jax.Array] = None):
    """The indexer's three projections of a layer's INPUT x [B,T,D] (the
    norm is taken again in float32, as early_router_logits does):
    (qI [B,T,Ni,Hi], kI [B,T,Hi], w [B,T,Ni]), float32. kI passes a
    LayerNorm; qI and kI are rotated (rotate-half over all Hi dims, the
    model's theta). cos/sin are the attention's own [B,T,H/2]: the
    index head's frequencies theta^(-j/(Hi/2)) are every (H/Hi)-th of
    the attention head's theta^(-m/(H/2)), to the bit.

    Beside latent attention (DeepSeek-V3.2's own form; GLM-5) x is the
    layer's NORMED input h, which kI and w read, and the index QUERIES
    read cq [B,T,q_lora_rank], the query latent that latent_proj hands
    on (w_qi is [Rq,Ni,Hi]); only the first cfg.index_rope_dim dims of
    qI's heads and of kI rotate, as the attention's rotary part does
    (by pairs under cfg.rope_interleave, at its cos/sin [B,T,rope/2]),
    and the rest pass."""
    ip = lp["index"]
    hp = lax.Precision.HIGHEST
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = f32(x) if cfg.is_latent \
        else pre_norm(x.astype(jnp.float32), lp["ln1"], cfg)
    qi = jnp.einsum("btd,dnh->btnh", f32(cq) if cfg.is_latent else h,
                    f32(ip["w_qi"]), precision=hp)
    ki = jnp.einsum("btd,dh->bth", h, f32(ip["w_ki"]), precision=hp)
    w = jnp.einsum("btd,dn->btn", h, f32(ip["w_w"]), precision=hp)
    ki = layer_norm(ki, ip["k_norm"]["scale"], ip["k_norm"]["bias"],
                    cfg.norm_eps)
    if cfg.is_latent:
        turn = apply_rope_pairs if cfg.rope_interleave else apply_rope
        R = cfg.index_rope_dim

        def rotate(a, cos, sin):
            return jnp.concatenate([turn(a[..., :R], cos, sin), a[..., R:]],
                                   axis=-1)
    else:
        rotate = apply_rope
        step = cfg.head_dim // cfg.index_head_dim
        cos, sin = cos[..., ::step], sin[..., ::step]
    qi = rotate(qi, cos, sin)
    ki = rotate(ki[:, :, None], cos, sin)[:, :, 0]
    return qi, ki, w


@jax.named_scope("attn_index")
def index_scores(qi: jax.Array, w: jax.Array, ki: jax.Array) -> jax.Array:
    """I [B,T,S] float32 of index queries qI [B,T,Ni,Hi] with their
    weights w [B,T,Ni] against index keys kI [B,S,Hi] as cached (any
    float dtype). A positive constant on I (DeepSeek's Hi^-1/2 Ni^-1/2)
    changes no selection and is left out."""
    s = jnp.einsum("btnh,bsh->btns", qi, ki.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)
    return jnp.einsum("btns,btn->bts", jax.nn.relu(s), w,
                      precision=lax.Precision.HIGHEST)


def _selected(s: jax.Array, valid: jax.Array, kth: jax.Array,
              k: int) -> jax.Array:
    """The selection as a mask, from the k-th highest score `kth`
    [..., 1] of s (scores, -inf where not valid): what scores above it,
    and of what scores equal to it the lower positions, as many as make
    k, which is the order lax.top_k takes them in."""
    above = s > kth
    level = (s == kth) & valid
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return valid & (above | (level & (jnp.cumsum(level, axis=-1) <= room)))


@jax.named_scope("attn_select")
def select_topk(scores: jax.Array, valid: jax.Array, k: int,
                with_mask: bool = False,
                payload: Optional[jax.Array] = None):
    """The k valid positions of the last axis that score highest, as
    (idx [..., k] int32, ok [..., k], mask): ok is False on the entries
    past the number of valid positions (idx then points anywhere).
    lax.top_k orders equal scores by position, the lower first. mask
    (with_mask; else None): the same selection over the last axis, as
    select_mask gives it.

    payload [..., S] int32: what to return of a selected position in
    place of its index (the paged cache: the pool row that holds it).
    It rides ONE stable sort beside the scores, highest first and equal
    scores by position, the lower first: the same k in the same order
    as lax.top_k's, without a lookup of k scalars behind it."""
    s = jnp.where(valid, scores, -jnp.inf)
    if payload is None:
        vals, idx = lax.top_k(s, k)
    else:
        # s + 0.0: a -0.0 sorts with the zeros, as it compares
        neg, idx = lax.sort((-(s + 0.0), payload), is_stable=True,
                            num_keys=1)
        vals, idx = -neg[..., :k], idx[..., :k]
    mask = _selected(s, valid, vals[..., -1:], k) if with_mask else None
    return idx, vals > -jnp.inf, mask


@jax.named_scope("attn_select")
def select_mask(scores: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """select_topk's selection as a mask over the last axis: `valid`
    narrowed to its k highest scores, every valid position where there
    are no more than k."""
    if scores.shape[-1] <= k:
        return valid
    s = jnp.where(valid, scores, -jnp.inf)
    return _selected(s, valid, lax.top_k(s, k)[0][..., -1:], k)


def index_cache_write(cki: jax.Array, ki: jax.Array,
                      start: jax.Array) -> jax.Array:
    """Write index keys ki [B,T,Hi] into the contiguous cache's
    cki [B,S,Hi] at per-sequence offsets (update_cache_layer's twin);
    a latent-attention model's rows [B,T,latent_row] are written so too."""
    def upd(cache_b, new_b, start_b):
        return lax.dynamic_update_slice(cache_b, new_b, (start_b, 0))
    return jax.vmap(upd)(cki, ki.astype(cki.dtype), start)


def indexer_unsupported(cfg: ModelConfig, what: str) -> None:
    """Refuse a model with a sparse-attention indexer on a path that
    does not carry its third kind of cached row, the index keys, or
    that attends through a kernel which knows no selection."""
    if cfg.has_indexer:
        raise NotImplementedError(
            f"{what} does not carry the index keys of a sparse-attention "
            f"indexer (index_topk {cfg.index_topk}): not supported for "
            "this model")


@jax.named_scope("attn")
def attn_gate(h: jax.Array, p: Params, cfg: ModelConfig
              ) -> Optional[jax.Array]:
    """The attention's output gate (cfg.attn_gate): sigmoid(h W_g)
    [B,T,Nq,H] of the sublayer's normed input h [B,T,D], which
    attn_output multiplies onto the heads' output before the output
    projection; None for a model without. Every site that projects
    queries, keys and values from h takes the gate from the same h."""
    if not cfg.attn_gate:
        return None
    return jax.nn.sigmoid(qeinsum("btd,dnh->btnh", h, p["wg"], h.dtype))


def gate_unsupported(cfg: ModelConfig, what: str) -> None:
    """Refuse a model whose attention has an output gate, a norm on
    both sides of a sublayer, or leading dense layers before its experts
    (without latent attention, whose forwards run as layer runs
    everywhere), on a path with a layer body or a layer scan of its own
    that carries none of them."""
    if cfg.attn_gate or cfg.sandwich_norm \
            or (cfg.first_k_dense and not cfg.is_latent):
        raise NotImplementedError(
            f"{what} has a layer body of its own over ONE stack of layers, "
            "which carries neither an attention output gate (attn_gate) "
            "nor a norm behind a sublayer (sandwich_norm) nor "
            "feed-forwards of two shapes (first_k_dense): not supported "
            "for this model")


@jax.named_scope("attn")
def attn_output(out: jax.Array, p: Params, cfg: ModelConfig,
                gate: Optional[jax.Array] = None) -> jax.Array:
    """Output projection of the attention sublayer. out: [B,T,Nq,H];
    for a latent model [B,T,Nq,v_head_dim], or the ABSORBED read's o'
    [B,T,Nq,kv_lora_rank], the weighted sum of latents, which each
    head's value expansion W_uv takes to its v_head_dim first. gate:
    attn_gate's, multiplied on before the projection."""
    if cfg.is_latent and out.shape[-1] == cfg.kv_lora_rank:
        with jax.named_scope("attn_latent_expand"):
            out = qeinsum("btnr,rnh->btnh", out, p["w_uv"], out.dtype)
    if gate is not None:
        out = out * gate.astype(out.dtype)
    out = qeinsum("btnh,nhd->btd", out, p["wo"], out.dtype)
    if cfg.use_bias:
        out = out + p["bo"]
    return out


# ---------------------------------------------------------------------------
# Latent attention (MLA; cfg.kv_lora_rank and the split head dims)
#
#   c_q = norm_q(h W_dq);  [q_nope | q_rope] = c_q W_uq      a head: nope | rope
#   [c_kv | k_r] = h W_dkv;  c_kv = norm_kv(c_kv);  k_r ONE rotary key a token
#   q_rope, k_r rotated.  What a token CACHES: [c_kv | k_r], one row
#
# EXPANDED (no cached context is read): k_nope,h = c_kv W_uk,h and v_h =
# c_kv W_uv,h a head, score = (q_nope . k_nope + q_rope . k_r) x scale.
# ABSORBED (cached rows are read): q'_h = q_nope,h W_uk,h^T, score =
# ([q'_h | q_rope,h] . [c_kv | k_r]) x scale: multi-query attention of
# Nq heads over ONE shared row, whose first kv_lora_rank values are also
# its "values": o'_h = sum p c_kv, and attn_output expands o'_h W_uv,h.
# The same numbers; no per-head key or value of the context exists in
# the absorbed form, and the row is read once. scale = (nope + rope) **
# -0.5 both ways (times m^2 under a "yarn" rotation: cfg.attn_scale).
# latent_proj, latent_queries and the two attends below
# are the ONE definition that the contiguous path and the paged path
# (cache/paged.py latent_paged_attend) share.
# ---------------------------------------------------------------------------

def latent_unsupported(cfg: ModelConfig, what: str) -> None:
    """Refuse a latent-attention model on a path that does not carry
    its cached row: one latent a token, no heads (nothing to shard by
    head or to quantize a head at a time) and no separate values."""
    if cfg.is_latent:
        raise NotImplementedError(
            f"{what} does not carry the cached latent row of a "
            f"latent-attention model (kv_lora_rank {cfg.kv_lora_rank}: one "
            f"row of {cfg.latent_row} a token, no heads, no values): not "
            "supported for this model")


@jax.named_scope("attn_latent_proj")
def latent_proj(h: jax.Array, p: Params, cfg: ModelConfig, cos, sin):
    """The projections of the normed input h [B,T,D]: (q_nope
    [B,T,Nq,nope], q_rope [B,T,Nq,rope] rotated, row [B,T,latent_row] =
    [norm_kv(c_kv) | rotated k_r], what the token caches, c_q
    [B,T,q_lora_rank] the normed query latent, which an indexer's
    queries read: index_proj)."""
    dt = h.dtype
    rotate = apply_rope_pairs if cfg.rope_interleave else apply_rope
    cq = rms_norm(qeinsum("btd,dr->btr", h, p["w_dq"], dt),
                  p["q_norm"]["scale"], cfg.norm_eps)
    q = qeinsum("btr,rnh->btnh", cq, p["w_uq"], dt)
    q_nope, q_rope = q[..., :cfg.qk_nope_head_dim], \
        q[..., cfg.qk_nope_head_dim:]
    ckv = qeinsum("btd,dr->btr", h, p["w_dkv"], dt)
    c = rms_norm(ckv[..., :cfg.kv_lora_rank], p["kv_norm"]["scale"],
                 cfg.norm_eps)
    k_r = rotate(ckv[..., None, cfg.kv_lora_rank:], cos, sin)[:, :, 0]
    return q_nope, rotate(q_rope, cos, sin), \
        jnp.concatenate([c, k_r], axis=-1), cq


@jax.named_scope("attn_latent_proj")
def latent_queries(q_nope: jax.Array, q_rope: jax.Array, p: Params,
                   cfg: ModelConfig) -> jax.Array:
    """The ABSORBED queries [B,T,Nq,latent_row]: a head's q_nope taken
    through its key expansion W_uk,h^T onto the latent, beside its
    rotary part. W_uk's int8 scale runs along the dim contracted here,
    so the leaf is dequantized first (2 M values a layer)."""
    w_uk = maybe_dequant(p["w_uk"], q_nope.dtype)          # [R, Nq, nope]
    return jnp.concatenate(
        [jnp.einsum("btnh,rnh->btnr", q_nope, w_uk), q_rope], axis=-1)


@jax.named_scope("attn_latent")
def latent_attend(q: jax.Array, rows: jax.Array, mask: jax.Array,
                  cfg: ModelConfig) -> jax.Array:
    """The absorbed read: q [B,T,Nq,latent_row] (latent_queries) against
    cached rows [B,S,latent_row], mask [B,T,S]. Returns o'
    [B,T,Nq,kv_lora_rank]. Softmax in float32, as attend's."""
    scale = cfg.attn_scale
    s = jnp.einsum("btnr,bsr->bnts", q, rows,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bnts,bsr->btnr", p, rows[..., :cfg.kv_lora_rank])


@jax.named_scope("attn_latent")
def latent_attend_expanded(q_nope, q_rope, rows, mask, p: Params,
                           cfg: ModelConfig) -> jax.Array:
    """The expanded form over the call's OWN rows [B,T,latent_row] (a
    prefill that reads no cached context): every head's keys and values
    are materialised from the latents. Returns [B,T,Nq,v_head_dim]."""
    dt = q_nope.dtype
    c, k_r = rows[..., :cfg.kv_lora_rank], rows[..., cfg.kv_lora_rank:]
    k_nope = qeinsum("bsr,rnh->bsnh", c, p["w_uk"], dt)
    v = qeinsum("bsr,rnh->bsnh", c, p["w_uv"], dt)
    scale = cfg.attn_scale
    s = (jnp.einsum("btnh,bsnh->bnts", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("btnh,bsh->bnts", q_rope, k_r,
                      preferred_element_type=jnp.float32)) * scale
    s = jnp.where(mask[:, None], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1).astype(dt)
    return jnp.einsum("bnts,bsnh->btnh", pr, v)


def attention_block(x: jax.Array, p: Params, cfg: ModelConfig,
                    ck: jax.Array, cv: jax.Array,
                    positions: jax.Array, mask: jax.Array,
                    cos: jax.Array, sin: jax.Array,
                    fresh: bool = False,
                    k_s: Optional[jax.Array] = None,
                    v_s: Optional[jax.Array] = None,
                    pattern: Optional[Params] = None,
                    index=None, cki: Optional[jax.Array] = None):
    """One attention sublayer with contiguous-cache update.

    x: [B,T,D]; ck/cv: [B,S,Kv,H]; positions: [B,T]; mask: [B,T,S].
    `fresh` (static) asserts positions start at 0 and nothing LIVE
    precedes this call's tokens — the flash path then attends only over
    the freshly projected K/V. The cache buffers may still hold stale
    bytes from a recycled pool (engine cache reuse): correctness must
    come from position masking and overwrite-before-attend, never from
    assuming zeroed buffers. Warm multi-token calls (chunked prefill /
    continuation / prefix-hit resume) take the kernel too under
    cfg.attn_impl == "flash" (ISSUE 13): the cache rides in as the
    kernel's cached-prefix segment, count-masked per row at `start`, so
    warm prefill stops paying the dense O(T*S) fallback; dense attend
    stays as the non-flash path and the parity reference.

    int8 cache: pass codes ck/cv [B,Kv,S,H] + scales k_s/v_s [B,Kv,S];
    the return gains the updated scales — (out, ck, cv, k_s, v_s)
    instead of (out, ck, cv).

    ck is None (requires `fresh`): NO-CACHE mode for the fresh-prefill
    fast path (_fresh_prefill_forward) — nothing is written, attention
    runs over the just-projected K/V (flash, or a dense causal fallback
    over the same values), and the raw k/v come back so the caller can
    write the pools itself: returns (out, k, v).

    pattern: this layer's entry of cfg.layer_pattern() (layer_stack),
    traced scalars: whether it rotates, and its sliding window, which
    narrows `mask` and rides into the flash kernels.

    index, cki (a model with an indexer; never with ck None): index_proj's
    (qI, kI, w) of this layer and its index keys cki [B,S,Hi]. The new
    index keys are written first, the mask is narrowed to each query's
    selection (select_mask: the reference's arithmetic, dense) and the
    return gains the updated cki as its last value.
    """
    rope, sw = layer_pattern_of(pattern)
    q, k, v = qkv_proj(x, p, cfg, cos, sin, rope)
    gate = attn_gate(x, p, cfg)
    mask = layer_mask(mask, positions, sw)
    if index is not None:
        qi, ki, w = index
        cki = index_cache_write(cki, ki, positions[:, 0])
        mask = select_mask(index_scores(qi, w, cki), mask, cfg.index_topk)
    if ck is None:
        assert fresh, "no-cache attention_block is fresh-prefill only"
        out = None
        if cfg.attn_impl == "flash" and x.shape[1] > 1:
            out = flash_attention_sharded(q, k, v, causal=True,
                                          sliding_window=sw)
            if out is None:
                note_kernel("dense_fallback")
        if out is None:
            out = attend(q, k, v, mask, cfg)
        return attn_output(out, p, cfg, gate), k, v
    start = positions[:, 0]  # write offset per sequence
    if k_s is not None:  # int8 cache: write codes + scales
        ck, cv, k_s, v_s = update_cache_layer_q(ck, cv, k_s, v_s, k, v,
                                                start)
    else:
        ck, cv = update_cache_layer(ck, cv, k, v, start)
    out = None
    if cfg.attn_impl == "flash" and x.shape[1] > 1 and index is None:
        # None = no mesh axis can shard the kernel operands; use dense.
        # (Fresh prefill attends over the just-projected bf16 K/V, so the
        # kernel path is identical for int8 caches.)
        if fresh:
            out = flash_attention_sharded(q, k, v, causal=True,
                                          sliding_window=sw)
        else:
            # warm chunk (ISSUE 13): the kernel attends the cache as a
            # prefix segment count-masked at `start` (the chunk's own
            # just-written copy sits at >= start, excluded) plus the
            # fresh chunk. int8 caches mirror the written representation
            # for the chunk itself — quantize-dequantize the fresh K/V —
            # so the operand set is element-wise identical to what the
            # dense path reads back, the byte-parity argument.
            kf, vf = k, v
            if k_s is not None:
                kq, ksc = quantize_kv(k)
                vq, vsc = quantize_kv(v)
                kf = (kq.astype(jnp.float32)
                      * ksc[..., None]).astype(k.dtype)
                vf = (vq.astype(jnp.float32)
                      * vsc[..., None]).astype(v.dtype)
            out = flash_attention_sharded(
                q, kf, vf, causal=True, prefix_k=ck, prefix_v=cv,
                prefix_len=start, prefix_k_scale=k_s, prefix_v_scale=v_s,
                sliding_window=sw)
        if out is None:
            note_kernel("dense_fallback")
    if out is None:
        out = attend(q, ck, cv, mask, cfg, k_s, v_s)
    tail = () if index is None else (cki,)
    if k_s is not None:
        return (attn_output(out, p, cfg, gate), ck, cv, k_s, v_s, *tail)
    return (attn_output(out, p, cfg, gate), ck, cv, *tail)


def mlp_block(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    act = ACTIVATIONS[cfg.act]
    dt = x.dtype
    if cfg.arch == "gpt2":
        h = qeinsum("btd,df->btf", x, p["w_up"], dt)
        h = act(h + p["b_up"])
        out = qeinsum("btf,fd->btd", h, p["w_down"], dt)
        return out + p["b_down"]
    # llama-style gated SwiGLU
    g = qeinsum("btd,df->btf", x, p["w_gate"], dt)
    u = qeinsum("btd,df->btf", x, p["w_up"], dt)
    h = act(g) * u
    return qeinsum("btf,fd->btd", h, p["w_down"], dt)


@jax.named_scope("moe_route")
def router_logits(x: jax.Array, router_w: jax.Array) -> jax.Array:
    """Router logits [B,T,E] of the router's input x [B,T,D], in
    float32 at full precision. The choice of experts is discrete: a
    logit that is rounded to bfloat16 (its product's output, before a
    cast back up) sits within an ulp of its neighbour at every near-tie
    and picks another expert than the float32 model would, a whole
    expert's output wrong for a rounding. The product is [rows, D] x
    [D, E]: a thousandth of the expert products behind it."""
    return jnp.einsum("btd,de->bte", x.astype(jnp.float32),
                      router_w.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)


def _route_choice(logits: jax.Array, k: int, score: str, bias):
    """(what the router scores an expert, the k chosen [.., k] int32,
    their scores): the logits themselves, or (score "sigmoid") their
    sigmoid, the choice then by score + bias (a stored correction an
    expert, None for none) and the scores of the chosen WITHOUT it.
    lax.top_k takes equal scores by index, the lower first."""
    if score == "softmax":
        vals, idx = lax.top_k(logits, k)
        return idx, vals
    s = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(s if bias is None else s + bias.astype(s.dtype), k)
    return idx, jnp.take_along_axis(s, idx, axis=-1)


@jax.named_scope("moe_route")
def route_tokens(x: jax.Array, router_w: jax.Array, k: int,
                 logits: Optional[jax.Array] = None, *,
                 score: str = "softmax", bias: Optional[jax.Array] = None,
                 scale: float = 0.0) -> Tuple[jax.Array, jax.Array]:
    """Top-k MoE routing: f32 logits -> (gates [.., k], expert idx [.., k]).

    Softmax is over the SELECTED k (Mixtral convention; a softmax over
    all experts, its top k renormalised, is the same numbers). The single
    definition shared by the dense block and both EP dispatch paths —
    their exact-parity contract depends on byte-identical routing.
    `logits`: router logits taken elsewhere (cfg.router_input "attn":
    from the attention's normed input), which x is then not read for.

    score "sigmoid" (cfg.router_score; DeepSeek-V3's noaux_tc with one
    group): s = sigmoid(logits); the k are chosen by s + `bias` [E]
    (cfg.router_bias: it moves the CHOICE and never a weight); a
    chosen expert's gate is its s over the sum of the chosen s, times
    `scale` (cfg.routed_scaling_factor; 0 = none).
    """
    if logits is None:
        logits = router_logits(x, router_w)
    idx, vals = _route_choice(logits, k, score, bias)
    if score == "softmax":
        return jax.nn.softmax(vals, axis=-1), idx
    gates = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    return (gates * scale if scale else gates), idx


@jax.named_scope("moe_route")
def expert_load(logits: jax.Array, k: int, ok: jax.Array,
                score: str = "softmax",
                bias: Optional[jax.Array] = None,
                held: Optional[Tuple[int, int]] = None) -> jax.Array:
    """What one layer's routing asks of its experts in one step, f32 [3]:
    how many DISTINCT experts the rows marked `ok` touch (the experts a
    dispatch that skips unrouted ones would still stream), the rows of
    the fullest expert, and the mean rows of an expert (ok rows x k /
    E). logits [B,T,E] as route_tokens takes them, ok [B,T] bool;
    score and bias as route_tokens'.

    held (first, count): the chip's share of the experts
    (cfg.experts_held). The three then count over the experts HELD, and
    two values follow: of the step's ok rows x k assignments those that
    fell on a held expert, and all of them."""
    E = logits.shape[-1]
    idx, _ = _route_choice(logits, k, score, bias)
    rows = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                   * ok[..., None, None], axis=(0, 1, 2))           # [E]
    if held is None:
        return jnp.stack([jnp.sum(rows > 0), jnp.max(rows),
                          jnp.sum(rows) / E])
    mine = rows[held[0]:held[0] + held[1]]
    return jnp.stack([jnp.sum(mine > 0), jnp.max(mine),
                      jnp.sum(mine) / held[1], jnp.sum(mine),
                      jnp.sum(rows)])


def expert_gates(x: jax.Array, p: Params, cfg: ModelConfig,
                 logits: Optional[jax.Array] = None) -> jax.Array:
    """comb [B,T,E held]: a row's gate on each expert the leaves hold
    (route_tokens' k weights scattered over the experts; zero where the
    row did not choose it), float32. Under cfg.experts_held the columns
    of the experts experts_first .. + experts_held alone, the gates what
    the whole layer would give them. `logits`: as route_tokens'."""
    weights, idx = route_tokens(
        x, p["router"], cfg.num_experts_per_tok, logits,
        score=cfg.router_score, bias=p.get("router_bias"),
        scale=cfg.routed_scaling_factor)
    onehot = jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32)  # [B,T,k,E]
    comb = jnp.einsum("btk,btke->bte", weights, onehot)  # [B,T,E]
    if cfg.experts_held:
        comb = comb[..., cfg.experts_first:
                    cfg.experts_first + cfg.experts_held]
    return comb


def dense_experts(x: jax.Array, comb: jax.Array, p: Params,
                  act) -> jax.Array:
    """sum_e comb[.., e] * expert_e(x) with every expert's products over
    every row: x [B,T,D], comb [B,T,E] (expert_gates), p one layer's
    w_gate / w_up [E,D,F] and w_down [E,F,D]."""
    dt = x.dtype
    # The experts' codes stand FIRST in the gate and up products: the
    # TPU compiler then reads them as they are stored. Handed the rows
    # first, at 128, 256 or 384 rows (whole tiles) it wants the codes
    # contraction-minor and hoists a relayout of the WHOLE layer-stacked
    # tensor out of the layer scan (2.1 GB each for gate and up of 72
    # experts in ten layers: a decode block of 128 slots did not fit the
    # chip). At 32 and 64 rows the two orders run alike (PERF.md, PR 41:
    # measured in both older cells of experts), so there is one order.
    g = qeinsum("edf,btd->ebtf", p["w_gate"], x, dt)
    u = qeinsum("edf,btd->ebtf", p["w_up"], x, dt)
    h = act(g) * u
    y = qeinsum("ebtf,efd->ebtd", h, p["w_down"], dt)
    return jnp.einsum("ebtd,bte->btd", y, comb.astype(y.dtype))


@jax.named_scope("moe_experts")
def moe_block(x: jax.Array, p: Params, cfg: ModelConfig,
              logits: Optional[jax.Array] = None,
              ok: Optional[jax.Array] = None) -> jax.Array:
    """Dense-compute MoE (every expert sees every token, masked by router).

    The expert-parallel all_to_all path lives in parallel/expert.py; this
    dense form is the single-device reference and the EP fallback.
    `logits`: as route_tokens'.

    cfg.experts_held (one chip's share of a deployment's experts): the
    router ranges over all cfg.num_experts and the gates are what the
    whole layer would give them; the leaves hold the experts
    experts_first .. + experts_held alone and the result is THEIR part
    of the sum. What the absent experts would add is left out.

    Where p holds "layer" (experts_in_place left the experts' leaves
    WHOLE, layer-stacked, and this layer's index beside them) the same
    sum comes from ops/moe_experts.py, which reads the experts some real
    row chose and no other; ok [B,T]: the rows that are real (None:
    all), whose gates alone count there.
    """
    B, T, D = x.shape
    comb = expert_gates(x, p, cfg, logits)
    act = ACTIVATIONS[cfg.act]
    if "layer" not in p:
        return dense_experts(x, comb, p, act)
    if ok is not None:
        comb = jnp.where(ok[..., None], comb, 0.0)
    out = moe_kernel.moe_experts(x.reshape(B * T, D),
                                 comb.reshape(B * T, -1), p, p["layer"], act)
    return out.astype(x.dtype).reshape(B, T, D)


def experts_in_place(stack: Params, rows: int, cfg: ModelConfig,
                     use_kernel: bool):
    """A layer-stacked tree for a loop over its layers whose steps have
    `rows` rows: (what the loop slices a layer at a time, held). Where
    the step takes ops/moe_experts.py (its `takes`: kernels on, no mesh,
    int8 codes, few rows, experts that even routing leaves untouched)
    the experts' codes and scales leave the tree and are `held`: a slice
    of them handed to a custom call is a COPY of one layer's codes, so
    they stay whole and the loop's body lays them into its layer's slice
    with the layer's index (layer_experts). Anywhere else the tree as it
    is, and None."""
    moe = stack.get("moe")
    if moe is None or not cfg.routed or cfg.moe_impl == "ep" \
            or not moe_kernel.takes(rows, moe, cfg.num_experts_per_tok,
                                    cfg.num_experts, use_kernel):
        return stack, None
    held = {n: moe[n] for n in moe_kernel.LEAVES}
    return {**stack, "moe": {k: v for k, v in moe.items()
                             if k not in held}}, held


def layer_experts(lp: Params, held: Optional[Params], i) -> Params:
    """Layer i's slice `lp` of a tree experts_in_place cut, with the
    experts it held back laid in WHOLE and i (traced) beside them under
    "layer", which is how moe_block knows; lp itself where none were."""
    if held is None:
        return lp
    return {**lp, "moe": {**lp["moe"], **held, "layer": i}}


def pre_norm(x: jax.Array, norm_p: Params, cfg: ModelConfig) -> jax.Array:
    """The arch's norm (LayerNorm for gpt2, RMSNorm otherwise)."""
    if cfg.arch == "gpt2":
        return layer_norm(x, norm_p["scale"], norm_p["bias"], cfg.norm_eps)
    return rms_norm(x, norm_p["scale"], cfg.norm_eps)


def early_router_logits(x: jax.Array, lp: Params,
                        cfg: ModelConfig) -> Optional[jax.Array]:
    """The layer's router logits where the router stands BEFORE
    attention (cfg.router_input "attn"): read from the attention's
    normed input and carried across attention to ffn_block. x is the
    layer's INPUT: the norm is taken again in float32 for the router
    (router_logits says why; the attention reads the compute dtype's).
    None where the router reads the feed-forward's own input."""
    if cfg.routed and cfg.router_input == "attn":
        h = pre_norm(x.astype(jnp.float32), lp["ln1"], cfg)
        return router_logits(h, lp["moe"]["router"])
    return None


@jax.named_scope("mlp")
def ffn_block(h: jax.Array, lp: Params, cfg: ModelConfig,
              logits: Optional[jax.Array] = None,
              ok: Optional[jax.Array] = None) -> jax.Array:
    """FFN dispatch shared by every forward variant (contiguous, paged,
    pipeline, sequence-parallel): dense MLP, dense MoE, or EP MoE per
    cfg — one definition so the variants can't drift. `logits`:
    early_router_logits' of this layer, where the model has them; ok:
    moe_block's."""
    if cfg.routed and "moe" in lp:   # not a leading dense layer's (first_k_dense)
        if cfg.moe_impl == "ep":   # no early logits here: ModelConfig refuses
            from butterfly_tpu.parallel.expert import moe_block_ep
            out = moe_block_ep(h, lp["moe"], cfg)
        else:
            out = moe_block(h, lp["moe"], cfg, logits, ok)
        if cfg.shared_intermediate_size:
            # one shared expert, every token, added unweighted
            with jax.named_scope("moe_shared"):
                out = out + mlp_block(h, lp["shared"], cfg)
        return out
    return mlp_block(h, lp["mlp"], cfg)


# ---------------------------------------------------------------------------
# The residual path: ONE pair, stream_read before a sublayer and
# stream_write behind it, at every site that runs a sublayer (the
# contiguous forwards here, cache/paged.py, cache/ssm_state.py).
#
# cfg.hc_mult 0, every family but one: the carry of a layer scan is one
# stream x [B,T,D]; a sublayer reads norm(x) and writes x + y (times
# Granite's residual multiplier).
#
# cfg.hc_mult n > 0 (manifold-constrained hyper-connections,
# arXiv:2512.24880 over arXiv:2409.19606): the carry is n streams X
# [n,B,T,D] (embed_tokens copies the embedding n times, stream_fold sums
# them before the final norm). Each sublayer has leaves phi [nD, n+n+n^2],
# b [n+n+n^2], alpha [3] (lp["hc1"] the mixer's, lp["hc2"] the
# feed-forward's) and, a token, in float32:
#
#   v      = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)     over all n D values
#   pre~, post~, res~ = alpha[0|1|2] * (v @ phi[:, 0:n | n:2n | 2n:]) + b[..]
#   H_pre  = sigmoid(pre~) [n];   H_post = 2 sigmoid(post~) [n]
#   H_res  = M after hc_sinkhorn_iters rounds of { M /= colsum(M) + hc_eps;
#            M /= rowsum(M) + hc_eps },  M0 = exp(clip(res~ [n,n]))
#   h      = sum_i H_pre[i] X[i]            what the sublayer sees
#   X'     = H_res @ X + outer(H_post, F(norm(h)))
#
# The mixing is float32 whatever cfg.dtype is (_MIX): H_res multiplies
# every stream in every sublayer, and the streams are the model's whole
# memory of a token; only the carried streams are cfg.dtype, as the one
# stream is. The coefficients are held rows-minor ([n,n,R], [n,R]: R the
# B*T rows): the 20 rounds over a 4 x 4 are then elementwise over whole
# vectors of rows, not reductions inside a register.
# ---------------------------------------------------------------------------

_MIX = jnp.float32


def streams_unsupported(cfg: ModelConfig, what: str) -> None:
    """Refuse a model whose residual path is n streams on a path whose
    layer bodies add a sublayer's output to ONE stream themselves."""
    if cfg.hc_mult:
        raise NotImplementedError(
            f"{what} carries one residual stream [rows, D] between its "
            f"layers, not the {cfg.hc_mult} mixed by hyper-connections "
            "(hc_mult): not supported for this model")


def stream_read(x: jax.Array, lp: Params, sub: int, cfg: ModelConfig):
    """What sublayer `sub` of a layer (1 the mixer, 2 the feed-forward)
    reads of the residual path x, under the sublayer's own pre-norm
    lp["ln<sub>"], and what stream_write takes to put its output back:
    (h [B,T,D], mix). hc_mult 0: (norm(x), None); under cfg.post_norm
    (x, the norm's leaves): the norm waits for the sublayer's OUTPUT;
    under cfg.sandwich_norm (norm(x), the leaves of lp["ln<sub>_post"]):
    a norm before the sublayer and another on its output.
    Else x is [n,B,T,D]
    and mix = (H_res [n,n,R], H_post [n,R]) of lp["hc<sub>"] (the
    equations above)."""
    norm = lp[f"ln{sub}"]
    if cfg.post_norm:
        # the sublayer reads x as it is; stream_write norms its output
        return x, norm
    if cfg.sandwich_norm:
        # a norm on both sides: the second's leaves go to stream_write
        return pre_norm(x, norm, cfg), lp[f"ln{sub}_post"]
    if not cfg.hc_mult:
        return pre_norm(x, norm, cfg), None
    with jax.named_scope("hc_mix"):
        n, eps = cfg.hc_mult, cfg.hc_eps
        hp = lp[f"hc{sub}"]
        xf = x.astype(_MIX)
        D = x.shape[-1]
        # v @ phi as (X . phi) / rms: the product is linear in v
        inv = lax.rsqrt(jnp.mean(xf * xf, axis=(0, -1)) + eps)    # [B,T]
        z = jnp.einsum("nbtd,ndk->kbt", xf,
                       hp["phi"].astype(_MIX).reshape(n, D, -1),
                       precision=lax.Precision.HIGHEST) * inv
        z = z.reshape(z.shape[0], -1)                            # [K, R]
        alpha = jnp.repeat(hp["alpha"].astype(_MIX), np.array((n, n, n * n)),
                           total_repeat_length=n * (2 + n))
        z = z * alpha[:, None] + hp["b"].astype(_MIX)[:, None]
        pre = jax.nn.sigmoid(z[:n])                              # [n, R]
        post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
        res = jnp.exp(jnp.clip(z[2 * n:], cfg.hc_clamp_min, cfg.hc_clamp_max))
        # H_res entry by entry, each a vector of rows, and every sum
        # written out: 20 rounds are then elementwise over [R] from end
        # to end, which XLA makes a few fusions of. As m [n, n, R] with
        # jnp.sum over an axis each round was 150 operations a sublayer
        # of a microsecond each on the chip (PERF.md, PR 49)
        m = [[res[i * n + j] for j in range(n)] for i in range(n)]
        for _ in range(cfg.hc_sinkhorn_iters):
            cols = [sum(m[i][j] for i in range(n)) + eps for j in range(n)]
            m = [[m[i][j] / cols[j] for j in range(n)] for i in range(n)]
            rows = [sum(m[i]) + eps for i in range(n)]
            m = [[m[i][j] / rows[i] for j in range(n)] for i in range(n)]
        col = x.shape[1:-1] + (1,)
        h = sum(pre[i].reshape(col) * xf[i] for i in range(n))
        return pre_norm(h.astype(x.dtype), norm, cfg), \
            (jnp.stack([jnp.stack(r) for r in m]), post)


def stream_write(x: jax.Array, y: jax.Array, mix, cfg: ModelConfig
                 ) -> jax.Array:
    """A sublayer's output y [B,T,D] onto the residual path x, with
    stream_read's mix. hc_mult 0 (mix None): x + y, the output first
    times a family's residual multiplier (Granite); under cfg.post_norm
    or cfg.sandwich_norm x + norm(y), mix the norm's leaves. Else X' = H_res @ X
    + outer(H_post, y) over the n streams, in float32."""
    if cfg.post_norm or cfg.sandwich_norm:
        y, mix = pre_norm(y, mix, cfg), None
    if mix is None:
        if cfg.residual_multiplier:
            y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
        return x + y
    with jax.named_scope("hc_mix"):
        m, post = mix
        n = cfg.hc_mult
        rows = x.shape[1:-1] + (1,)
        xf, yf = x.astype(_MIX), y.astype(_MIX)
        return jnp.stack([
            sum(m[i, j].reshape(rows) * xf[j] for j in range(n))
            + post[i].reshape(rows) * yf for i in range(n)]).astype(x.dtype)


def stream_fold(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The residual path behind the last layer as ONE stream [B,T,D]:
    the n streams summed (hyper-connections' own fold), x itself for
    hc_mult 0. Every forward calls it where its layer scans end, before
    it picks the rows the head reads."""
    if not cfg.hc_mult:
        return x
    return jnp.sum(x.astype(_MIX), axis=0).astype(x.dtype)


# ---------------------------------------------------------------------------
# Mamba-2 mixer (cfg.layer_types "mamba"): a layer whose memory of a
# stream is a FIXED-SIZE recurrent state, not rows that grow. (The
# other recurrent kinds, Gated DeltaNet and Mamba-1, follow it below.)
#
#   [z | xBC | dt] = in_proj(h)          widths Di | Dc | Nh, no bias
#   xBC = silu(conv(xBC))                causal, depthwise, K taps with
#                                        bias, over the current and the
#                                        K-1 previous positions
#   xBC -> x [Nh, Hd], B [G, N], C [G, N]
#   dt = softplus(dt + dt_bias);  A = -exp(A_log)        (a head)
#   H_t = exp(dt A) H_{t-1} + dt x_t (outer) B_t         [Hd, N] a head
#   y_t = H_t C_t + D x_t
#   out = out_proj(RMSNorm_Di(y * silu(z)) * w)          gate, then ONE norm
#
# What a stream keeps between calls is H [Nh, Hd, N] and the conv's last
# K-1 inputs [K-1, Dc], in cfg.dtype; a call's arithmetic is
# float32. The pieces below are composed into a layer in ONE place,
# cache/ssm_state.py advance_packed, which the packed serving step
# (cache/paged.py) and the contiguous path (forward) both run: a decode
# row is T == 1, a prefill chunk T == C of which the first `count`
# columns are real.
# ---------------------------------------------------------------------------

#: a recurrent layer kind (cfg.recurrent_kind) by the name of its mixer
RECURRENT_NAMES = {"mamba": "Mamba-2", "linear_attention": "Gated DeltaNet",
                   "mamba1": "Mamba-1"}


#: the top-level stack of params that holds a recurrent kind's mixers
RECURRENT_STACKS = {"mamba": "mamba", "linear_attention": "gdn",
                    "mamba1": "mamba1"}


def ssm_unsupported(cfg: ModelConfig, what: str) -> None:
    """Refuse a model with recurrent layers (Mamba-2, Gated DeltaNet or
    Mamba-1) on a path that does not carry a recurrent state a stream
    (it has no pages to hash, export, roll back or shard)."""
    if cfg.has_ssm:
        raise NotImplementedError(
            f"{what} does not carry the recurrent state of a model with "
            f"{RECURRENT_NAMES[cfg.recurrent_kind]} layers "
            f"({cfg.num_ssm_layers} of {cfg.num_layers}): "
            "not supported for this model")


def layer_runs(cfg: ModelConfig, by_window: bool = False):
    """The model's layers as runs of one kind, in the published order:
    [(kind, first layer, layers in the run, index of the first among the
    layers of ITS kind)]. Kinds have unlike parameter shapes, so one
    scan body cannot carry both: each run is one scan over its kind's
    stack. A model without layer_types is one run of attention. So have
    a dense feed-forward and a layer of experts (cfg.first_k_dense):
    a run ends where the leading dense layers do (ffn_run says which
    stack a run's feed-forward is in).

    by_window (a cache that keeps the sliding layers' rows apart:
    cache/paged.py): a run of attention also ends where sliding layers
    meet full ones (cfg.slides), since the two kinds read unlike pools,
    and each run comes as (kind, first, n, index among the attention
    layers, index among the layers of ITS window kind, slides)."""
    kinds = cfg.layer_types or ("attention",) * cfg.num_layers
    slides = cfg.slides if by_window else ()
    runs, seen = [], dict.fromkeys((*RECURRENT_STACKS, "attention"), 0)
    of_kind = [0, 0]
    for l, kind in enumerate(kinds):
        slide = bool(slides and slides[l])
        if runs and runs[-1][0] == kind and l != cfg.first_k_dense \
                and runs[-1][-1] == slide:
            runs[-1][2] += 1
        else:
            runs.append([kind, l, 1, seen[kind], of_kind[slide], slide])
        seen[kind] += 1
        of_kind[slide] += 1
    return [tuple(r if by_window else r[:4]) for r in runs]


def ffn_run(params: Params, first: int, cfg: ModelConfig):
    """(stack, first layer of the stack) of the feed-forward weights of
    the run that starts at layer `first` (layer_runs): params["dense"]
    for the leading dense layers of a model of experts
    (cfg.first_k_dense), params["sparse"] (experts, router, shared
    expert) for the layers behind them; None for a model whose
    feed-forwards are all alike and lie in params["layers"]."""
    if not cfg.first_k_dense:
        return None
    return (params["dense"], 0) if first < cfg.first_k_dense \
        else (params["sparse"], cfg.first_k_dense)


def run_layer_at(params: Params, ffn, l, cfg: ModelConfig,
                 held: Optional[Params] = None) -> Params:
    """Layer l (traced) of a model whose layers run as runs: what every
    layer has (params["layers"]) beside its run's feed-forward
    (ffn_run's) and, where the model's layers are unlike
    (cfg.layer_pattern), its pattern; held: the experts experts_in_place
    kept out of that run's stack, laid in whole at the layer's index in
    it (layer_experts)."""
    lp = layer_at(params["layers"], l, cfg)
    pattern = cfg.layer_pattern()
    if pattern is not None:
        # the layer's entry, as layer_stack hands a scan's body its slice
        lp = {**lp, "pattern": {k: jnp.asarray(v)[l]
                                for k, v in pattern.items()}}
    if ffn is None:
        return lp
    return layer_experts({**lp, **layer_at(ffn[0], l - ffn[1], cfg)}, held,
                         l - ffn[1])


def layer_at(stack: Params, i, cfg: ModelConfig) -> Params:
    """Layer i (a traced index) of a layer-stacked tree, in the compute
    dtype: the slice a scan's xs would hand its body, for a scan that
    rides the layer's index instead (two stacks of unlike length)."""
    return jax.tree.map(
        lambda a: _cast_float(lax.dynamic_index_in_dim(a, i, 0,
                                                       keepdims=False),
                              jnp.dtype(cfg.dtype)), stack)


@jax.named_scope("ssm_proj")
def ssm_in_proj(h: jax.Array, mp: Params, cfg: ModelConfig):
    """(z [B,T,Di], xBC [B,T,Dc], dt [B,T,Nh]) of the normed input."""
    zxd = qeinsum("btd,dp->btp", h, mp["in_proj"], h.dtype)
    Di, Dc = cfg.ssm_inner, cfg.ssm_conv_dim
    return zxd[..., :Di], zxd[..., Di:Di + Dc], zxd[..., Di + Dc:]


def _causal_conv(xbc: jax.Array, tail: jax.Array, mp: Params, count):
    """The causal depthwise conv over a row's stream: xbc [B,T,Dc] the
    call's inputs, tail [B,K-1,Dc] the K-1 before them, mp["conv_w"]
    [K,Dc] and, where the family has one, mp["conv_b"]. Returns
    (silu(conv) [B,T,Dc] float32, the tail after the row's first
    `count` [B] inputs: unchanged where count is 0)."""
    T = xbc.shape[1]
    w = mp["conv_w"].astype(jnp.float32)                     # [K, Dc]
    K = w.shape[0]
    full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    u = sum(full[:, k:k + T].astype(jnp.float32) * w[k] for k in range(K))
    if "conv_b" in mp:
        u = u + mp["conv_b"].astype(jnp.float32)
    at = count[:, None] + jnp.arange(K - 1)[None, :]         # [B, K-1]
    return jax.nn.silu(u), jnp.take_along_axis(full, at[:, :, None], axis=1)


ssm_conv = jax.named_scope("ssm_conv")(_causal_conv)


@jax.named_scope("conv_step")
def conv_step(planes: jax.Array, x: jax.Array, mp: Params, live: jax.Array):
    """_causal_conv at T == 1 over the tail AS THE STATE HOLDS IT, any
    recurrent kind: planes [K-1, S, Dc] one layer's tails (plane k the
    input k - (K-1) positions back, slots down the sublanes:
    cache/ssm_state.py), x [S, Dc] the rows' inputs, live [S] bool the
    rows that decode. The same float32 sum, term for term, with x the
    last plane; a live row's new plane k is the old plane k + 1 (the
    last one x), any other row keeps its tail. Elementwise over whole
    [S, Dc] planes: nothing is transposed, joined or gathered. Returns
    (silu(conv) [S, 1, Dc] float32, the K-1 new planes [S, Dc] in x's
    dtype, A LIST: stacked into one [K-1, S, Dc] value they were a
    concatenate whose layout XLA chose slots-major at granite's widths,
    a relayout of the layer's planes every layer-step; PERF.md, PR 64)."""
    w = mp["conv_w"].astype(jnp.float32)                     # [K, Dc]
    K = w.shape[0]
    full = [planes[k].astype(x.dtype) for k in range(K - 1)] + [x]
    u = sum(full[k].astype(jnp.float32) * w[k] for k in range(K))
    if "conv_b" in mp:
        u = u + mp["conv_b"].astype(jnp.float32)
    return jax.nn.silu(u)[:, None], [
        jnp.where(live[:, None], full[k + 1], full[k]) for k in range(K - 1)]


def ssm_step_inputs(u: jax.Array, dt: jax.Array, mp: Params,
                    cfg: ModelConfig, count):
    """What the recurrence reads of its positions, formed before any
    state is touched: u [B,T,Dc] float32 (ssm_conv), dt [B,T,Nh] as
    projected, count [B] the row's real positions. Returns (x
    [B,T,Nh,Hd], dA [B,T,Nh], dtx [B,T,Nh,Hd], B and C [B,T,G,N] by
    GROUP, real [B,T]), all float32 but `real`."""
    B, T = u.shape[:2]
    Nh, Hd, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, \
        cfg.ssm_groups
    Di = cfg.ssm_inner
    x = u[..., :Di].reshape(B, T, Nh, Hd)
    Bg = u[..., Di:Di + G * N].reshape(B, T, G, N)
    Cg = u[..., Di + G * N:].reshape(B, T, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + mp["dt_bias"].astype(jnp.float32))
    dA = jnp.exp(dt * -jnp.exp(mp["A_log"].astype(jnp.float32)))  # [B,T,Nh]
    dtx = dt[..., None] * x                                  # [B,T,Nh,Hd]
    real = jnp.arange(T)[None, :] < count[:, None]           # [B,T]
    return x, dA, dtx, Bg, Cg, real


def ssm_skip(y: jax.Array, x: jax.Array, mp: Params) -> jax.Array:
    """The readout y [B,T,Nh,Hd] plus the mixer's skip term D x."""
    return y + mp["D"].astype(jnp.float32)[:, None] * x


@jax.named_scope("ssm_scan")
def ssm_scan(u: jax.Array, dt: jax.Array, mp: Params, cfg: ModelConfig,
             state: jax.Array, count):
    """The selective scan: u [B,T,Dc] float32 (ssm_conv), dt [B,T,Nh]
    as projected, state [B,Nh,Hd,N] float32 BEFORE the call. A row's
    state advances through its first `count` [B] positions and no
    further (a chunk's filler columns, a dead decode row). Returns
    (y [B,T,Nh,Hd] float32, state after). T == 1 is the one-step
    recurrence of a decode row (ops/ssm_step.py is the same step as one
    pass over the stored state); longer rows scan their positions."""
    T = u.shape[1]
    rep = cfg.ssm_heads // cfg.ssm_groups
    x, dA, dtx, Bg, Cg, real = ssm_step_inputs(u, dt, mp, cfg, count)
    Bm = jnp.repeat(Bg, rep, axis=2)                         # [B,T,Nh,N]
    Cm = jnp.repeat(Cg, rep, axis=2)

    def step(h, t):
        dA_t, dtx_t, B_t, C_t, real_t = t
        new = dA_t[:, :, None, None] * h \
            + dtx_t[..., None] * B_t[:, :, None, :]
        h = jnp.where(real_t[:, None, None, None], new, h)
        return h, jnp.sum(h * C_t[:, :, None, :], axis=-1)   # [B,Nh,Hd]

    if T == 1:
        state, y = step(state, (dA[:, 0], dtx[:, 0], Bm[:, 0], Cm[:, 0],
                                real[:, 0]))
        y = y[:, None]
    else:
        state, y = lax.scan(step, state, tuple(
            jnp.moveaxis(a, 1, 0) for a in (dA, dtx, Bm, Cm, real)))
        y = jnp.moveaxis(y, 0, 1)
    return ssm_skip(y, x, mp), state


@jax.named_scope("ssm_gate")
def ssm_gate_out(y: jax.Array, z: jax.Array, mp: Params,
                 cfg: ModelConfig) -> jax.Array:
    """y [B,T,Nh,Hd] float32 gated by silu(z), ONE RMSNorm over all Di
    (one group) with a learned weight, then the out-projection."""
    B, T = y.shape[:2]
    g = y.reshape(B, T, -1) * jax.nn.silu(z.astype(jnp.float32))
    g = rms_norm(g, mp["norm"]["scale"], cfg.norm_eps).astype(z.dtype)
    return qeinsum("bti,id->btd", g, mp["out_proj"], z.dtype)


# ---------------------------------------------------------------------------
# Gated DeltaNet mixer (cfg.layer_types "linear_attention";
# arXiv:2412.06464): a layer whose memory of a stream is ONE MATRIX a
# head, rewritten by the delta rule. H heads, keys of dk, values of dv:
#
#   [q | k | v | z] = in_proj(x)         widths H dk | H dk | H dv | H dv
#   [a | b] = ab_proj(x)                 H | H, no bias anywhere
#   [q | k | v] = silu(conv([q | k | v]))  causal, depthwise, K taps, no
#                                        bias: ONE conv over 2 H dk + H dv
#   q = q / |q| * dk^-0.5;  k = k / |k|  a head at a time (|.|^2 + 1e-6)
#   beta = sigmoid(b) (x 2 under gdn_neg_eigval)
#   alpha = exp(-exp(A_log) softplus(a + dt_bias))       (a head)
#   S' = alpha S;  u = beta (v - S' k);  S = S' + u k^T;  o = S q
#   out = out_proj(RMSNorm_dv(o) * w * silu(z))   norm a head, THEN gate
#
# S [dv, dk] a head, zero at position 0. The delta rule READS what the
# state holds for the incoming key and writes the difference, where
# Mamba-2's state is decayed and added to. What a stream keeps between
# calls is S and the conv's last K-1 inputs, in cfg.dtype; a call's
# arithmetic is float32. The pieces are composed into a layer in ONE
# place, cache/ssm_state.py advance_packed, beside the Mamba-2 pieces:
# a decode row is gdn_step over the state where it lies, a chunk
# gdn_chunk (the chunkwise form: a chunk's positions at once).
# ---------------------------------------------------------------------------

#: positions gdn_chunk solves at once; longer rows scan such pieces
GDN_CHUNK = 32


@jax.named_scope("gdn_proj")
def gdn_in_proj(h: jax.Array, gp: Params, cfg: ModelConfig):
    """(qkv [B,T,Dc], z [B,T,H dv], a [B,T,H], b [B,T,H]) of the
    mixer's input."""
    qkvz = qeinsum("btd,dp->btp", h, gp["in_proj"], h.dtype)
    ab = qeinsum("btd,dp->btp", h, gp["ab_proj"], h.dtype)
    Dc, H = cfg.gdn_conv_dim, cfg.gdn_heads
    return qkvz[..., :Dc], qkvz[..., Dc:], ab[..., :H], ab[..., H:]


gdn_conv = jax.named_scope("gdn_conv")(_causal_conv)


def gdn_step_inputs(u: jax.Array, a: jax.Array, b: jax.Array, gp: Params,
                    cfg: ModelConfig, count):
    """What the recurrence reads of its positions, formed before any
    state is touched: u [B,T,Dc] float32 (gdn_conv), a and b [B,T,H] as
    projected, count [B] the row's real positions. Returns (q [B,T,H,dk]
    normalised and scaled, k [B,T,H,dk] normalised, v [B,T,H dv] FLAT,
    log_alpha [B,T,H], beta [B,T,H]), float32. A position that is not
    real has log_alpha 0 and beta 0: it leaves a state as it is, bit
    for bit."""
    B, T = u.shape[:2]
    H, dk, Dk = cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_key_width

    def unit(x):
        x = x.reshape(B, T, H, dk)
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q, k, v = unit(u[..., :Dk]) * dk ** -0.5, unit(u[..., Dk:2 * Dk]), \
        u[..., 2 * Dk:]
    real = jnp.arange(T)[None, :, None] < count[:, None, None]  # [B,T,1]
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    if cfg.gdn_neg_eigval:
        beta = 2.0 * beta
    dt = jax.nn.softplus(a.astype(jnp.float32)
                         + gp["dt_bias"].astype(jnp.float32))
    log_alpha = -jnp.exp(gp["A_log"].astype(jnp.float32)) * dt
    return q, k, v, jnp.where(real, log_alpha, 0.0), \
        jnp.where(real, beta, 0.0)


def head_lanes(a: jax.Array, dv: int) -> jax.Array:
    """a [..., g], a number a head of a group -> [..., g dv]: head i's
    over its own dv lanes. A chain of selects over a broadcast: no
    reshape of the lanes, which the device would copy where dv is no
    whole number of 128."""
    g = a.shape[-1]
    head = np.arange(g * dv) // dv      # a constant of the program
    out = jnp.broadcast_to(a[..., :1], a.shape[:-1] + (g * dv,))
    for i in range(1, g):
        out = jnp.where(head == i, a[..., i:i + 1], out)
    return out


@jax.named_scope("gdn_step")
def gdn_step(h: jax.Array, m, q: jax.Array, k: jax.Array, v: jax.Array,
             log_alpha: jax.Array, beta: jax.Array, cfg: ModelConfig):
    """One position of the delta rule for every slot of layer m, over
    the state where it lies: h [Ls, S, H/g, dk, g dv] the WHOLE carried
    state (cache/ssm_state.py has the layout: g heads' values share a
    row of lanes), q and k [S,H,dk], v [S, H dv] flat, log_alpha and
    beta [S,H] (gdn_step_inputs at T == 1). One reduction over layer
    m's state gives alpha S k and alpha S q at once, the readout is
    S q = alpha S q + u (k . q), and the update alpha S + u k^T is
    written in place at the layer's index: the state is read twice and
    written once, and no value of its size is formed beside it. A decode
    row takes this step where the engine's kernels are off (the CPU) or
    the state is not whole tiles; elsewhere it takes ops/gdn_step.py,
    the same step as one pass, which is tested against this one
    (cache/ssm_state.py _DeltaNet.decode chooses).
    Returns (o [S, H dv] float32 flat, h)."""
    S, H, dk = k.shape
    g, dv = cfg.gdn_head_group, cfg.gdn_value_dim
    J = H // g

    def lanes(a):               # [S,H,X] -> [S,J,X,g dv]
        return head_lanes(jnp.moveaxis(a.reshape(S, J, g, -1), 2, -1), dv)

    st = lax.dynamic_index_in_dim(h, m, 0, keepdims=False) \
        .astype(jnp.float32)                               # [S,J,dk,g dv]
    kx, qx = lanes(k), lanes(q)
    alpha = lanes(jnp.exp(log_alpha)[..., None])           # [S,J,1,g dv]
    bt = lanes(beta[..., None])[:, :, 0]                   # [S,J,g dv]
    kq = lanes(jnp.sum(k * q, axis=-1, keepdims=True))[:, :, 0]
    r = alpha[:, :, 0] * jnp.sum(st * kx, axis=2)          # alpha S k
    p = alpha[:, :, 0] * jnp.sum(st * qx, axis=2)          # alpha S q
    u = bt * (v.reshape(S, J, g * dv) - r)
    new = alpha * st + u[:, :, None, :] * kx
    h = lax.dynamic_update_index_in_dim(h, new.astype(h.dtype), m, 0)
    return (p + u * kq).reshape(S, H * dv), h


def _unit_lower_inverse(A: jax.Array) -> jax.Array:
    """(I + A)^-1 for A [..., c, c] strictly lower triangular, c a power
    of two: forward substitution by blocks. With X the inverse of the
    diagonal blocks of size b and L the blocks under them that pair
    them up, the inverse of the blocks of size 2b is X - X L X."""
    c = A.shape[-1]
    t = np.arange(c)                    # the masks: constants
    X = jnp.broadcast_to(jnp.eye(c, dtype=A.dtype), A.shape)
    b = 1
    while b < c:
        under = (t[:, None] // (2 * b) == t[None, :] // (2 * b)) \
            & (t[:, None] % (2 * b) >= b) & (t[None, :] % (2 * b) < b)
        L = jnp.where(under, A, 0.0)
        X = X - jnp.einsum("...ij,...jk,...kl->...il", X, L, X,
                           precision=lax.Precision.HIGHEST)
        b *= 2
    return X


def _gdn_piece(cfg: ModelConfig, state, piece):
    """gdn_chunk over c positions solved at once: state [P,H/g,dk,g dv]
    AS HELD; piece = (q, k [P,c,H,dk], v [P,c,H dv] flat, log_alpha,
    beta [P,c,H]). With G_t the running sum of log_alpha and D[t,i] =
    exp(G_t - G_i):
        (I + A) U = beta (V - exp(G) K S0^T),  A[t,i] = beta_t D[t,i] k_t.k_i, i < t
        o_t = exp(G_t) S0 q_t + sum_{i<=t} D[t,i] (q_t.k_i) u_i
        S   = exp(G_c) S0 + sum_i exp(G_c - G_i) u_i k_i^T
    which is the per-position rule unrolled (U's rows are its u_t). The
    two things that touch the state, S0 k and S0 q before and the sum of
    u k^T after, are a product with it and a sum into it in the
    layout it is HELD in: it is never transposed
    (XLA gave the whole carried state the transposed layout, and copied
    it, when one slot of it was). The c x c solve is a head at a time,
    on values of a chunk's size."""
    q, k, v, la, beta = piece
    hi = lax.Precision.HIGHEST
    P, c, H, dk = k.shape
    g, dv = cfg.gdn_head_group, cfg.gdn_value_dim
    J = H // g

    def lanes(a):               # [P,n,H,X] -> [P,n,J,X,g dv]
        return head_lanes(
            jnp.moveaxis(a.reshape(P, a.shape[1], J, g, -1), 3, -1), dv)

    def heads(a):               # [P,c,J,g dv] -> [P,H,c,dv]
        return jnp.moveaxis(a.reshape(P, c, H, dv), 1, 2)

    kx = lanes(k)                                          # [P,c,J,dk,L]
    # S0 k_t and S0 q_t: one product over the state as it is held, a
    # group's g heads' keys and queries against all of its g dv lanes,
    # of which each head keeps its own (as a broadcast product and a
    # sum over dk, 32 positions cost what 64 slots' decode rows do)
    kq = jnp.concatenate([k.reshape(P, c, J, g, dk),
                          q.reshape(P, c, J, g, dk)], axis=3)
    own = np.arange(g * dv) // dv == np.arange(g)[:, None]  # [g, L]
    r = jnp.einsum("pcjik,pjkl->pcjil", kq, state, precision=hi)
    sk = heads(jnp.sum(jnp.where(own, r[..., :g, :], 0.0), axis=-2))
    sq = heads(jnp.sum(jnp.where(own, r[..., g:, :], 0.0), axis=-2))
    q, k, v = (jnp.moveaxis(a.reshape(P, c, H, -1), 1, 2) for a in (q, k, v))
    la, beta = (jnp.moveaxis(a, 1, 2) for a in (la, beta))  # [P,H,c]
    G = jnp.cumsum(la, axis=-1)
    t = np.arange(c)
    seen = t[:, None] >= t[None, :]                        # i <= t
    D = jnp.exp(jnp.where(seen, G[..., :, None] - G[..., None, :], -jnp.inf))
    eG = jnp.exp(G)[..., None]
    kk = jnp.einsum("phtk,phik->phti", k, k, precision=hi)
    A = jnp.where(t[:, None] > t[None, :], beta[..., None] * D * kk, 0.0)
    U = jnp.einsum("phti,phiv->phtv", _unit_lower_inverse(A),
                   beta[..., None] * (v - eG * sk), precision=hi)
    qk = jnp.einsum("phtk,phik->phti", q, k, precision=hi)
    o = eG * sq + jnp.einsum("phti,phiv->phtv", D * qk, U, precision=hi)
    # the state after: decayed, plus each u_i k_i^T decayed from i on
    uw = U * jnp.exp(G[..., -1:] - G)[..., None]           # [P,H,c,dv]
    uw = jnp.moveaxis(uw, 2, 1).reshape(P, c, J, 1, g * dv)
    decay = lanes(jnp.moveaxis(G, 1, 2)[:, -1:, :, None])[:, 0]  # [P,J,1,L]
    state = jnp.exp(decay) * state + jnp.sum(uw * kx, axis=1)
    return state, jnp.moveaxis(o, 1, 2)                    # [P,c,H,dv]


@jax.named_scope("gdn_chunk")
def gdn_chunk(q: jax.Array, k: jax.Array, v: jax.Array,
              log_alpha: jax.Array, beta: jax.Array, state: jax.Array,
              cfg: ModelConfig):
    """The delta rule over rows of T positions, in the chunkwise form:
    q and k [P,T,H,dk], v [P,T,H dv] flat, log_alpha and beta [P,T,H]
    (gdn_step_inputs: a position that is not real has 0 and 0, and
    passes the state on), state [P,H/g,dk,g dv] float32 BEFORE the
    call, in the layout it is held in. Pieces of GDN_CHUNK positions
    (fewer for a short row: the next power of two) are solved at once
    (_gdn_piece), the state handed from piece to piece; the row is
    padded to whole pieces with positions that are not real. Returns
    (o [P,T,H,dv] float32, state after)."""
    T = q.shape[1]
    c = min(GDN_CHUNK, 1 << max(T - 1, 0).bit_length())
    n = -(-T // c)

    def pieces(a):             # [P,T,..] -> [n,P,c,..]
        a = jnp.pad(a, ((0, 0), (0, n * c - T)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((a.shape[0], n, c) + a.shape[2:]), 1, 0)

    xs = tuple(pieces(a) for a in (q, k, v, log_alpha, beta))
    if n == 1:
        state, o = _gdn_piece(cfg, state, tuple(a[0] for a in xs))
    else:
        state, o = lax.scan(partial(_gdn_piece, cfg), state, xs)
        o = jnp.moveaxis(o, 0, 1).reshape((o.shape[1], n * c) + o.shape[3:])
    return o[:, :T], state


@jax.named_scope("gdn_gate")
def gdn_gate_out(o: jax.Array, z: jax.Array, gp: Params,
                 cfg: ModelConfig) -> jax.Array:
    """o [B,T,H,dv] float32 normed a head at a time (ONE learned weight
    of dv shared by the heads), THEN gated by silu(z), then the
    out-projection (Mamba-2's ssm_gate_out gates first and norms all
    heads at once)."""
    B, T = o.shape[:2]
    y = rms_norm(o, gp["norm"]["scale"], cfg.norm_eps).reshape(B, T, -1)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
    return qeinsum("bti,id->btd", y, gp["out_proj"], z.dtype)


# ---------------------------------------------------------------------------
# Mamba-1 mixer (cfg.layer_types "mamba1"; arXiv:2312.00752, as the
# `jamba` family has it): a layer whose memory of a stream is N numbers a
# CHANNEL, each decayed at a rate of its own. Di channels, a state of N,
# dt through a bottleneck of R:
#
#   [u | z] = in_proj(x)                 widths Di | Di, no bias
#   u = silu(conv(u) + b)                causal, depthwise, K taps, over
#                                        u ALONE
#   [r | B | C] = x_proj(u)              R | N | N, no bias: from the
#                                        conv's OUTPUT
#   r, B, C = RMSNorm(r) w, RMSNorm(B) w, RMSNorm(C) w   (mamba1_norms)
#   dt = softplus(dt_proj(r) + dt_bias)  [Di]: a step size a channel
#   A = -exp(A_log)                      [N, Di] as held here
#   h[n, c] = exp(dt[c] A[n, c]) h[n, c] + dt[c] B[n] u[c]
#   y[c] = sum_n h[n, c] C[n] + D[c] u[c]
#   out = out_proj(y * silu(z))          NO norm behind the gate
#
# Mamba-2 decays a head's whole [Hd, N] state by ONE scalar and projects
# dt, B and C from the layer's input; here every one of the Di x N state
# values has its own rate and its own exponential a step. What a stream
# keeps between calls is h [N, Di] (channels on the lanes: N = 16 is one
# tile of bfloat16 rows; cache/ssm_state.py) and the conv's last K-1
# inputs [K-1, Di], in cfg.dtype; a call's arithmetic is float32. The
# pieces are composed in cache/ssm_state.py advance_packed beside the
# other two kinds'.
# ---------------------------------------------------------------------------


@jax.named_scope("mamba1_proj")
def mamba1_in_proj(h: jax.Array, mp: Params, cfg: ModelConfig):
    """(u [B,T,Di], z [B,T,Di]) of the normed input: u first."""
    uz = qeinsum("btd,dp->btp", h, mp["in_proj"], h.dtype)
    return uz[..., :cfg.mamba1_inner], uz[..., cfg.mamba1_inner:]


mamba1_conv = jax.named_scope("mamba1_conv")(_causal_conv)


@jax.named_scope("mamba1_inputs")
def mamba1_step_inputs(u: jax.Array, mp: Params, cfg: ModelConfig):
    """What the recurrence reads of its positions beside u itself, from
    the conv's OUTPUT u [B,T,Di] float32 (mamba1_conv): (dt [B,T,Di]
    after its bias and softplus, B and C [B,T,N]), float32. The two
    small products take their operand in the compute dtype and sum in
    float32."""
    R, N = cfg.mamba1_dt_rank, cfg.mamba1_state
    cdt, f32 = jnp.dtype(cfg.dtype), jnp.float32
    rbc = jnp.einsum("bti,ip->btp", u.astype(cdt), mp["x_proj"].astype(cdt),
                     preferred_element_type=f32)
    r, Bm, Cm = rbc[..., :R], rbc[..., R:R + N], rbc[..., R + N:]
    # the family's inner norms (cfg.mamba1_norms: init_params), each
    # where the layer's weights have one, as _causal_conv takes a bias
    r, Bm, Cm = (rms_norm(a, mp[n]["scale"], cfg.norm_eps) if n in mp else a
                 for a, n in ((r, "dt_norm"), (Bm, "b_norm"), (Cm, "c_norm")))
    dt = jnp.einsum("btr,ri->bti", r.astype(cdt), mp["dt_proj"].astype(cdt),
                    preferred_element_type=f32)
    return jax.nn.softplus(dt + mp["dt_bias"].astype(f32)), Bm, Cm


def _mamba1_positions(u, dt, Bm, Cm, mp: Params, state, count):
    """The recurrence over a row's positions: u and dt [B,T,Di], Bm and
    Cm [B,T,N] float32, state [B,N,Di] float32 BEFORE the call. A row's
    state advances through its first `count` [B] positions and no
    further. T == 1 is one step; longer rows scan their positions, one
    exponential a state value and position. Returns (y [B,T,Di] float32
    with the skip term D u, state after)."""
    T = u.shape[1]
    A = -jnp.exp(mp["A_log"].astype(jnp.float32))           # [N, Di]
    real = jnp.arange(T)[None, :] < count[:, None]           # [B,T]

    def step(h, t):
        u_t, dt_t, B_t, C_t, real_t = t
        new = jnp.exp(dt_t[:, None, :] * A) * h \
            + (dt_t * u_t)[:, None, :] * B_t[:, :, None]
        h = jnp.where(real_t[:, None, None], new, h)
        return h, jnp.sum(h * C_t[:, :, None], axis=1)       # [B,Di]

    if T == 1:
        state, y = step(state, (u[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0],
                                real[:, 0]))
        y = y[:, None]
    else:
        state, y = lax.scan(step, state, tuple(
            jnp.moveaxis(a, 1, 0) for a in (u, dt, Bm, Cm, real)))
        y = jnp.moveaxis(y, 0, 1)
    return y + mp["D"].astype(jnp.float32) * u, state


@jax.named_scope("mamba1_step")
def mamba1_step(h: jax.Array, m, u, dt, Bm, Cm, mp: Params, count):
    """One position of every slot of layer m, over the state where it
    lies: h [Lm, S, N, Di] the WHOLE carried state, u and dt [S,1,Di],
    Bm and Cm [S,1,N] (mamba1_step_inputs at T == 1), count [S] (1: the
    row decodes). The layer's slots are read as float32, stepped, and
    written back in place at the layer's index. Returns (y [S,1,Di]
    float32, h)."""
    st = lax.dynamic_index_in_dim(h, m, 0, keepdims=False)
    y, new = _mamba1_positions(u, dt, Bm, Cm, mp, st.astype(jnp.float32),
                               count)
    return y, lax.dynamic_update_index_in_dim(h, new.astype(h.dtype), m, 0)


#: a chunk's recurrence: rows of T positions, a position at a time
#: (state [P,N,Di] float32 BEFORE the call)
mamba1_scan = jax.named_scope("mamba1_scan")(_mamba1_positions)


@jax.named_scope("mamba1_gate")
def mamba1_gate_out(y: jax.Array, z: jax.Array, mp: Params) -> jax.Array:
    """y [B,T,Di] float32 gated by silu(z), then the out-projection: no
    norm between them (Mamba-2's ssm_gate_out has one)."""
    g = (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
    return qeinsum("bti,id->btd", g, mp["out_proj"], z.dtype)


def ffn_close(x: jax.Array, lp: Params, cfg: ModelConfig, route=None,
              ok=None):
    """A layer from its mixer's residual on: the feed-forward with its
    norm and residual. route: early_router_logits' of the layer. Returns
    (x, load): with `ok` [B,T], the rows that are real, and a model of
    experts, `load` is what the layer's routing asked of them
    (expert_load: five values under cfg.experts_held), else None."""
    h, mix = stream_read(x, lp, 2, cfg)
    load = None
    if ok is not None and cfg.routed and "moe" in lp:
        if route is None:
            route = router_logits(h, lp["moe"]["router"])
        load = expert_load(
            route, cfg.num_experts_per_tok, ok, cfg.router_score,
            lp["moe"].get("router_bias"),
            (cfg.experts_first, cfg.experts_held) if cfg.experts_held
            else None)
    return stream_write(x, ffn_block(h, lp, cfg, route, ok), mix, cfg), load


def transformer_layer(x: jax.Array, lp: Params, cfg: ModelConfig,
                      ck: jax.Array, cv: jax.Array,
                      positions: jax.Array, mask: jax.Array,
                      cos: jax.Array, sin: jax.Array,
                      fresh: bool = False,
                      k_s: Optional[jax.Array] = None,
                      v_s: Optional[jax.Array] = None,
                      cki: Optional[jax.Array] = None):
    """Pre-norm residual block: x + attn(norm(x)); x + ffn(norm(x)),
    or the same two sublayers over n streams (stream_read, stream_write).

    Returns (x, ck, cv), or (x, ck, cv, k_s, v_s) with an int8 cache,
    and the layer's index keys cki after them for a model with an
    indexer; in attention_block's no-cache fresh mode (ck None),
    (x, k, v) with the layer's raw projected K/V.
    """
    h, mix = stream_read(x, lp, 1, cfg)
    # x itself where a router or an indexer reads the layer's input:
    # ModelConfig refuses either beside hc_mult
    route = early_router_logits(x, lp, cfg)
    index = index_proj(x, lp, cfg, cos, sin) if cfg.has_indexer else None
    attn_out, *rest = attention_block(
        h, lp["attn"], cfg, ck, cv, positions, mask, cos, sin, fresh,
        k_s, v_s, lp.get("pattern"), index, cki)
    x, _ = ffn_close(stream_write(x, attn_out, mix, cfg), lp, cfg, route)
    return (x, *rest)


# ---------------------------------------------------------------------------
# Full forward
# ---------------------------------------------------------------------------

def make_mask(positions: jax.Array, S: int,
              sliding_window=None) -> jax.Array:
    """Causal mask over the cache: [B,T,S], True where query may attend.

    A query at absolute position p attends to cache slots j <= p. Slots
    beyond the written region have j > p and are excluded automatically
    (new tokens are written into the cache before attending).
    sliding_window (a scalar, may be traced): the mask's lower bound,
    p - j < sliding_window; 0 = none, as for a full layer of a model
    whose other layers slide.
    """
    j = jnp.arange(S)[None, None, :]
    p = positions[:, :, None]
    mask = j <= p
    if sliding_window is not None:
        mask = mask & ((sliding_window <= 0) | (p - j < sliding_window))
    return mask


def layer_stack(layers: Params, cfg: ModelConfig) -> Params:
    """The layer-stacked tree a layer scan rides as xs: the weights
    and, where the model's layers are unlike (cfg.layer_pattern), each
    layer's pattern beside them under "pattern", as data: the scan's
    one compiled body serves every kind of layer. The leading dim may
    be the model's first layers alone; a slice that does not start at layer 0
    (a pipeline stage) is not handled here."""
    pattern = cfg.layer_pattern()
    if pattern is None:
        return layers
    n = jax.tree.leaves(layers)[0].shape[0]
    return {**layers,
            "pattern": {k: jnp.asarray(v[:n]) for k, v in pattern.items()}}


def uniform_layers_only(cfg: ModelConfig, what: str) -> None:
    """Refuse a model whose layers are unlike, or whose router stands
    before attention, on a path that scans layer slices of its own
    (pipeline stages, sequence-parallel bodies): neither carries the
    pattern nor the early router logits yet."""
    if cfg.layer_pattern() is not None \
            or (cfg.routed and cfg.router_input == "attn"):
        raise NotImplementedError(
            f"{what} runs models whose layers are all alike; this one "
            "has a per-layer attention pattern or routes before attention")
    gate_unsupported(cfg, what)


def layer_pattern_of(pattern: Optional[Params]):
    """(rope, sliding_window) of one layer's slice of layer_stack's
    "pattern" (traced scalars), or (None, None) for a model without."""
    if pattern is None:
        return None, None
    return pattern["rope"], pattern["sliding_window"]


def layer_mask(mask: jax.Array, positions: jax.Array, sliding_window):
    """`mask` [B,T,S] narrowed to the layer's sliding window."""
    if sliding_window is None:
        return mask
    return mask & make_mask(positions, mask.shape[-1], sliding_window)


def embed_tokens(params: Params, cfg: ModelConfig, tokens: jax.Array,
                 positions: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Token (+pos) embedding. Returns (x [B,T,D], cos, sin); x is
    [n,B,T,D] for a model of n residual streams (cfg.hc_mult): the
    embedding, n times."""
    B, T = tokens.shape
    compute_dtype = jnp.dtype(cfg.dtype)
    x = params["embed"]["tok"].astype(compute_dtype)[tokens]
    if cfg.mup_embed:
        x = (x.astype(jnp.float32) * cfg.hidden_size ** 0.5
             ).astype(compute_dtype)
    if cfg.embedding_multiplier:
        x = x * jnp.asarray(cfg.embedding_multiplier, compute_dtype)
    if cfg.hc_mult:
        x = jnp.broadcast_to(x, (cfg.hc_mult,) + x.shape)
    if cfg.pos_embedding == "learned":
        x = x + params["embed"]["pos"].astype(compute_dtype)[positions]
        cos = sin = jnp.zeros((B, T, cfg.head_dim // 2), jnp.float32)
    else:
        cos, sin = rope_freqs(cfg, positions)
    return x, cos, sin


def scan_layers(layer_params: Params, cfg: ModelConfig, x: jax.Array,
                k: jax.Array, v: jax.Array, positions: jax.Array,
                mask: jax.Array, cos: jax.Array, sin: jax.Array,
                fresh: bool = False,
                k_s: Optional[jax.Array] = None,
                v_s: Optional[jax.Array] = None,
                ki: Optional[jax.Array] = None):
    """lax.scan of transformer_layer over layer-stacked leaves.

    Works on any leading-layer-count slice (full model, or one pipeline
    stage's slice — parallel/pipeline.py scans each stage's local layers
    with this same body). Returns (x, new_k, new_v), plus
    (new_k_s, new_v_s) when scanning an int8 cache (k_s/v_s [L,B,Kv,S]),
    plus new_ki last for a model with an indexer (ki [L,B,S,Hi]).
    """
    compute_dtype = jnp.dtype(cfg.dtype)
    quant = k_s is not None

    def body(x, scanned):
        lp, ck, cv, *rest = scanned
        lp = jax.tree.map(lambda a: _cast_float(a, compute_dtype), lp)
        scales = rest[:2] if quant else (None, None)
        x, *kv = transformer_layer(x, lp, cfg, ck, cv,
                                   positions, mask, cos, sin, fresh,
                                   *scales, rest[-1] if cfg.has_indexer
                                   else None)
        return x, tuple(kv)

    layer_params = layer_stack(layer_params, cfg)
    xs = (layer_params, k, v) + ((k_s, v_s) if quant else ()) \
        + ((ki,) if cfg.has_indexer else ())
    x, out = lax.scan(body, x, xs)
    return (x, *out)


def final_logits(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Final norm + LM head. Returns logits [B,T,V] float32."""
    compute_dtype = jnp.dtype(cfg.dtype)
    if cfg.arch == "gpt2":
        x = layer_norm(x, params["final_norm"]["scale"],
                       params["final_norm"]["bias"], cfg.norm_eps)
    else:
        x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)

    if cfg.tie_embeddings and "lm_head" not in params:
        logits = jnp.einsum("btd,vd->btv", x,
                            params["embed"]["tok"].astype(compute_dtype))
    else:
        # untied, or a tied head held a second time as int8 codes
        # (quant/int8.py tied_head): a step reads half the bytes
        logits = qeinsum("btd,dv->btv", x, params["lm_head"], compute_dtype)
    logits = logits.astype(jnp.float32)
    if cfg.logits_scaling:
        logits = logits / cfg.logits_scaling
    return logits


def decode_attend(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                  ck: jax.Array, cv: jax.Array, start: jax.Array,
                  cfg: ModelConfig, k_s: Optional[jax.Array] = None,
                  v_s: Optional[jax.Array] = None,
                  wk: Optional[jax.Array] = None,
                  wv: Optional[jax.Array] = None,
                  wk_s: Optional[jax.Array] = None,
                  wv_s: Optional[jax.Array] = None,
                  sliding_window=None) -> jax.Array:
    """One-token attention over (old cache) + (the token itself).

    The general path writes K/V into the cache BEFORE attending, which
    forces a per-layer scattered cache update inside the layer scan — 2L
    batched-dynamic-slice scatters per decode step, the dominant cost of
    the decode loop at serving batch sizes (measured on v5e). Attending
    over the unmodified cache (positions < start, no write yet) plus an
    explicit self-attention term is mathematically identical for causal
    decode and lets the caller write ALL layers' new K/V in one batched
    update after the scan (see _decode_forward).

    q [B,1,Nq,H]; k_new/v_new [B,1,Kv,H]; ck/cv [B,S,Kv,H]; start [B].
    int8 cache: ck/cv are codes in [B,Kv,S,H] order with scales k_s/v_s
    [B,Kv,S]; only int8 bytes stream from HBM (the convert + scale fuse
    into the dots) and the self term stays full precision.

    Window (write-combining fused decode, engine._generate_fused): wk/wv
    hold the previous not-yet-flushed decoded tokens' K/V for this
    layer, in the cache's REPRESENTATION (int8 codes + scales in quant
    mode), stacked step-major: [W,B,Kv,H] both modes, scales wk_s/wv_s
    [W,B,Kv]. Every entry is LIVE (the unrolled fused loop passes
    exactly the steps decoded so far — see decode_step_win); they sit
    at absolute positions start..start+W-1. `start` is the FLUSHED
    length per row (= tokens actually in ck/cv).

    sliding_window (the layer's, a traced scalar; 0 = a full layer):
    the token, at position start + W, attends position c only where
    start + W - c < sliding_window.
    """
    B, _, Nq, H = q.shape
    quant = k_s is not None
    S = ck.shape[2] if quant else ck.shape[1]
    Kv = k_new.shape[2]
    G = Nq // Kv
    qg = q.reshape(B, Kv, G, H)
    compute = q.dtype
    scale = 1.0 / jnp.sqrt(jnp.asarray(H, jnp.float32))
    k_eq = "bksh" if quant else "bskh"
    s_c = jnp.einsum(f"bkgh,{k_eq}->bkgs", qg, _cast_float(ck, compute),
                     preferred_element_type=jnp.float32)
    if quant:
        s_c = s_c * k_s[:, :, None, :]
    s_c = s_c * scale
    older = jnp.arange(S)[None, :] < start[:, None]          # strictly past
    W = 0 if wk is None else wk.shape[0]
    if sliding_window is not None:
        # the token's own lower bound, over the cache and the window
        lo = jnp.where(sliding_window > 0, start + W - sliding_window, -1)
        older = older & (jnp.arange(S)[None, :] > lo[:, None])
    s_c = jnp.where(older[:, None, None, :], s_c, -1e30)
    parts_s = [s_c]

    if wk is not None:
        s_w = jnp.einsum("bkgh,cbkh->bkgc", qg, _cast_float(wk, compute),
                         preferred_element_type=jnp.float32)
        if quant:
            s_w = s_w * jnp.moveaxis(wk_s, 0, -1)[:, :, None, :]
        s_w = s_w * scale
        if sliding_window is not None:
            inside = start[:, None] + jnp.arange(W)[None, :] > lo[:, None]
            s_w = jnp.where(inside[:, None, None, :], s_w, -1e30)
        parts_s.append(s_w)

    s_self = jnp.sum(qg.astype(jnp.float32) *
                     k_new.reshape(B, Kv, 1, H).astype(jnp.float32),
                     axis=-1, keepdims=True) * scale          # [B,Kv,G,1]
    parts_s.append(s_self)
    s = jnp.concatenate(parts_s, axis=-1)
    p = jax.nn.softmax(s, axis=-1)

    p_c = p[..., :S]
    if quant:
        p_c = p_c * v_s[:, :, None, :]
    out = jnp.einsum(f"bkgs,{k_eq}->bkgh", p_c.astype(compute),
                     _cast_float(cv, compute))
    if wk is not None:
        p_w = p[..., S:-1]
        if quant:
            p_w = p_w * jnp.moveaxis(wv_s, 0, -1)[:, :, None, :]
        out = out + jnp.einsum("bkgc,cbkh->bkgh", p_w.astype(compute),
                               _cast_float(wv, compute))
    out = out + p[..., -1:].astype(v_new.dtype) * v_new.reshape(B, Kv, 1, H)
    return out.reshape(B, 1, Nq, H)


def _decode_layer_body(x, lp, cfg: ModelConfig, cache: KVCache, i,
                       cos, sin, start, wk_i=None, wv_i=None, wks_i=None,
                       wvs_i=None):
    """One decode layer against layer `i`'s slice of the closed-over
    cache (+ optional write-combining window entries for THIS layer:
    wk_i/wv_i [W,B,Kv,H], scales [W,B,Kv] — already layer-sliced by the
    caller). The single layer body shared by _decode_forward and
    decode_step_win so the per-step and windowed decode paths cannot
    drift. Returns (x, k_new, v_new) with k/v [B,1,Kv,H] in compute
    dtype.
    """
    compute_dtype = jnp.dtype(cfg.dtype)
    lp = jax.tree.map(lambda a: _cast_float(a, compute_dtype), lp)
    ck = lax.dynamic_index_in_dim(cache.k, i, 0, keepdims=False)
    cv = lax.dynamic_index_in_dim(cache.v, i, 0, keepdims=False)
    k_s = v_s = None
    if cache.quantized:
        k_s = lax.dynamic_index_in_dim(cache.k_scale, i, 0, keepdims=False)
        v_s = lax.dynamic_index_in_dim(cache.v_scale, i, 0, keepdims=False)
    h, mix = stream_read(x, lp, 1, cfg)
    route = early_router_logits(x, lp, cfg)
    rope, sw = layer_pattern_of(lp.get("pattern"))
    q, k, v = qkv_proj(h, lp["attn"], cfg, cos, sin, rope)
    out = decode_attend(q, k, v, ck, cv, start, cfg, k_s, v_s,
                        wk_i, wv_i, wks_i, wvs_i, sliding_window=sw)
    x = stream_write(x, attn_output(out, lp["attn"], cfg,
                                    attn_gate(h, lp["attn"], cfg)), mix, cfg)
    x, _ = ffn_close(x, lp, cfg, route)
    return x, k, v


def _decode_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                    cache: KVCache, positions: jax.Array
                    ) -> Tuple[jax.Array, KVCache]:
    """Single-token decode step with ONE batched cache write.

    The layer scan attends via decode_attend (old cache + self term) and
    emits each layer's fresh K/V as stacked scan outputs; the cache is
    then updated for every layer at once with a single vmapped
    dynamic-update-slice — O(1) update ops per step instead of O(L).
    """
    B = tokens.shape[0]
    x, cos, sin = embed_tokens(params, cfg, tokens, positions)
    start = positions[:, 0]
    quant = cache.quantized

    # The cache is READ-ONLY inside the layer scan (writes are deferred
    # to the one-shot update below), so it is closed over and indexed
    # in-body rather than passed as scan xs: xs slicing materializes a
    # dynamic-slice COPY of every layer's [B,S,Kv,H] slice per step —
    # measured as the single largest op (~45% of decode step time) in
    # the v5e fused-generate trace.
    def layer(carry, lp):
        x, i = carry
        x, k, v = _decode_layer_body(x, lp, cfg, cache, i, cos, sin, start)
        if quant:
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            return (x, i + 1), (kq, vq, ksc, vsc)
        return (x, i + 1), (k.astype(cache.k.dtype),
                            v.astype(cache.v.dtype))

    (x, _), outs = lax.scan(layer, (x, 0),
                            layer_stack(params["layers"], cfg))
    logits = final_logits(params, cfg, stream_fold(x, cfg))

    if quant:
        # codes: [L,B,Kv,S,H] <- scan outputs [L,B,1,Kv,H] -> [L,B,Kv,1,H]
        def updq(c_b, n_b, s_b):  # [L,Kv,S,H] <- [L,Kv,1,H] at (0,0,s,0)
            return lax.dynamic_update_slice(c_b, n_b, (0, 0, s_b, 0))

        def upd_s(c_b, n_b, s_b):  # [L,Kv,S] <- [L,Kv,1] at (0,0,s)
            return lax.dynamic_update_slice(c_b, n_b, (0, 0, s_b))

        kq, vq, ksc, vsc = outs
        new_k = jax.vmap(updq, in_axes=(1, 1, 0), out_axes=1)(
            cache.k, kq.transpose(0, 1, 3, 2, 4), start)
        new_v = jax.vmap(updq, in_axes=(1, 1, 0), out_axes=1)(
            cache.v, vq.transpose(0, 1, 3, 2, 4), start)
        new_ks = jax.vmap(upd_s, in_axes=(1, 1, 0), out_axes=1)(
            cache.k_scale, ksc.transpose(0, 1, 3, 2), start)
        new_vs = jax.vmap(upd_s, in_axes=(1, 1, 0), out_axes=1)(
            cache.v_scale, vsc.transpose(0, 1, 3, 2), start)
        return logits, KVCache(new_k, new_v, cache.length + 1,
                               new_ks, new_vs)

    def upd(c_b, n_b, s_b):  # [L,S,Kv,H] <- [L,1,Kv,H] at (0, s_b, 0, 0)
        return lax.dynamic_update_slice(c_b, n_b, (0, s_b, 0, 0))

    ks, vs = outs
    new_k = jax.vmap(upd, in_axes=(1, 1, 0), out_axes=1)(cache.k, ks, start)
    new_v = jax.vmap(upd, in_axes=(1, 1, 0), out_axes=1)(cache.v, vs, start)
    return logits, KVCache(new_k, new_v, cache.length + 1)


# ---------------------------------------------------------------------------
# Write-combined decode window (engine fused generate)
#
# Every in-loop update of the big cache costs a copy of the whole pool on
# TPU (XLA does not alias scatters into while-loop carries here; measured
# ~2.4 ms/step at the 1B/batch-128 operating point — the largest single
# term of the decode step). The fused generate therefore decodes C tokens
# per outer scan iteration and flushes all C into the big cache with ONE
# ragged write per C steps, amortizing the copy. The C steps are UNROLLED
# inside the iteration, so the not-yet-flushed "window" needs no device
# buffer at all: each step's K/V is an SSA value held in a Python list
# (r4 had a [.., C, ..] window buffer updated per step with
# dynamic-update-slice; XLA's layout assignment made every insert a
# strided scatter of H-byte segments at 15 GiB/s — 19% of the decode step
# in the r5 v5e profile — and reassigned any step-major
# layout right back). The window uses the cache's representation (int8
# codes + scales in quant mode), so attention numerics are bit-identical
# to the step-by-step path.
# ---------------------------------------------------------------------------

def decode_step_win(params: Params, cfg: ModelConfig, tokens: jax.Array,
                    cache: KVCache, prev: list, wstep: int):
    """One decode step against (cache + prior window steps + self).

    tokens [B,1]; the token sits at absolute position cache.length +
    wstep (cache.length = flushed tokens; `prev` holds steps
    0..wstep-1 of the current flush group as a list of new_kv tuples —
    exactly what this function returned for them). No cache writes.
    Returns (logits, new_kv): the per-layer stacked K/V of this token —
    fp (ks [L,B,Kv,H], vs) / quant (kq, vq, ks_scale [L,B,Kv], vs_scale).

    The prior steps are stacked ONCE per step into [L,W,...] arrays and
    ride into the layer scan as `xs` leaves, so each layer's xs slice is
    one CONTIGUOUS [W,B,Kv,H] window operand for decode_attend (stacking
    inside the layer body instead costs ~2x the step's window traffic in
    128KB strided slices + concats — measured on v5e, r5 profile).
    """
    indexer_unsupported(cfg, "the write-combined fused generate")
    ssm_unsupported(cfg, "the write-combined fused generate")
    latent_unsupported(cfg, "the write-combined fused generate")
    quant = cache.quantized
    positions = (cache.length + wstep)[:, None]
    x, cos, sin = embed_tokens(params, cfg, tokens, positions)
    start = cache.length
    win = ()
    if prev:  # [L,W,B,Kv,H] codes (+ [L,W,B,Kv] scales in quant mode)
        win = tuple(jnp.stack(c, axis=1) for c in zip(*prev))

    def layer(carry, scanned):
        x, i = carry
        lp, w = scanned  # w: per-layer [W,B,Kv,H] (+ [W,B,Kv]) or ()
        wk_i, wv_i, *wsc = w if w else (None, None)
        wks_i, wvs_i = wsc if wsc else (None, None)
        x, k, v = _decode_layer_body(x, lp, cfg, cache, i, cos, sin, start,
                                     wk_i, wv_i, wks_i, wvs_i)
        if quant:
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            return (x, i + 1), (kq[:, 0], vq[:, 0], ksc[:, 0], vsc[:, 0])
        return (x, i + 1), (k[:, 0].astype(cache.k.dtype),
                            v[:, 0].astype(cache.v.dtype))

    (x, _), new_kv = lax.scan(
        layer, (x, 0), (layer_stack(params["layers"], cfg), win))
    return final_logits(params, cfg, stream_fold(x, cfg)), new_kv


def flush_window(cache: KVCache, steps: list,
                 uniform: bool = False) -> KVCache:
    """Write a whole flush group (C tokens per row, `steps` = the list of
    decode_step_win new_kv tuples) into the big cache at each row's
    flushed length — the one ragged write per C steps. The stack into
    cache dim order is a copy of the small window only, amortized over
    C steps.

    `uniform` (static) asserts every row's flushed length is equal (all
    prompts the same length — the batch-benchmark shape). The update is
    then ONE dynamic_update_slice at a scalar offset, which XLA aliases
    with the scan carry and performs in place; the general ragged path
    (vmapped per-row updates) rolls into a loop whose first update
    COPIES each pool — ~1.8 ms per pool per flush at the 1B/batch-128
    operating point in the r5 v5e profile."""
    start = cache.length
    C = len(steps)
    if cache.quantized:
        kq = jnp.stack([s[0] for s in steps], axis=3)   # [L,B,Kv,C,H]
        vq = jnp.stack([s[1] for s in steps], axis=3)
        ksc = jnp.stack([s[2] for s in steps], axis=3)  # [L,B,Kv,C]
        vsc = jnp.stack([s[3] for s in steps], axis=3)
        if uniform:
            s0 = start[0]
            new_k = lax.dynamic_update_slice(cache.k, kq, (0, 0, 0, s0, 0))
            new_v = lax.dynamic_update_slice(cache.v, vq, (0, 0, 0, s0, 0))
            new_ks = lax.dynamic_update_slice(cache.k_scale, ksc,
                                              (0, 0, 0, s0))
            new_vs = lax.dynamic_update_slice(cache.v_scale, vsc,
                                              (0, 0, 0, s0))
            return KVCache(new_k, new_v, cache.length + C, new_ks, new_vs)

        def updq(c_b, n_b, s_b):  # [L,Kv,S,H] <- [L,Kv,C,H] at (0,0,s,0)
            return lax.dynamic_update_slice(c_b, n_b, (0, 0, s_b, 0))

        def upd_s(c_b, n_b, s_b):  # [L,Kv,S] <- [L,Kv,C] at (0,0,s)
            return lax.dynamic_update_slice(c_b, n_b, (0, 0, s_b))

        new_k = jax.vmap(updq, in_axes=(1, 1, 0), out_axes=1)(
            cache.k, kq, start)
        new_v = jax.vmap(updq, in_axes=(1, 1, 0), out_axes=1)(
            cache.v, vq, start)
        new_ks = jax.vmap(upd_s, in_axes=(1, 1, 0), out_axes=1)(
            cache.k_scale, ksc, start)
        new_vs = jax.vmap(upd_s, in_axes=(1, 1, 0), out_axes=1)(
            cache.v_scale, vsc, start)
        return KVCache(new_k, new_v, cache.length + C, new_ks, new_vs)

    ks = jnp.stack([s[0] for s in steps], axis=2)       # [L,B,C,Kv,H]
    vs = jnp.stack([s[1] for s in steps], axis=2)
    if uniform:
        s0 = start[0]
        new_k = lax.dynamic_update_slice(cache.k, ks, (0, 0, s0, 0, 0))
        new_v = lax.dynamic_update_slice(cache.v, vs, (0, 0, s0, 0, 0))
        return KVCache(new_k, new_v, cache.length + C)

    def upd(c_b, n_b, s_b):  # [L,S,Kv,H] <- [L,C,Kv,H] at (0,s,0,0)
        return lax.dynamic_update_slice(c_b, n_b, (0, s_b, 0, 0))

    new_k = jax.vmap(upd, in_axes=(1, 1, 0), out_axes=1)(cache.k, ks, start)
    new_v = jax.vmap(upd, in_axes=(1, 1, 0), out_axes=1)(cache.v, vs, start)
    return KVCache(new_k, new_v, cache.length + C)


def _fresh_prefill_forward(params: Params, cfg: ModelConfig,
                           tokens: jax.Array, cache: KVCache, positions,
                           last_index) -> Tuple[jax.Array, KVCache]:
    """Fresh-prefill fast path: the cache stays OUT of the layer scan.

    A fresh prefill (positions 0..T-1, nothing live in the cache) never
    READS the cache — attention is over the freshly-projected K/V (flash
    kernel, or a dense causal fallback over the same bf16 values). So
    the pools ride the scan CARRY and each layer writes its (already
    cache-representation) K/V with one dynamic_update_slice at the
    layer index — XLA's canonical in-place carry update. The general
    path instead threads pools as scan xs/ys: the xs slicing copies a
    layer slice per step and the stacked ys make a SECOND full pool —
    2x pool HBM, the term that pushed 8B/batch-128 prefill over a v5e
    chip's 16 GiB.

    Padded rows: like the general path, pad positions' K/V land in the
    cache; they sit at slots >= true_len that no causal query reaches
    until decode overwrites them (engine/engine.py padding contract).
    """
    B, T = tokens.shape
    quant = cache.quantized
    compute_dtype = jnp.dtype(cfg.dtype)
    x, cos, sin = embed_tokens(params, cfg, tokens, positions)
    mask = make_mask(positions, T)  # causal over the chunk itself

    def body(carry, lp):
        x, pools, i = carry
        lp = jax.tree.map(lambda a: _cast_float(a, compute_dtype), lp)
        # no-cache layer body: same recipe as every other path, with the
        # raw projected K/V returned for the pool write below
        x, k, v = transformer_layer(x, lp, cfg, None, None, positions,
                                    mask, cos, sin, fresh=True)
        ck, cv, cks, cvs = pools
        if quant:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            ck = lax.dynamic_update_slice(
                ck, kq.transpose(0, 2, 1, 3)[None], (i, 0, 0, 0, 0))
            cv = lax.dynamic_update_slice(
                cv, vq.transpose(0, 2, 1, 3)[None], (i, 0, 0, 0, 0))
            cks = lax.dynamic_update_slice(
                cks, ks.transpose(0, 2, 1)[None], (i, 0, 0, 0))
            cvs = lax.dynamic_update_slice(
                cvs, vs.transpose(0, 2, 1)[None], (i, 0, 0, 0))
        else:
            ck = lax.dynamic_update_slice(
                ck, k.astype(ck.dtype)[None], (i, 0, 0, 0, 0))
            cv = lax.dynamic_update_slice(
                cv, v.astype(cv.dtype)[None], (i, 0, 0, 0, 0))
        return (x, (ck, cv, cks, cvs), i + 1), None

    pools0 = (cache.k, cache.v, cache.k_scale, cache.v_scale)
    (x, pools, _), _ = lax.scan(body, (x, pools0, 0),
                                layer_stack(params["layers"], cfg))
    x = stream_fold(x, cfg)
    if last_index is not None:
        x = jnp.take_along_axis(
            x, last_index[:, None, None].astype(jnp.int32), axis=1)
    logits = final_logits(params, cfg, x)
    new_len = cache.length + T
    return logits, KVCache(pools[0], pools[1], new_len, pools[2], pools[3])


def _hybrid_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                    cache: KVCache, positions: jax.Array,
                    last_index: Optional[jax.Array]
                    ) -> Tuple[jax.Array, KVCache]:
    """forward for a model with recurrent layers (cfg.layer_types:
    Mamba-2, Gated DeltaNet or Mamba-1): the
    layers run as scans over runs of one kind (layer_runs), each run
    riding its kind's stack by index. cache.k/v hold the attention
    layers alone. A recurrent layer is the packed serving step's
    (cache/ssm_state.py advance_packed): the batch's B rows are B chunks
    of T columns, row b the chunk of slot b of cache.ssm, so a row's
    state advances by its REAL positions: all T, or last_index + 1
    where the caller pads its prompts (engine/engine.py), and a row at
    position 0 starts from zero whatever the recycled buffers hold."""
    from butterfly_tpu.cache.ssm_state import StateRows, advance_packed
    B, T = tokens.shape
    x, cos, sin = embed_tokens(params, cfg, tokens, positions)
    mask = make_mask(positions, cache.max_seq)
    count = jnp.full((B,), T, jnp.int32) if last_index is None \
        else last_index.astype(jnp.int32) + 1
    rows = StateRows(
        active=jnp.zeros((0,), bool),
        ok=(jnp.arange(T)[None, :] < count[:, None]).reshape(-1),
        chunk_slot=jnp.arange(B), chunk_ok=count > 0, chunk_pos=positions)

    mixers = params[RECURRENT_STACKS[cfg.recurrent_kind]]

    def recurrent(carry, idx):
        x, state = carry
        l, m = idx
        x, state, _ = advance_packed(
            x, layer_at(params["layers"], l, cfg),
            layer_at(mixers, m, cfg), state, m, rows, cfg)
        return (x, state), None

    def attention(carry, idx):
        x, ck, cv = carry
        l, a = idx
        lp = layer_at(params["layers"], l, cfg)
        h, mix = stream_read(x, lp, 1, cfg)
        out, k, v = attention_block(
            h, layer_at(params["attn"], a, cfg),
            cfg, lax.dynamic_index_in_dim(ck, a, 0, keepdims=False),
            lax.dynamic_index_in_dim(cv, a, 0, keepdims=False),
            positions, mask, cos, sin)
        x, _ = ffn_close(stream_write(x, out, mix, cfg), lp, cfg)
        return (x, lax.dynamic_update_index_in_dim(ck, k, a, 0),
                lax.dynamic_update_index_in_dim(cv, v, a, 0)), None

    k, v, state = cache.k, cache.v, cache.ssm
    for kind, first, n, at in layer_runs(cfg):
        idx = (first + jnp.arange(n), at + jnp.arange(n))
        if kind != "attention":
            (x, state), _ = lax.scan(
                recurrent, (x.reshape(B * T, 1, -1), state), idx)
            x = x.reshape(B, T, -1)
        else:
            (x, k, v), _ = lax.scan(attention, (x, k, v), idx)
    if last_index is not None:
        x = jnp.take_along_axis(
            x, last_index[:, None, None].astype(jnp.int32), axis=1)
    return final_logits(params, cfg, x), KVCache(
        k, v, cache.length + T, ssm=state)


def _latent_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                    cache: KVCache, positions: jax.Array, fresh: bool,
                    last_index: Optional[jax.Array]
                    ) -> Tuple[jax.Array, KVCache]:
    """forward for a latent-attention model: the layers as runs
    (layer_runs: a leading dense feed-forward, then layers of experts),
    each a scan that rides the layers' indices and carries the cache,
    which holds ONE row a token and layer. A call writes its rows and
    then reads the cached rows ABSORBED (latent_attend), its own among
    them: a decode call, a chunk over a cached context, and a prefill
    alike, so what the paged path's decode rows compute is what this
    path's calls compute. `fresh` with more than one token (nothing
    cached is read) takes the EXPANDED form over the call's own rows."""
    B, T = tokens.shape
    x, cos, sin = embed_tokens(params, cfg, tokens, positions)
    expanded = fresh and T > 1
    mask = make_mask(positions, T if expanded else cache.max_seq)

    def layer(ffn, carry, l):
        x, ck, cki = carry
        lp = run_layer_at(params, ffn, l, cfg)
        h, mix = stream_read(x, lp, 1, cfg)
        q_nope, q_rope, rows, cq = latent_proj(h, lp["attn"], cfg, cos, sin)
        # the layer's rows [B,S,latent_row], as index keys are written
        mine = index_cache_write(
            lax.dynamic_index_in_dim(ck, l, 0, keepdims=False)[:, :, 0],
            rows, positions[:, 0])
        sel = mask
        if cfg.has_indexer:
            # the layer's index keys are written, then every query's
            # mask narrows to its selection (attention_block does so)
            qi, ki, w = index_proj(h, lp, cfg, cos, sin, cq)
            keys = index_cache_write(
                lax.dynamic_index_in_dim(cki, l, 0, keepdims=False), ki,
                positions[:, 0])
            cki = lax.dynamic_update_index_in_dim(cki, keys, l, 0)
            sel = select_mask(
                index_scores(qi, w, ki if expanded else keys), mask,
                cfg.index_topk)
        if expanded:
            out = latent_attend_expanded(q_nope, q_rope, rows, sel,
                                         lp["attn"], cfg)
        else:
            out = latent_attend(
                latent_queries(q_nope, q_rope, lp["attn"], cfg), mine,
                sel, cfg)
        x = stream_write(x, attn_output(out, lp["attn"], cfg), mix, cfg)
        x, _ = ffn_close(x, lp, cfg)
        return (x, lax.dynamic_update_index_in_dim(ck, mine[:, :, None], l,
                                                   0), cki), None

    ck, cki = cache.k, cache.ki
    for _, first, n, _ in layer_runs(cfg):
        (x, ck, cki), _ = lax.scan(
            partial(layer, ffn_run(params, first, cfg)), (x, ck, cki),
            first + jnp.arange(n))
    x = stream_fold(x, cfg)
    if last_index is not None:
        x = jnp.take_along_axis(
            x, last_index[:, None, None].astype(jnp.int32), axis=1)
    return final_logits(params, cfg, x), KVCache(
        ck, None, cache.length + T, ki=cki)


def _runs_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  cache: KVCache, positions: jax.Array, fresh: bool,
                  last_index: Optional[jax.Array]
                  ) -> Tuple[jax.Array, KVCache]:
    """forward for a grouped-query model whose feed-forwards are of two
    shapes (cfg.first_k_dense without latent attention): the layers as
    runs (layer_runs), each a scan that rides the layers' indices and
    carries the cache; a layer is transformer_layer, its pattern read at
    the layer's index as layer_stack would hand it."""
    x, cos, sin = embed_tokens(params, cfg, tokens, positions)
    mask = make_mask(positions, cache.max_seq)

    def layer(ffn, carry, l):
        x, ck, cv = carry
        lp = run_layer_at(params, ffn, l, cfg)
        x, k, v = transformer_layer(
            x, lp, cfg, lax.dynamic_index_in_dim(ck, l, 0, keepdims=False),
            lax.dynamic_index_in_dim(cv, l, 0, keepdims=False), positions,
            mask, cos, sin, fresh)
        return (x, lax.dynamic_update_index_in_dim(ck, k, l, 0),
                lax.dynamic_update_index_in_dim(cv, v, l, 0)), None

    ck, cv = cache.k, cache.v
    for _, first, n, _ in layer_runs(cfg):
        (x, ck, cv), _ = lax.scan(
            partial(layer, ffn_run(params, first, cfg)), (x, ck, cv),
            first + jnp.arange(n))
    if last_index is not None:
        x = jnp.take_along_axis(
            x, last_index[:, None, None].astype(jnp.int32), axis=1)
    return final_logits(params, cfg, x), KVCache(ck, cv, cache.length
                                                 + tokens.shape[1])


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            cache: KVCache, positions: Optional[jax.Array] = None,
            fresh: bool = False,
            last_index: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, KVCache]:
    """Run the model over `tokens` [B,T], reading/updating `cache`.

    positions defaults to cache.length[:,None] + arange(T) (append).
    `fresh` (static) = no LIVE entries precede this call's tokens and
    positions start at 0 (recycled buffers may hold stale bytes —
    masking, not zeroing, is the correctness mechanism); only
    then may the flash prefill kernel be used (see attention_block).
    Single-token warm calls take the decode fast path (_decode_forward:
    deferred one-shot cache write). Returns (logits [B,T,V] float32,
    updated cache).

    last_index [B]: when given, the LM head runs ONLY on each row's
    hidden state at that (row-relative) index — logits come back
    [B,1,V]. Prefill needs just the last real token's logits, and the
    full-T head is the single largest prefill term at LLM vocab sizes
    (8B/V=128k at B=T=128: an 8.4 GB f32 [B,T,V] buffer plus 6% of the
    prefill FLOPs).
    """
    B, T = tokens.shape
    if positions is None:
        positions = cache.length[:, None] + jnp.arange(T)[None, :]
    if cfg.has_ssm:
        return _hybrid_forward(params, cfg, tokens, cache, positions,
                               last_index)
    if cfg.is_latent:
        return _latent_forward(params, cfg, tokens, cache, positions, fresh,
                               last_index)
    if cfg.first_k_dense:
        return _runs_forward(params, cfg, tokens, cache, positions, fresh,
                             last_index)
    # A model with an indexer takes the general path for every shape:
    # the two fast paths below attend before the cache is written, and
    # neither carries the index keys (the serving path, cache/paged.py,
    # is where such a model reads only what it selected).
    if T == 1 and not fresh and not cfg.has_indexer:
        return _decode_forward(params, cfg, tokens, cache, positions)
    if fresh and T > 1 and not cfg.has_indexer:
        return _fresh_prefill_forward(params, cfg, tokens, cache,
                                      positions, last_index)

    x, cos, sin = embed_tokens(params, cfg, tokens, positions)
    mask = make_mask(positions, cache.max_seq)
    x, new_k, new_v, *rest = scan_layers(
        params["layers"], cfg, x, cache.k, cache.v, positions, mask, cos,
        sin, fresh, cache.k_scale, cache.v_scale, cache.ki)
    x = stream_fold(x, cfg)
    if last_index is not None:
        x = jnp.take_along_axis(
            x, last_index[:, None, None].astype(jnp.int32), axis=1)
    logits = final_logits(params, cfg, x)
    new_ki = rest.pop() if cfg.has_indexer else None
    return logits, KVCache(new_k, new_v, cache.length + T, *rest,
                           **({} if new_ki is None else {"ki": new_ki}))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def stream_seed(cfg: ModelConfig) -> Tuple[float, float]:
    """(the embedding's std, a sublayer norm's scale) of a SEEDED model.
    A pre-norm sublayer reads norm(x), so the stream's size is nothing
    to it: N(0, .02) and ones. Under cfg.post_norm a sublayer reads the
    RAW stream and adds a vector of the norm's scale to it whatever it
    read: with ones over an embedding of .02 the stream is the first
    sublayer's output from the first layer on and grows to sqrt(2L), a
    rounding of the stream is a large TURN of it while it is short
    (each sublayer multiplies an error's square by 1 + g^2 / l at depth
    l, g the sublayer's own gain: 1e-3 at the embedding came out 0.3
    to 0.6 at the logits of 32 layers), and a and b of a Gated DeltaNet
    mixer sit at +-10 (beta at 0 or 2, alpha at 0 or 1). Nothing could
    be compared with such a model below the noise (PERF.md, PR 56). So
    the embedding is seeded at 1 and each of the 2L norms at
    (2L)^-1/2: the stream stays between 1 and sqrt(2), every sublayer
    counts the same, and a, b stay where beta spans (0, 2)."""
    if cfg.post_norm:
        return 1.0, (2 * cfg.num_layers) ** -0.5
    return 0.02, 1.0


#: how a SEEDED Mamba-1 mixer's small leaves are drawn, by leaf name
#: (`drawn`'s kinds; init_params and quant/int8.py init_params_by_leaf
#: alike; every other weight of the stack is N(0, .02)). At N(0, .02)
#: every one of the Di x N rates is -1 and every step .69: one decay for
#: the whole layer, forgotten within ten positions, behind a conv and a
#: skip that pass a fiftieth of their input, so the mixer adds nothing a
#: comparison with the reference could see (PERF.md, PR 58). So the taps
#: and the skip are drawn as a Mamba-2 mixer's are (init_params), and
#: the rates and steps over the ranges Mamba-1 is trained from
#: (arXiv:2312.00752: A in 1-16, dt log-uniform in .001-.1), here a
#: number of its own for EVERY channel and state index: a step that
#: decays a layer, a head or a channel by one number differs from such a
#: model at once. dt's projection is drawn at the scale the same source
#: gives it, R^-1/2 (N(0, .02) through a rank of 160 moves a step by a
#: fifth, and a program without the norm on dt passed the check).
MAMBA1_SEEDS = {"conv_w": "normal_half", "D": "normal_1",
                "A_log": "rates", "dt_bias": "steps", "dt_proj": "fan_in"}
_STD = {"normal": 0.02, "normal_half": 0.5, "normal_1": 1.0}


def drawn(k: jax.Array, shape, kind: str) -> jax.Array:
    """A seeded leaf's float32 values by kind: "normal" N(0, .02),
    "normal_half" N(0, .5), "normal_1" N(0, 1), "fan_in" N(0, n^-1/2)
    of a projection [.., n, m]; of a Mamba-1 mixer (MAMBA1_SEEDS)
    "rates" (A_log: log of A uniform in 1-16) and "steps" (dt_bias: the
    inverse softplus of dt log-uniform in .001-.1)."""
    if kind in _STD or kind == "fan_in":
        std = _STD[kind] if kind in _STD else shape[-2] ** -0.5
        return jax.random.normal(k, shape, jnp.float32) * std
    if kind == "rates":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if kind == "steps":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return jnp.log(jnp.expm1(dt))
    raise ValueError(f"no seeded leaf is drawn as {kind!r}")


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random init (normal, 0.02 std — GPT-2 style; stream_seed has what
    a post_norm model changes, MAMBA1_SEEDS a Mamba-1 mixer's small
    leaves) in cfg.param_dtype."""
    pdt = jnp.dtype(cfg.param_dtype)
    emb_std, ln = stream_seed(cfg)
    L, D, Nq, Kv, H, F, V = (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
                             cfg.num_kv_heads, cfg.head_dim,
                             cfg.intermediate_size, cfg.vocab_size)
    keys = iter(jax.random.split(key, 32))

    def w(k, *shape, std=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(pdt)

    # a model with layer_types stacks each KIND's mixer apart, top-level
    # beside "layers", which keeps what every layer has (norms,
    # feed-forward); every other model's attention is in "layers"
    La = cfg.num_attn_layers
    if cfg.is_latent:
        # latent attention: the query's latent, the joint latent with
        # the rotary key, its two expansions a head
        R, Rq = cfg.kv_lora_rank, cfg.q_lora_rank
        nope, Hv = cfg.qk_nope_head_dim, cfg.v_head_dim
        attn = {
            "w_dq": w(next(keys), La, D, Rq),
            "q_norm": {"scale": jnp.ones((La, Rq), pdt)},
            "w_uq": w(next(keys), La, Rq, Nq, cfg.qk_head_dim),
            "w_dkv": w(next(keys), La, D, cfg.latent_row),
            "kv_norm": {"scale": jnp.ones((La, R), pdt)},
            "w_uk": w(next(keys), La, R, Nq, nope),
            "w_uv": w(next(keys), La, R, Nq, Hv),
            "wo": w(next(keys), La, Nq, Hv, D),
        }
    else:
        attn = {
            "wq": w(next(keys), La, D, Nq, H),
            "wk": w(next(keys), La, D, Kv, H),
            "wv": w(next(keys), La, D, Kv, H),
            "wo": w(next(keys), La, Nq, H, D),
        }
    if cfg.attn_gate:
        attn["wg"] = w(next(keys), La, D, Nq, H)
    layers: Params = {
        "ln1": {"scale": jnp.full((L, D), ln, pdt)},
        "ln2": {"scale": jnp.full((L, D), ln, pdt)},
    }
    if cfg.sandwich_norm:
        # the norms BEHIND the sublayers, seeded at (2L)^-1/2 as the
        # family scales them by depth: with ones every sublayer would
        # add a vector of the stream's own size (stream_seed)
        for sub in ("ln1_post", "ln2_post"):
            layers[sub] = {"scale": jnp.full((L, D), (2 * L) ** -0.5, pdt)}
    if not cfg.layer_types:
        layers["attn"] = attn
    if cfg.qk_norm:
        attn["q_norm"] = {"scale": jnp.ones((La, H), pdt)}
        attn["k_norm"] = {"scale": jnp.ones((La, H), pdt)}
    if cfg.qk_norm_wide:
        attn["q_norm"] = {"scale": jnp.ones((La, Nq, H), pdt)}
        attn["k_norm"] = {"scale": jnp.ones((La, Kv, H), pdt)}
    if cfg.has_indexer:
        Ni, Hi = cfg.index_heads, cfg.index_head_dim
        layers["index"] = {
            # beside latent attention the index queries read the query
            # latent (index_proj)
            "w_qi": w(next(keys), L, cfg.q_lora_rank or D, Ni, Hi),
            "w_ki": w(next(keys), L, D, Hi),
            "w_w": w(next(keys), L, D, Ni),
            "k_norm": {"scale": jnp.ones((L, Hi), pdt),
                       "bias": jnp.zeros((L, Hi), pdt)},
        }
    if cfg.use_bias:
        layers["ln1"]["bias"] = jnp.zeros((L, D), pdt)
        layers["ln2"]["bias"] = jnp.zeros((L, D), pdt)
        attn.update(
            bq=jnp.zeros((La, Nq, H), pdt), bk=jnp.zeros((La, Kv, H), pdt),
            bv=jnp.zeros((La, Kv, H), pdt), bo=jnp.zeros((La, D), pdt),
        )
    # a model of experts with leading dense layers (first_k_dense)
    # stacks each kind of feed-forward apart, top-level: "dense" and
    # "sparse" (ffn_run); every other model's is in "layers"
    Ld = cfg.first_k_dense
    sparse = {} if Ld else layers
    dense = {} if Ld else layers
    if cfg.routed:
        E, Fe, Ls = cfg.num_experts, cfg.expert_width, L - Ld
        # the router ranges over every expert; the expert leaves hold
        # the chip's share where the configuration states one
        Eh = cfg.local_experts
        sparse["moe"] = {
            "router": w(next(keys), Ls, D, E),
            "w_gate": w(next(keys), Ls, Eh, D, Fe),
            "w_up": w(next(keys), Ls, Eh, D, Fe),
            "w_down": w(next(keys), Ls, Eh, Fe, D),
        }
        if cfg.router_bias:
            # a stored buffer, zero in a checkpoint that never balanced;
            # seeded away from zero here so that a program which adds it
            # to the weights, or leaves it out of the choice, shows
            sparse["moe"]["router_bias"] = w(next(keys), Ls, E, std=0.1)
        if cfg.shared_intermediate_size:
            Fs = cfg.shared_intermediate_size
            sparse["shared"] = {
                "w_gate": w(next(keys), Ls, D, Fs),
                "w_up": w(next(keys), Ls, D, Fs),
                "w_down": w(next(keys), Ls, Fs, D),
            }
    if cfg.arch == "gpt2" and not cfg.routed:
        layers["mlp"] = {
            "w_up": w(next(keys), L, D, F), "b_up": jnp.zeros((L, F), pdt),
            "w_down": w(next(keys), L, F, D), "b_down": jnp.zeros((L, D), pdt),
        }
    elif Ld or not cfg.routed:
        n = Ld or L
        dense["mlp"] = {
            "w_gate": w(next(keys), n, D, F),
            "w_up": w(next(keys), n, D, F),
            "w_down": w(next(keys), n, F, D),
        }

    params: Params = {
        "embed": {"tok": w(next(keys), V, D, std=emb_std)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((D,), pdt)},
    }
    if Ld:
        params["dense"], params["sparse"] = dense, sparse
    if cfg.layer_types:
        params["attn"] = attn
    if cfg.recurrent_kind == "linear_attention":
        Ls, Dc, Dv = cfg.num_ssm_layers, cfg.gdn_conv_dim, cfg.gdn_value_width
        Hg = cfg.gdn_heads
        params["gdn"] = {
            # q | k | v | z, then a | b: the second stays out of the
            # int8 quantiser (quant/int8.py), two numbers a head
            "in_proj": w(next(keys), Ls, D, Dc + Dv),
            "ab_proj": w(next(keys), Ls, D, 2 * Hg),
            "conv_w": w(next(keys), Ls, cfg.gdn_conv, Dc, std=0.5),
            "dt_bias": w(next(keys), Ls, Hg),
            "A_log": w(next(keys), Ls, Hg),
            "norm": {"scale": jnp.ones((Ls, cfg.gdn_value_dim), pdt)},
            "out_proj": w(next(keys), Ls, Dv, D),
        }
    if cfg.recurrent_kind == "mamba":
        Lm, Di, Dc = cfg.num_ssm_layers, cfg.ssm_inner, cfg.ssm_conv_dim
        Nh = cfg.ssm_heads
        params["mamba"] = {
            "in_proj": w(next(keys), Lm, D, Di + Dc + Nh),
            "conv_w": w(next(keys), Lm, cfg.ssm_conv, Dc, std=0.5),
            "conv_b": w(next(keys), Lm, Dc),
            "dt_bias": w(next(keys), Lm, Nh),
            "A_log": w(next(keys), Lm, Nh),
            "D": w(next(keys), Lm, Nh, std=1.0),
            "norm": {"scale": jnp.ones((Lm, Di), pdt)},
            "out_proj": w(next(keys), Lm, Di, D),
        }
    if cfg.recurrent_kind == "mamba1":
        Lm, Di, N = cfg.num_ssm_layers, cfg.mamba1_inner, cfg.mamba1_state
        R = cfg.mamba1_dt_rank
        shapes = {
            # u | z; x_proj r | B | C and dt_proj stay out of the int8
            # quantiser (quant/int8.py): a fortieth of the mixer, and
            # what they give is exponentiated
            "in_proj": (Lm, D, 2 * Di),
            "conv_w": (Lm, cfg.mamba1_conv, Di),
            "conv_b": (Lm, Di),
            "x_proj": (Lm, Di, R + 2 * N),
            "dt_proj": (Lm, R, Di),
            "dt_bias": (Lm, Di),
            # held [N, Di], as the state is: channels on the lanes
            "A_log": (Lm, N, Di),
            "D": (Lm, Di),
            "out_proj": (Lm, Di, D),
        }
        params["mamba1"] = {
            name: drawn(next(keys), shape,
                        MAMBA1_SEEDS.get(name, "normal")).astype(pdt)
            for name, shape in shapes.items()}
        if cfg.mamba1_norms:
            for name, n in (("dt_norm", R), ("b_norm", N), ("c_norm", N)):
                params["mamba1"][name] = {"scale": jnp.ones((Lm, n), pdt)}
    if cfg.pos_embedding == "learned":
        params["embed"]["pos"] = w(next(keys), cfg.max_seq_len, D)
    if cfg.arch == "gpt2":
        params["final_norm"]["bias"] = jnp.zeros((D,), pdt)
    if not cfg.tie_embeddings:
        params["lm_head"] = w(next(keys), D, V)
    if cfg.hc_mult:
        # the residual streams' mixing, a sublayer (stream_read): drawn
        # last, so that no other leaf's key moves. b away from zero and
        # alpha small: a seeded model's H_res is a generic doubly
        # stochastic matrix, NOT the identity a trained one starts
        # from, which would hide a wrong mix from every comparison
        n = cfg.hc_mult
        for sub in ("hc1", "hc2"):
            layers[sub] = {
                "phi": w(next(keys), L, n * D, n * (2 + n)),
                "b": w(next(keys), L, n * (2 + n), std=1.0),
                "alpha": jnp.full((L, 3), 0.01, pdt),
            }
    return params


@dataclasses.dataclass(frozen=True)
class Model:
    """Thin handle bundling a config with the functional API."""

    cfg: ModelConfig

    def init(self, key: jax.Array) -> Params:
        return init_params(self.cfg, key)

    def init_cache(self, batch: int, max_seq: int, dtype=None) -> KVCache:
        return init_cache(self.cfg, batch, max_seq, dtype)

    def __call__(self, params: Params, tokens: jax.Array, cache: KVCache,
                 positions: Optional[jax.Array] = None):
        return forward(params, self.cfg, tokens, cache, positions)
