"""Sequence/context parallelism: ring attention + Ulysses (long context).

The reference never mentions long-context mechanisms (SURVEY.md §5: absent
from all 6 files); this realizes the survey's required surface the TPU way:

* **Ring attention** (context parallel): Q/K/V are sequence-sharded over
  the `seq` mesh axis. Each of the N ring steps computes the visiting
  K/V block's *partial flash statistics* — the Pallas online-softmax
  kernel, or its jnp twin on the CPU backend (`ops/ring_attention`,
  ISSUE 20) — folds them into the
  running stats with the associative merge, then rotates K/V (+ their
  positions, + int8 scales) to the next neighbor with `lax.ppermute`.
  On TPU the ring rides neighbor ICI links and the permute overlaps the
  block's kernel. Causality comes from comparing rotated K positions to
  local Q positions, so any chunk order works and no step is skipped
  (static schedule).

* **Ulysses**: `lax.all_to_all` reshards [B, T/N, H_all] -> [B, T, H/N]
  (heads scatter, sequence gathers), runs ordinary full attention on the
  now-complete local sequence for its head group, and reshards back.
  Requires num_kv_heads % N == 0; ring has no such constraint.

* **sp_forward**: whole-model long-context prefill under shard_map manual
  over {'seq'} — norms/MLP/MoE are token-pointwise (trivially sequence-
  parallel), attention uses ring or Ulysses; `tensor`/`data` axes remain
  GSPMD-auto inside, so SP composes with TP. Returns logits and the
  sequence-sharded KV cache (each device keeps the K/V it computed —
  that sharded layout IS the context-parallel cache). Under
  `kv_quant="int8"` each device quantizes its chunk ONCE and every
  attention read goes through codes+scales (dequant-in-kernel, the pool
  representation) — the sharded cache comes back quantized, so a 128k
  prefix costs a quarter of the bf16 HBM.

Masking uses the sanitized-position contract of `ops/ring_attention`:
the ONE predicate everywhere is `k_pos <= q_pos`; invalid key slots
(prompt padding past the real length, unwritten suffix slots) carry
position `INVALID_POS`, so causality, raggedness and padding are a
single comparison with no per-case mask tensors.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from butterfly_tpu.core.config import ModelConfig
from butterfly_tpu.models.common import (
    KVCache, Params, _cast_float, attend, attn_output, embed_tokens,
    ffn_block, final_logits, pre_norm, qkv_proj, quantize_kv,
    indexer_unsupported, ssm_unsupported, streams_unsupported,
    uniform_layers_only, update_cache_layer,
    update_cache_layer_q)
from butterfly_tpu.ops.ring_attention import (
    INVALID_POS, block_stats, finalize_stats, merge_stats, zero_stats)


def ring_stats(q: jax.Array, k: jax.Array, v: jax.Array,
               q_pos: jax.Array, k_pos: jax.Array,
               axis_name: str = "seq",
               k_scale: Optional[jax.Array] = None,
               v_scale: Optional[jax.Array] = None,
               kernel: Optional[bool] = None):
    """Merged (unfinalized) flash stats over all N ring blocks.

    The ring loop of `ring_attention` without the final normalization:
    callers that must fold in ANOTHER key segment (the paged-pool
    prefix of a chunked seq-parallel prefill) merge these stats with
    that segment's before one shared `finalize_stats`.
    """
    B, Tq, Nq, H = q.shape
    N = lax.axis_size(axis_name)
    perm = [(i, (i + 1) % N) for i in range(N)]
    stats = zero_stats(B, Nq, Tq, H)

    def step(carry, _):
        stats, k, v, k_pos, ks, vs = carry
        blk = block_stats(q, k, v, q_pos, k_pos, ks, vs, kernel=kernel)
        stats = merge_stats(stats, blk)
        k, v, k_pos, ks, vs = lax.ppermute(
            (k, v, k_pos, ks, vs), axis_name, perm)
        return (stats, k, v, k_pos, ks, vs), None

    (stats, _, _, _, _, _), _ = lax.scan(
        step, (stats, k, v, k_pos, k_scale, v_scale), None, length=N)
    return stats


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   q_pos: jax.Array, k_pos: jax.Array,
                   axis_name: str = "seq",
                   k_scale: Optional[jax.Array] = None,
                   v_scale: Optional[jax.Array] = None,
                   kernel: Optional[bool] = None) -> jax.Array:
    """Causal GQA over a sequence ring (call inside shard_map).

    q: [B, Tq, Nq, H] local chunk; float k/v: [B, Tk, Kv, H] local
    chunk; int8 k/v: codes [B, Kv, Tk, H] with k_scale/v_scale
    [B, Kv, Tk] (the pool representation — dequantized inside the
    block kernel). q_pos/k_pos: [B, T*] absolute positions, invalid
    keys sanitized to INVALID_POS. Returns [B, Tq, Nq, H].
    """
    return finalize_stats(
        ring_stats(q, k, v, q_pos, k_pos, axis_name, k_scale, v_scale,
                   kernel=kernel), q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      q_pos: jax.Array, axis_name: str = "seq") -> jax.Array:
    """All-to-all head<->sequence reshard + local full causal attention.

    q: [B, T/N, Nq, H]; k/v: [B, T/N, Kv, H]. Needs Nq % N == 0; when
    Kv < N (realistic GQA, e.g. Llama-3 Kv=8 on a 16-way seq axis) and
    N % Kv == 0, KV heads are REPLICATED r = N/Kv times before the
    all_to_all so device d receives the kv head (d // r) its q-head
    block contracts with — the seq axis is no longer capped at Kv, at
    the cost of r x the K/V all_to_all volume. Returns [B, T/N, Nq, H].
    """
    N = lax.axis_size(axis_name)
    B, Tl, Nq, H = q.shape
    Kv = k.shape[2]
    if Kv % N != 0:
        if N % Kv != 0 or Nq % N != 0:
            raise ValueError(
                f"ulysses needs Kv % N == 0 or (N % Kv == 0 and "
                f"Nq % N == 0); got Nq={Nq}, Kv={Kv}, N={N}")
        # head replication: q heads [d*Nq/N, (d+1)*Nq/N) all map to kv
        # head d // r (block size Nq/N divides the GQA group G = Nq/Kv
        # because Kv < N), so repeating each kv head r times puts the
        # right copy on every device after the head-scatter.
        r = N // Kv
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
    # heads scatter (axis 2), sequence gathers (axis 1)
    qq = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kk = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vv = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    # full global positions for the gathered sequence
    pos = lax.all_gather(q_pos, axis_name, axis=1, tiled=True)  # [B, T]
    mask = pos[:, None, :] <= pos[:, :, None]                   # [B,T,T]
    out = attend(qq, kk, vv, mask, None)  # attend() reads only shapes+mask
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


# ---------------------------------------------------------------------------
# Whole-model sequence-parallel prefill
# ---------------------------------------------------------------------------

def sp_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
               mesh: Mesh, impl: str = "ring", kv_quant: str = "none"
               ) -> Tuple[jax.Array, KVCache]:
    """Long-context prefill with activations sharded over `seq`.

    tokens: [B, T] (T divisible by the seq axis). Returns
    (logits [B,T,V] seq-sharded on T, KVCache with S = T seq-sharded —
    int8 codes+scales when kv_quant="int8", sharded over the S dim of
    the kv-major layout).
    """
    uniform_layers_only(cfg, "sequence parallelism")
    indexer_unsupported(cfg, "sequence parallelism")
    ssm_unsupported(cfg, "sequence parallelism")
    streams_unsupported(cfg, "sequence parallelism")
    N = mesh.shape["seq"]
    B, T = tokens.shape
    if T % N != 0:
        raise ValueError(f"seq len {T} not divisible by seq axis {N}")
    if kv_quant not in ("none", "int8"):
        raise ValueError(f"unknown kv quant {kv_quant!r}")
    quant = kv_quant == "int8"

    body = partial(_sp_body, cfg=cfg, impl=impl, quant=quant)
    layer_in = jax.tree.map(lambda _: P(), params["layers"])
    head_in = jax.tree.map(lambda _: P(), {
        k: v for k, v in params.items() if k != "layers"})
    if quant:
        cache_out = (P(None, None, None, "seq", None),   # codes [L,B,Kv,T,H]
                     P(None, None, None, "seq", None),
                     P(None, None, None, "seq"),         # scales [L,B,Kv,T]
                     P(None, None, None, "seq"))
    else:
        cache_out = (P(None, None, "seq"),               # [L,B,T,Kv,H]
                     P(None, None, "seq"))
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(layer_in, head_in, P(None, "seq")),
        out_specs=(P(None, "seq"), cache_out),
        axis_names={"seq"}, check_vma=False)
    logits, cache_parts = fn(params["layers"],
                             {k: v for k, v in params.items()
                              if k != "layers"},
                             tokens)
    length = jnp.full((B,), T, jnp.int32)
    if quant:
        ks, vs, ksc, vsc = cache_parts
        cache = KVCache(k=ks, v=vs, length=length, k_scale=ksc, v_scale=vsc)
    else:
        ks, vs = cache_parts
        cache = KVCache(k=ks, v=vs, length=length)
    return logits, cache


def sp_decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                   positions: jax.Array, prefix: KVCache, suffix: KVCache,
                   mesh: Mesh,
                   prefix_len: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, KVCache]:
    """One decode step consuming sp_forward's sequence-sharded cache.

    The long prefix stays sharded over `seq` exactly where prefill left it
    (never regathered); generated tokens live in a small replicated
    contiguous `suffix` cache. Attention is computed as one online-softmax
    merge (the `ops/ring_attention` stats algebra): each device attends its
    local prefix chunk into partial (m, l, acc), the partials merge across
    the ring with pmax/psum — collectives sized [B,Nq,H], never [B,T,*] —
    and the suffix block folds in locally via the same `merge_stats`.

    int8: when `prefix.quantized`, the suffix cache must be quantized too
    (init_cache(..., quant="int8")) — both segments then read codes +
    scales exactly like the dense int8 reference reads its cache back.

    tokens/positions: [B,1] (positions = prefix length + step).
    Returns (last-token logits [B,V], suffix cache with the new K/V).

    prefix_len [B]: number of REAL prefix tokens per row; prefix slots at
    or past it are masked out. Defaults to prefix.length (no padding).
    generate_long pads prompts up to a multiple of the seq axis, so the
    tail of the sharded prefix holds pad K/V that must not be attended.

    Capacity contract (as for the paged pool, where the host allocator
    guarantees pages): the caller must size the suffix cache for the
    whole decode run — a step past suffix.max_seq would clamp its write
    onto the last slot. Checked eagerly when lengths are concrete.
    """
    uniform_layers_only(cfg, "sequence parallelism")
    indexer_unsupported(cfg, "sequence parallelism")
    ssm_unsupported(cfg, "sequence parallelism")
    streams_unsupported(cfg, "sequence parallelism")
    if not isinstance(suffix.length, jax.core.Tracer):
        if int(jnp.max(suffix.length)) >= suffix.max_seq:
            raise ValueError(
                f"suffix cache full ({suffix.max_seq} slots): size "
                "init_cache(max_seq=...) for the whole decode run")
    if prefix_len is None:
        prefix_len = prefix.length
    quant = prefix.quantized
    if quant != suffix.quantized:
        raise ValueError("prefix and suffix caches must agree on kv_quant")
    body = partial(_sp_decode_body, cfg=cfg, quant=quant)
    layer_in = jax.tree.map(lambda _: P(), params["layers"])
    head = {k: v for k, v in params.items() if k != "layers"}
    head_in = jax.tree.map(lambda _: P(), head)
    if quant:
        seq_kv = P(None, None, None, "seq", None)  # codes [L,B,Kv,T,H]
        seq_sc = P(None, None, None, "seq")        # scales [L,B,Kv,T]
        cache_args = (prefix.k, prefix.v, prefix.k_scale, prefix.v_scale,
                      suffix.k, suffix.v, suffix.k_scale, suffix.v_scale)
        cache_in = (seq_kv, seq_kv, seq_sc, seq_sc, P(), P(), P(), P())
        out_specs = (P(), P(), P(), P(), P())
    else:
        seq_kv = P(None, None, "seq")   # [L,B,T,Kv,H]: local T chunk
        cache_args = (prefix.k, prefix.v, suffix.k, suffix.v)
        cache_in = (seq_kv, seq_kv, P(), P())
        out_specs = (P(), P(), P())
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(layer_in, head_in, P(), P()) + cache_in + (P(), P()),
        out_specs=out_specs,
        axis_names={"seq"}, check_vma=False)
    out = fn(params["layers"], head, tokens, positions, *cache_args,
             suffix.length, prefix_len)
    if quant:
        logits, sk, sv, sks, svs = out
        new_suffix = KVCache(sk, sv, suffix.length + 1,
                             k_scale=sks, v_scale=svs)
    else:
        logits, sk, sv = out
        new_suffix = KVCache(sk, sv, suffix.length + 1)
    return logits, new_suffix


def _sp_decode_body(layers, head, tokens, positions, *rest,
                    cfg: ModelConfig, quant: bool):
    """Per-device decode step (inside shard_map, manual over seq)."""
    if quant:
        pk, pv, pks, pvs, sck, scv, scks, scvs, slen, plen = rest
    else:
        pk, pv, sck, scv, slen, plen = rest
        pks = pvs = scks = scvs = None

    B = tokens.shape[0]
    Smax = sck.shape[3] if quant else sck.shape[2]
    Tl = pk.shape[3] if quant else pk.shape[2]
    x, cos, sin = embed_tokens(head, cfg, tokens, positions)
    compute_dtype = jnp.dtype(cfg.dtype)
    # sanitized key positions, built ONCE outside the layer scan:
    # suffix slot j holds the token written at global position plen + j;
    # slots past slen (this step's write is slot slen itself, visible)
    # and prefix pad slots (generate_long's divisibility padding) are
    # INVALID_POS, so the kernels' single k_pos <= q_pos comparison is
    # the whole mask.
    j = jnp.arange(Smax)
    suf_pos = jnp.where(j[None, :] <= slen[:, None],
                        plen[:, None] + j[None, :], INVALID_POS)  # [B,Smax]
    idx = lax.axis_index("seq")
    gpos = idx * Tl + jnp.arange(Tl)                              # [Tl]
    pre_pos = jnp.where(gpos[None, :] < plen[:, None],
                        gpos[None, :], INVALID_POS)               # [B,Tl]

    def layer(x, scanned):
        if quant:
            lp, pkl, pvl, pksl, pvsl, ck, cv, cks, cvs = scanned
        else:
            lp, pkl, pvl, ck, cv = scanned
            pksl = pvsl = cks = cvs = None
        lp = jax.tree.map(lambda a: _cast_float(a, compute_dtype), lp)
        h = pre_norm(x, lp["ln1"], cfg)
        q, k, v = qkv_proj(h, lp["attn"], cfg, cos, sin)     # q [B,1,Nq,H]
        if quant:
            ck, cv, cks, cvs = update_cache_layer_q(ck, cv, cks, cvs,
                                                    k, v, slen)
        else:
            ck, cv = update_cache_layer(ck, cv, k, v, slen)

        # local prefix chunk -> partial flash stats (Pallas kernel on
        # TPU, jnp twin elsewhere), merged across the seq ring with
        # tiny collectives: [B,Nq,*], never [B,T,*]
        m_i, l_i, acc_i = block_stats(q, pkl, pvl, positions, pre_pos,
                                      pksl, pvsl)
        m_g = lax.pmax(m_i, "seq")
        corr = jnp.exp(m_i - m_g)
        l_g = lax.psum(l_i * corr, "seq")
        acc_g = lax.psum(acc_i * corr[..., None], "seq")

        # suffix block (replicated): same stats helper, local merge
        suf = block_stats(q, ck, cv, positions, suf_pos, cks, cvs)
        out = finalize_stats(merge_stats((m_g, l_g, acc_g), suf), x.dtype)
        x = x + attn_output(out, lp["attn"], cfg)
        x = x + ffn_block(pre_norm(x, lp["ln2"], cfg), lp, cfg)
        if quant:
            return x, (ck, cv, cks, cvs)
        return x, (ck, cv)

    if quant:
        xs = (layers, pk, pv, pks, pvs, sck, scv, scks, scvs)
    else:
        xs = (layers, pk, pv, sck, scv)
    x, new_suffix = lax.scan(layer, x, xs)
    logits = final_logits(head, cfg, x)
    return (logits[:, -1, :],) + new_suffix


def sp_chunk_body(layers, head, tokens, start, *rest, cfg: ModelConfig,
                  quant: bool):
    """Per-device slice of ONE paged long-prompt prefill chunk (inside
    shard_map, manual over `seq`) — the serving-path sibling of
    `_sp_body` (ISSUE 20 move 3).

    tokens: local [B=1, Cl] slice of the (padded) chunk buffer whose
    first token sits at absolute position `start` (scalar — also the
    count of already-flushed pool-prefix tokens). `rest` is the slot's
    REPLICATED gathered pool prefix: (pk, pv) [L,B,S,Kv,H] when float,
    (pk, pv, pks, pvs) codes [L,B,Kv,S,H] + scales [L,B,Kv,S] when the
    pool is int8. Each query attends that prefix locally (replicated →
    plain block_stats, no collective) and the fresh chunk via the seq
    ring; the two partials share one finalize. Chunk padding needs no
    sanitization — pad positions exceed every real query's, so the
    kernels' k_pos <= q_pos drops them — and the pad K/V rows are
    routed to the null page by the caller's scatter. Returns
    (logits [B,Cl,V], per-layer fresh-chunk K/V in pool
    representation: int8 codes+scales when quant, compute-dtype floats
    otherwise).
    """
    uniform_layers_only(cfg, "the sequence-parallel prefill lane")
    indexer_unsupported(cfg, "the sequence-parallel prefill lane")
    ssm_unsupported(cfg, "the sequence-parallel prefill lane")
    streams_unsupported(cfg, "the sequence-parallel prefill lane")
    if quant:
        pk, pv, pks, pvs = rest
    else:
        pk, pv = rest
        pks = pvs = None
    B, Cl = tokens.shape
    S = pk.shape[3] if quant else pk.shape[2]
    idx = lax.axis_index("seq")
    positions = start + idx * Cl + jnp.arange(Cl)[None, :] + jnp.zeros(
        (B, 1), jnp.int32)                                   # [B,Cl] global
    x, cos, sin = embed_tokens(head, cfg, tokens, positions)
    compute_dtype = jnp.dtype(cfg.dtype)
    # sanitized prefix key positions: exactly the flushed tokens
    # (< start) are attendable; null-page slots and the unwritten tail
    # go to INVALID_POS (built ONCE outside the layer scan)
    gpos = jnp.arange(S)[None, :]
    pre_pos = jnp.broadcast_to(
        jnp.where(gpos < start, gpos, INVALID_POS), (B, S))  # [B,S]

    def layer(x, scanned):
        if quant:
            lp, pkl, pvl, pksl, pvsl = scanned
        else:
            lp, pkl, pvl = scanned
            pksl = pvsl = None
        lp = jax.tree.map(lambda a: _cast_float(a, compute_dtype), lp)
        h = pre_norm(x, lp["ln1"], cfg)
        q, k, v = qkv_proj(h, lp["attn"], cfg, cos, sin)
        pre = block_stats(q, pkl, pvl, positions, pre_pos, pksl, pvsl)
        if quant:
            # quantize the local chunk ONCE (the pool representation);
            # fresh-chunk reads go through codes+scales like the dense
            # int8 reference reading its just-written pool back
            kq, ks = quantize_kv(jnp.moveaxis(k, 2, 1))      # [B,Kv,Cl,H]
            vq, vs = quantize_kv(jnp.moveaxis(v, 2, 1))
            fresh = ring_stats(q, kq, vq, positions, positions,
                               k_scale=ks, v_scale=vs)
            kv_out = (kq, vq, ks, vs)
        else:
            fresh = ring_stats(q, k, v, positions, positions)
            kv_out = (k.astype(compute_dtype), v.astype(compute_dtype))
        out = finalize_stats(merge_stats(pre, fresh), x.dtype)
        x = x + attn_output(out, lp["attn"], cfg)
        x = x + ffn_block(pre_norm(x, lp["ln2"], cfg), lp, cfg)
        return x, kv_out

    xs = (layers, pk, pv, pks, pvs) if quant else (layers, pk, pv)
    x, kv = lax.scan(layer, x, xs)
    logits = final_logits(head, cfg, x)
    return logits, kv


def _sp_body(layers, head, tokens, *, cfg: ModelConfig, impl: str,
             quant: bool):
    """Per-device chunk of the model (inside shard_map, manual over seq)."""
    idx = lax.axis_index("seq")
    B, Tl = tokens.shape
    positions = idx * Tl + jnp.arange(Tl)[None, :] + jnp.zeros(
        (B, 1), jnp.int32)                                   # [B,Tl] global
    x, cos, sin = embed_tokens(head, cfg, tokens, positions)
    compute_dtype = jnp.dtype(cfg.dtype)

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(compute_dtype), lp)
        h = pre_norm(x, lp["ln1"], cfg)
        q, k, v = qkv_proj(h, lp["attn"], cfg, cos, sin)
        if quant:
            # quantize the local chunk ONCE (the representation the
            # sharded cache keeps); every attention read then goes
            # through codes+scales, matching what the dense int8
            # reference reads back from its just-written cache.
            kq, ks = quantize_kv(jnp.moveaxis(k, 2, 1))      # [B,Kv,Tl,H]
            vq, vs = quantize_kv(jnp.moveaxis(v, 2, 1))
            if impl == "ring":
                out = ring_attention(q, kq, vq, positions, positions,
                                     k_scale=ks, v_scale=vs)
            else:
                # ulysses gathers full sequences for dense attend; feed
                # it the dequantized values (same operand set, no
                # scale-plumbing through the all_to_alls)
                kf = jnp.moveaxis(kq.astype(jnp.float32) * ks[..., None],
                                  1, 2).astype(compute_dtype)
                vf = jnp.moveaxis(vq.astype(jnp.float32) * vs[..., None],
                                  1, 2).astype(compute_dtype)
                out = ulysses_attention(q, kf, vf, positions)
            kv_out = (kq, vq, ks, vs)
        else:
            if impl == "ring":
                out = ring_attention(q, k, v, positions, positions)
            else:
                out = ulysses_attention(q, k, v, positions)
            kv_out = (k.astype(compute_dtype), v.astype(compute_dtype))
        x = x + attn_output(out, lp["attn"], cfg)
        x = x + ffn_block(pre_norm(x, lp["ln2"], cfg), lp, cfg)
        return x, kv_out

    x, kv = lax.scan(layer, x, layers)
    logits = final_logits(head, cfg, x)
    return logits, kv
