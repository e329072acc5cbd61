"""Pallas ring-attention inner kernel: partial flash stats + merges.

The seq-parallel paths (parallel/sequence.py) used plain jnp einsums
for every K/V block a ring step visits — a full dense score matrix per
step, no online softmax (ISSUE 20). This module factors the per-block
work into the SAME flash-attention recurrence the prefill kernel uses
(`ops/flash_attention.py:_block_update`), exposed as *partial,
unnormalized* statistics so ring steps compose:

    stats = (m [B,Nq,T], l [B,Nq,T], acc [B,Nq,T,H])   all f32

where for the keys visited so far  m = max score,  l = sum exp(s - m),
acc = sum exp(s - m) * v.  Two partials merge associatively
(`merge_stats`) and a final `finalize_stats` normalizes — the standard
online-softmax decomposition, so the ring loop (and the decode path's
cross-device pmax/psum reduction) never rescales V accumulators by a
denominator until every block has been seen.

Masking contract (single mask, no per-case wheres): the only in-block
predicate is  k_pos <= q_pos.  Callers sanitize invalid key positions
(padding, beyond the live prefix, unwritten suffix slots) to
`INVALID_POS` (int32 max) so one causal comparison covers causality,
raggedness and padding at once. Masked-out rows produce m = NEG_INF
(a FINITE -1e30, never -inf), l = 0, acc = 0 — every merge identity
then needs no isinf/NaN guards: exp(NEG_INF - anything) underflows to
an honest 0.

int8: K/V may arrive as pool-representation codes [B,Kv,S,H] with
per-vector scales [B,Kv,S]; the K scale multiplies score columns
output-side and the V scale folds into the probs (dequant-in-kernel,
exactly the warm-prefix flash segment / models.common.attend order).

Two legs with one contract:

* `ring_block_stats` — the Pallas kernel (grid (B, Nq, Tq/bq, S/bk),
  reduction axis "arbitrary", VMEM f32 scratch). Called directly on
  the CPU backend it runs in interpreter mode so CPU tests cover the
  exact kernel numerics.
* `ring_block_stats_ref` — the jnp twin: the CPU path inside
  shard_map and the parity reference.

`block_stats` dispatches between them on the backend
(ops/__init__.py has the rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from butterfly_tpu.ops import (kernels_default, note_kernel,
                               resolve_interpret, sublane_multiple)
from butterfly_tpu.ops.flash_attention import (NEG_INF, _block_update,
                                               shard_kernel, shardable_axes)

#: sanitized "never attend" key position: k_pos <= q_pos is False for
#: every real query position.
INVALID_POS = 2**31 - 1


# ---------------------------------------------------------------------------
# Stats algebra (shared by both legs and the ring/decode merges)
# ---------------------------------------------------------------------------

def zero_stats(B: int, Nq: int, T: int, H: int):
    """Identity element of `merge_stats` (m = finite NEG_INF)."""
    return (jnp.full((B, Nq, T), NEG_INF, jnp.float32),
            jnp.zeros((B, Nq, T), jnp.float32),
            jnp.zeros((B, Nq, T, H), jnp.float32))


def merge_stats(a, b):
    """Merge two partial flash stats over disjoint key sets.

    The running-max correction: both accumulators rescale from their
    own max to the joint max before adding. m is always >= NEG_INF
    (finite), so the exps are well-defined with no isneginf guard —
    a fully-masked partial (m = NEG_INF, l = acc = 0) merges as a
    clean no-op.
    """
    m_a, l_a, acc_a = a
    m_b, l_b, acc_b = b
    m = jnp.maximum(m_a, m_b)
    c_a = jnp.exp(m_a - m)
    c_b = jnp.exp(m_b - m)
    l = l_a * c_a + l_b * c_b
    acc = acc_a * c_a[..., None] + acc_b * c_b[..., None]
    return m, l, acc


def finalize_stats(stats, dtype):
    """Normalize merged stats -> [B, T, Nq, H] attention output."""
    _, l, acc = stats
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 1, 2).astype(dtype)     # [B,Nq,T,H]->[B,T,Nq,H]


def block_stats(q, k, v, q_pos, k_pos, k_scale=None, v_scale=None,
                kernel=None):
    """Backend dispatch: the jnp twin on the CPU backend, the Pallas
    kernel everywhere else.

    The shard_map bodies run the twin on CPU because interpret-mode
    pallas inside shard_map is slow; the kernel leg is covered there by
    calling `ring_block_stats` directly in interpreter mode
    (tests/test_longctx.py parity grid).
    """
    if kernel is None:
        kernel = kernels_default()
    if not kernel:
        return ring_block_stats_ref(q, k, v, q_pos, k_pos, k_scale,
                                    v_scale)
    quant = k_scale is not None
    note_kernel("ring_int8" if quant else "ring", resolve_interpret(None))
    # The SP bodies are shard_maps manual over `seq` alone, so the other
    # mesh axes are still Auto here and the kernel needs its own wrap
    # (shard_kernel): batch over `data`, heads over `tensor`, where they
    # divide — the layout GSPMD gave q/k/v — and replicated otherwise.
    B, _, Nq, _ = q.shape
    d, t = shardable_axes(B, Nq, k.shape[1] if quant else k.shape[2])
    qspec = P(d, None, t, None)
    kspec = P(d, t, None, None) if quant else qspec
    in_specs = (qspec, kspec, kspec, P(d, None), P(d, None)) \
        + ((P(d, t, None),) * 2 if quant else ())
    fn = shard_kernel(
        ring_block_stats, in_specs=in_specs,
        out_specs=(P(d, t, None), P(d, t, None), P(d, t, None, None)))
    return fn(q, k, v, q_pos, k_pos, *((k_scale, v_scale) if quant else ()))


# ---------------------------------------------------------------------------
# jnp twin (reference + fallback)
# ---------------------------------------------------------------------------

def ring_block_stats_ref(q, k, v, q_pos, k_pos, k_scale=None, v_scale=None):
    """jnp reference for one K/V block's partial flash stats.

    q: [B,T,Nq,H]; float k/v: [B,S,Kv,H]; int8 k/v: codes [B,Kv,S,H]
    with k_scale/v_scale [B,Kv,S]. q_pos [B,T], k_pos [B,S] int32 —
    invalid keys sanitized to INVALID_POS. Returns (m, l, acc) as
    [B,Nq,T] / [B,Nq,T] / [B,Nq,T,H] f32, head order n = kv*G + g
    (matches the kernel's n // G head map).
    """
    B, T, Nq, H = q.shape
    quant = k_scale is not None
    Kv = k.shape[1] if quant else k.shape[2]
    G = Nq // Kv
    scale = 1.0 / jnp.sqrt(jnp.asarray(H, jnp.float32))
    qh = jnp.moveaxis(q, 2, 1).reshape(B, Kv, G, T, H)
    kf = k.astype(jnp.float32) if quant else \
        jnp.moveaxis(k, 2, 1).astype(jnp.float32)    # [B,Kv,S,H]
    vf = v.astype(jnp.float32) if quant else \
        jnp.moveaxis(v, 2, 1).astype(jnp.float32)
    s = jnp.einsum("bkgth,bksh->bkgts", qh.astype(jnp.float32), kf,
                   preferred_element_type=jnp.float32)
    if quant:
        s = s * k_scale[:, :, None, None, :]
    s = s * scale
    mask = k_pos[:, None, None, None, :] <= q_pos[:, None, None, :, None]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)                           # [B,Kv,G,T] finite
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    if quant:
        p = p * v_scale[:, :, None, None, :]
    acc = jnp.einsum("bkgts,bksh->bkgth", p, vf,
                     preferred_element_type=jnp.float32)
    return (m.reshape(B, Nq, T), l.reshape(B, Nq, T),
            acc.reshape(B, Nq, T, H))


# ---------------------------------------------------------------------------
# Pallas kernel leg
# ---------------------------------------------------------------------------

def _ring_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, *rest,
                 quant: bool):
    ks_ref = vs_ref = None
    if quant:
        ks_ref, vs_ref, *rest = rest
    m_ref, l_ref, acc_ref, m_sc, l_sc, acc_sc = rest
    j = pl.program_id(3)          # k block (reduction axis)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0].astype(jnp.float32)              # [BQ, H]
    kf = k_ref[0, 0].astype(jnp.float32)             # [BK, H]
    vf = v_ref[0, 0].astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.asarray(q.shape[-1], jnp.float32))
    s = jnp.dot(q, kf.T, preferred_element_type=jnp.float32)
    vs_row = None
    if quant:
        s = s * ks_ref[0, 0]                         # [1, BK] K scale cols
        vs_row = vs_ref[0, 0]
    s = s * scale
    # the ONE mask: sanitized positions (INVALID_POS keys never pass)
    mask = kp_ref[0, 0] <= qp_ref[0, 0]              # [1,BK] vs [BQ,1]
    _block_update(s, mask, vf, m_sc, l_sc, acc_sc, vs_row)

    @pl.when(j == nk - 1)
    def _out():
        # scratch m is >= NEG_INF (finite) once any block ran: masked
        # scores are NEG_INF, not -inf, so max() lifts off the -inf init
        m_ref[0, 0] = m_sc[:]
        l_ref[0, 0] = l_sc[:]
        acc_ref[0, 0] = acc_sc[:]


def ring_block_stats(q, k, v, q_pos, k_pos, k_scale=None, v_scale=None,
                     block_q: int = 128, block_k: int = 128,
                     interpret=None):
    """Pallas leg: same contract as `ring_block_stats_ref`.

    Grid (B, Nq, Tq/bq, S/bk); the last axis streams K/V blocks through
    one VMEM-resident online-softmax state per q tile (the
    flash-attention layout), but writes out raw (m, l, acc) instead of
    normalizing — ring merges happen outside. Positions ride as int32
    planes ([B,1,Tq,1] / [B,1,1,S] so their blocks are 2-D tiles, the
    warm kernel's 4-D scale-row trick); key padding is sanitized to
    INVALID_POS here, so callers only sanitize semantic invalidity.
    """
    B, T, Nq, H = q.shape
    quant = k_scale is not None
    Kv = k.shape[1] if quant else k.shape[2]
    S = k.shape[2] if quant else k.shape[1]
    G = Nq // Kv
    interpret = resolve_interpret(interpret)

    # whole Mosaic tiles: 16 rows of bf16 queries, 32 of int8 codes
    sub_q, sub_k = sublane_multiple(q.dtype), sublane_multiple(k.dtype)
    bq = min(block_q, -(-T // sub_q) * sub_q)
    bk = min(block_k, -(-S // sub_k) * sub_k)
    Tq = -(-T // bq) * bq
    Tk = -(-S // bk) * bk

    qt = jnp.pad(jnp.moveaxis(q, 2, 1), ((0, 0), (0, 0), (0, Tq - T), (0, 0)))
    if quant:
        kt, vt = k, v                                 # already kv-major
    else:
        kt = jnp.moveaxis(k, 2, 1)                    # [B, Kv, S, H]
        vt = jnp.moveaxis(v, 2, 1)
    kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Tk - S), (0, 0)))
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, Tk - S), (0, 0)))
    qp = jnp.pad(q_pos.astype(jnp.int32), ((0, 0), (0, Tq - T)))
    kp = jnp.pad(k_pos.astype(jnp.int32), ((0, 0), (0, Tk - S)),
                 constant_values=INVALID_POS)

    def q_map(b, n, i, j):
        return (b, n, i, 0)

    def kv_map(b, n, i, j, G=G):
        return (b, n // G, j, 0)

    in_specs = [
        pl.BlockSpec((1, 1, bq, H), q_map),
        pl.BlockSpec((1, 1, bk, H), kv_map),
        pl.BlockSpec((1, 1, bk, H), kv_map),
        pl.BlockSpec((1, 1, bq, 1), q_map),
        pl.BlockSpec((1, 1, 1, bk), lambda b, n, i, j: (b, 0, 0, j)),
    ]
    args = [qt, kt, vt,
            qp.reshape(B, 1, Tq, 1), kp.reshape(B, 1, 1, Tk)]
    if quant:
        # [B,Kv,S] -> [B,Kv,1,S]: 4-D form keeps the (1, bk) scale row a
        # real 2-D tile (the warm kernel's sublane trick)
        ks = jnp.pad(k_scale, ((0, 0), (0, 0), (0, Tk - S)))
        vs = jnp.pad(v_scale, ((0, 0), (0, 0), (0, Tk - S)))
        sc_map = functools.partial(lambda b, n, i, j, G=G: (b, n // G, 0, j))
        in_specs += [pl.BlockSpec((1, 1, 1, bk), sc_map),
                     pl.BlockSpec((1, 1, 1, bk), sc_map)]
        args += [ks.reshape(B, Kv, 1, Tk), vs.reshape(B, Kv, 1, Tk)]

    m, l, acc = pl.pallas_call(
        functools.partial(_ring_kernel, quant=quant),
        grid=(B, Nq, Tq // bq, Tk // bk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, 1), q_map),
            pl.BlockSpec((1, 1, bq, 1), q_map),
            pl.BlockSpec((1, 1, bq, H), q_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Nq, Tq, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Nq, Tq, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Nq, Tq, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),         # running max
            pltpu.VMEM((bq, 1), jnp.float32),         # running denom
            pltpu.VMEM((bq, H), jnp.float32),         # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*args)
    return (m[:, :, :T, 0], l[:, :, :T, 0], acc[:, :, :T])
