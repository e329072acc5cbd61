"""Pallas flash attention (TPU/Mosaic): blockwise causal self-attention.

The prefill-side hot kernel (SURVEY.md §2.2 C4/C5 "hand-written kernels go
in Pallas — the TPU-idiomatic replacement for the CUDA kernels the north
star attributes to the original design"). Design:

* grid (B, Nq, Tq/BQ, S/BK); the last axis is a reduction ("arbitrary")
  dimension — the out block's index map ignores it, so the same out tile
  stays VMEM-resident while K/V blocks stream through, and the online-
  softmax state (m, l, acc f32 scratch) carries across it.
* Causality works on absolute positions (q_pos >= k_pos); blocks entirely
  in the future contribute nothing (their exp() underflows to 0 via the
  -inf mask — no branch divergence, MXU stays busy on the diagonal).
* GQA: q head n reads k/v head n // (Nq/Kv) via the k/v index maps — no
  materialized head broadcast.
* Warm-prefix prefill (ISSUE 13): chunk continuations / prefix-cache
  resumes hand the kernel the CACHED context (a gathered pool view or a
  contiguous cache slice, float or int8 codes + scales) as extra
  reduction-axis blocks AHEAD of the causal fresh-chunk blocks, per-row
  count-masked at the scalar-prefetched `start` — the append-to-KV-
  history attention shape online softmax was built for, replacing the
  dense O(T*S) warm fallback.
* On the CPU backend the wrapper runs the same kernel in interpreter
  mode, so CPU tests validate the exact kernel code path numerics;
  everywhere else it is compiled (ops/__init__.py has the rule).

Used by the engine for fresh AND warm multi-token prefills
(cfg.attn_impl="flash"); decode-side paged attention lives in
ops/paged_attention.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from butterfly_tpu.ops import (note_kernel, resolve_interpret,
                               sublane_multiple)

NEG_INF = -1e30


def _block_update(s, mask, vf, m_ref, l_ref, acc_ref, vs_row=None):
    """One online-softmax accumulation step shared by the fresh-chunk
    blocks and the cached-prefix segment (the same recurrence
    ops/paged_attention.py uses for its page/window blocks): s [BQ, C]
    raw scores, mask [BQ, C] (True = attend), vf [C, H] values, vs_row
    optional [1, C] V scales folded into the probs (int8 prefix)."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev, l_prev = m_ref[:], l_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[:] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    if vs_row is not None:
        p = p * vs_row                                 # V scale into probs
    acc_ref[:] = acc_ref[:] * corr + jnp.dot(
        p, vf, preferred_element_type=jnp.float32)
    m_ref[:] = m_new


def _in_window(q_pos, k_pos, sw_ref):
    """A layer's sliding window on absolute (or equally offset)
    positions: q attends k only where q - k < sw; sw 0 = the layer is
    full. sw_ref: the scalar-prefetched [1] window."""
    sw = sw_ref[0]
    return (sw <= 0) | (q_pos - k_pos < sw)


def _flash_kernel(*refs, bq: int, bk: int, seq_len: int, causal: bool,
                  sliding: bool):
    """sliding: the first ref is the scalar-prefetched sliding window
    of the layer (a value: layers that slide and layers that do not
    share this one compiled kernel)."""
    sw_ref = None
    if sliding:
        sw_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # k block (reduction axis)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # [BQ, H]
    k = k_ref[0, 0].astype(jnp.float32)            # [BK, H]
    v = v_ref[0, 0].astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.asarray(q.shape[-1], jnp.float32))
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_len                          # padded keys
    if causal:
        mask = mask & (q_pos >= k_pos)
    if sliding:
        mask = mask & _in_window(q_pos, k_pos, sw_ref)
    _block_update(s, mask, v, m_ref, l_ref, acc_ref)

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[:] /
                       jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def _flash_warm_kernel(start_ref, *rest,
                       bq: int, bk: int, bp: int, np_blocks: int,
                       seq_len: int, quant: bool, sliding: bool):
    """Warm-prefix flash prefill kernel (ISSUE 13): the reduction axis
    runs `np_blocks` cached-prefix blocks — read from the contiguous
    cache view, masked per row by the scalar-prefetched `start` (the
    count of live cached tokens; garbage past it never contributes) —
    AHEAD of the causal fresh-chunk blocks, all sharing one
    online-softmax state (`_block_update`, PR 12's window-segment
    pattern). Every valid prefix position precedes every query's
    absolute position (queries sit at start..start+T-1), so the prefix
    needs only the `< start` count mask, no causal triangle. Blocks
    entirely past a row's `start` skip their compute via `pl.when`
    (the DMA still runs, like the paged kernel's dead-page blocks).

    quant: the prefix arrives as int8 codes with per-vector scales
    (the pool representation) — K scales multiply the score columns
    output-side, V scales fold into the probs, exactly like
    models.common.attend / the paged kernel's int8 blocks. The fresh
    chunk is always float (the caller mirrors the cache's
    quantize-dequantize there for operand parity with the dense path).

    sliding: a second prefetched scalar, the layer's sliding window. A
    prefix position c is then attended by the query at start + t only
    where start + t - c < window, and a prefix block wholly before
    every query's window skips its compute like a block past `start`.
    """
    sw_ref = None
    if sliding:
        sw_ref, *rest = rest
    q_ref, k_ref, v_ref, pk_ref, pv_ref, *rest = rest
    pks_ref = pvs_ref = None
    if quant:
        pks_ref, pvs_ref, *rest = rest
    o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # reduction axis: prefix then fresh
    nj = pl.num_programs(3)
    start = start_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live = (j < np_blocks) & (j * bp < start)
    if sliding:
        # the block's last column against the first query's lower bound
        lo = jnp.where(sw_ref[0] > 0, start + i * bq - sw_ref[0] + 1, 0)
        live = live & ((j + 1) * bp > lo)

    @pl.when(live)
    def _prefix():
        q = q_ref[0, 0].astype(jnp.float32)        # [BQ, H]
        kf = pk_ref[0, 0].astype(jnp.float32)      # [BP, H]
        vf = pv_ref[0, 0].astype(jnp.float32)
        scale = jax.lax.rsqrt(jnp.asarray(q.shape[-1], jnp.float32))
        s = jnp.dot(q, kf.T, preferred_element_type=jnp.float32)
        if quant:
            s = s * pks_ref[0, 0]                  # [1, BP] K scale columns
        s = s * scale
        cols = j * bp + jax.lax.broadcasted_iota(jnp.int32, (bq, bp), 1)
        mask = cols < start
        if sliding:
            q_pos = start + i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bp), 0)
            mask = mask & _in_window(q_pos, cols, sw_ref)
        _block_update(s, mask, vf, m_ref, l_ref, acc_ref,
                      pvs_ref[0, 0] if quant else None)

    @pl.when(j >= np_blocks)
    def _fresh():
        jf = j - np_blocks
        q = q_ref[0, 0].astype(jnp.float32)
        kf = k_ref[0, 0].astype(jnp.float32)
        vf = v_ref[0, 0].astype(jnp.float32)
        scale = jax.lax.rsqrt(jnp.asarray(q.shape[-1], jnp.float32))
        s = jnp.dot(q, kf.T, preferred_element_type=jnp.float32) * scale
        # chunk-relative causality: absolute positions share the row's
        # start offset, so the relative triangle is exact
        q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = jf * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (k_pos < seq_len) & (q_pos >= k_pos)
        if sliding:
            mask = mask & _in_window(q_pos, k_pos, sw_ref)
        _block_update(s, mask, vf, m_ref, l_ref, acc_ref)

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[:] /
                       jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def _auto_axes(mesh) -> set:
    """Axis names of the ambient mesh still under GSPMD (Auto) control."""
    from jax.sharding import AxisType
    return {n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t == AxisType.Auto}


def shardable_axes(batch: int, nq: int, kv: int):
    """(data_axis, tensor_axis) of the ambient mesh usable to shard an
    attention operand set: `data` must divide the batch/slot dim, `tensor`
    must divide both head counts; an axis is skipped when absent, size 1,
    or already Manual from an enclosing shard_map (e.g. the pipeline's
    `stage`). Shared eligibility rule for both kernel wrappers."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None, None
    auto = _auto_axes(mesh)
    d = "data" if ("data" in auto and mesh.shape["data"] > 1
                   and batch % mesh.shape["data"] == 0) else None
    t = "tensor" if ("tensor" in auto and mesh.shape["tensor"] > 1
                     and nq % mesh.shape["tensor"] == 0
                     and kv % mesh.shape["tensor"] == 0) else None
    return d, t


def shard_kernel(fn, in_specs, out_specs):
    """`fn` (a kernel call) under the ambient mesh, or `fn` itself where
    no mesh axis is left to the partitioner.

    The shard_map names EVERY mesh axis, not only the axes the specs
    shard over: Mosaic refuses a kernel in a program that leaves any
    mesh axis — even one of size 1 — to the automatic partitioner
    ("Mosaic kernels cannot be automatically partitioned"; only the TPU
    lowering checks, so interpret mode on the CPU never sees it), and
    it judges by the innermost shard_map alone, so inside the
    pipeline's `stage` body or an SP `seq` body the kernel's own wrap
    names those already-Manual axes again. An axis the specs do not
    name sees its operands replicated, which is what GSPMD would have
    done with them."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not _auto_axes(mesh):
        return fn
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(mesh.axis_names), check_vma=False)


def live_auto_mesh() -> bool:
    """True when the ambient mesh has any multi-device axis still under
    GSPMD (Auto) control — a bare pallas_call traced there would be an
    opaque custom call the partitioner can't shard."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return False
    return any(mesh.shape[n] > 1 for n in _auto_axes(mesh))


@jax.named_scope("attn")
def flash_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                            causal: bool = True,
                            prefix_k: jax.Array = None,
                            prefix_v: jax.Array = None,
                            prefix_len: jax.Array = None,
                            prefix_k_scale: jax.Array = None,
                            prefix_v_scale: jax.Array = None,
                            sliding_window=None) -> jax.Array:
    """Mesh-aware flash attention (SURVEY.md §7 stages 4/6).

    A pallas_call is an opaque custom call GSPMD cannot partition, so under
    an active mesh we wrap the kernel in `shard_map` (shard_kernel: manual
    over every mesh axis), sharding the operands the way the partitioner
    did: batch over `data`, heads over `tensor` (parallel/partition.py
    puts q-heads/kv-heads there via the column-parallel wq/wk/wv).
    Attention is purely local to a (batch, head) shard — each shard runs
    the unmodified kernel on its slice, no collectives. Axes that don't
    divide see replicated operands; axes already Manual from an enclosing
    shard_map (e.g. the pipeline's `stage`) are left alone; with no mesh
    at all this is exactly `flash_attention`.

    Returns None when a live multi-device Auto mesh is present but no
    axis can shard the operands: the caller MUST fall back to its dense
    path there (a bare pallas_call under GSPMD is an opaque custom call
    — the failure mode the engines' old mesh-disables-kernels guard
    existed to prevent).

    Warm-prefix prefill (ISSUE 13): prefix_k/prefix_v + prefix_len give
    the kernel a cached-context segment ahead of the fresh chunk (see
    flash_attention). The cache/scale operands shard on the same axes —
    batch/slots over `data`, kv heads over `tensor`
    (parallel/partition.py warm_prefix_specs, matching the pool
    sharding paged_cache_specs assigns).

    Mixed-dispatch note (ISSUE 18): the fused mixed block does NOT call
    this prefill entry point — inside the scan every lane (decode OR
    prefill chunk) attends through the per-step paged/window attention
    of the decode program, with per-slot lengths/cursors doing the
    masking. This kernel serves the contiguous engine's prefill, the
    seq-parallel lane's chunks and, where a ModelConfig says
    attn_impl="flash", cache/paged.py paged_attend's multi-token
    branches (a packed step's chunk with the window off, the
    speculative verify).

    sliding_window: the layer's window out of its pattern (a traced
    scalar, 0 = a full layer; None = the model has no pattern and the
    kernels compile without it), replicated to every shard.
    """
    from jax.sharding import PartitionSpec as P

    B, T, Nq, H = q.shape
    Kv = k.shape[2]
    d, t = shardable_axes(B, Nq, Kv)
    if d is None and t is None and live_auto_mesh():
        return None
    note_kernel("flash" + ("_warm" if prefix_k is not None else "")
                + ("_int8" if prefix_k_scale is not None else ""),
                resolve_interpret(None))
    spec = P(d, None, t, None)
    sw, sw_spec = (), ()
    if sliding_window is not None:
        sw, sw_spec = (jnp.asarray(sliding_window, jnp.int32),), (P(),)
    if prefix_k is None:
        def _fresh(q, k, v, *sw):
            return flash_attention(q, k, v, causal=causal,
                                   sliding_window=sw[0] if sw else None)

        fn = shard_kernel(_fresh, in_specs=(spec, spec, spec) + sw_spec,
                          out_specs=spec)
        return fn(q, k, v, *sw)
    # lazy: partition imports models.common at module level, which now
    # imports this module — an import here would close the cycle
    from butterfly_tpu.parallel.partition import warm_prefix_specs
    quant = prefix_k_scale is not None
    args = [q, k, v, prefix_k, prefix_v, prefix_len]
    if quant:
        args += [prefix_k_scale, prefix_v_scale]

    def _warm(q, k, v, pk, pv, plen, *rest):
        kw = {}
        if quant:
            kw = dict(prefix_k_scale=rest[0], prefix_v_scale=rest[1])
        if sw:
            kw["sliding_window"] = rest[-1]
        return flash_attention(q, k, v, causal=causal, prefix_k=pk,
                               prefix_v=pv, prefix_len=plen, **kw)

    fn = shard_kernel(
        _warm, in_specs=(spec, spec, spec) + warm_prefix_specs(d, t, quant)
        + sw_spec, out_specs=spec)
    return fn(*args, *sw)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128,
                    interpret: bool | None = None,
                    prefix_k: jax.Array = None,
                    prefix_v: jax.Array = None,
                    prefix_len: jax.Array = None,
                    prefix_k_scale: jax.Array = None,
                    prefix_v_scale: jax.Array = None,
                    sliding_window=None) -> jax.Array:
    """Blockwise (flash) attention over fresh Q/K/V.

    q: [B, T, Nq, H]; k/v: [B, T, Kv, H] (same T: self-attention).
    Returns [B, T, Nq, H] in q.dtype. Softmax/accum in f32.

    Warm-prefix prefill (ISSUE 13): prefix_k/prefix_v hand the kernel a
    CACHED-CONTEXT segment attended ahead of the (causal) fresh chunk —
    the append-to-KV-history shape chunked/warm prefill needs, in the
    same representation models.common.attend consumes:

    * float view [B, Sp, Kv, H] (a gathered pool view or a contiguous
      cache slice), or
    * int8 codes [B, Kv, Sp, H] with per-vector scales
      prefix_k_scale/prefix_v_scale [B, Kv, Sp] dequantized in-kernel.

    prefix_len [B] int32 is each row's live cached-token count
    (scalar-prefetched; positions at or past it — recycled-buffer
    garbage, batch padding rows, the chunk's own already-written copy —
    never contribute). Queries sit at absolute positions
    prefix_len[b] + 0..T-1, so `causal` must be True.

    sliding_window (int32 scalar, may be traced; None = none): a query
    attends a key only where their positions are less than this apart;
    0 = no bound. It rides the scalar prefetch, so one compiled kernel
    serves the layers of a model that slide and those that do not.
    """
    B, T, Nq, H = q.shape
    Kv = k.shape[2]
    G = Nq // Kv
    interpret = resolve_interpret(interpret)

    # Block shapes must keep the sublane dim a whole number of Mosaic
    # tiles (8 rows of f32, 16 of bf16): odd T like 20 would otherwise
    # produce 20xH blocks; padding below already handles T < block.
    sub = sublane_multiple(q.dtype)
    bq = min(block_q, -(-T // sub) * sub)
    bk = min(block_k, -(-T // sub) * sub)
    Tq = -(-T // bq) * bq
    Tk = -(-T // bk) * bk

    qt = jnp.moveaxis(q, 2, 1)                      # [B, Nq, T, H]
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Tq - T), (0, 0)))
    kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Tk - T), (0, 0)))
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, Tk - T), (0, 0)))

    if prefix_k is not None:
        if not causal:
            raise ValueError("warm-prefix flash attention is causal-only")
        out = _flash_warm_call(qt, kt, vt, prefix_k, prefix_v, prefix_len,
                               prefix_k_scale, prefix_v_scale, T=T, bq=bq,
                               bk=bk, block_k=block_k, G=G,
                               interpret=interpret,
                               sliding_window=sliding_window)
        return jnp.moveaxis(out[:, :, :T, :], 1, 2)  # [B, T, Nq, H]

    sliding = sliding_window is not None
    # index maps take the prefetched window, where there is one, last
    q_spec = pl.BlockSpec((1, 1, bq, H), lambda b, n, i, j, *_: (b, n, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, H),
                           lambda b, n, i, j, *_: (b, n // G, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=int(sliding),
        grid=(B, Nq, Tq // bq, Tk // bk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),       # running max
            pltpu.VMEM((bq, 1), jnp.float32),       # running denom
            pltpu.VMEM((bq, H), jnp.float32),       # accumulator
        ],
    )
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, seq_len=T,
                               causal=causal, sliding=sliding)
    prefetch = [jnp.asarray(sliding_window, jnp.int32).reshape(1)] \
        if sliding else []
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Nq, Tq, H), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*prefetch, qt, kt, vt)
    return jnp.moveaxis(out[:, :, :T, :], 1, 2)     # [B, T, Nq, H]


def _flash_warm_call(qt, kt, vt, prefix_k, prefix_v, prefix_len,
                     prefix_k_scale, prefix_v_scale, *, T: int, bq: int,
                     bk: int, block_k: int, G: int, interpret: bool,
                     sliding_window=None):
    """Build + dispatch the warm-prefix pallas_call. qt/kt/vt arrive
    head-major and padded ([B, N, Tq/Tk, H]); returns [B, Nq, Tq, H].

    The prefix canonicalizes to kv-major [B, Kv, Sp, H] (the int8 pool
    order; the float view moveaxes into it, the same relayout the q/k/v
    operands already pay) and pads Sp to the prefix block. The per-row
    `start` vector rides as the one scalar-prefetch operand so the
    BlockSpec index maps and the in-kernel masks see it before the body
    runs (the paged kernel's PrefetchScalarGridSpec pattern); the
    layer's sliding window, where the model has one, rides beside it."""
    B, Nq, Tq, H = qt.shape
    Kv = kt.shape[1]
    quant = prefix_k_scale is not None
    if quant:
        pk, pv = prefix_k, prefix_v            # [B, Kv, Sp, H] codes
    else:
        pk = jnp.moveaxis(prefix_k, 2, 1)      # [B, Sp, Kv, H] -> kv-major
        pv = jnp.moveaxis(prefix_v, 2, 1)
    Sp = pk.shape[2]
    sub = sublane_multiple(pk.dtype)    # 32 rows for an int8 prefix
    bp = min(block_k, -(-Sp // sub) * sub)
    Sp_pad = -(-Sp // bp) * bp
    np_blocks = Sp_pad // bp
    nf = kt.shape[2] // bk
    pk = jnp.pad(pk, ((0, 0), (0, 0), (0, Sp_pad - Sp), (0, 0)))
    pv = jnp.pad(pv, ((0, 0), (0, 0), (0, Sp_pad - Sp), (0, 0)))

    def q_map(b, n, i, j, *_):
        return (b, n, i, 0)

    def k_map(b, n, i, j, *_):
        # prefix steps clamp to fresh block 0 (DMA runs, block unused)
        return (b, n // G, jnp.clip(j - np_blocks, 0, nf - 1), 0)

    def p_map(b, n, i, j, *_):
        # fresh steps clamp to the last prefix block (unused)
        return (b, n // G, jnp.minimum(j, np_blocks - 1), 0)

    def ps_map(b, n, i, j, *_):
        return (b, n // G, 0, jnp.minimum(j, np_blocks - 1))

    in_specs = [
        pl.BlockSpec((1, 1, bq, H), q_map),
        pl.BlockSpec((1, 1, bk, H), k_map),
        pl.BlockSpec((1, 1, bk, H), k_map),
        pl.BlockSpec((1, 1, bp, H), p_map),
        pl.BlockSpec((1, 1, bp, H), p_map),
    ]
    args = [qt, kt, vt, pk, pv]
    if quant:
        # [B, Kv, Sp] -> [B, Kv, 1, Sp] (free bitcast): a (1, 1, bp)
        # block of the 3-D array would put a size-1 sublane against Kv;
        # (1, 1, 1, bp) of the 4-D form matches the array (the paged
        # kernel's flat-scale-row trick)
        pks = jnp.pad(prefix_k_scale, ((0, 0), (0, 0), (0, Sp_pad - Sp)))
        pvs = jnp.pad(prefix_v_scale, ((0, 0), (0, 0), (0, Sp_pad - Sp)))
        in_specs += [
            pl.BlockSpec((1, 1, 1, bp), ps_map),
            pl.BlockSpec((1, 1, 1, bp), ps_map),
        ]
        args += [pks.reshape(B, Kv, 1, Sp_pad),
                 pvs.reshape(B, Kv, 1, Sp_pad)]
    sliding = sliding_window is not None
    prefetch = [prefix_len.astype(jnp.int32)]
    if sliding:
        prefetch.append(jnp.asarray(sliding_window, jnp.int32).reshape(1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, Nq, Tq // bq, np_blocks + nf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bq, H), q_map),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),       # running max
            pltpu.VMEM((bq, 1), jnp.float32),       # running denom
            pltpu.VMEM((bq, H), jnp.float32),       # accumulator
        ],
    )
    kernel = functools.partial(_flash_warm_kernel, bq=bq, bk=bk, bp=bp,
                               np_blocks=np_blocks, seq_len=T, quant=quant,
                               sliding=sliding)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Nq, Tq, H), qt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*prefetch, *args)
