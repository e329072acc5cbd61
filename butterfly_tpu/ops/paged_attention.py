"""Pallas paged attention: decode-step attention over the paged KV pool.

The decode-side hot kernel for continuous batching (BASELINE.json
configs[4]). The reference gather path (cache/paged.py gather_paged_layer)
materializes every slot's full [S_max] K/V view — reading null pages and
unallocated tail pages for short sequences. This kernel instead walks each
slot's block table and touches ONLY its live pages:

* `PrefetchScalarGridSpec`: the layer, the block table and the lengths
  arrive before the body runs, so the K/V BlockSpec *index maps*
  dereference `(layer, table[slot, j])` — the DMA engine streams exactly
  the pages the slot owns, straight from where the WHOLE pool
  [L, P, Kv, page, H] lies in HBM, double-buffered by the Mosaic
  pipeline. This is the TPU analogue of vLLM's CUDA paged-attention
  gather, with the page walk moved into the grid index maps. The layer
  is an index and not a slice because a custom call's operand is a
  buffer: one layer cut out of the pool in XLA is a copy of that layer
  (67 MB of int8 codes at 7B, for keys and again for values) in every
  layer of every step, of which the kernel then reads the live pages.
* grid (slots, pages of the LONGEST live context): per-slot online
  softmax across its pages (f32 scratch, same recurrence as
  ops/flash_attention.py); pages at or past the slot's length are
  predicated off with `pl.when` (their DMA still runs — at one page it
  is cheaper than a branchy pipeline). The second bound is a value, the
  maximum of `lengths`, not max_pages: a predicated-off step is not
  free (its index maps, the pipeline's bookkeeping), and with a table
  sized for max_seq most steps were such.
* Decode has one query token per slot, so the MXU sees [Nq, H] x
  [H, page] per step — small, but the kernel is bandwidth-bound and reads
  ceil(len/page) pages instead of S_max.
* int8 pools: codes stream as-is (half the bytes — the entire point);
  per-vector scales ride along as one lane-aligned [Kv*page] row per
  page and fuse into the dots exactly like models.common.attend does for
  the contiguous int8 cache: K scales multiply the score columns, V
  scales fold into the probs. No dequantized copy is ever materialized.

* A layer that slides (`sliding_window`; not to be confused with the
  write-combined staging `window` below) attends only the last
  `sliding_window` positions. The layer's window rides the scalar
  prefetch beside the layer index, so the layers of one model that
  slide and those that do not share ONE compiled kernel. Pages wholly
  before every live slot's lower bound are skipped by the grid, which
  then STARTS at the first page any slot still reads; a slot's own
  dead pages are predicated off and its first live page is masked.

On the CPU backend the wrapper runs the kernel in interpreter mode (CPU
tests cover the exact kernel path); everywhere else it is compiled
(ops/__init__.py has the rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from butterfly_tpu.ops import note_kernel, resolve_interpret

NEG_INF = -1e30


def _block_update(s, mask, vf, m_ref, l_ref, acc_ref, vs_row):
    """One online-softmax accumulation step shared by the page blocks
    and the window segment: s [Nq, C] masked scores, vf [C, H] values,
    vs_row optional [1, C] V scales folded into the probs."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev, l_prev = m_ref[:], l_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)       # [Nq, C]
    corr = jnp.exp(m_prev - m_new)
    l_ref[:] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    if vs_row is not None:
        p = p * vs_row                                 # V scale into probs
    acc_ref[:] = acc_ref[:] * corr + jnp.dot(
        p, vf, preferred_element_type=jnp.float32)
    m_ref[:] = m_new


def _paged_kernel(layer_ref, table_ref, len_ref, *rest, page: int,
                  kv_heads: int, quant: bool, window: int, sliding: bool):
    """layer_ref [layer] is read by the index maps alone, or with
    `sliding` [layer, sliding_window, first_page]: the layer's sliding
    window (0 = a full layer) and the page the grid's page steps start
    at. The pool's blocks
    arrive as [1, Kv, page, H], the layer dim squeezed. The grid's
    second dim is as long as the longest live context needs (the
    wrapper's dynamic bound), not max_pages. window > 0: one extra
    trailing grid step attends the slot's write-combined window segment
    [Kv, W, H] — staged-but-unflushed K/V at absolute positions
    length..length+win_count-1 — folded into the same online-softmax
    recurrence as the page blocks (the kv_write_combine serving path;
    cache/paged.py window docs)."""
    if window:
        wc_ref, *rest = rest
    q_ref, k_ref, v_ref, *rest = rest
    ks_ref = vs_ref = wk_ref = wv_ref = wks_ref = wvs_ref = None
    if quant:
        ks_ref, vs_ref, *rest = rest
    if window:
        wk_ref, wv_ref, *rest = rest
        if quant:
            wks_ref, wvs_ref, *rest = rest
    o_ref, m_ref, l_ref, acc_ref = rest
    slot = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    npages = nj - 1 if window else nj
    length = len_ref[slot]
    # the slot's lower bound: the query sits at the last of its `total`
    # positions and attends position c only where total - 1 - c < sw
    first, lo = 0, None
    if sliding:
        first = layer_ref[2]
        total = length + (wc_ref[slot] if window else 0)
        lo = jnp.where(layer_ref[1] > 0, total - layer_ref[1], 0)
    jp = first + j                 # the page this step attends

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live = (j < npages) & (jp * page < length)
    if sliding:
        live = live & ((jp + 1) * page > lo)

    @pl.when(live)
    def _compute():
        # Mosaic-friendly GQA: ONE 2D matmul against the flattened
        # [Kv*page, H] block, with cross-group scores masked off. The
        # Kv-fold column redundancy is tiny (Kv*page cols) and keeps
        # everything on the plain MXU path (batched matmuls with
        # mismatched batch dims don't lower). The pool's [Kv, page, H]
        # block collapses its two leading dims for free (address
        # arithmetic only), so column c = kv*page + p — the same
        # kv-major order the flat scale rows use.
        q = q_ref[0].astype(jnp.float32)               # [Nq, H]
        kf = k_ref[0].astype(jnp.float32).reshape(kv_heads * page, -1)
        vf = v_ref[0].astype(jnp.float32).reshape(kv_heads * page, -1)
        Nq, H = q.shape
        G = Nq // kv_heads
        scale = jax.lax.rsqrt(jnp.asarray(H, jnp.float32))

        s = jnp.dot(q, kf.T, preferred_element_type=jnp.float32)
        if quant:
            # per-column K scale (scores = q . (codes*scale) done
            # output-side — same associativity as attend()). [1, C]
            # broadcasts over the Nq sublanes.
            s = s * ks_ref[0]
        s = s * scale
        cols = jax.lax.broadcasted_iota(jnp.int32, (Nq, kv_heads * page), 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, (Nq, kv_heads * page), 0)
        col_kv, col_p = cols // page, cols % page
        group_ok = col_kv == rows // G                 # head n <-> kv n//G
        pos = jp * page + col_p
        mask = group_ok & (pos < length)
        if sliding:
            mask = mask & (pos >= lo)
        _block_update(s, mask, vf, m_ref, l_ref, acc_ref,
                      vs_ref[0] if quant else None)

    if window:
        @pl.when(j == nj - 1)
        def _window():
            # the window segment is one more "page" of width W at
            # positions >= length, masked by the slot's staged count —
            # identical recurrence, kv-major flat columns c = kv*W + w
            q = q_ref[0].astype(jnp.float32)
            kf = wk_ref[0].astype(jnp.float32).reshape(kv_heads * window, -1)
            vf = wv_ref[0].astype(jnp.float32).reshape(kv_heads * window, -1)
            Nq, H = q.shape
            G = Nq // kv_heads
            scale = jax.lax.rsqrt(jnp.asarray(H, jnp.float32))
            s = jnp.dot(q, kf.T, preferred_element_type=jnp.float32)
            if quant:
                s = s * wks_ref[0]
            s = s * scale
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (Nq, kv_heads * window), 1)
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (Nq, kv_heads * window), 0)
            col_kv, col_w = cols // window, cols % window
            mask = (col_kv == rows // G) & (col_w < wc_ref[slot])
            if sliding:
                mask = mask & (length + col_w >= lo)
            _block_update(s, mask, vf, m_ref, l_ref, acc_ref,
                          wvs_ref[0] if quant else None)

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] /
                    jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


@jax.named_scope("attn")
def paged_attention_sharded(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, layer,
                            page_table: jax.Array, lengths: jax.Array,
                            k_scale_pages: jax.Array = None,
                            v_scale_pages: jax.Array = None,
                            win_k: jax.Array = None,
                            win_v: jax.Array = None,
                            win_count: jax.Array = None,
                            win_k_scale: jax.Array = None,
                            win_v_scale: jax.Array = None,
                            sliding_window=None) -> jax.Array:
    """Mesh-aware paged attention for meshed serving (SURVEY.md §7 stage 6).

    shard_map (flash_attention.shard_kernel: manual over every mesh
    axis) with the operands laid out as the paged partitioner lays them
    out (parallel/partition.py paged_cache_specs): slots over `data`, q/kv
    heads over `tensor`; the layer and page-id dims stay replicated (any
    slot may reference any page), so the whole pool enters as it lies,
    with no movement. A `tensor` shard of the flat [Kv*page] scale dim
    is the same contiguous kv-group chunk as the code pool's Kv shard, so
    one spec set covers both. Each shard walks its own slots' block
    tables with the unmodified kernel — purely local, no collectives.

    Returns None when a live multi-device Auto mesh is present but no
    axis can shard the operands — the caller must use the gather path
    (see flash_attention_sharded for the opaque-custom-call rationale);
    with no mesh at all this is exactly `paged_attention`.

    win_k/win_v [S, Kv, W, H] (+ win_k/v_scale [S, Kv, W] iff quant) +
    win_count [S]: the write-combined window segment (kv_write_combine)
    — slots shard over `data` with q/table/lengths, kv-heads over
    `tensor` with the pools.

    sliding_window: the layer's window out of its pattern (a traced
    scalar, 0 = a full layer; None = the model has no pattern and the
    kernel compiles without it). It rides beside `layer` to every shard.
    """
    from jax.sharding import PartitionSpec as P

    from butterfly_tpu.ops.flash_attention import (live_auto_mesh,
                                                   shard_kernel,
                                                   shardable_axes)

    S, Nq, H = q.shape
    Kv = k_pages.shape[2]          # pools are [L, P, Kv, page, H]
    d, t = shardable_axes(S, Nq, Kv)
    if d is None and t is None and live_auto_mesh():
        return None
    note_kernel("paged" + ("_int8" if k_scale_pages is not None else "")
                + ("_win" if win_k is not None else ""),
                resolve_interpret(None))
    kv_spec = P(None, None, t, None, None)
    in_specs = [P(d, t, None), kv_spec, kv_spec, P(), P(d, None), P(d)]
    args = [q, k_pages, v_pages, jnp.asarray(layer, jnp.int32), page_table,
            lengths]
    # the operands only some callers have, by paged_attention's names
    named = {}
    if k_scale_pages is not None:
        named.update(k_scale_pages=(k_scale_pages, P(None, None, t)),
                     v_scale_pages=(v_scale_pages, P(None, None, t)))
    if win_k is not None:
        win_spec = P(d, t, None, None)
        named.update(win_k=(win_k, win_spec), win_v=(win_v, win_spec),
                     win_count=(win_count, P(d)))
        if win_k_scale is not None:
            named.update(win_k_scale=(win_k_scale, P(d, t, None)),
                         win_v_scale=(win_v_scale, P(d, t, None)))
    if sliding_window is not None:
        named.update(sliding_window=(
            jnp.asarray(sliding_window, jnp.int32), P()))

    def _kernel(*a):
        return paged_attention(*a[:6], **dict(zip(named, a[6:])))

    fn = shard_kernel(_kernel if named else paged_attention,
                      in_specs=(*in_specs, *(s for _, s in named.values())),
                      out_specs=P(d, t, None))
    return fn(*args, *(v for v, _ in named.values()))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    layer, page_table: jax.Array, lengths: jax.Array,
                    k_scale_pages: jax.Array = None,
                    v_scale_pages: jax.Array = None,
                    win_k: jax.Array = None,
                    win_v: jax.Array = None,
                    win_count: jax.Array = None,
                    win_k_scale: jax.Array = None,
                    win_v_scale: jax.Array = None,
                    sliding_window=None,
                    interpret: bool | None = None) -> jax.Array:
    """Single-token attention over each slot's paged KV.

    q: [slots, Nq, H] (the one decode token per slot, post-rope);
    k_pages/v_pages: [L, P, Kv, page, H], the WHOLE pool, every layer's,
    as it lies in HBM; layer: int32 scalar, the layer to attend (it
    rides the scalar prefetch, so only that layer's live pages are ever
    fetched; a caller that holds one layer's [P, Kv, page, H] passes
    `pages[None]` and layer 0, a free reshape);
    page_table: [slots, max_pages] int32; lengths: [slots] int32 —
    number of cache tokens INCLUDING the just-written current token;
    k/v_scale_pages: [L, P, Kv*page] f32 per-vector scales iff the pool
    holds int8 codes. Returns [slots, Nq, H].

    Write-combined window (kv_write_combine): win_k/win_v [S, Kv, W, H]
    hold each slot's staged-but-unflushed K/V (pool representation —
    int8 codes with win_k/v_scale [S, Kv, W] when the pool is
    quantized), at absolute positions lengths[s]..lengths[s] +
    win_count[s] - 1; `lengths` is then the FLUSHED pool length only
    and win_count INCLUDES the just-staged current token. The segment
    is one extra grid step folded into the same online-softmax
    recurrence as the page blocks (its DMA is one [Kv, W, H] block per
    slot — the staged run never round-trips through the pool).

    sliding_window (int32 scalar, may be traced; None = none): the
    slot's one query, at the last of its lengths (+ win_count)
    positions, attends only the last `sliding_window` of them; 0 = all.
    """
    S, Nq, H = q.shape
    L, Pp, Kv, page, H2 = k_pages.shape
    max_pages = page_table.shape[1]
    quant = k_scale_pages is not None
    window = 0 if win_k is None else win_k.shape[2]
    interpret = resolve_interpret(interpret)
    layer = jnp.asarray(layer, jnp.int32)
    # The page steps of the grid end with the longest live context, not
    # with max_seq: a step past every slot's length fetches the table's
    # tail (the null page) and attends nothing, yet costs its index
    # maps and the pipeline's bookkeeping, and a slot's table is mostly
    # tail (at 32 slots of 2048 with contexts of a few hundred tokens,
    # nine steps in ten). The bound is a value, so one compiled kernel
    # serves every length.
    least = 0 if window else 1
    npages = jnp.clip(-(-jnp.max(lengths) // page), least, max_pages)
    meta = [layer.reshape(1)]
    sliding = sliding_window is not None
    if sliding:
        # the page steps START where the first live slot's lower bound
        # lies: a page wholly before every slot's window is no step
        sw = jnp.asarray(sliding_window, jnp.int32)
        total = lengths + (win_count if window else 0)
        lo = jnp.where(sw > 0, jnp.maximum(total - sw, 0), 0)
        first = jnp.min(jnp.where(total > 0, lo // page, max_pages))
        first = jnp.clip(first, 0, npages - least)
        npages = npages - first
        meta += [sw.reshape(1), first.reshape(1)]

    # scalar-prefetch operands: (layer[, sliding window, first page],
    # table, lengths[, win_count]) — the index maps see them all; the
    # pool maps clamp the page to the table (the trailing window step
    # re-fetches the last page, unused)
    npre = 4 if window else 3

    def page_of(s, j, ly, t):
        return t[s, jnp.minimum((ly[2] if sliding else 0) + j,
                                max_pages - 1)]

    def pool_map(s, j, ly, t, ln, *wc):
        return (ly[0], page_of(s, j, ly, t), 0, 0, 0)

    def pool_scale_map(s, j, ly, t, ln, *wc):
        return (page_of(s, j, ly, t), 0, 0)

    def slot_map(s, j, ly, t, ln, *wc):
        return (s, 0, 0)

    def win_map(s, j, ly, t, ln, *wc):
        return (s, 0, 0, 0)

    # the layer dim is squeezed out of the block (None): the body sees
    # the [1, Kv, page, H] page it always saw
    in_specs = [
        pl.BlockSpec((1, Nq, H), slot_map),
        pl.BlockSpec((None, 1, Kv, page, H), pool_map),
        pl.BlockSpec((None, 1, Kv, page, H), pool_map),
    ]
    args = [q, k_pages, v_pages]
    if quant:
        # The scales alone are cut to the layer here, in XLA, and not
        # fetched from the whole pool by the index map. Mosaic requires
        # a block's minor-two dims to tile (8, 128) or equal the
        # array's: a (1, C) block of [.., P, C] does neither, (1, 1, C)
        # of [.., P, 1, C] matches the array exactly. But that reshape
        # is no bitcast on the chip (the tiled layout pads P to eights,
        # and the TPU compiler keeps f32[L, P, C] with L, not P, second
        # minor), so whatever enters it is copied: one layer's rows
        # (2 MB at 7B), where the whole scale pool would be 67 MB, for
        # keys and again for values, in every layer of every step.
        def layer_scales(a):
            return jax.lax.dynamic_index_in_dim(
                a, layer, 0, keepdims=False).reshape(Pp, 1, Kv * page)

        in_specs += [
            pl.BlockSpec((1, 1, Kv * page), pool_scale_map),
            pl.BlockSpec((1, 1, Kv * page), pool_scale_map),
        ]
        args += [layer_scales(k_scale_pages), layer_scales(v_scale_pages)]
    if window:
        in_specs += [
            pl.BlockSpec((1, Kv, window, H), win_map),
            pl.BlockSpec((1, Kv, window, H), win_map),
        ]
        args += [win_k, win_v]
        if quant:
            in_specs += [
                pl.BlockSpec((1, 1, Kv * window), slot_map),
                pl.BlockSpec((1, 1, Kv * window), slot_map),
            ]
            args += [win_k_scale.reshape(S, 1, Kv * window),
                     win_v_scale.reshape(S, 1, Kv * window)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=npre,
        grid=(S, npages + (1 if window else 0)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Nq, H), slot_map),
        scratch_shapes=[
            pltpu.VMEM((Nq, 1), jnp.float32),
            pltpu.VMEM((Nq, 1), jnp.float32),
            pltpu.VMEM((Nq, H), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, page=page, kv_heads=Kv,
                               quant=quant, window=window, sliding=sliding)
    # (one operand: the layer's own [1], a free reshape, as it always was)
    prefetch = [jnp.concatenate(meta) if sliding else meta[0], page_table,
                lengths]
    if window:
        prefetch.append(win_count)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Nq, H), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, *args)
