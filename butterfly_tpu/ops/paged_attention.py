"""Pallas paged attention: decode-step attention over the paged KV pool.

The decode-side hot kernel for continuous batching (BASELINE.json
configs[4]). The reference gather path (cache/paged.py gather_paged_layer)
materializes every slot's full [S_max] K/V view — reading null pages and
unallocated tail pages for short sequences. This kernel instead walks each
slot's block table and touches ONLY its live pages:

* the WHOLE pool [L, P, Kv, page, H] stays where it lies in HBM (memory
  space ANY); the layer, the block table and the lengths ride the scalar
  prefetch (`PrefetchScalarGridSpec`), and the kernel copies the pages
  the slot owns, `(layer, table[slot, j])`, itself. This is the TPU
  analogue of vLLM's CUDA paged-attention gather. The layer is an index
  and not a slice because a custom call's operand is a buffer: one layer
  cut out of the pool in XLA is a copy of that layer (67 MB of int8
  codes at 7B, for keys and again for values) in every layer of every
  step, of which the kernel then reads the live pages.
* grid (slots): a slot loops over ITS OWN live pages in chunks, a
  dynamic trip count from its length, so a short stream costs a short
  loop and nobody visits the tail of a table sized for max_seq nor
  waits for the longest context. A chunk is up to `pages_per_chunk`
  page copies (one DMA a page and pool: the pages of a stream lie
  anywhere) into one of two VMEM buffers, issued and awaited by a ROLLED
  loop over the chunk's LIVE pages, and the next chunk's copies are in
  flight while this one is multiplied. The chunk's size comes from the
  page's bytes against VMEM (`_pages_per_chunk`); the lowered body is
  the same size whatever it is (a serving program holds this body once
  for each of its traced calls, and traces and lowers it at every
  start: tests/test_kernels.py holds the jaxpr's size). Until PR 46 the
  grid was (slots, pages of the LONGEST live context), one pipelined
  block copy, one grid step's bookkeeping and two small products for
  every 16-token page, which ran at a seventh of the memory's rate
  (PERF.md, PRs 40-41); ops/latent_attention.py walks its pages the
  same way, its copies rolled by groups of eight since PR 50.
* a chunk is multiplied SUB_PAGES pages an online-softmax step (float32
  scores, maxima and sums carried as the loop's values), a page ONE
  product of all Nq heads against its flattened [Kv*page, H] rows with
  the cross-group columns masked, the step a batch of such products:
  the MXU sees a page's rows as one tile either way, and a page's flat
  scale row [1, Kv*page] lies along the lanes as the pool holds it.
  Both products take their operands in the wider of the query's dtype
  and the pool's: bfloat16 against bfloat16 rows or int8 codes is one
  pass whose products are exact in the float32 they are summed in.
* int8 pools: codes stream as-is (half the bytes — the entire point);
  per-vector scales ride along as one lane-aligned [Kv*page] row per
  page and fuse into the dots exactly like models.common.attend does for
  the contiguous int8 cache: K scales multiply the score columns, V
  scales fold into the probs. No dequantized copy is ever materialized.

* A layer that slides (`sliding_window`; not to be confused with the
  write-combined staging `window` below) attends only the last
  `sliding_window` positions. The layer's window rides the scalar
  prefetch beside the layer index, so the layers of one model that
  slide and those that do not share ONE compiled kernel. A slot's loop
  STARTS at the page its own lower bound falls in, and that page is
  masked in part.

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

from butterfly_tpu.ops import (note_kernel, resolve_interpret,
                               sublane_multiple)
from butterfly_tpu.ops.window_stage import in_hbm, window_step

NEG_INF = -1e30
#: bytes of VMEM the two buffers of keys and the two of values may take
VMEM_BUFFERS = 4 << 20
#: pages one chunk holds at most
MAX_PAGES_PER_CHUNK = 32
#: pages one online-softmax step multiplies (256 positions of 16): a step
#: is a chain (scores, maximum, exp, sums) the next one waits for, so few
#: large steps beat many small ones (8 pages a step read 8 % slower on
#: the chip, 4 pages 12-26 %, 32 pages 17 % at contexts of 170: PERF.md)
SUB_PAGES = 16


def fits(k_pages: jax.Array, q_dtype) -> bool:
    """Can the kernel serve this pool [L, P, Kv, page, H]? Compiled, a
    page is whole sublane tiles of the dtype its rows are multiplied in
    (a chunk's [pages, Kv, page, H] collapses to rows without moving
    anything) and a row is whole lanes; interpreted (the CPU backend)
    any pool will do. Any other pool takes the `jnp` gather."""
    return resolve_interpret(None) or (
        k_pages.shape[3] % sublane_multiple(_product_dtype(
            q_dtype, k_pages.dtype)) == 0 and k_pages.shape[4] % 128 == 0)


def _product_dtype(q_dtype, pool_dtype):
    """What both products' operands are: the wider of the query's dtype
    and the pool's (int8 codes are whole numbers under 128, exact in
    either): bfloat16 against a bfloat16 or int8 pool is ONE pass of the
    operands as they are stored, their products exact in the float32
    they are summed in."""
    if jnp.issubdtype(pool_dtype, jnp.integer):
        return jnp.dtype(q_dtype)
    return jnp.promote_types(q_dtype, pool_dtype)


def _pages_per_chunk(kv_heads: int, page: int, head_dim: int, dtype) -> int:
    """Pages one chunk holds: what two buffers of keys and two of values
    may take of VMEM (VMEM_BUFFERS), a power of two, at most
    MAX_PAGES_PER_CHUNK (a chunk is copied by a rolled loop, so its size
    costs no code: it is how much of a stream is in flight)."""
    one = kv_heads * page * head_dim * jnp.dtype(dtype).itemsize
    n = max(1, VMEM_BUFFERS // (4 * one))
    return min(MAX_PAGES_PER_CHUNK, 1 << (n.bit_length() - 1))


def _update(q, k, v, mask, carry, ks, vs, scale: float):
    """One online-softmax step over b blocks of C columns: q [b, Nq, H],
    k and v [b, C, H] (the product dtype), mask [b, Nq, C], ks and vs
    [b, 1, C] float32 scales of int8 codes or None: K scales multiply
    the score columns, V scales fold into the probabilities. carry
    (m [Nq, 1], l [Nq, 1], acc [Nq, H]) -> the same, float32."""
    m_prev, l_prev, acc = carry
    # DEFAULT precision, said: one pass of the operands as they are,
    # whatever the ambient matmul precision asks of float32 (Mosaic
    # refuses a float32 contraction of bfloat16 operands)
    one_pass = jax.lax.Precision.DEFAULT
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            precision=one_pass,
                            preferred_element_type=jnp.float32)
    if ks is not None:
        s = s * ks
    s = jnp.where(mask, s * scale, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(jnp.max(s, axis=0), axis=-1,
                                        keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)           # [b, Nq, C]
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(jnp.sum(p, axis=0), axis=-1,
                                    keepdims=True)
    if vs is not None:
        p = p * vs
    pv = jax.lax.dot_general(p.astype(v.dtype), v,
                             (((2,), (1,)), ((0,), (0,))),
                             precision=one_pass,
                             preferred_element_type=jnp.float32)
    return m_new, l_new, acc * corr + jnp.sum(pv, axis=0)


def _paged_kernel(meta_ref, table_ref, len_ref, *rest, page: int,
                  kv_heads: int, pages_per_chunk: int, quant: bool,
                  window: int, sliding: bool, ring: bool = False):
    """One grid step is one slot. meta_ref [layer] or, with `sliding`,
    [layer, sliding_window] (0 = a full layer); its LAST entry is the
    layer's index in the window's leaves (the pool's own where the
    caller gave no other). ring: the table is a ring of its R entries,
    position p's page in entry (p // page) % R. The pools k_ref, v_ref
    [L, P, Kv, page, H] lie in HBM; sc_ref [P, 2, lanes] is the layer's
    K and V scale rows of an int8 pool, a page's two side by side. The
    slot's live pages, from the page its lower bound falls in to the
    page its length ends in, are copied `pages_per_chunk` at a time into
    one of two buffers by a ROLLED loop (the body is the same size
    whatever the chunk) while the chunk before is multiplied, SUB_PAGES
    pages an online-softmax step. window > 0: the slot's write-combined
    window segment [Kv, W, H], staged-but-unflushed K/V at absolute
    positions length .. length + win_count - 1, is more steps of the
    same recurrence, as many as hold what is staged (the
    kv_write_combine serving path; cache/paged.py window docs). The
    window's leaves come WHOLE, [L, S, Kv, W, H], and the block a grid
    step sees is (layer, slot)'s, the layer out of the prefetched
    scalars as the pool's is; an int8 window's scales come as they are
    stored, a step's one flat kv-major row [W/ws, Kv*ws]."""
    if window:
        wc_ref, *rest = rest
    q_ref, k_ref, v_ref, *rest = rest
    sc_ref = wk_ref = wv_ref = wks_ref = wvs_ref = sbuf = None
    if quant:
        sc_ref, *rest = rest
    if window:
        wk_ref, wv_ref, *rest = rest
        if quant:
            wks_ref, wvs_ref, *rest = rest
    o_ref, kbuf, vbuf, *rest = rest
    if quant:
        sbuf, *rest = rest
    sem, par = rest
    slot = pl.program_id(0)
    layer = meta_ref[0]
    max_pages = table_ref.shape[1]
    n = pages_per_chunk
    sub = min(n, SUB_PAGES)
    Nq, H = q_ref.shape[1:]
    G = Nq // kv_heads
    C = kv_heads * page
    dt = _product_dtype(q_ref.dtype, k_ref.dtype)
    scale = H ** -0.5

    @pl.when(slot == 0)
    def _clear():
        # a step multiplies SUB_PAGES pages though fewer were copied:
        # what lies behind them is masked, and must be numbers
        for buf in (kbuf, vbuf, sbuf):
            if buf is not None:
                buf[...] = jnp.zeros_like(buf)

    def span(s):
        """A slot's length, its lower bound, the first page it reads and
        how many: the query sits at the last of its `total` positions
        and attends position c only where total - 1 - c < the layer's
        sliding window."""
        length, lo = len_ref[s], 0
        if sliding:
            total = length + (wc_ref[s] if window else 0)
            lo = jnp.where(meta_ref[1] > 0,
                           jnp.maximum(total - meta_ref[1], 0), 0)
        first = lo // page
        return length, lo, first, jnp.maximum(
            (length + page - 1) // page - first, 0)

    def copies(s, c, b, go):
        """Start (go) or await the page copies of slot s's chunk c into
        buffer b: as many as the chunk has live pages."""
        _, _, first, npages = span(s)

        def one(i, _):
            at = first + c * n + i
            pid = table_ref[s, at % max_pages if ring
                            else jnp.minimum(at, max_pages - 1)]
            dmas = [pltpu.make_async_copy(k_ref.at[layer, pid],
                                          kbuf.at[b, i], sem.at[b]),
                    pltpu.make_async_copy(v_ref.at[layer, pid],
                                          vbuf.at[b, i], sem.at[b])]
            if quant:
                dmas.append(pltpu.make_async_copy(
                    sc_ref.at[pid], sbuf.at[b, i], sem.at[b]))
            for dma in dmas:
                dma.start() if go else dma.wait()
            return 0
        jax.lax.fori_loop(0, jnp.minimum(n, npages - c * n), one, 0)

    length, lo, first, npages = span(slot)
    nchunks = (npages + n - 1) // n
    # A slot's first chunk is on its way before its grid step begins:
    # the slot before starts it beside its own last chunk (slot 0 its
    # own, here), so a short context does not wait out a copy's latency
    # slot after slot. `par` says which buffer it went to.
    after = jnp.minimum(slot + 1, pl.num_programs(0) - 1)
    more = slot + 1 < pl.num_programs(0)

    @pl.when(slot == 0)
    def _first():
        par[0] = 0
        copies(0, 0, 0, True)

    b0 = par[0]
    par[0] = (b0 + nchunks) % 2

    @pl.when((nchunks == 0) & more)
    def _pass_on():
        copies(after, 0, b0, True)

    q = q_ref[...].astype(dt)                               # [1, Nq, H]
    # Mosaic-friendly GQA: a page is ONE product against its flattened
    # [Kv*page, H] rows, cross-group scores masked off (column c =
    # kv*page + p, the kv-major order the flat scale rows have); a step
    # is a batch of such products. The pool's [Kv, page, H] collapses
    # its leading dims for free (address arithmetic only).
    def group_ok(width):
        shape = (1, Nq, kv_heads * width)
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        cols = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        return cols // width == rows // G                  # head n <-> kv n//G

    own = group_ok(page)
    qs = jnp.broadcast_to(q, (sub, Nq, H))
    blk = jax.lax.broadcasted_iota(jnp.int32, (sub, 1, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (sub, 1, C), 2) % page

    def chunk(c, carry):
        b = (b0 + c) % 2
        last = c + 1 == nchunks

        @pl.when(jnp.logical_not(last) | more)
        def _next():
            copies(jnp.where(last, after, slot), jnp.where(last, 0, c + 1),
                   1 - b, True)

        copies(slot, c, b, False)

        def step(j, carry):
            at = pl.ds(pl.multiple_of(j * sub, sub), sub)
            pos = (first + c * n + j * sub + blk) * page + col
            live = (pos < length) & (pos >= lo)
            return _update(
                qs, kbuf[b, at].astype(dt).reshape(sub, C, H),
                vbuf[b, at].astype(dt).reshape(sub, C, H), live & own, carry,
                sbuf[b, at, 0:1, :C] if quant else None,
                sbuf[b, at, 1:2, :C] if quant else None, scale)

        return jax.lax.fori_loop(
            0, (jnp.minimum(n, npages - c * n) + sub - 1) // sub, step, carry)

    carry = (jnp.full((Nq, 1), -jnp.inf, jnp.float32),
             jnp.zeros((Nq, 1), jnp.float32),
             jnp.zeros((Nq, H), jnp.float32))
    carry = jax.lax.fori_loop(0, nchunks, chunk, carry)
    if window:
        # the window segment is more blocks of the same recurrence, of
        # width `ws` at positions length + w, as many as hold the slot's
        # staged count (a decode row stages a token a step: a few of the
        # window's hundreds): kv-major flat columns c = kv*ws + w
        ws = window_step(window)
        staged = wc_ref[slot]
        w = jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, kv_heads * ws), 2) % ws
        own_w = group_ok(ws)
        # (a [Kv, ws, H] block collapses to rows as whole tiles)
        via = dt if ws % sublane_multiple(dt) == 0 else jnp.float32

        def wstep(j, carry):
            at = pl.ds(pl.multiple_of(j * ws, ws), ws)
            live = (j * ws + w < staged) & (length + j * ws + w >= lo)

            def flat(ref):
                return ref[0, :, at, :].astype(via).reshape(
                    1, kv_heads * ws, H).astype(dt)

            return _update(q, flat(wk_ref), flat(wv_ref), live & own_w,
                           carry,
                           wks_ref[0, pl.ds(j, 1)][None] if quant else None,
                           wvs_ref[0, pl.ds(j, 1)][None] if quant else None,
                           scale)

        carry = jax.lax.fori_loop(0, (staged + ws - 1) // ws, wstep, carry)
    _, l, acc = carry
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


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
                            sliding_window=None, win_layer=None,
                            ring: bool = False) -> jax.Array:
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
    axis can shard the operands, or when the pool's pages are no whole
    tiles (`fits`) — the caller must use the gather path (see
    flash_attention_sharded for the opaque-custom-call rationale); with
    no mesh at all this is exactly `paged_attention`.

    win_k/win_v [L, S, Kv, W, H] (+ win_k/v_scale [L, S, W/ws, Kv*ws]
    iff quant) + win_count [S]: the write-combined window, whole, of
    which `layer` is read (kv_write_combine) — slots shard over `data`
    with q/table/lengths, kv-heads over `tensor` with the pools (a
    `tensor` shard of a scale row's flat kv-major dim is its heads').

    sliding_window: the layer's window out of its pattern (a traced
    scalar, 0 = a full layer; None = the model has no pattern and the
    kernel compiles without it). It rides beside `layer` to every shard.

    ring, win_layer: paged_attention's (a pool of the sliding layers
    alone whose table is a ring; the layer's index in the window).
    """
    from jax.sharding import PartitionSpec as P

    from butterfly_tpu.ops.flash_attention import (live_auto_mesh,
                                                   shard_kernel,
                                                   shardable_axes)

    S, Nq, H = q.shape
    Kv = k_pages.shape[2]          # pools are [L, P, Kv, page, H]
    d, t = shardable_axes(S, Nq, Kv)
    if (d is None and t is None and live_auto_mesh()) \
            or not fits(k_pages, q.dtype):
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
        win_spec = P(None, d, t, None, None)
        named.update(win_k=(win_k, win_spec), win_v=(win_v, win_spec),
                     win_count=(win_count, P(d)))
        if win_k_scale is not None:
            named.update(win_k_scale=(win_k_scale, P(None, d, None, t)),
                         win_v_scale=(win_v_scale, P(None, d, None, t)))
    if sliding_window is not None:
        named.update(sliding_window=(
            jnp.asarray(sliding_window, jnp.int32), P()))
    if win_layer is not None:
        named.update(win_layer=(jnp.asarray(win_layer, jnp.int32), P()))

    def _kernel(*a):
        return paged_attention(*a[:6], ring=ring, **dict(zip(named, a[6:])))

    fn = shard_kernel(_kernel if named or ring else paged_attention,
                      in_specs=(*in_specs, *(s for _, s in named.values())),
                      out_specs=P(d, t, None))
    return fn(*args, *(v for v, _ in named.values()))


@functools.partial(jax.jit, static_argnames=("interpret", "ring"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    layer, page_table: jax.Array, lengths: jax.Array,
                    k_scale_pages: jax.Array = None,
                    v_scale_pages: jax.Array = None,
                    win_k: jax.Array = None,
                    win_v: jax.Array = None,
                    win_count: jax.Array = None,
                    win_k_scale: jax.Array = None,
                    win_v_scale: jax.Array = None,
                    sliding_window=None, win_layer=None,
                    ring: bool = False,
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

    Write-combined window (kv_write_combine): win_k/win_v
    [L, S, Kv, W, H], the WHOLE window as it rides the layer scan (a
    caller that holds one layer's [S, Kv, W, H] passes `win[None]`
    beside a pool of that one layer), hold each slot's
    staged-but-unflushed K/V (pool representation — int8 codes with
    win_k/v_scale [L, S, W/ws, Kv*ws], a step's scales one flat kv-major
    row as cache/paged.py stores them, when the pool is quantized), at
    absolute positions lengths[s]..lengths[s] + win_count[s] - 1;
    `lengths` is then the FLUSHED pool length only and win_count
    INCLUDES the just-staged current token. The segment is more steps
    of the same online-softmax recurrence as the chunks of pages (its
    DMA is one [Kv, W, H] block per slot, (layer, slot)'s, pipelined by
    its BlockSpec — the staged run never round-trips through the pool,
    and no layer is cut out of the window).

    sliding_window (int32 scalar, may be traced; None = none): the
    slot's one query, at the last of its lengths (+ win_count)
    positions, attends only the last `sliding_window` of them; 0 = all.

    ring (static): the pool holds the SLIDING layers alone and
    page_table [slots, R] is a ring: position p's row lies in page
    table[s, (p // page) % R] (cache/paged.py ring_pages: R pages hold
    the window, what is staged and a page more, so a page is rewritten
    only when no query can reach its rows). win_layer: the layer's
    index in the window's leaves where it is not `layer` (the window
    holds every attention layer, a pool by kind its own).
    """
    S, Nq, H = q.shape
    L, Pp, Kv, page, H2 = k_pages.shape
    quant = k_scale_pages is not None
    window = 0 if win_k is None else win_k.shape[3]
    interpret = resolve_interpret(interpret)
    sliding = sliding_window is not None
    # scalar-prefetch operands: (layer[, sliding window], table,
    # lengths[, win_count]). One kernel serves every length and, the
    # window being a value (0 = all), the layers of one model that slide
    # and those that do not.
    meta = [jnp.asarray(layer, jnp.int32).reshape(1)]
    if sliding:
        meta.append(jnp.asarray(sliding_window, jnp.int32).reshape(1))
    if win_layer is not None:
        meta.append(jnp.asarray(win_layer, jnp.int32).reshape(1))
    w_at = len(meta) - 1 if win_layer is not None else 0
    prefetch = [jnp.concatenate(meta), page_table, lengths]
    if window:
        prefetch.append(win_count)

    def slot_map(s, *_):
        return (s, 0, 0)

    def win_map(rank):
        """(layer, slot)'s block of a window leaf of `rank` dims."""
        return lambda s, meta_ref, *_: (meta_ref[w_at], s) + (0,) * (rank - 2)

    n = _pages_per_chunk(Kv, page, H, k_pages.dtype)
    pool = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, Nq, H), slot_map), pool, pool]
    args = [q, k_pages, v_pages]
    scratch = [pltpu.VMEM((2, n, Kv, page, H), k_pages.dtype),
               pltpu.VMEM((2, n, Kv, page, H), v_pages.dtype)]
    if quant:
        # The scales alone are cut to the layer here, in XLA, a page's K
        # row and V row side by side so that ONE copy fetches both, in
        # whole lanes. A row of [L, P, C] is one sublane of a tile: Mosaic
        # copies it only from an operand tiled by rows, and to hand it
        # such a one XLA copies the WHOLE pool (67 MB at 7B; the compile
        # for the chip shows it as a temporary). So what is copied is one
        # layer's rows (4 MB at 7B, 13 us of a call on the chip), for
        # keys and values, in every layer of every step; a scale pool
        # STORED a row a page would read in place (ROADMAP A2 b).
        def layer_scales(a):
            return jax.lax.dynamic_index_in_dim(a, meta[0][0], 0,
                                                keepdims=False)

        rows = jnp.stack([layer_scales(k_scale_pages),
                          layer_scales(v_scale_pages)], axis=1)
        rows = jnp.pad(rows, ((0, 0), (0, 0), (0, -(Kv * page) % 128)))
        in_specs.append(pool)
        args.append(rows)
        scratch.append(pltpu.VMEM((2, n, *rows.shape[1:]), jnp.float32))
    if window:
        if not interpret:
            win_k, win_v, win_k_scale, win_v_scale = in_hbm(
                (win_k, win_v, win_k_scale, win_v_scale))
        # the layer's block of the whole leaf: the layer dim squeezed
        in_specs += [pl.BlockSpec((None, 1, Kv, window, H), win_map(5))] * 2
        args += [win_k, win_v]
        if quant:
            # a step's scales are one flat kv-major row, like a page's,
            # and stored so: read where they lie
            in_specs += [pl.BlockSpec((None, 1, *win_k_scale.shape[2:]),
                                      win_map(4))] * 2
            args += [win_k_scale, win_v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=(S,), in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Nq, H), slot_map),
        scratch_shapes=[*scratch, pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)])
    kernel = functools.partial(_paged_kernel, page=page, kv_heads=Kv,
                               pages_per_chunk=n, quant=quant, window=window,
                               sliding=sliding, ring=ring)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Nq, H), q.dtype),
        # the buffers are cleared at slot 0 and reused slot after slot
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, *args)
