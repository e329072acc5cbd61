"""Pallas latent paged attention: a decode row's ABSORBED read of its
cached latent rows (models/common.py has the equations).

A latent-attention model caches ONE row a token and layer, [latent |
rotary key] (kv_lora_rank + qk_rope_head_dim values: 576, 1,152 B in
bfloat16, for JoyAI-LLM-Flash), and a decode row attends it as
multi-query attention: Nq query heads of the row's width against the
row, whose first kv_lora_rank values are also the values. This kernel
reads each live row ONCE (ops/paged_attention.py, handed the same pool
as keys and again as values, would fetch it twice):

* the WHOLE pool [L, P, 1, page, R] stays where it lies in HBM
  (memory space ANY); the layer, the block table and the lengths ride
  the scalar prefetch;
* grid (slots): a slot loops over ITS OWN live pages in chunks of
  PAGES_PER_CHUNK pages, a dynamic trip count from its length, so a
  short stream costs a short loop and nobody visits the tail of a table
  sized for max_seq. A chunk is up to PAGES_PER_CHUNK page copies (one
  DMA a page: the pages of a stream lie anywhere) into one of two VMEM
  buffers [chunk, R], issued and awaited by a ROLLED loop over the
  groups of GROUP_PAGES pages that the chunk's LIVE pages fill: a
  group's starts side by side, its wait one; a group the stream has no
  page in is not copied, and the lowered body is the same size whatever
  the chunk (a serving program holds this body once for each of its
  traced calls, and traces and lowers it at every start:
  tests/test_joyai.py holds the jaxpr's size). The next chunk's copies
  are in flight while this one is multiplied, and beside a slot's LAST
  chunk the first chunk of the slot after it, so a short context does
  not wait out a copy's latency slot after slot (ops/paged_attention.py
  is the same walk over two pools with scales and head groups, since
  PR 46; until PR 50 this kernel copied every chunk whole, its 32
  copies unrolled, and awaited a slot's first chunk with nothing beside
  it: PERF.md);
* a page's copy is a dozen scalar operations, and on the chip they, not
  the bytes, bound the call: the wrapper hands the kernel the pool's
  layers end to end and the table flat, so an address is one sum and
  nothing is clamped page by page;
* a chunk's scores are ONE product q [Nq, R] x rows^T and its sum ONE
  product p [Nq, chunk] x rows[:, :rank], bfloat16 operands into
  float32, the online softmax carried in float32 as the loop's values
  (steps by the chunk's live pages read slower at every step size: a
  step is a chain of 0.28 us whatever its width, PERF.md, PR 50);
* the rows of a chunk that the stream does not have are masked to
  probability 0, and what lies there in the buffer is numbers: the
  buffers are cleared at slot 0 and hold copied pages ever after (the
  pool is born zero and only ever written with projections);
* the write-combined window [L, S, 1, W, R] (cache/paged.py: staged
  rows at positions lengths .. lengths + win_count - 1) comes whole, as
  it rides the layer scan; (layer, slot)'s block of it is one more
  chunk, pipelined a slot by its BlockSpec;
* a model whose rows a sparse-attention indexer SELECTS (GLM-5: DSA
  over MLA) reads through the same walk, the selection joining the
  length mask as in ops/sparse_attention.py (PR 51): `sel` [S, S_max]
  comes a slot a block, int32 and a chunk a row, a row that is dead OR
  unselected gets probability 0, and the window's staged rows are
  masked by the selection at their positions (the one or two chunk rows
  they fall in, rotated by the offset inside a chunk, so such a window
  is no wider than a chunk). At a table of a few times index_topk
  nearly every live page holds a selected row (at 2,048 of 7,168 rows
  a page of 16 holds none with probability 0.5 %), so a walk of the
  pages that hold one is this walk. It is a call of its own name,
  `latent_select_attention`: nothing of the unselected read's program
  changes, and a trace tells the two apart.

On the CPU backend the wrapper runs the kernel in interpreter mode;
everywhere else it is compiled (ops/__init__.py has the rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from butterfly_tpu.ops import (note_kernel, resolve_interpret,
                               sublane_multiple)
from butterfly_tpu.ops.sparse_attention import window_selected
from butterfly_tpu.ops.window_stage import in_hbm

NEG_INF = -1e30
#: pages one chunk of the context takes: 32 pages of 16 tokens are 512
#: rows, 590 KB of bfloat16 a buffer, two buffers
PAGES_PER_CHUNK = 32
#: pages whose copies are started side by side and awaited as one: a
#: chunk's copies are a rolled loop over the groups its live pages fill
GROUP_PAGES = 8


def fits(pages: jax.Array, rank: int, select: bool = False,
         window: int = 0) -> bool:
    """Can the kernel serve this pool [L, P, 1, page, Rp]? Compiled, a
    page is whole sublane tiles of the pool's dtype and a row and its
    values are whole lanes (Mosaic copies and slices whole tiles);
    interpreted (the CPU backend) any pool of rows will do. Any other
    pool takes the `jnp` read. select: the read takes a selection, whose
    chunk rows are then whole lanes and no narrower than the
    write-combined window of `window` rows a slot."""
    page = pages.shape[3]
    if pages.shape[2] != 1 or pages.shape[1] < GROUP_PAGES \
            or (select and window > PAGES_PER_CHUNK * page):
        return False
    return resolve_interpret(None) or (
        page % sublane_multiple(pages.dtype) == 0
        and pages.shape[4] % 128 == 0 and rank % 128 == 0
        and not (select and (PAGES_PER_CHUNK * page) % 128))


def _update(q, rows, live, carry, rank: int, scale: float):
    """One online-softmax step over `rows` [C, R] (live [1, C] marks the
    columns that exist): carry (m, l, acc) -> the same, float32."""
    m_prev, l_prev, acc = carry
    # DEFAULT precision, said: one pass of the operands as they are
    # stored, whatever the ambient matmul precision asks of float32
    one_pass = jax.lax.Precision.DEFAULT
    s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                            precision=one_pass,
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(live, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    acc = acc * corr + jnp.dot(p.astype(rows.dtype), rows[:, :rank],
                               precision=one_pass,
                               preferred_element_type=jnp.float32)
    return m_new, l_prev * corr + jnp.sum(p, axis=-1, keepdims=True), acc


def _latent_kernel(layer_ref, table_ref, len_ref, *rest, page: int,
                   pages_per_chunk: int, group_pages: int, max_pages: int,
                   pool_pages: int, rank: int, scale: float, window: int,
                   select: bool = False):
    """One grid step is one slot. The pool lies in HBM, its layers end
    to end [L * pool_pages, page, R]; the table is flat, a slot's
    `max_pages` entries after another's (and a group of page 0 behind
    the last, where they are no whole groups). The slot's live pages are
    copied `pages_per_chunk` at a time into one of two buffers
    [n, page, R] by a ROLLED loop over groups of `group_pages` (the body
    is the same size whatever the chunk) while the chunk before is
    multiplied, one online-softmax step a chunk. window > 0: (layer,
    slot)'s block [W, R] of the write-combined window is one more step,
    its first win_count rows live. select: a chunk's columns are masked
    by its row of the selection sel_ref [1, chunks, n * page] besides,
    and the window's rows by the selection at their positions, length ..
    length + W - 1 (ops/sparse_attention.py's two differences)."""
    if window:
        wc_ref, *rest = rest
    q_ref, *rest = rest
    if select:
        sel_ref, *rest = rest
    pool_ref, *rest = rest
    win_ref = None
    if window:
        win_ref, *rest = rest
    o_ref, buf, sem, par = rest
    slot = pl.program_id(0)
    n, grp = pages_per_chunk, group_pages
    layer_base = layer_ref[0] * pool_pages
    q = q_ref[0]                                           # [Nq, R]
    Nq, R = q.shape

    @pl.when(slot == 0)
    def _clear():
        # a step multiplies the whole buffer though fewer pages were
        # copied: what lies behind them is masked, and must be numbers
        buf[...] = jnp.zeros_like(buf)

    def live_pages(s):
        return jnp.minimum((len_ref[s] + page - 1) // page, max_pages)

    def copies(s, c, b, go):
        """Start (go) or await the page copies of slot s's chunk c into
        buffer b, a group of `grp` pages at a time as far as the chunk
        has live pages: a group's starts side by side, its wait ONE (a
        wait counts bytes, a group's; its descriptor's source is never
        read). The last group's entries past the stream's pages name the
        null page, or any page: rows that are masked."""
        first = s * max_pages + c * n       # in the flat table
        live = jnp.minimum(n, live_pages(s) - c * n)

        def group(g, _):
            at = pl.multiple_of(g * grp, grp)
            if not go:
                pltpu.make_async_copy(pool_ref.at[pl.ds(0, grp)],
                                      buf.at[b, pl.ds(at, grp)],
                                      sem.at[b]).wait()
                return 0
            for i in range(grp):
                pltpu.make_async_copy(
                    pool_ref.at[layer_base + table_ref[first + at + i]],
                    buf.at[b, at + i], sem.at[b]).start()
            return 0
        jax.lax.fori_loop(0, (live + grp - 1) // grp, group, 0)

    length, npages = len_ref[slot], live_pages(slot)
    nchunks = (npages + n - 1) // n
    # A slot's first chunk is on its way before its grid step begins:
    # the slot before starts it beside its own last chunk, so a short
    # context does not wait out a copy's latency slot after slot. Slot 0
    # starts its own, here; a slot with no pages passes the start on to
    # the slot after it. `par` says which buffer it went to.
    after = jnp.minimum(slot + 1, pl.num_programs(0) - 1)
    more = slot + 1 < pl.num_programs(0)

    @pl.when(slot == 0)
    def _first():
        par[0] = 0

    b0 = par[0]
    par[0] = (b0 + nchunks) % 2

    @pl.when((slot == 0) | ((nchunks == 0) & more))
    def _start():
        copies(jnp.where(nchunks > 0, slot, after), 0, b0, True)

    col = jax.lax.broadcasted_iota(jnp.int32, (1, n * page), 1)

    def chunk(c, carry):
        b = (b0 + c) % 2
        last = c + 1 == nchunks

        @pl.when(jnp.logical_not(last) | more)
        def _next():
            copies(jnp.where(last, after, slot), jnp.where(last, 0, c + 1),
                   1 - b, True)

        copies(slot, c, b, False)

        # ONE product over the whole chunk, the rows past the stream's
        # end masked: a step is a chain (scores, maximum, exponential,
        # sums, a second product) of 0.28 us on the chip whatever its
        # width, so steps by the live pages read slower at every size
        # (PERF.md, PR 50). [n, page, R] collapses to rows as whole tiles.
        pos = c * n * page + col
        rows = buf[b].reshape(n * page, R)
        live = pos < length
        if select:
            live = live & (sel_ref[0, pl.ds(c, 1), :] != 0)
        return _update(q, rows, live, carry, rank, scale)

    carry = (jnp.full((Nq, 1), -jnp.inf, jnp.float32),
             jnp.zeros((Nq, 1), jnp.float32),
             jnp.zeros((Nq, rank), jnp.float32))
    carry = jax.lax.fori_loop(0, nchunks, chunk, carry)
    if window:
        wcol = jax.lax.broadcasted_iota(jnp.int32, (1, window), 1)
        staged = win_ref[0, 0]
        live = wcol < wc_ref[slot]
        if select:
            live = live \
                & (window_selected(sel_ref, length, col, window) != 0) \
                & (length + wcol < max_pages * page)
        carry = _update(q, staged, live, carry, rank, scale)
    _, l, acc = carry
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


# The jitted function's name is the Mosaic call's name in a device
# trace (`_latent_attention.N = bf16[S, Nq, rank]`): the benchmark's
# readers tell the read by it (servebench/latent_peaks.py), and it is
# NOT ops/paged_attention.py's, whose share of the busy time another
# metric reads.
@jax.named_scope("attn_latent")
@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def latent_attention(q: jax.Array, pages: jax.Array, layer,
                           page_table: jax.Array, lengths: jax.Array,
                           win: jax.Array = None,
                           win_count: jax.Array = None, *, rank: int,
                           scale: float,
                           interpret: bool | None = None) -> jax.Array:
    """Single-token absorbed attention over each slot's cached latents.

    q: [slots, Nq, R] (models.common.latent_queries of the one decode
    token a slot); pages: [L, P, 1, page, R], the WHOLE pool as it lies;
    layer: int32 scalar; page_table: [slots, max_pages] int32; lengths:
    [slots] int32, the rows of the pool a slot attends (0: none, and
    with no window rows either its output is zeros). rank: the leading
    values of a row that are its "values" (kv_lora_rank); scale: the
    score scale. Returns o' [slots, Nq, rank].

    win [L, S, 1, W, R] + win_count [S]: the write-combined window,
    whole, of which `layer` is read: its
    staged rows at positions lengths[s] .. lengths[s] + win_count[s] - 1
    (win_count INCLUDES the just-staged current token; `lengths` is then
    the FLUSHED length alone), as ops/paged_attention.py takes them."""
    return _read(q, pages, layer, page_table, lengths, None, win, win_count,
                 rank, scale, interpret)


# A call of its own name (`latent_select_attention.N = bf16[S, Nq,
# rank]`): the benchmark tells the selecting read from the plain one by
# it (servebench/dsa_peaks.py), and nothing of latent_attention's
# program moves.
@jax.named_scope("attn_latent")
@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def latent_select_attention(q: jax.Array, pages: jax.Array, layer,
                            page_table: jax.Array, lengths: jax.Array,
                            sel: jax.Array, win: jax.Array = None,
                            win_count: jax.Array = None, *, rank: int,
                            scale: float,
                            interpret: bool | None = None) -> jax.Array:
    """latent_attention over the SELECTED positions of each slot's
    cached rows, read by its live pages: sel [slots, max_pages * page]
    int32 or bool, of the positions `lengths` (and the window's count)
    make live the ones the row attends (cache/paged.py _selection); it
    holds the window's positions as it holds the pool's. Everything else
    as latent_attention's."""
    return _read(q, pages, layer, page_table, lengths, sel, win, win_count,
                 rank, scale, interpret)


def _read(q, pages, layer, page_table, lengths, sel, win, win_count,
          rank: int, scale: float, interpret) -> jax.Array:
    """latent_attention and latent_select_attention (sel None: the
    plain read)."""
    S, Nq, R = q.shape
    L, P, _, page, _ = pages.shape
    window = 0 if win is None else win.shape[3]
    interpret = resolve_interpret(interpret)
    note_kernel("latent" + ("" if sel is None else "_select")
                + ("_win" if window else ""), interpret)
    # A page's copy is a dozen scalar operations on the chip, and they
    # bound the call (PERF.md, PR 50): the pool's layers end to end and
    # the table flat make an address one sum, and nothing is clamped page
    # by page: a slot's last group reads past its entries only where
    # they are no whole groups, and there the table gets page 0 behind it.
    pool = pages.reshape(L * P, page, R)
    table = page_table.reshape(-1)
    group = min(PAGES_PER_CHUNK, GROUP_PAGES)
    if page_table.shape[1] % group:
        table = jnp.pad(table, (0, group))

    def slot_map(s, *_):
        return (s, 0, 0)

    in_specs = [pl.BlockSpec((1, Nq, R), slot_map)]
    args = [q]
    if sel is not None:
        # the selection a chunk a row: [S, chunks, n * page] int32 (a
        # 32-bit row is its own sublane, so a chunk's slice is an index)
        rows = PAGES_PER_CHUNK * page
        chunks = -(-page_table.shape[1] // PAGES_PER_CHUNK)
        sel = jnp.pad(sel.astype(jnp.int32),
                      ((0, 0), (0, chunks * rows - sel.shape[1])))
        in_specs.append(pl.BlockSpec((1, chunks, rows), slot_map))
        args.append(sel.reshape(S, chunks, rows))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    args.append(pool)
    prefetch = [jnp.asarray(layer, jnp.int32).reshape(1), table, lengths]
    if window:
        in_specs.append(pl.BlockSpec(
            (None, 1, 1, window, R),
            lambda s, layer_ref, *_: (layer_ref[0], s, 0, 0, 0)))
        args.append(win if interpret else in_hbm((win,))[0])
        prefetch.append(win_count)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=(S,), in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Nq, rank), slot_map),
        scratch_shapes=[pltpu.VMEM((2, PAGES_PER_CHUNK, page, R), pages.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)])
    kernel = functools.partial(
        _latent_kernel, page=page, pages_per_chunk=PAGES_PER_CHUNK,
        group_pages=group, max_pages=page_table.shape[1], pool_pages=P,
        rank=rank, scale=scale, window=window, select=sel is not None)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Nq, rank), q.dtype),
        # the buffers are cleared at slot 0 and reused slot after slot
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, *args)
