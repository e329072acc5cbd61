"""Pallas index scores: a decode row of a model with a sparse-attention
indexer scores its slot's LIVE index keys where they lie in the pool
(cache/paged.py _index_selection has the rule for when).

Such a row scores EVERY position of its stream before it attends the
index_topk that score highest (models/common.py index_scores). In XLA
that is the table's index keys gathered to one view of S_max positions a
slot (gather_paged_layer), a row of 64 bfloat16 relaid behind it, and
the view read again in float32: 0.60 ms a layer-step of
`keye30b.think`'s 2.2, where the model's 128 B a live position are 17 us
at the memory's rate (PERF.md, PR 53). This is
ops/latent_attention.py's walk (its docstring has the why of each
piece: the pool whole in HBM, layers end to end and the table flat; a
grid over slots; a slot's OWN live pages in chunks of PAGES_PER_CHUNK
into one of two buffers, a rolled loop over groups of GROUP_PAGES with
one wait a group; the next chunk's copies, and the next slot's first,
in flight) with, and only with, these differences:

* ONE pool of index keys [L, P, 1, page, Hi], a page a copy, and no
  softmax: a chunk's scores are written to the chunk's columns of the
  result and nothing is carried from chunk to chunk. (A product by the
  GROUP, in the loop body that starts a group of the next chunk's
  copies so that scalar and vector work share one instruction stream,
  was tried and read a third slower, 220 -> 295 us a call: a product is
  a chain whatever its width, as PR 50 found of the latent read's
  steps. PERF.md, PR 53);
* the scores are models/common.py index_scores to the letter,
  s = qi x k^T over Hi, relu, times w, summed over the Ni index heads,
  and to its precision: there the query is float32 and the product at
  HIGHEST. Here the query's three bfloat16 pieces (q = hi + mid + lo,
  exact for a float32) are stacked [3 * Ni, Hi] and multiplied ONCE
  with the keys as cached, which ARE bfloat16, into float32: the
  passes HIGHEST makes for such a key, so the same positions are
  selected (tests/test_index_scores.py holds the masks equal);
* the result [S, S_max] float32 is ONE block, whole in fast memory
  for the grid's length and written back once: zeroed at slot 0, a
  slot's grid step writes its row where it lies. Columns past a slot's
  last live chunk stay 0, those of that chunk past the length are the
  scores of whatever an older copy left in the buffer (numbers: the
  buffers are cleared at slot 0 and hold copied pages ever after), and
  the caller's `valid` masks both. (A row a block is [S, 1, S_max], a
  row a TILE, T(1,128): XLA's sort of the scores inherited that layout
  and `glm5-ep16.think` read 27 % slower for it. PERF.md, PR 53.)
  S_max is a dim of the result as a trace prints it (`index_scores.N =
  f32[S,S_max]`), which is how the benchmark's readers of the selecting
  path tell its operations (servebench/sparse_peaks.py,
  servebench/dsa_peaks.py);
* the write-combined window's index keys [L, S, 1, W, Hi] come whole
  and (layer, slot)'s block is one more product: its W scores take the
  staged rows' positions, lengths .. lengths + W - 1, in the one or two
  chunks of the result they fall in, rotated by the offset inside a
  chunk (so a window is no wider than a chunk). Every staged row is
  scored, the ones past the slot's count too: their positions lie past
  the row's own, where `valid` is False.

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
from butterfly_tpu.ops.flash_attention import live_auto_mesh
from butterfly_tpu.ops.window_stage import in_hbm

#: pages one chunk of the context takes: 64 pages of 16 tokens are 1,024
#: positions, 256 KB of bfloat16 a buffer at 128 wide, two buffers. Twice
#: the other walks' chunk: there a chunk's online-softmax step is the
#: cost (ops/latent_attention.py), here a chunk is one product and a
#: store whose fixed part a wider chunk halves (the call alone at the
#: cells' contexts 219 -> 196 us at 64 pages, 189 with groups of 16:
#: PERF.md, PR 53). A table of 7,168 is seven such chunks whole
PAGES_PER_CHUNK = 64
#: pages whose copies are started side by side and awaited as one
GROUP_PAGES = 16


def fits(pages: jax.Array, window: int = 0) -> bool:
    """Can the kernel serve this pool of index keys [L, P, 1, page, Hi],
    and a write-combined window of `window` rows a slot? Never under a
    mesh that GSPMD still partitions (a bare Mosaic call is opaque to
    it), and a window is no wider than a chunk. Compiled, a page (and
    the window) is whole sublane tiles of the pool's dtype, a key and a
    chunk's scores whole lanes; interpreted (the CPU backend) any
    token-major pool will do. Any other pool takes the gathered view."""
    page = pages.shape[3]
    rows = PAGES_PER_CHUNK * page
    if pages.shape[2] != 1 or pages.shape[1] < GROUP_PAGES \
            or live_auto_mesh() or window > rows:
        return False
    tile = sublane_multiple(pages.dtype)
    return resolve_interpret(None) or (
        page % tile == 0 and window % tile == 0
        and pages.shape[4] % 128 == 0 and rows % 128 == 0)


def _scores(q3, w, k):
    """[1, C] float32: one query's index scores of the rows k [C, Hi] as
    cached. q3 [3 * Ni, Hi]: the float32 query's three pieces in the
    keys' dtype, the largest first; w [Ni, 1] float32."""
    Ni = w.shape[0]
    # DEFAULT precision, said: one pass over operands that are pieces
    # already, whatever the ambient matmul precision asks of float32
    s3 = jax.lax.dot_general(q3, k, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.DEFAULT,
                             preferred_element_type=jnp.float32)
    s = (s3[2 * Ni:] + s3[Ni:2 * Ni]) + s3[:Ni]            # [Ni, C]
    return jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)


def _index_kernel(layer_ref, table_ref, len_ref, q_ref, w_ref, pool_ref,
                  *rest, page: int, pages_per_chunk: int, group_pages: int,
                  max_pages: int, pool_pages: int, window: int):
    """One grid step is one slot. The pool lies in HBM, its layers end
    to end [L * pool_pages, page, Hi]; the table is flat, a slot's
    `max_pages` entries after another's (and a group of page 0 behind
    the last, where they are no whole groups). The slot's live pages are
    copied `pages_per_chunk` at a time into one of two buffers
    [n, page, Hi] by a ROLLED loop over groups of `group_pages` while
    the chunk before is scored, one product a chunk, into its columns of
    the slot's row of o_ref [S, chunks * n * page], the result whole.
    window > 0: (layer, slot)'s block
    [W, Hi] of the write-combined window's index keys is one more
    product, and its scores take the positions length .. length + W - 1."""
    win_ref = None
    if window:
        win_ref, *rest = rest
    o_ref, buf, sem, par = rest
    slot = pl.program_id(0)
    n, grp = pages_per_chunk, group_pages
    rows, chunks = n * page, o_ref.shape[1] // (n * page)
    row = pl.ds(slot, 1)
    layer_base = layer_ref[0] * pool_pages
    Hi = q_ref.shape[2]
    q = q_ref[0]                                           # [Ni, Hi] f32
    dt, f32 = buf.dtype, jnp.float32
    hi = q.astype(dt)
    left = q - hi.astype(f32)
    mid = left.astype(dt)
    q3 = jnp.concatenate([hi, mid, (left - mid.astype(f32)).astype(dt)],
                         axis=0)
    w = w_ref[0]                                           # [Ni, 1]

    @pl.when(slot == 0)
    def _clear():
        # a product is over the whole buffer though fewer pages were
        # copied: what lies behind them is masked by the caller, and
        # must be numbers
        buf[...] = jnp.zeros_like(buf)
        # and the columns of the chunks no page of which is live stay 0
        o_ref[...] = jnp.zeros_like(o_ref)

    def live_pages(s):
        return jnp.minimum((len_ref[s] + page - 1) // page, max_pages)

    def copies(s, c, b, go):
        """Start (go) or await the page copies of slot s's chunk c into
        buffer b, a group of `grp` pages at a time as far as the chunk
        has live pages: a group's starts side by side, its wait ONE (a
        wait counts bytes, a group's; its descriptor's source is never
        read). The last group's entries past the stream's pages name the
        null page, or any page: positions the caller masks."""
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

    def columns(c):
        return pl.ds(pl.multiple_of(c * rows, rows), rows)

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

    def chunk(c, _):
        b = (b0 + c) % 2
        last = c + 1 == nchunks

        @pl.when(jnp.logical_not(last) | more)
        def _next():
            copies(jnp.where(last, after, slot), jnp.where(last, 0, c + 1),
                   1 - b, True)

        copies(slot, c, b, False)
        # ONE product over the whole chunk (by the group, in the loop
        # that starts the next chunk's copies, it read a third SLOWER:
        # a product is a chain whatever its width, PERF.md, PR 53);
        # [n, page, Hi] collapses to rows as whole tiles
        o_ref[row, columns(c)] = _scores(q3, w, buf[b].reshape(rows, Hi))
        return 0

    jax.lax.fori_loop(0, nchunks, chunk, 0)
    if window:
        # the staged rows' scores, turned right by the offset of their
        # first position inside a chunk: what stays inside that chunk
        # and what wraps into the one after it. Positions past the
        # table's end are dropped.
        col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        sw = _scores(q3, w, win_ref[0, 0])                 # [1, W]
        if window < rows:
            sw = jnp.concatenate(
                [sw, jnp.zeros((1, rows - window), f32)], axis=1)
        first, off = length // rows, length % rows
        turned = pltpu.roll(sw, off, 1)
        for c, here in ((first, (col >= off) & (col < off + window)),
                        (first + 1, col < off + window - rows)):
            @pl.when(c < chunks)
            def _place(c=c, here=here):
                at = columns(c)
                o_ref[row, at] = jnp.where(here, turned, o_ref[row, at])


# The jitted function's name is the Mosaic call's name in a device
# trace (`index_scores.N = f32[S, S_max]`), and S_max in its result
# is what the benchmark's readers of the selecting path count it by.
@jax.named_scope("attn_index")
@functools.partial(jax.jit, static_argnames=("interpret",))
def index_scores(qi: jax.Array, w: jax.Array, pages: jax.Array, layer,
                 page_table: jax.Array, lengths: jax.Array,
                 win: jax.Array = None, *,
                 interpret: bool | None = None) -> jax.Array:
    """The index scores [slots, S_max] float32 of one decode token a
    slot against its stream's cached index keys, read by its live pages:
    models.common.index_scores over the gathered view, at the positions
    that are live.

    qi [slots, Ni, index_head_dim], w [slots, Ni]: float32, as
    index_proj gives them; pages [L, P, 1, page, Hi]: the WHOLE pool of
    index keys as it lies, a key in whole lanes (Hi: cache/paged.py
    index_row); layer: int32 scalar; page_table [slots, max_pages] int32;
    lengths [slots] int32: the positions of the pool a slot scores (0:
    none). Columns past them hold numbers that mean nothing.

    win [L, S, 1, W, Hi]: the write-combined window's index keys, whole,
    of which `layer` is read: a slot's staged rows lie at positions
    lengths[s] .. lengths[s] + W - 1 (`lengths` is then the FLUSHED
    length), and their scores take those columns."""
    S, Ni, _ = qi.shape
    L, P, _, page, Hi = pages.shape
    n, max_pages = PAGES_PER_CHUNK, page_table.shape[1]
    window = 0 if win is None else win.shape[3]
    interpret = resolve_interpret(interpret)
    note_kernel("index_scores", interpret)
    # the pool's layers end to end and the table flat make an address
    # one sum, and nothing is clamped page by page: a slot's last group
    # reads past its entries only where they are no whole groups, and
    # there the table gets page 0 behind it (ops/latent_attention.py)
    table = page_table.reshape(-1)
    group = min(n, GROUP_PAGES)
    if max_pages % group:
        table = jnp.pad(table, (0, group))
    chunks = -(-max_pages // n)

    def slot_map(s, *_):
        return (s, 0, 0)

    in_specs = [pl.BlockSpec((1, Ni, Hi), slot_map),
                pl.BlockSpec((1, Ni, 1), slot_map),
                pl.BlockSpec(memory_space=pl.ANY)]
    # a key narrower than a lane tile is cached with zeros behind it
    # (cache/paged.py index_row), and the query gets as many
    qi = jnp.pad(qi.astype(jnp.float32),
                 ((0, 0), (0, 0), (0, Hi - qi.shape[2])))
    args = [qi, w.astype(jnp.float32)[:, :, None],
            pages.reshape(L * P, page, Hi)]
    if window:
        in_specs.append(pl.BlockSpec(
            (None, 1, 1, window, Hi),
            lambda s, layer_ref, *_: (layer_ref[0], s, 0, 0, 0)))
        args.append(win if interpret else in_hbm((win,))[0])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(S,), in_specs=in_specs,
        # the result whole, every slot's row written where it lies and
        # the block written back once (a row a block would be [S, 1,
        # S_max], a row a TILE, and what XLA makes of it inherits that)
        out_specs=pl.BlockSpec((S, chunks * n * page), lambda s, *_: (0, 0)),
        scratch_shapes=[pltpu.VMEM((2, n, page, Hi), pages.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)])
    kernel = functools.partial(
        _index_kernel, page=page, pages_per_chunk=n, group_pages=group,
        max_pages=max_pages, pool_pages=P, window=window)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, chunks * n * page), jnp.float32),
        # the buffers are cleared at slot 0 and reused slot after slot
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), table, lengths, *args)
    # a table that is no whole number of chunks (no cell's): the
    # columns behind it go
    return out[:, :max_pages * page]
