"""Pallas sparse paged attention: a decode row of a model with a
sparse-attention indexer reads its slot's LIVE pages once and attends
its selection as a MASK (cache/paged.py sparse_paged_attend has the
rule for when).

Such a row attends the index_topk positions its indexer scored highest.
Read by the row, that is one gather of topk token rows out of the key
pool and one out of the value pool, and XLA's gather pays by the ROW:
12.5 ns for a row of 1 KB, a tenth of the memory's rate (PERF.md, PRs
36-37). A kernel that copied single rows would pay as much in scalar
work (14-21 ns a copy, PR 50). But where the table is a few times topk
nearly every PAGE holds a selected row, and a page is ONE copy: so the
kernel walks the slot's live pages as ops/paged_attention.py and
ops/latent_attention.py do, and the selection joins the length mask. A
softmax over the selected positions and a masked softmax over the live
ones with the unselected masked out are the same sum. This is
ops/latent_attention.py's walk (its docstring has the why of each
piece) with, and only with, these differences:

* TWO token-major pools [L, P, 1, page, Kv*H], keys and values (a
  token's KV heads contiguous in its row: cache/paged.py pool_row), a
  page of each a copy, both on one semaphore a buffer;
* a KV head is a lane-aligned slice of H of the row, as
  models/common.py attend_token_rows reads it in XLA: for each of the
  Kv heads q_g [G, H] x k_g^T and p_g [G, rows] x v_g, bfloat16 operands
  into float32, the online softmax carried in float32 [Kv, G, .] as the
  loop's values, scale H^-1/2;
* the selection [S, S_max] comes a slot a block, int32 and a chunk a
  row, so that a chunk's slice is an index; a row that is dead OR
  unselected gets probability 0;
* the write-combined window's keys and values [L, S, 1, W, Kv*H] come
  whole and (layer, slot)'s blocks are one more chunk, masked by the
  selection at the staged rows' positions lengths .. lengths + W - 1:
  the one or two chunk rows of the selection they fall in, rotated by
  the offset inside a chunk (so a window is no wider than a chunk);
* the result is declared [S, Kv, 1, G, H], a KV head's [G, H] as the
  body writes it, and reshaped to [S, Nq, H].

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

NEG_INF = -1e30
#: pages one chunk of the context takes: 32 pages of 16 tokens are 512
#: rows, 512 KB of bfloat16 a pool and buffer at 4 KV heads of 128, two
#: pools, two buffers
PAGES_PER_CHUNK = 32
#: pages whose copies are started side by side and awaited as one a pool
GROUP_PAGES = 8


def fits(k_pages: jax.Array, head_dim: int, window: int = 0) -> bool:
    """Can the kernel serve these pools [L, P, 1, page, Kv*H], and a
    write-combined window of `window` rows a slot? Never under a mesh
    that GSPMD still partitions (a bare Mosaic call is opaque to it; the
    pools' rows are sharded there), and a window is no wider than a
    chunk. Compiled, a page (and the window) is whole sublane tiles of
    the pool's dtype, a head whole lanes and a chunk's selection whole
    lanes; interpreted (the CPU backend) any token-major pool will do.
    Any other pool takes the gather."""
    page = k_pages.shape[3]
    if k_pages.shape[2] != 1 or k_pages.shape[1] < GROUP_PAGES \
            or k_pages.shape[4] % head_dim or live_auto_mesh() \
            or window > PAGES_PER_CHUNK * page:
        return False
    tile = sublane_multiple(k_pages.dtype)
    return resolve_interpret(None) or (
        page % tile == 0 and window % tile == 0 and head_dim % 128 == 0
        and (PAGES_PER_CHUNK * page) % 128 == 0)


def _update(q, k, v, live, carry, scale: float):
    """One online-softmax step over rows k, v [C, Kv*H] (live [1, C]
    marks the columns that exist AND are selected) for the queries q
    [Kv, G, H] of each KV head: carry (m, l [Kv, G, 1], acc [Kv, G, H])
    -> the same, float32. A head's keys and values are a lane-aligned
    slice of H of the row."""
    m_prev, l_prev, acc = carry
    Kv, _, H = q.shape
    # DEFAULT precision, said: one pass of the operands as they are
    # stored, whatever the ambient matmul precision asks of float32
    one_pass = jax.lax.Precision.DEFAULT
    s = jnp.stack([jax.lax.dot_general(
        q[g], k[:, g * H:(g + 1) * H], (((1,), (1,)), ((), ())),
        precision=one_pass, preferred_element_type=jnp.float32)
        for g in range(Kv)]) * scale                       # [Kv, G, C]
    s = jnp.where(live, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    pv = jnp.stack([jnp.dot(p[g].astype(v.dtype), v[:, g * H:(g + 1) * H],
                            precision=one_pass,
                            preferred_element_type=jnp.float32)
                    for g in range(Kv)])                   # [Kv, G, H]
    return (m_new, l_prev * corr + jnp.sum(p, axis=-1, keepdims=True),
            acc * corr + pv)


def window_selected(sel_ref, length, col, window: int):
    """[1, window] int32, nonzero where selected: the selection at the
    write-combined window's staged rows, which lie at positions length .. length + window - 1.
    sel_ref [1, chunks, rows] holds the selection a chunk a row; col is
    the iota [1, rows]. The positions fall in the chunk row that
    `length` falls in and the one after it: both rotated left by its
    offset in a chunk, the first up to the chunk's end and the second
    behind it (so a window is no wider than a chunk). Shared with
    ops/latent_attention.py's selecting read."""
    rows, last_row = sel_ref.shape[2], sel_ref.shape[1] - 1
    first, off = jnp.minimum(length // rows, last_row), length % rows
    turned = [pltpu.roll(sel_ref[0, pl.ds(c, 1), :], (rows - off) % rows, 1)
              for c in (first, jnp.minimum(first + 1, last_row))]
    return jnp.where(col + off < rows, *turned)[:, :window]


def _sparse_kernel(layer_ref, table_ref, len_ref, *rest, page: int,
                   pages_per_chunk: int, group_pages: int, max_pages: int,
                   pool_pages: int, window: int):
    """One grid step is one slot. The pools lie in HBM, their layers end
    to end [L * pool_pages, page, Kv*H]; the table is flat, a slot's
    `max_pages` entries after another's (and a group of page 0 behind
    the last, where they are no whole groups). The slot's live pages are
    copied `pages_per_chunk` at a time into one of two buffers a pool
    [n, page, Kv*H] by a ROLLED loop over groups of `group_pages` (the
    body is the same size whatever the chunk) while the chunk before is
    multiplied, one online-softmax step a chunk, its columns masked by
    the chunk's row of the selection sel_ref [1, chunks, n * page].
    window > 0: (layer, slot)'s blocks [W, Kv*H] of the write-combined
    window are one more step, the first win_count rows live where the
    selection holds their positions, length .. length + W - 1."""
    if window:
        wc_ref, *rest = rest
    q_ref, sel_ref, k_ref, v_ref, *rest = rest
    if window:
        wk_ref, wv_ref, *rest = rest
    o_ref, kbuf, vbuf, sem, par = rest
    slot = pl.program_id(0)
    n, grp = pages_per_chunk, group_pages
    layer_base = layer_ref[0] * pool_pages
    q = q_ref[0]                                           # [Kv, G, H]
    Kv, G, H = q.shape
    scale = H ** -0.5

    @pl.when(slot == 0)
    def _clear():
        # a step multiplies the whole buffer though fewer pages were
        # copied: what lies behind them is masked, and must be numbers
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def live_pages(s):
        return jnp.minimum((len_ref[s] + page - 1) // page, max_pages)

    def copies(s, c, b, go):
        """Start (go) or await the page copies of slot s's chunk c into
        buffer b of both pools, a group of `grp` pages at a time as far
        as the chunk has live pages: a group's starts side by side, its
        waits ONE a pool (a wait counts bytes, a group's; its
        descriptor's source is never read). The last group's entries
        past the stream's pages name the null page, or any page: rows
        that are masked."""
        first = s * max_pages + c * n       # in the flat table
        live = jnp.minimum(n, live_pages(s) - c * n)

        def group(g, _):
            at = pl.multiple_of(g * grp, grp)
            if not go:
                for ref, buf in ((k_ref, kbuf), (v_ref, vbuf)):
                    pltpu.make_async_copy(ref.at[pl.ds(0, grp)],
                                          buf.at[b, pl.ds(at, grp)],
                                          sem.at[b]).wait()
                return 0
            for i in range(grp):
                pid = layer_base + table_ref[first + at + i]
                for ref, buf in ((k_ref, kbuf), (v_ref, vbuf)):
                    pltpu.make_async_copy(ref.at[pid], buf.at[b, at + i],
                                          sem.at[b]).start()
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

        # ONE step over the whole chunk, as the latent kernel's (a step
        # is a chain the next one waits for, whatever its width); the
        # rows past the stream's end and the rows the indexer left out
        # are masked. [n, page, Kv*H] collapses to rows as whole tiles.
        live = (c * n * page + col < length) \
            & (sel_ref[0, pl.ds(c, 1), :] != 0)
        return _update(q, kbuf[b].reshape(n * page, Kv * H),
                       vbuf[b].reshape(n * page, Kv * H), live, carry, scale)

    carry = (jnp.full((Kv, G, 1), -jnp.inf, jnp.float32),
             jnp.zeros((Kv, G, 1), jnp.float32),
             jnp.zeros((Kv, G, H), jnp.float32))
    carry = jax.lax.fori_loop(0, nchunks, chunk, carry)
    if window:
        wsel = window_selected(sel_ref, length, col, window)
        wcol = col[:, :window]
        live = (wcol < wc_ref[slot]) & (wsel != 0) \
            & (length + wcol < max_pages * page)
        carry = _update(q, wk_ref[0, 0], wv_ref[0, 0], live, carry, scale)
    _, l, acc = carry
    o_ref[0, :, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


# The jitted function's name is the Mosaic call's name in a device
# trace (`sparse_attention.N = bf16[S, Kv, 1, G, H]`), and the 5-D
# result is the shape by which the benchmark's reader of the sparse path
# counts a KV head's output (servebench/sparse_peaks.py); it is NOT
# ops/paged_attention.py's name, whose share another metric reads.
@jax.named_scope("attn_sparse")
@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     layer, page_table: jax.Array, lengths: jax.Array,
                     sel: jax.Array, win_k: jax.Array = None,
                     win_v: jax.Array = None, win_count: jax.Array = None,
                     *, interpret: bool | None = None) -> jax.Array:
    """Single-token attention over the SELECTED positions of each slot's
    cached keys and values, read by its live pages.

    q: [slots, Nq, H] (the one decode token a slot); k_pages, v_pages:
    [L, P, 1, page, Kv*H], the WHOLE token-major pools as they lie;
    layer: int32 scalar; page_table: [slots, max_pages] int32; lengths:
    [slots] int32, the rows of the pool a slot could attend (0: none,
    and with no window rows either its output is zeros); sel [slots,
    max_pages * page] int32 or bool: of those positions the ones it
    attends (cache/paged.py _selection). Returns [slots, Nq, H].

    win_k, win_v [L, S, 1, W, Kv*H] + win_count [S]: the write-combined
    window, whole, of which `layer` is read: its staged rows at
    positions lengths[s] .. lengths[s] + win_count[s] - 1 (win_count
    INCLUDES the just-staged current token; `lengths` is then the
    FLUSHED length alone), as ops/latent_attention.py takes them; `sel`
    holds their positions as it holds the pool's."""
    S, Nq, H = q.shape
    L, P, _, page, R = k_pages.shape
    Kv, n = R // H, PAGES_PER_CHUNK
    max_pages = page_table.shape[1]
    window = 0 if win_k is None else win_k.shape[3]
    interpret = resolve_interpret(interpret)
    note_kernel("sparse_attention", interpret)
    # the pools' layers end to end and the table flat make an address
    # one sum, and nothing is clamped page by page: a slot's last group
    # reads past its entries only where they are no whole groups, and
    # there the table gets page 0 behind it (ops/latent_attention.py)
    table = page_table.reshape(-1)
    group = min(n, GROUP_PAGES)
    if max_pages % group:
        table = jnp.pad(table, (0, group))
    # the selection a chunk a row: [S, chunks, n * page] int32 (a
    # 32-bit row is its own sublane, so a chunk's slice is an index)
    chunks = -(-max_pages // n)
    sel = jnp.pad(sel.astype(jnp.int32),
                  ((0, 0), (0, chunks * n * page - sel.shape[1])))

    def slot_map(s, *_):
        return (s, 0, 0)

    def pool_rows(pages):
        return pages.reshape(L * P, page, R)

    in_specs = [pl.BlockSpec((1, Kv, Nq // Kv, H), lambda s, *_: (s, 0, 0, 0)),
                pl.BlockSpec((1, chunks, n * page), slot_map),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    args = [q.reshape(S, Kv, Nq // Kv, H), sel.reshape(S, chunks, n * page),
            pool_rows(k_pages), pool_rows(v_pages)]
    prefetch = [jnp.asarray(layer, jnp.int32).reshape(1), table, lengths]
    if window:
        leaf = pl.BlockSpec(
            (None, 1, 1, window, R),
            lambda s, layer_ref, *_: (layer_ref[0], s, 0, 0, 0))
        in_specs += [leaf, leaf]
        args += (win_k, win_v) if interpret else in_hbm((win_k, win_v))
        prefetch.append(win_count)
    buffer = pltpu.VMEM((2, n, page, R), k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=(S,), in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Kv, 1, Nq // Kv, H),
                               lambda s, *_: (s, 0, 0, 0, 0)),
        scratch_shapes=[buffer, buffer, pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)])
    kernel = functools.partial(
        _sparse_kernel, page=page, pages_per_chunk=n, group_pages=group,
        max_pages=max_pages, pool_pages=P, window=window)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Kv, 1, Nq // Kv, H), q.dtype),
        # the buffers are cleared at slot 0 and reused slot after slot
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, *args)
    return out.reshape(S, Nq, H)
