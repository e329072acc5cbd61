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
* grid (slots): a slot loops over ITS OWN context in chunks of
  PAGES_PER_CHUNK pages, a dynamic trip count, so a short stream costs
  a short loop and nobody visits the tail of a table sized for
  max_seq. A chunk is PAGES_PER_CHUNK page copies (one DMA a page: the
  pages of a stream lie anywhere) into one VMEM buffer [chunk, R], and
  the next chunk's copies are in flight while this one is multiplied
  (ops/paged_attention.py walks so too since PR 46, its copies rolled;
  its page chain, one page a grid step, ran at a seventh of the
  memory bandwidth until then: PERF.md, PRs 41 and 46);
* the chunk's scores are ONE product q [Nq, R] x rows^T and its sum ONE
  product p [Nq, chunk] x rows[:, :rank], bfloat16 operands into
  float32, the online softmax carried in float32 as the loop's values;
* a table entry past a stream's pages names the null page, which holds
  finite numbers like every page (the pool is born zero and only ever
  written with projections): its columns are masked to probability 0,
  so whole chunks are copied without a branch a page;
* the write-combined window [L, S, 1, W, R] (cache/paged.py: staged
  rows at positions lengths .. lengths + win_count - 1) comes whole, as
  it rides the layer scan; (layer, slot)'s block of it is one more
  chunk, pipelined a slot by its BlockSpec.

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
from butterfly_tpu.ops.window_stage import in_hbm

NEG_INF = -1e30
#: pages one chunk of the context takes: 32 pages of 16 tokens are 512
#: rows, 590 KB of bfloat16 a buffer, two buffers
PAGES_PER_CHUNK = 32


def fits(pages: jax.Array, rank: int) -> bool:
    """Can the kernel serve this pool [L, P, 1, page, Rp]? Compiled, a
    page is whole sublane tiles of the pool's dtype and a row and its
    values are whole lanes (Mosaic copies and slices whole tiles);
    interpreted (the CPU backend) any pool of rows will do. Any other
    pool takes the `jnp` read."""
    if pages.shape[2] != 1:
        return False
    return resolve_interpret(None) or (
        pages.shape[3] % sublane_multiple(pages.dtype) == 0
        and pages.shape[4] % 128 == 0 and rank % 128 == 0)


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
                   pages_per_chunk: int, rank: int, scale: float,
                   window: int):
    if window:
        wc_ref, q_ref, pool_ref, win_ref, o_ref, buf, sem = rest
    else:
        q_ref, pool_ref, o_ref, buf, sem = rest
    slot = pl.program_id(0)
    length = len_ref[slot]
    layer = layer_ref[0]
    max_pages = table_ref.shape[1]
    chunk = pages_per_chunk * page
    nchunks = (length + chunk - 1) // chunk
    q = q_ref[0]                                           # [Nq, R]
    Nq = q.shape[0]

    def copies(b, c):
        """The page copies of chunk c into buffer b."""
        return [pltpu.make_async_copy(
            pool_ref.at[layer, table_ref[slot, jnp.minimum(
                c * pages_per_chunk + i, max_pages - 1)], 0],
            buf.at[b, pl.ds(i * page, page)], sem.at[b])
            for i in range(pages_per_chunk)]

    @pl.when(nchunks > 0)
    def _first():
        for dma in copies(0, 0):
            dma.start()

    def body(c, carry):
        b = c % 2

        @pl.when(c + 1 < nchunks)
        def _next():
            for dma in copies(1 - b, c + 1):
                dma.start()

        for dma in copies(b, c):
            dma.wait()
        pos = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        return _update(q, buf[b], pos < length, carry, rank, scale)

    carry = (jnp.full((Nq, 1), -jnp.inf, jnp.float32),
             jnp.zeros((Nq, 1), jnp.float32),
             jnp.zeros((Nq, rank), jnp.float32))
    carry = jax.lax.fori_loop(0, nchunks, body, carry)
    if window:
        col = jax.lax.broadcasted_iota(jnp.int32, (1, window), 1)
        carry = _update(q, win_ref[0, 0], col < wc_ref[slot], carry, rank,
                        scale)
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
    S, Nq, R = q.shape
    page = pages.shape[3]
    window = 0 if win is None else win.shape[3]
    interpret = resolve_interpret(interpret)
    note_kernel("latent" + ("_win" if window else ""), interpret)
    chunk = PAGES_PER_CHUNK * page

    def slot_map(s, *_):
        return (s, 0, 0)

    in_specs = [pl.BlockSpec((1, Nq, R), slot_map),
                pl.BlockSpec(memory_space=pl.ANY)]
    args = [q, pages]
    prefetch = [jnp.asarray(layer, jnp.int32).reshape(1), page_table,
                lengths]
    if window:
        in_specs.append(pl.BlockSpec(
            (None, 1, 1, window, R),
            lambda s, layer_ref, *_: (layer_ref[0], s, 0, 0, 0)))
        args.append(win if interpret else in_hbm((win,))[0])
        prefetch.append(win_count)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=(S,), in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Nq, rank), slot_map),
        scratch_shapes=[pltpu.VMEM((2, chunk, R), pages.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    kernel = functools.partial(
        _latent_kernel, page=page, pages_per_chunk=PAGES_PER_CHUNK,
        rank=rank, scale=scale, window=window)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Nq, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, *args)
