"""Pallas window staging: a step's fresh rows written INTO the
write-combined window where it lies (cache/paged.py KVWindow).

The window's leaves [L, S, Kv, W, H] ride every layer scan WHOLE, in the
scan's carry, and the kernels that read them (ops/paged_attention.py,
ops/latent_attention.py) take a layer of them by a prefetched index, as
they take the pool. What a layer stages is one entry a decode row and a
chunk's few dozen columns: `L x rows x Kv x H` values a step, where a
window that rode the scan by slices was read and written whole, every
step, to stage them (a scan's stacked output is a fresh buffer, and
XLA's scatter and a Mosaic call want two layouts of one buffer: PERF.md,
PR 47). So the writer is a Mosaic call too, with the reader's layout:

* the leaves stay in HBM, each aliased input to output; the layer and
  the rows' (slot, window index, count) ride the scalar prefetch;
* an entry is ONE position of W, and W is the tiled dimension (16
  bfloat16 or 32 int8 positions share a tile, two or four a 32-bit
  word): no copy can land one row, so a row is a read-modify-write of
  its GROUP of `window_step(W)` positions [Kv, 32, H]: copied in,
  the row's words replaced, copied back;
* rows come in RUNS: T consecutive positions of one slot (a decode row
  is a run of 1, a chunk a run of C columns), a run the span of groups
  it can touch, all its rows merged between ONE copy in and one copy
  out; the runs of one call name distinct slots (a slot stages a decode
  row or a chunk, never both), so their copies are in flight together,
  issued and awaited by a ROLLED loop, one grid step in all;
* a row is merged as 32-bit words: XLA hands the fresh rows
  zero-extended and shifted to their place in the word (`_words`), the
  kernel masks the old word and ors the new one in, one compare and
  select a vreg, whatever the leaf's dtype;
* an int8 window's scales [L, S, W/32, Kv*32] (a step's scales one flat
  kv-major row, as the paged kernel multiplies them) are float32, a
  position a lane of its row: the slot's rows in, a select, out.

Where kernels are off the writer is XLA's scatter with the layer's
index (cache/paged.py), and so is the reader.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from butterfly_tpu.ops import note_kernel, resolve_interpret

#: positions of the window that share a group: an int8 tile's rows, and
#: what one step of the paged kernel's window segment multiplies
WINDOW_STEP = 32
#: runs whose copies are in flight together
DEPTH = 16


def window_step(window: int) -> int:
    """Positions of the write-combined window in one group: whole tiles
    of any pool's dtype where the window is such, else all of it."""
    return WINDOW_STEP if window % WINDOW_STEP == 0 else window


def fits(leaf: jax.Array) -> bool:
    """Can the kernel stage into this leaf [L, S, Kv, W, H]? Compiled, a
    group is whole tiles of any dtype and a row whole lanes (Mosaic
    refuses to copy a row of 64, which is why an index key is cached in
    a row of 128: cache/paged.py index_row); interpreted (the CPU
    backend) any leaf will do."""
    return resolve_interpret(None) or (
        leaf.shape[3] % WINDOW_STEP == 0 and leaf.shape[4] % 128 == 0)


def in_hbm(leaves):
    """The window's leaves as operands that STAY in HBM, said to XLA:
    left to choose, it moves a whole leaf small enough (an int8 window's
    scales, 8 MB) into its fast memory before a Mosaic call that names
    it and back out for the next one, in every layer of every step, of
    which the call touches a slot's few rows (the compiled HLO showed
    copy-start / slice-start of the whole leaf around both calls)."""
    return [None if a is None
            else pltpu.with_memory_space_constraint(a, pltpu.HBM)
            for a in leaves]


def _span(T: int, window: int) -> int:
    """Positions a run of T consecutive entries can touch, in whole
    groups, the window at most."""
    gw = window_step(window)
    return min(window, ((T + gw - 2) // gw + 1) * gw)


def _words(rows: jax.Array, idx: jax.Array) -> jax.Array:
    """rows [N, Kv, H] of a leaf's dtype as int32 words, each value's
    bits zero-extended and shifted to where position idx [N] lies in its
    word (4 int8 or 2 bfloat16 positions a word, in position order from
    the low bits, as the chip's tiles pack the second-minor dim)."""
    bits = 8 * rows.dtype.itemsize
    w = jax.lax.bitcast_convert_type(rows, jnp.dtype(f"uint{bits}"))
    w = w.astype(jnp.uint32) << (bits * (idx % (32 // bits))).astype(
        jnp.uint32)[:, None, None]
    return jax.lax.bitcast_convert_type(w, jnp.int32)


def _stage_kernel(meta_ref, *rest, groups, n_kv: int, n_sc: int,
                  window: int):
    """One grid step stages every run. meta_ref [layer]; a [3, R] table
    a group of runs: slot, first window index, entries that land (0: the
    run stages nothing); then the fresh rows (words [N, Kv, H] int32 a
    row leaf, scales [N, Kv*gw] float32 a scale leaf), the leaves in and
    out (HBM, aliased), and a group's buffers and semaphores."""
    G = len(groups)
    n = n_kv + n_sc
    tables, rest = rest[:G], rest[G:]
    fresh, ins, outs = rest[:n], rest[n:2 * n], rest[2 * n:3 * n]
    scratch = rest[3 * n:]
    layer = meta_ref[0]
    gw = window_step(window)
    base = 0
    for g, (R, T) in enumerate(groups):
        bufs = scratch[g * (n + 2):g * (n + 2) + n]
        sem_in, sem_out = scratch[g * (n + 2) + n:(g + 1) * (n + 2)]
        table = tables[g]
        span = _span(T, window)
        depth = min(DEPTH, R)
        lag = depth // 2

        def run(r, table=table, span=span):
            slot, w0, cnt = table[0, r], table[1, r], table[2, r]
            at = jnp.clip(w0 // gw * gw, 0, window - span)
            return slot, w0, cnt, pl.multiple_of(at, gw)

        def copies(r, out: bool, bufs=bufs, sem_in=sem_in, sem_out=sem_out,
                   run=run, span=span, depth=depth):
            """The copies of run r's groups into (or out of) its
            buffers, every leaf's."""
            slot, _, _, at = run(r)
            b = r % depth
            made = []
            for i in range(n):
                leaf = (outs if out else ins)[i]
                hbm = leaf.at[layer, slot, :, pl.ds(at, span)] if i < n_kv \
                    else leaf.at[layer, slot]
                made.append(pltpu.make_async_copy(
                    bufs[i].at[b], hbm, sem_out.at[b]) if out
                    else pltpu.make_async_copy(hbm, bufs[i].at[b],
                                               sem_in.at[b]))
            return made

        def merge(r, bufs=bufs, run=run, base=base, T=T, span=span,
                  depth=depth):
            _, w0, cnt, at = run(r)
            b = r % depth
            for i in range(n_kv):
                buf = bufs[i]
                Kv = buf.shape[1]
                bits = 8 * buf.dtype.itemsize
                pack = 32 // bits
                xs = [pltpu.bitcast(buf[b, kv], jnp.int32)
                      for kv in range(Kv)]                 # [span/pack, H]
                word = jax.lax.broadcasted_iota(
                    jnp.int32, (span // pack, 1), 0)

                def row(t, xs, i=i, Kv=Kv, bits=bits, pack=pack, word=word):
                    p = w0 - at + t
                    # the position's bits of the old word go, the row's
                    # (already in their place) come
                    keep = jnp.int32(0) if pack == 1 else \
                        ~(jnp.int32((1 << bits) - 1) << (bits * (p % pack)))
                    new = fresh[i][pl.ds(base + r * T + t, 1)][0]  # [Kv, H]
                    return [jnp.where(word == p // pack,
                                      (x & keep) | new[kv:kv + 1], x)
                            for kv, x in enumerate(xs)]

                xs = row(0, xs) if T == 1 \
                    else jax.lax.fori_loop(0, cnt, row, xs)
                for kv in range(Kv):
                    buf[b, kv] = pltpu.bitcast(xs[kv], buf.dtype)
            for i in range(n_kv, n):
                buf = bufs[i]
                shape = buf.shape[1:]                      # [W/gw, Kv*gw]
                step = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1) % gw

                def srow(t, s, i=i, step=step, lane=lane):
                    p = w0 + t
                    new = fresh[i][pl.ds(base + r * T + t, 1)]   # [1, Kv*gw]
                    return jnp.where((step == p // gw) & (lane == p % gw),
                                     new, s)

                buf[b] = srow(0, buf[b]) if T == 1 \
                    else jax.lax.fori_loop(0, cnt, srow, buf[b])

        def lands(r, table=table, R=R):
            return table[2, jnp.clip(r, 0, R - 1)] > 0

        def step(i, _, R=R, depth=depth, lag=lag, copies=copies,
                 merge=merge, lands=lands):
            # the buffer run i takes is the one run i - depth leaves
            @pl.when((i >= depth) & lands(i - depth))
            def _():
                for c in copies(i - depth, True):
                    c.wait()

            @pl.when((i < R) & lands(i))
            def _():
                for c in copies(i, False):
                    c.start()

            m = i - lag

            @pl.when((m >= 0) & (m < R) & lands(m))
            def _():
                for c in copies(m, False):
                    c.wait()
                merge(m)
                for c in copies(m, True):
                    c.start()
            return 0

        jax.lax.fori_loop(0, R + depth, step, 0)
        base += R * T


@functools.partial(jax.jit,
                   static_argnames=("widths", "looped", "interpret"))
def stage_window(leaves, scales, rows, scale_rows, layer, runs, *,
                 widths, looped: bool = True,
                 interpret: bool | None = None):
    """Stage fresh rows into the window's leaves, in place.

    leaves: the row leaves [L, S, Kv, W, H] (keys, values, index keys:
    any dtypes, any Kv and H, one L, S and W); rows: as many [N, Kv, H],
    each in its leaf's dtype. scales: the scale leaves
    [L, S, W/gw, Kv*gw] float32 of an int8 window (gw = window_step(W));
    scale_rows: as many [N, Kv]. layer: int32 scalar. runs: a table
    [3, R] int32 a group of runs (slot, first window index, entries
    that land), widths: the static T of each group: the N rows are group
    after group, run after run, a run's T consecutive entries. The runs
    that land anything name distinct slots, and a run's entries end
    inside the window. looped: the call stands in a scan of more than
    one layer (below). Returns (leaves, scales) as written."""
    interpret = resolve_interpret(interpret)
    note_kernel("stage_win", interpret)
    L, S, _, W, _ = leaves[0].shape
    gw = window_step(W)
    groups = tuple((t.shape[1], T) for t, T in zip(runs, widths))
    # every row's window index, for its place in a word
    idx = jnp.concatenate([
        (t[1][:, None] + jnp.arange(T, dtype=jnp.int32)[None]).reshape(-1)
        for t, T in zip(runs, widths)])
    fresh = [_words(r, idx) for r in rows] \
        + [jnp.repeat(s, gw, axis=-1) for s in scale_rows]
    held = [*leaves, *scales]
    n_kv, n = len(leaves), len(held)
    whole = [pl.BlockSpec(f.shape, lambda i, *_, nd=f.ndim: (0,) * nd)
             for f in fresh]
    hbm = [pl.BlockSpec(memory_space=pltpu.HBM)] * n
    out_shape = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in held]
    if not interpret and looped and L > 1:
        # the scale leaves are held to HBM (in_hbm), and an output
        # aliased to a held operand has to say so too. Inside a loop
        # only: XLA's memory assignment ABORTS on a held leaf that is
        # the program's own undonated parameter (a step traced alone, as
        # the parity tools trace it, whose run of one layer is no loop
        # to XLA), so a run of one layer leaves its scales to XLA, and
        # the row leaves are never held: those of a cell are 34 MB and
        # more, and the compiled blocks move none
        held[n_kv:] = in_hbm(held[n_kv:])
        out_shape[n_kv:] = [pltpu.HBM(a.shape, a.dtype) for a in scales]
    scratch = []
    for R, T in groups:
        depth, span = min(DEPTH, R), _span(T, W)
        scratch += [pltpu.VMEM((depth, a.shape[2], span, a.shape[4]), a.dtype)
                    for a in leaves]
        scratch += [pltpu.VMEM((depth, *a.shape[2:]), a.dtype)
                    for a in scales]
        scratch += [pltpu.SemaphoreType.DMA((depth,))] * 2
    prefetch = [jnp.asarray(layer, jnp.int32).reshape(1),
                *(t.astype(jnp.int32) for t in runs)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=(1,),
        in_specs=[*whole, *hbm], out_specs=hbm, scratch_shapes=scratch)
    kernel = functools.partial(_stage_kernel, groups=groups, n_kv=n_kv,
                               n_sc=n - n_kv, window=W)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases={len(prefetch) + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, *fresh, *held)
    return tuple(out[:n_kv]), tuple(out[n_kv:])


def stage_window_sharded(leaves, scales, rows, scale_rows, layer, runs,
                         widths, looped: bool = True):
    """stage_window under the ambient mesh, as the paged kernel goes
    under it (ops/paged_attention.py paged_attention_sharded): slots
    over `data`, KV heads over `tensor` (a scale row's flat kv-major dim
    with them), no collective: a shard stages the runs of ITS slots,
    every row's heads it holds. Returns None where the caller must
    stage with XLA's scatter: a leaf the compiled kernel cannot take
    (`fits`; an int8 window of fewer than 4 KV heads a shard, whose
    scale rows are no whole lanes), or a live mesh that shards the
    leaves another way (a token-major row's minor dim) or cannot shard
    them at all."""
    from jax.sharding import PartitionSpec as P

    from butterfly_tpu.ops.flash_attention import (live_auto_mesh,
                                                   shard_kernel,
                                                   shardable_axes)
    if not all(fits(a) for a in leaves):
        return None
    S = leaves[0].shape[1]
    heads = {a.shape[2] for a in leaves}
    d, t = shardable_axes(S, min(heads), min(heads))
    mesh = jax.sharding.get_abstract_mesh()
    if live_auto_mesh():
        by_head = t is not None or dict(mesh.shape).get("tensor", 1) == 1
        if len(heads) > 1 or not by_head or (d is None and t is None):
            return None
    # a shard's scale rows are whole lanes too (2 KV heads' are 64)
    lanes = {a.shape[3] // (mesh.shape[t] if t else 1) for a in scales}
    if not resolve_interpret(None) and any(n % 128 for n in lanes):
        return None

    def local(leaves, scales, rows, scale_rows, layer, runs):
        if d is not None:
            # a shard's slots are a contiguous range of the table's
            n = leaves[0].shape[1]
            first = jax.lax.axis_index(d) * n
            runs = tuple(jnp.stack(
                [r[0] - first, r[1],
                 jnp.where((r[0] >= first) & (r[0] < first + n), r[2], 0)])
                for r in runs)
        return stage_window(leaves, scales, rows, scale_rows, layer, runs,
                            widths=widths, looped=looped)

    held = ((P(None, d, t, None, None),) * len(leaves),
            (P(None, d, None, t),) * len(scales))
    fn = shard_kernel(
        local, in_specs=(*held, (P(None, t, None),) * len(rows),
                         (P(None, t),) * len(scale_rows), P(),
                         (P(),) * len(runs)),
        out_specs=held)
    return fn(tuple(leaves), tuple(scales), tuple(rows), tuple(scale_rows),
              jnp.asarray(layer, jnp.int32), tuple(runs))
