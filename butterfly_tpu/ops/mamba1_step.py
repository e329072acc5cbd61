"""Pallas Mamba-1 decode step: one pass over the recurrent state.

A decode row advances its slot's state by one position: every one of
the N x Di state values is decayed at a rate of its own, the input is
added, and the channels are read out,

    new[n, c] = exp(dt[c] A[n, c]) h[n, c] + (dt[c] u[c]) B[n]
    y[c]      = sum_n new[n, c] C[n]

Written in `jnp` (models.common.mamba1_step) XLA makes two fusions of
it, the update in place and the readout, and the readout forms the same
update a second time from the state it reads AGAIN: 63 MB a layer-step
at Jamba2-3B's geometry where the model needs 42 (PERF.md, PR 58). This
kernel loads a slot's state once, forms `new` in float32, reduces y
from that same value, and stores `new` in the state's dtype back to the
place it came from:

* the WHOLE carried state h [Lm, S, N, Di] is the operand (channels on
  the lanes, the state index down the sublanes: cache/ssm_state.py has
  the layout), aliased to its result; the layer `m` rides the scalar
  prefetch (as ops/ssm_step.py takes its layer: one layer cut out in
  XLA is a copy of it), so only layer m's blocks are visited and the
  others are untouched through the alias;
* grid (blocks of slots, blocks of lanes): one slot's [N, Di] is 164 KB
  at Jamba2-3B's widths, half a microsecond of copies and no more than
  a grid step's own cost, so a block is SEVERAL whole slots, [slots, N,
  lanes] under BLOCK_BYTES (lanes: all of Di where it is BLOCK_TILES
  lane tiles at most), streamed in and out by the Mosaic pipeline and
  worked a slot a trip of a loop whose body is the slot's lane tiles
  [N, 128], each at a place the compiler knows; the last block may hold
  fewer slots than the others (the pipeline drops what lies past the
  state's end);
* A [N, Di] float32 (-exp(A_log), formed once outside) has a constant
  index map and is fetched once; dt and u arrive [S, Di] float32 with
  the channels on the lanes, as the state has them: a slot's row of dt
  and of dt u is staged once a slot and broadcast down the SUBLANES by
  the loads that read it a lane tile at a time; y leaves [S, Di]
  float32 the same way. The arithmetic is float32 whatever the state
  is stored in, in the `jnp` step's own order but for the sum over n
  (on the chip y and the state are the `jnp` step's bit for bit:
  tools/chip_kernels.py);
* B and C [S, N] are wanted down the sublanes and equal across the
  lanes. They arrive with N on the lanes; XLA lays each [S, N, 128]
  (one lane tile that every lane tile of the slot multiplies by: a
  fiftieth of the state's bytes at 128 slots), so the kernel's loop
  holds no cross-lane work at all: the readout's sum is over sublanes
  (ops/ssm_step.py has what a transpose and a lane broadcast cost a
  kernel that also reduces over lanes);
* a row that is not real (dead, free, in prefill phase, the slot a
  chunk wrote this step) does no arithmetic: its state is stored as it
  was loaded, bit for bit, and its y is ZERO, where the `jnp` step
  reads the state out as it stands: nothing takes that row's y (the
  step's caller samples live rows alone).

The kernel's FIRST result is y [S, Di]: a device trace names a call by
its results' shapes, and the benchmark's reader of this kind's mixers
tells them by a dim of Di (servebench/mamba1_peaks.py).

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

#: a lane tile
LANES = 128
#: bytes of one state block [slots, N, lanes] as stored; in and out,
#: each double-buffered, beside A and the rows stay inside the 16 MB of
#: scoped VMEM (Jamba2-3B: 8 slots of [16, 5120] in bfloat16, 1.3 MB)
BLOCK_BYTES = 3 << 19
#: the most lane tiles of a slot one block holds: the kernel's body is
#: one copy of a tile's work for each (their places in the block are
#: known to the compiler: a lane tile at an index it does not know cost
#: half as much again, PERF.md, PR 59), and a program that holds the
#: call traces and lowers the body at every start of a server
BLOCK_TILES = 40


def lanes_per_block(h: jax.Array) -> int:
    """Channels of a slot one block holds: the most whole lane tiles,
    BLOCK_TILES at most, that divide Di."""
    tiles = h.shape[3] // LANES
    return LANES * max(d for d in range(1, min(tiles, BLOCK_TILES) + 1)
                       if tiles % d == 0)


def slots_per_block(h: jax.Array) -> int:
    """Slots a grid step takes: all S where they fit BLOCK_BYTES, else
    the most whole tiles of 8 rows (the step's [slots, lanes] float32
    operands) that do; 0 when 8 slots do not."""
    S, N = h.shape[1:3]
    most = BLOCK_BYTES // (N * lanes_per_block(h) * h.dtype.itemsize)
    return S if S <= most else most // 8 * 8


def fits(h: jax.Array) -> bool:
    """Can the kernel serve this state? Its two minor dims are whole
    Mosaic tiles (the channels on the lanes, N on the sublanes of the
    state's dtype) and its slots cut into blocks; any other state takes
    the `jnp` step."""
    N, Di = h.shape[2:]
    return Di % LANES == 0 and N % sublane_multiple(h.dtype) == 0 \
        and slots_per_block(h) > 0


def _step_kernel(meta_ref, real_ref, dt_ref, u_ref, b_ref, c_ref, a_ref,
                 h_ref, y_ref, o_ref, rows_ref):
    """One block: h_ref, o_ref [slots, N, lanes] (the layer squeezed
    out); real_ref [blocks * slots] in SMEM; dt_ref, u_ref, y_ref
    [slots, lanes]; b_ref, c_ref [slots, N, 128]; a_ref [N, lanes];
    rows_ref [3, lanes] scratch, the slot's dt, dt u and y.
    meta_ref [layer] is read by the index maps alone."""
    f32 = jnp.float32
    slots, _, width = h_ref.shape
    first = pl.program_id(0) * slots

    def slot(i, carry):
        row = pl.ds(i, 1)

        @pl.when(real_ref[first + i] == 0)
        def _():    # a row that does not decode: the state as it came
            o_ref[i] = h_ref[i]
            y_ref[row, :] = jnp.zeros((1, width), f32)

        @pl.when(real_ref[first + i] > 0)
        def _():
            # the slot's rows pass through a place the compiler knows:
            # Mosaic moves a row at an index it does not know whole, and
            # not a lane tile of it
            dt = dt_ref[row, :]
            rows_ref[0:1, :] = dt
            rows_ref[1:2, :] = dt * u_ref[row, :]
            B, C = b_ref[i], c_ref[i]                       # [N, 128]
            for lo in range(0, width, LANES):
                at = pl.ds(lo, LANES)
                new = jnp.exp(rows_ref[0:1, at] * a_ref[:, at]) \
                    * h_ref[i, :, at].astype(f32) + rows_ref[1:2, at] * B
                o_ref[i, :, at] = new.astype(o_ref.dtype)
                rows_ref[2:3, at] = jnp.sum(new * C, axis=0, keepdims=True)
            y_ref[row, :] = rows_ref[2:3, :]

        return carry

    # a loop over the slots, not a block's eight copies of a slot's
    # work: the body is a slot's lane tiles and no more
    jax.lax.fori_loop(0, slots, slot, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mamba1_step(h, m, u, dt, Bm, Cm, A, real, interpret: bool):
    Lm, S, N, Di = h.shape
    sb, width = slots_per_block(h), lanes_per_block(h)
    nb = pl.cdiv(S, sb)
    f32 = jnp.float32

    def state_map(b, l, meta, *_):
        return (meta[0], b, 0, l)

    def row_map(b, l, *_):
        return (b, l)

    def tile_map(b, l, *_):
        return (b, 0, 0)

    def lanes(a):           # [S, N] -> [S, N, 128], equal across lanes
        return jnp.broadcast_to(a.astype(f32)[:, :, None], (S, N, LANES))

    state_spec = pl.BlockSpec((None, sb, N, width), state_map)
    row_spec = pl.BlockSpec((sb, width), row_map)
    tile_spec = pl.BlockSpec((sb, N, LANES), tile_map)
    a_spec = pl.BlockSpec((N, width), lambda b, l, *_: (0, l))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, Di // width),
        in_specs=[row_spec, row_spec, tile_spec, tile_spec, a_spec,
                  state_spec],
        out_specs=[row_spec, state_spec],
        scratch_shapes=[pltpu.VMEM((3, width), f32)],
    )
    return pl.pallas_call(
        _step_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, Di), f32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        # operand 7 (after the two prefetched scalars) is the state
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="mamba1_step",
    )(jnp.asarray(m, jnp.int32).reshape(1),
      jnp.pad(real.astype(jnp.int32), (0, nb * sb - S)),
      dt.astype(f32), u.astype(f32), lanes(Bm), lanes(Cm), A.astype(f32), h)


@jax.named_scope("mamba1_step")
def mamba1_step(h: jax.Array, m, u: jax.Array, dt: jax.Array, Bm: jax.Array,
                Cm: jax.Array, A: jax.Array, real: jax.Array,
                interpret: bool | None = None):
    """One decode step of Mamba-1 layer `m` over every slot's state:
    models.common.mamba1_step's recurrence (without the skip term D u),
    one pass.

    h: [Lm, S, N, Di], the WHOLE carried state in its stored dtype
    (fits(h) must hold); m: int32 scalar, the layer among the Mamba
    layers (may be traced); u and dt [S, Di], Bm and Cm [S, N], float32
    as mamba1_step_inputs forms them at T == 1; A [N, Di] float32,
    -exp(A_log); real [S] bool, the rows that decode. Returns
    (y [S, Di] float32, zero where not real; h with layer m's real
    slots advanced, every other slot and layer as it was). The caller
    donates h (the engine's block programs do) or pays a copy."""
    if not fits(h):
        raise ValueError(f"mamba1_step cannot cut {h.dtype}{list(h.shape)} "
                         f"into whole tiles and blocks of slots")
    interpret = resolve_interpret(interpret)
    note_kernel("mamba1_step", interpret)
    return _mamba1_step(h, m, u, dt, Bm, Cm, A, real, interpret=interpret)
