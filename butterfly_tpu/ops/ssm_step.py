"""Pallas Mamba-2 decode step: one pass over the recurrent state.

A decode row advances its slot's state by one position: per head
`new = dA H + dtx (outer) B`, and reads it out, `y = sum_n(new C)`.
Written in `jnp` (models.common.ssm_scan at T == 1, then the in-place
update of cache/ssm_state.py) XLA makes two fusions of it, the update in
place and the reduction, and a step reads every slot's state TWICE. This
kernel loads H [Hd, N] once, forms `new` in float32, reduces y from that
same float32 value, and stores `new` in the state's dtype back to the
place it came from:

* the WHOLE carried state h [Lm, S, Nh, Hd, N] is the operand, aliased
  to its result; the layer `m` rides the scalar prefetch (as the paged
  kernel takes its layer: one layer cut out in XLA is a copy of it), so
  only layer m's blocks are visited and the others are untouched
  through the alias;
* grid (slots, groups of heads): a block is one slot's `heads` heads,
  [heads, Hd, N] with N on the lanes, streamed in and out by the Mosaic
  pipeline; the arithmetic is float32 whatever the state is stored in;
* the step's small operands arrive as ssm_scan computes them: dA
  [S, Nh] (scalars: SMEM), dtx [S, Nh, Hd], B and C [S, G, N] by GROUP
  (never repeated to heads), real [S]. A row that is not real (dead,
  free, in prefill phase) keeps its state bit for bit, and its y is
  read from the state as it stands, as the `jnp` step's is.

**The outer product's column.** `dtx (outer) B` wants a head's dtx
[Hd] down the SUBLANES and equal across the lanes, and it arrives with
Hd on the lanes. A transpose and a lane broadcast of one column a head
(`vperm`) is what XLA's own fusion does; in a kernel that also reduces
over the lanes the two kinds of cross-lane work take turns on the same
unit and the step ran at 1.47-1.83 ms a layer at granite-4.0-h-small's
geometry where the pipeline's DMA alone is 0.86 (PERF.md, PR 42). So
the broadcast goes through the idle MXU, EXACTLY: the block's dtx is
cut into three bfloat16 pieces (hi + mid + lo is the float32 value, to
the bit), transposed once a block with head i's pieces in lanes i,
heads + i and 2 heads + i; a head masks every other lane off and
multiplies by a matrix of ones: each product is exact, the float32
accumulator adds three addends whose partial sums are representable,
and every lane of the result row hd is dtx[i, hd]. The multiply by B,
the decay, the sum and the readout are float32 vector arithmetic in the
`jnp` step's own order, and on the chip the kernel's y and state are
the `jnp` step's bit for bit (tools/chip_kernels.py).

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

#: the MXU's contraction width: three pieces of `heads` lanes each must
#: fit one pass
LANES = 128
#: bytes of one state block [heads, Hd, N] as stored; in and out, each
#: double-buffered, stay far inside the 16 MB of scoped VMEM
BLOCK_BYTES = 1 << 20


def heads_per_block(h: jax.Array) -> int:
    """Heads of one slot a grid step takes: the largest divisor of Nh
    whose three pieces fit the MXU's 128 lanes, whose rows tile the
    step's [heads, Hd] operands (a multiple of 8, or all of Nh) and
    whose block stays under BLOCK_BYTES; 0 when there is none."""
    Nh, Hd, N = h.shape[2:]
    return max((d for d in range(1, Nh + 1)
                if Nh % d == 0 and 3 * d <= LANES
                and (d % 8 == 0 or d == Nh)
                and d * Hd * N * h.dtype.itemsize <= BLOCK_BYTES),
               default=0)


def fits(h: jax.Array) -> bool:
    """Can the kernel serve this state? Its two minor dims are whole
    Mosaic tiles (N on the lanes, Hd on the sublanes of the state's
    dtype) and its heads cut into blocks; any other state takes the
    `jnp` step."""
    Hd, N = h.shape[-2:]
    return N % LANES == 0 and Hd % sublane_multiple(h.dtype) == 0 \
        and heads_per_block(h) > 0


def _step_kernel(meta_ref, real_ref, dA_ref, dtx_ref, b_ref, c_ref, h_ref,
                 y_ref, o_ref, yT_ref, *, heads: int, all_heads: int,
                 per_group: int):
    """One slot's `heads` heads: h_ref, o_ref [heads, Hd, N] (the layer
    and the slot squeezed out); dA_ref [S * Nh] in SMEM, a head's decay a
    scalar; dtx_ref, y_ref [heads, Hd]; b_ref, c_ref [G, N]; yT_ref
    [Hd, heads] scratch, a head's y its column. meta_ref [layer] is read
    by the index maps alone."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    s, first = pl.program_id(0), pl.program_id(1) * heads
    real = real_ref[s] > 0
    Hd, N = h_ref.shape[-2:]
    # the block's dtx as three bfloat16 pieces (held in float32 until a
    # head is masked out), one transpose: [Hd, 128], lane p * heads + i
    # is piece p of head i
    d = dtx_ref[...]
    hi = d.astype(bf16).astype(f32)
    mid = (d - hi).astype(bf16).astype(f32)
    lo = (d - hi - mid).astype(bf16).astype(f32)
    pieces = jnp.concatenate(
        [hi, mid, lo, jnp.zeros((LANES - 3 * heads, Hd), f32)]).T
    lane = jax.lax.broadcasted_iota(jnp.int32, pieces.shape, 1)
    head_of = jnp.where(lane < 3 * heads, lane % heads, -1)
    ones = jnp.ones((LANES, N), bf16)
    for i in range(heads):
        g = 0 if per_group == all_heads else (first + i) // per_group
        B, C = b_ref[pl.ds(g, 1), :], c_ref[pl.ds(g, 1), :]     # [1, N]
        old = h_ref[i].astype(f32)                              # [Hd, N]
        # row hd: dtx[i, hd] in every lane
        # (one bfloat16 pass whatever the ambient matmul precision: the
        # pieces ARE bfloat16)
        col = jnp.dot(jnp.where(head_of == i, pieces, 0.0).astype(bf16),
                      ones, precision=jax.lax.Precision.DEFAULT,
                      preferred_element_type=f32)
        new = jnp.where(real, dA_ref[s * all_heads + first + i] * old
                        + col * B, old)
        o_ref[i] = new.astype(o_ref.dtype)
        yT_ref[:, i:i + 1] = jnp.sum(new * C, axis=-1, keepdims=True)
    y_ref[...] = yT_ref[...].T


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_step(h, m, dA, dtx, Bm, Cm, real, interpret: bool):
    Lm, S, Nh, Hd, N = h.shape
    G = Bm.shape[1]
    hb = heads_per_block(h)

    def state_map(s, j, meta, *_):
        return (meta[0], s, j, 0, 0)

    def row_map(s, j, *_):
        return (s, j, 0)

    def group_map(s, j, *_):
        return (s, 0, 0)

    state_spec = pl.BlockSpec((None, None, hb, Hd, N), state_map)
    row_spec = pl.BlockSpec((None, hb, Hd), row_map)
    group_spec = pl.BlockSpec((None, G, N), group_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, Nh // hb),
        in_specs=[row_spec, group_spec, group_spec, state_spec],
        out_specs=[row_spec, state_spec],
        scratch_shapes=[pltpu.VMEM((Hd, hb), jnp.float32)],
    )
    f32 = jnp.float32
    y, h = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb, all_heads=Nh,
                          per_group=Nh // G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, Nh, Hd), f32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        # operand 6 (after the three prefetched scalars) is the state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_step",
    )(jnp.asarray(m, jnp.int32).reshape(1), real.astype(jnp.int32),
      dA.astype(f32).reshape(S * Nh), dtx.astype(f32), Bm.astype(f32),
      Cm.astype(f32), h)
    return y, h


@jax.named_scope("ssm_scan")
def ssm_step(h: jax.Array, m, dA: jax.Array, dtx: jax.Array, Bm: jax.Array,
             Cm: jax.Array, real: jax.Array, interpret: bool | None = None):
    """One decode step of Mamba layer `m` over every slot's state.

    h: [Lm, S, Nh, Hd, N], the WHOLE carried state in its stored dtype
    (fits(h) must hold); m: int32 scalar, the layer among the Mamba
    layers (may be traced); dA [S, Nh], dtx [S, Nh, Hd], Bm and Cm
    [S, G, N] (G groups of Nh // G heads), float32 as ssm_scan forms
    them; real [S] bool. Returns (y [S, Nh, Hd] float32, h with layer
    m's slots advanced where real, every other layer as it was). The
    caller donates h (the engine's block programs do) or pays a copy."""
    if not fits(h):
        raise ValueError(f"ssm_step cannot cut {h.dtype}{list(h.shape)} "
                         f"into whole tiles and blocks of heads")
    interpret = resolve_interpret(interpret)
    note_kernel("ssm_step", interpret)
    return _ssm_step(h, m, dA, dtx, Bm, Cm, real, interpret=interpret)
