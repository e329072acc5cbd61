"""Pallas Gated DeltaNet decode step: one pass over the recurrent state.

A decode row advances its slot's state by one position of the delta
rule: per head, with S the state, `r = alpha S k` (what the state holds
for the incoming key), `u = beta (v - r)`, `new = alpha S + u k^T`, and
the readout `o = new q = alpha S q + u (k . q)`. Written in `jnp`
(models.common.gdn_step) XLA makes two fusions of it, the reduction
(alpha S k and alpha S q) and the update in place, and a step reads
every slot's state TWICE. This kernel loads a slot's state once, forms
both sums, the update and the readout from that one float32 value, and
stores `new` in the state's dtype back to the place it came from:

* the WHOLE carried state h [Ls, S, H/g, dk, g dv] is the operand (g
  heads' values share a row of lanes, keys down the sublanes:
  cache/ssm_state.py has the layout), aliased to its result; the layer
  `m` rides the scalar prefetch (as ops/ssm_step.py takes its layer:
  one layer cut out in XLA is a copy of it), so only layer m's blocks
  are visited and the others are untouched through the alias;
* grid (slots, blocks of head groups): a block is `groups` whole
  groups of one slot, [groups, dk, g dv], streamed in and out by the
  Mosaic pipeline and worked a group a trip of a loop, a lane tile
  [dk, 128] at a time; the arithmetic is float32 whatever the state is
  stored in, in the `jnp` step's own order but for the sums over dk;
* the step's small operands arrive as gdn_step_inputs forms them at
  T == 1: q and k [S, H, dk], v [S, H dv] flat, alpha = exp(log_alpha)
  and beta [S, H], and k . q [S, H] (a head's three numbers are
  scalars: SMEM), and real [S]. A row that is not real (dead, free, in
  prefill phase, the slot a chunk wrote this step) does no arithmetic:
  its block is stored as it was loaded, bit for bit (what log_alpha 0
  and beta 0 give in the `jnp` step), and its o is ZERO, where the
  `jnp` step reads the state out as it stands: nothing takes that
  row's o (the step's caller samples live rows alone).

**The keys' columns.** Both sums and the rank-one update want a head's
key (and query) [dk] down the SUBLANES and equal across its dv lanes,
and they arrive with dk on the lanes: the transposed broadcast
ops/ssm_step.py met, once for k and once for q. As there, it goes
through the otherwise idle MXU, EXACTLY: a block's k and q are cut into
three bfloat16 pieces each (hi + mid + lo is the float32 value, to the
bit) and transposed once a slot, head i's pieces in lanes i, n + i and
2 n + i (n the block's heads, rounded up to whole sublane tiles);
a lane tile of a group multiplies them by a constant matrix of ones and
zeros that sends the three pieces of the lane's OWN head to it. Each
product is exact, the float32 accumulator adds three addends whose
partial sums are representable, so row d of the result holds k[head of
the lane, d] in every lane: no value of the state's size is formed
outside the kernel (kx and qx in float32 would be twice the layer's
state each), and no lane is masked or selected by the vector unit,
which has the two multiply-and-sums, the decay and the update to do.

On the CPU backend the wrapper runs the kernel in interpreter mode;
everywhere else it is compiled (ops/__init__.py has the rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from butterfly_tpu.ops import (note_kernel, resolve_interpret,
                               sublane_multiple)

#: a lane tile, and the MXU's contraction width: three pieces of a
#: block's heads must fit one pass
LANES = 128
#: bytes of one state block [groups, dk, g dv] as stored; in and out,
#: each double-buffered, beside the selectors stay inside the 16 MB of
#: scoped VMEM (Olmo-Hybrid's 15 groups of [96, 384] in bfloat16: 1.1 MB)
BLOCK_BYTES = 3 << 19


def _padded(heads: int) -> int:
    """A block's heads rounded up to whole tiles of 8 rows."""
    return -(-heads // 8) * 8


def groups_per_block(h: jax.Array, heads: int) -> int:
    """Head groups of one slot a grid step takes: the largest divisor of
    H/g whose heads' three pieces fit the MXU's 128 lanes, whose rows
    tile the step's [groups, g dv] operands (a multiple of 8, or all of
    them) and whose block stays under BLOCK_BYTES; 0 when there is
    none."""
    J, dk, L = h.shape[2:]
    g = heads // J
    return max((d for d in range(1, J + 1)
                if J % d == 0 and 3 * _padded(d * g) <= LANES
                and (d % 8 == 0 or d == J)
                and d * dk * L * h.dtype.itemsize <= BLOCK_BYTES),
               default=0)


def fits(h: jax.Array, heads: int) -> bool:
    """Can the kernel serve this state of `heads` heads? Its two minor
    dims are whole Mosaic tiles (g dv on the lanes, dk on the sublanes
    of the state's dtype) and its groups cut into blocks; any other
    state takes the `jnp` step."""
    J, dk, L = h.shape[2:]
    return L % LANES == 0 and dk % sublane_multiple(h.dtype) == 0 \
        and heads % J == 0 and L % (heads // J) == 0 \
        and groups_per_block(h, heads) > 0


def _selectors(groups: int, g: int, dv: int) -> np.ndarray:
    """[groups, 128, g dv] of ones and zeros: row p * n + i (piece p of
    the block's head i, n the block's heads padded) is one in the lanes
    of head i's values in ITS group's row, and nowhere else."""
    n = _padded(groups * g)
    row = np.arange(LANES)
    head = np.where(row < 3 * n, row % n, -1)                # [128]
    lane_head = np.arange(g * dv) // dv                      # [g dv]
    own = head[None, :, None] == (np.arange(groups)[:, None, None] * g
                                  + lane_head[None, None, :])
    return own.astype(jnp.bfloat16)


def _step_kernel(meta_ref, real_ref, alpha_ref, beta_ref, kq_ref, k_ref,
                 q_ref, v_ref, sel_ref, h_ref, y_ref, o_ref, *, groups: int,
                 g: int, dv: int, all_heads: int):
    """One slot's `groups` groups: h_ref, o_ref [groups, dk, g dv] (the
    layer and the slot squeezed out); real_ref [S], alpha_ref, beta_ref,
    kq_ref [S * H] in SMEM; k_ref, q_ref [n, dk] (the block's heads,
    padded); v_ref, y_ref [groups, g dv]; sel_ref [groups, 128, g dv]
    (_selectors). meta_ref [layer] is read by the index maps alone."""
    s = pl.program_id(0)

    @pl.when(real_ref[s] == 0)
    def _():        # a row that does not decode: the block as it came
        o_ref[...] = h_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)

    # (a program's id is read outside the branches: the interpreter
    # knows it nowhere else)
    pl.when(real_ref[s] > 0)(functools.partial(
        _advance, s * all_heads + pl.program_id(1) * groups * g, alpha_ref, beta_ref, kq_ref, k_ref, q_ref, v_ref,
        sel_ref, h_ref, y_ref, o_ref, groups=groups, g=g, dv=dv))


def _advance(first, alpha_ref, beta_ref, kq_ref, k_ref, q_ref, v_ref, sel_ref,
             h_ref, y_ref, o_ref, *, groups: int, g: int, dv: int):
    """_step_kernel for a slot that decodes; first: the place of the
    block's first head in the SMEM operands."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    n, dk = k_ref.shape
    L = g * dv

    def columns(ref):
        # three bfloat16 pieces of every head, one transpose: [dk, 128],
        # lane p * n + i is piece p of head i
        x = ref[...]
        hi = x.astype(bf16).astype(f32)
        mid = (x - hi).astype(bf16).astype(f32)
        lo = (x - hi - mid).astype(bf16).astype(f32)
        return jnp.concatenate(
            [hi, mid, lo, jnp.zeros((LANES - 3 * n, dk), f32)]).T.astype(bf16)

    pieces = jnp.concatenate([columns(k_ref), columns(q_ref)])  # [2 dk, 128]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def of_lanes(ref, base, lo):
        """A head's scalar over the lanes [lo, lo + 128) of a group's
        row: the heads that own them, by selects over a splat."""
        heads = range(lo // dv, (lo + LANES - 1) // dv + 1)
        out = ref[base + heads[0]]
        for i in heads[1:]:
            out = jnp.where(lane >= i * dv - lo, ref[base + i], out)
        return out

    tiles = range(0, L, LANES)
    row = jax.lax.broadcasted_iota(jnp.int32, (groups, LANES), 0)

    def group(j, ys):
        """Group j of the block, a lane tile at a time; ys: the block's
        readout so far, a [groups, 128] value a tile (a group's row of v
        is picked, and its row of y put, by the row's number: Mosaic
        loads no single row at an index it does not know)."""
        mine, out = row == j, []
        for lo, y in zip(tiles, ys):
            at = pl.ds(lo, LANES)
            # row d: k (then q) of the lane's own head at d, in every
            # lane (one bfloat16 pass whatever the ambient matmul
            # precision: the pieces ARE bfloat16)
            cols = jnp.dot(pieces, sel_ref[j, :, at],
                           precision=jax.lax.Precision.DEFAULT,
                           preferred_element_type=f32)
            kx, qx = cols[:dk], cols[dk:]
            alpha = of_lanes(alpha_ref, first + j * g, lo)
            beta = of_lanes(beta_ref, first + j * g, lo)
            kq = of_lanes(kq_ref, first + j * g, lo)
            st = h_ref[j, :, at].astype(f32)                    # [dk, 128]
            v = jnp.sum(jnp.where(mine, v_ref[:, at], 0.0), axis=0,
                        keepdims=True)
            r = alpha * jnp.sum(st * kx, axis=0, keepdims=True)
            p = alpha * jnp.sum(st * qx, axis=0, keepdims=True)
            u = beta * (v - r)                                  # [1, 128]
            o_ref[j, :, at] = (alpha * st + u * kx).astype(o_ref.dtype)
            out.append(jnp.where(mine, p + u * kq, y))
        return tuple(out)

    # a loop, not a block's 15 copies of its body: a program that holds
    # the call traces and lowers the body at every start of a server,
    # and the whole block unrolled cost a start 12 s of 26 (PERF.md,
    # PR 57)
    ys = jax.lax.fori_loop(
        0, groups, group,
        tuple(jnp.zeros((groups, LANES), f32) for _ in tiles))
    for lo, y in zip(tiles, ys):
        y_ref[:, pl.ds(lo, LANES)] = y


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gdn_step(h, m, q, k, v, alpha, beta, real, interpret: bool):
    Ls, S, J, dk, L = h.shape
    H = k.shape[1]
    g = H // J
    dv = L // g
    gb = groups_per_block(h, H)
    nb, nh = J // gb, gb * g
    n = _padded(nh)
    f32 = jnp.float32

    def rows(a):            # [S, H, dk] -> [S, blocks, n, dk]
        return jnp.pad(a.astype(f32).reshape(S, nb, nh, dk),
                       ((0, 0), (0, 0), (0, n - nh), (0, 0)))

    def state_map(s, b, meta, *_):
        return (meta[0], s, b, 0, 0)

    def row_map(s, b, *_):
        return (s, b, 0, 0)

    def lane_map(s, b, *_):
        return (s, b, 0)

    state_spec = pl.BlockSpec((None, None, gb, dk, L), state_map)
    row_spec = pl.BlockSpec((None, None, n, dk), row_map)
    lane_spec = pl.BlockSpec((None, gb, L), lane_map)
    sel_spec = pl.BlockSpec((gb, LANES, L), lambda s, b, *_: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(S, nb),
        in_specs=[row_spec, row_spec, lane_spec, sel_spec, state_spec],
        out_specs=[lane_spec, state_spec],
    )
    kq = jnp.sum(k * q, axis=-1)
    y, h = pl.pallas_call(
        functools.partial(_step_kernel, groups=gb, g=g, dv=dv, all_heads=H),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, J, L), f32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        # operand 9 (after the five prefetched scalars) is the state
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="gdn_step",
    )(jnp.asarray(m, jnp.int32).reshape(1), real.astype(jnp.int32),
      alpha.astype(f32).reshape(S * H), beta.astype(f32).reshape(S * H),
      kq.astype(f32).reshape(S * H),
      rows(k), rows(q), v.astype(f32).reshape(S, J, L),
      jnp.asarray(_selectors(gb, g, dv)), h)
    return y.reshape(S, H * dv), h


@jax.named_scope("gdn_step")
def gdn_step(h: jax.Array, m, q: jax.Array, k: jax.Array, v: jax.Array,
             log_alpha: jax.Array, beta: jax.Array, real: jax.Array,
             interpret: bool | None = None):
    """One decode step of Gated DeltaNet layer `m` over every slot's
    state: models.common.gdn_step's contract, one pass.

    h: [Ls, S, H/g, dk, g dv], the WHOLE carried state in its stored
    dtype (fits(h, H) must hold); m: int32 scalar, the layer among the
    recurrent layers (may be traced); q and k [S, H, dk], v [S, H dv]
    flat, log_alpha and beta [S, H], float32 as gdn_step_inputs forms
    them at T == 1; real [S] bool, the rows that decode. Returns
    (o [S, H dv] float32 flat, zero where not real; h with layer m's
    real slots advanced, every other slot and layer as it was). The
    caller donates h (the engine's block programs do) or pays a copy."""
    if not fits(h, k.shape[1]):
        raise ValueError(f"gdn_step cannot cut {h.dtype}{list(h.shape)} "
                         f"into whole tiles and blocks of head groups")
    interpret = resolve_interpret(interpret)
    note_kernel("gdn_step", interpret)
    return _gdn_step(h, m, q, k, v, jnp.exp(log_alpha), beta, real,
                     interpret=interpret)
