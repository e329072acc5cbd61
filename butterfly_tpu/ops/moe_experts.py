"""Pallas routed experts: one layer's experts, the touched ones alone.

A layer of experts written in `jnp` (models.common.moe_block) forms the
gate, up and down products of EVERY held expert over EVERY row of the
step and multiplies what no row chose by a gate of zero. At 32-64 rows
an expert's int8 codes bind, not its products, and XLA already streams
them at the rate the MXU takes them in; what is left is WHICH codes are
streamed: of GLM-5's 16 held experts three in ten are touched by no real
row of a step (PERF.md, PR 61). This kernel computes the same sum,

    out[r] = sum_e comb[r, e] * (act(x[r] Wg[e] sg[e]) * (x[r] Wu[e] su[e]))
                                 Wd[e] sd[e]

over the experts that some real row chose, and reads no other:

* the WHOLE layer-stacked codes are the operands ([L, E, D, F] gate and
  up, [L, E, F, D] down, their scales [L, E, 1, F] and [L, E, 1, D]); the
  layer rides the scalar prefetch, as ops/gdn_step.py takes its layer:
  one layer's slice cut out in XLA before a custom call is a COPY of it
  (604 MB a layer at GLM-5's widths), where a fusion reads it in place;
* the experts with a real row, compacted to the front of a list padded
  by REPEATING its last entry, and their count n ride the scalar
  prefetch too (touched). The grid is (E held, tiles of F): the index
  maps of the code operands read the list, and a padded step names the
  LAST tile of the last touched expert, the block the step before it
  held, so the pipeline issues no copy for it; the body is under
  pl.when(step < n). An expert no real row chose is never read: a NaN in
  its scales stays where it is;
* a step holds one tile of F of all three matrices ([D, tf], [D, tf],
  [tf, D]: runs of whole (32, 128) tiles of codes) and is complete in
  itself: gate and up are contracted over D a chunk at a time into
  float32 scratch (the codes converted to the compute dtype a chunk at a
  time, the scale a channel on the OUTPUT: quant/int8.py qeinsum's
  semantics), the activation and the row's gate are applied in float32,
  and the down product of the tile is added, times its scale, into ONE
  [rows, D] float32 result that stays in fast memory for the whole
  call: `g`, `u` and `y` of shape [E, rows, F] and the closing sum over
  experts are never formed. Every row of a visited expert is multiplied
  (the MXU takes a weight tile in at the same rate for 1 row or 64);
* the loops over chunks are loops, not copies of their body: a program
  that holds the call traces and lowers it at every start of a server.

Who takes the call is read from the step itself (takes): the rows, the
leaves' type, k of E. On the CPU backend the wrapper runs the kernel in
interpreter mode; everywhere else it is compiled (ops/__init__.py has
the rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from butterfly_tpu.ops import note_kernel, resolve_interpret
from butterfly_tpu.ops.flash_attention import live_auto_mesh

LANES = 128
#: the leaves of an expert stack the kernel reads (gate, up, down)
LEAVES = ("w_gate", "w_up", "w_down")
#: bytes of one tile of F of ONE matrix as stored ([D, tf] int8): a
#: WHOLE expert where it fits (GLM-5's [6144, 2048], 12 MiB; Keye's
#: [2048, 768]). The three matrices, double-buffered, are 75.5 MB of
#: VMEM_LIMIT then. A step's codes are then three unbroken runs, and a
#: layer is 16 grid steps: at GLM-5's geometry, all 16 touched, tiles of
#: 512, 1,024 and 2,048 columns read 905, 895 and 844 us a layer beside
#: the dense products' 842 (PERF.md, PR 61)
TILE_BYTES = 12 << 20
#: rows of the contraction one trip of a loop converts and multiplies
#: (256, 512 and 1,024 read alike on the chip)
CHUNK = 512
#: of a v5e core's 128 MiB
VMEM_LIMIT = 100 << 20
#: int8 codes bind under some 120 rows (197 TFLOP/s over 819 GB/s is 240
#: operations a byte and a code is two a row: servebench/peaks.py); the
#: cells part at 32-64 rows and 96-160, a wider step's dense products
#: run near the nearer bound (PERF.md section 5), and the call was timed
#: at 32 and at 64
ROWS_CODES_BIND = 64
#: the least share of the experts that EVEN routing leaves without a
#: row, (1 - k/E)^rows, which seeded or trained routing only raises: what
#: the call costs over the dense products with EVERY expert touched (1.2 %
#: at Keye's 4.7 MB an expert, 0.3 % at GLM-5's 37.75: PERF.md, PR 61), so
#: that no routing makes it a loss. GLM-5 at 64 rows leaves 13 %, at 32
#: rows 36 %; Keye 1.6 % and 13 %; SmallThinker 0.2 % and 4 %
UNTOUCHED_SHARE = 0.012


def f_tile(D: int, F: int) -> int:
    """Columns of F one grid step takes of each matrix: the most whole
    lane tiles that divide F and keep a [D, tf] block under TILE_BYTES;
    0 where one lane tile does not fit."""
    return max((t for t in range(LANES, F + 1, LANES)
                if F % t == 0 and D * t <= TILE_BYTES), default=0)


def _chunk(n: int) -> int:
    """Rows of a contraction over n a loop's trip takes: CHUNK where it
    divides n, else the most whole lane tiles under it that do."""
    return max(c for c in range(LANES, CHUNK + 1, LANES) if n % c == 0)


def fits(experts) -> bool:
    """Can the kernel serve this stack of experts? Its leaves are int8
    codes with a scale an output channel, [L, E, D, F] and [L, E, F, D]
    with D and F whole lane tiles, and a tile of F fits fast memory."""
    if not all(isinstance(experts.get(n), dict) and "q8" in experts[n]
               for n in LEAVES):
        return False
    gate, down = experts["w_gate"]["q8"], experts["w_down"]["q8"]
    if gate.ndim != 4 or gate.dtype != jnp.int8:
        return False
    D, F = gate.shape[2:]
    return D % LANES == 0 and F % LANES == 0 and f_tile(D, F) > 0 \
        and down.shape[2:] == (F, D)


def takes(rows: int, experts, k: int, E: int, use_kernel: bool) -> bool:
    """Does a step of `rows` rows compute this stack's experts through
    the kernel? ONE rule, read from the step: the engine's kernels are
    on and no mesh axis is left to GSPMD (a bare Mosaic call is opaque
    to it); the leaves are int8 codes the kernel can tile (fits); the
    rows are few enough that the codes' bytes bind and not the products
    (ROWS_CODES_BIND); and even routing of k of E over that many rows
    leaves UNTOUCHED_SHARE of the experts without one (a held share of
    the E meets the same odds an expert). Anything else keeps the dense
    products of models.common.moe_block."""
    return bool(use_kernel and not live_auto_mesh() and fits(experts)
                and rows <= ROWS_CODES_BIND
                and (1.0 - k / E) ** rows >= UNTOUCHED_SHARE)


def touched(comb: jax.Array):
    """(ids [E] int32, n [1] int32) of comb [rows, E], a row's gate on
    each expert and zero where it chose none or is not real: the experts
    with a nonzero gate in ascending order at the front of `ids`, the
    rest of it REPEATING the last of them, and how many they are."""
    E = comb.shape[1]
    hit = jnp.any(comb != 0, axis=0)                             # [E]
    at = jnp.cumsum(hit) - 1              # a touched expert's place
    e = jnp.arange(E, dtype=jnp.int32)
    ids = jnp.sum(jnp.where(hit[:, None] & (at[:, None] == e[None, :]),
                            e[:, None], 0), axis=0)
    n = jnp.sum(hit).astype(jnp.int32)
    last = jnp.max(jnp.where(hit, e, 0))
    return jnp.where(e < n, ids, last).astype(jnp.int32), n.reshape(1)


def _experts_kernel(meta_ref, ids_ref, x_ref, comb_ref, wg_ref, sg_ref,
                    wu_ref, su_ref, wd_ref, sd_ref, o_ref, gu_ref, h_ref, *,
                    act):
    """One tile of F of one touched expert: x_ref [D / kc, rows, kc];
    comb_ref [rows, E in whole lane tiles]; wg_ref, wu_ref [D, tf] and
    wd_ref [tf, D] codes, sg_ref, su_ref [1, tf] and sd_ref [1, D] their
    scales (layer and expert squeezed out); o_ref [rows, D] float32, the
    call's ONE result; gu_ref [2, rows, tf] float32 and h_ref
    [tf / fc, rows, fc] scratch. meta_ref [layer, n] and ids_ref [E]:
    the index maps read both, the body n and this step's expert."""
    f32 = jnp.float32
    # one pass in the compute dtype whatever the ambient matmul
    # precision: the operands ARE that dtype
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=f32)
    step = pl.program_id(0)
    first = (step == 0) & (pl.program_id(1) == 0)
    dt = x_ref.dtype
    kc, fc = x_ref.shape[2], h_ref.shape[2]

    @pl.when(first)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, f32)

    @pl.when(step < meta_ref[1])
    def _():
        gu_ref[...] = jnp.zeros(gu_ref.shape, f32)

        def contract(k, carry):
            rows = pl.ds(pl.multiple_of(k * kc, kc), kc)
            xk = x_ref[k]
            gu_ref[0] += dot(xk, wg_ref[rows, :].astype(dt))
            gu_ref[1] += dot(xk, wu_ref[rows, :].astype(dt))
            return carry

        jax.lax.fori_loop(0, x_ref.shape[0], contract, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, comb_ref.shape, 1)
        gate = jnp.sum(jnp.where(lane == ids_ref[step], comb_ref[...], 0.0),
                       axis=1, keepdims=True)                 # [rows, 1]
        g = gu_ref[0] * sg_ref[...].astype(f32)
        u = gu_ref[1] * su_ref[...].astype(f32)
        h = (act(g) * u * gate).astype(dt)
        for j in range(h_ref.shape[0]):
            h_ref[j] = h[:, j * fc:(j + 1) * fc]
        scale = sd_ref[...].astype(f32)

        def down(j, carry):
            rows = pl.ds(pl.multiple_of(j * fc, fc), fc)
            o_ref[...] += dot(h_ref[j], wd_ref[rows, :].astype(dt)) * scale
            return carry

        jax.lax.fori_loop(0, h_ref.shape[0], down, 0)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def _moe_experts(x, comb, experts, layer, act, interpret: bool):
    R, D = x.shape
    gate, up, down = (experts[n] for n in LEAVES)
    F = gate["q8"].shape[3]
    E = comb.shape[1]
    tf = f_tile(D, F)
    nf, kc, fc = F // tf, _chunk(D), _chunk(tf)
    ids, n = touched(comb)

    def tile(s, f, meta):       # a padded step: the last step's tile
        return jnp.where(s < meta[1], f, nf - 1)

    def cols_map(s, f, meta, ids):      # [L, E, D or 1, F] by tiles of F
        return (meta[0], ids[s], 0, tile(s, f, meta))

    def rows_map(s, f, meta, ids):      # [L, E, F, D] by tiles of F
        return (meta[0], ids[s], tile(s, f, meta), 0)

    def expert_map(s, f, meta, ids):    # [L, E, 1, D]
        return (meta[0], ids[s], 0, 0)

    def whole(shape):
        return pl.BlockSpec(shape, lambda s, f, *_: (0,) * len(shape))

    lanes = -(-E // LANES) * LANES
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(E, nf),
        in_specs=[whole((D // kc, R, kc)), whole((R, lanes)),
                  pl.BlockSpec((None, None, D, tf), cols_map),
                  pl.BlockSpec((None, None, 1, tf), cols_map),
                  pl.BlockSpec((None, None, D, tf), cols_map),
                  pl.BlockSpec((None, None, 1, tf), cols_map),
                  pl.BlockSpec((None, None, tf, D), rows_map),
                  pl.BlockSpec((None, None, 1, D), expert_map)],
        out_specs=whole((R, D)),
        scratch_shapes=[pltpu.VMEM((2, R, tf), jnp.float32),
                        pltpu.VMEM((tf // fc, R, fc), x.dtype)],
    )
    return pl.pallas_call(
        functools.partial(_experts_kernel, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_experts",
    )(jnp.concatenate([jnp.asarray(layer, jnp.int32).reshape(1), n]), ids,
      x.reshape(R, D // kc, kc).transpose(1, 0, 2),
      jnp.pad(comb.astype(jnp.float32), ((0, 0), (0, lanes - E))),
      gate["q8"], gate["s"], up["q8"], up["s"], down["q8"], down["s"])


@jax.named_scope("moe_experts")
def moe_experts(x: jax.Array, comb: jax.Array, experts, layer, act,
                interpret: bool | None = None) -> jax.Array:
    """One layer's routed experts over the rows of a step, the experts
    some row chose alone: sum_e comb[r, e] * expert_e(x[r]).

    x: [rows, D] in the compute dtype; comb: [rows, E] float32, a row's
    gate on each HELD expert, zero where it chose none and in every row
    that is not real (models.common.moe_block forms it); experts: the
    layer-STACKED leaves {w_gate, w_up, w_down: {q8, s}} (fits(experts)
    must hold), of which layer `layer` (int32 scalar, may be traced) is
    read, where it lies; act: the activation (a function of float32).
    Returns [rows, D] float32; zeros where no expert is touched."""
    if not fits(experts):
        raise ValueError("moe_experts cannot tile these experts' leaves")
    interpret = resolve_interpret(interpret)
    note_kernel("moe_experts", interpret)
    R = x.shape[0]
    pad = -R % 16           # whole sublane tiles of the compute dtype
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        comb = jnp.pad(comb, ((0, pad), (0, 0)))
    return _moe_experts(x, comb, {n: experts[n] for n in LEAVES}, layer,
                        act=act, interpret=interpret)[:R]
