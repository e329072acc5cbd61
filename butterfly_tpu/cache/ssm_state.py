"""The recurrent state of a model with recurrent layers (Mamba-2 mixers,
Gated DeltaNet mixers or Mamba-1 mixers: cfg.recurrent_kind says which
ONE kind a model has): a second KIND of per-stream memory beside the
paged pool.

A page pool grows with a stream: a token adds a row to every attention
layer. A recurrent layer keeps a FIXED-SIZE state a stream instead, the
same bytes at position 10 and at position 100,000: each head's state
and the last K-1 inputs of the causal conv [K-1, Dc]. It is never
paged, hashed or exported; it belongs to a SLOT, is read and written by
every step, and starts from zero when a stream starts (position 0),
whatever the slot's last tenant left.

The interface is narrow on purpose (the scheduler learns nothing of it):

* `init_ssm_state(cfg, slots)`: allocate for S slots (None for a model
  without such layers);
* the value is a pytree (SSMState): the engine donates it to every
  block program and rebinds it from the result, exactly like the KV
  window, and it rides the block scan's CARRY (engine/serving.py);
* `advance_packed(...)`: one recurrent layer of a packed mixed step, by
  the layer's kind: a decode row advances its slot's state by one
  position, a prefill chunk advances its OWN slot's state by its real
  columns, filler columns, idle chunks and dead rows advance nothing,
  and a chunk that starts at position 0 starts from zero INSIDE the
  program (a recomputed preemption or a reused slot needs no host edit);
* `reset_slots(state, slots)`: zero slots from the host (tests, and a
  caller that wants a scrubbed slot; serving never needs it);
* `state_info(cfg, slots)`: what /health and the ready line say: the
  kind, the layout held and its bytes a slot.

Layout, by kind (`state_shapes`). The conv's tail is [Ls, K-1, S, Dc]
for all three (slots on the sublanes: a [.., K-1, Dc] minor pair would pad
K-1 = 3 to a tile of 16 rows in bfloat16, five times the bytes): K-1
PLANES [S, Dc] a layer, plane k every slot's input k - (K-1) positions
back, each in whole tiles. Nobody reads it swapped. The decode rows read
and write layer m's planes where they lie (`decode_rows_conv`: a live
row's new plane k is its old plane k + 1, the last one its input, and
the conv's output is the sum of K planes times K rows of taps, all
elementwise over planes: models.common.conv_step and an update in
place a plane at (m, k), XLA's own, kernels on or off), and only a
CHUNK's own slot is cut out,
a row of Dc at a time (`_slot_tail`: a few KB), run through
models.common._causal_conv as [K-1, Dc] and written back by slot.
Mamba-2: h [Lm, S, Nh, Hd, N], a head's state with N on the lanes.
Gated DeltaNet: a head's state is [dv, dk] and neither need be a whole
number of 128 lanes (Olmo-Hybrid: 192 x 96), so g heads' VALUES share a
row of lanes, g the fewest that fill whole lanes (cfg.gdn_head_group: 2
x 192 = 384 = 3 x 128): h [Ls, S, H/g, dk, g dv], keys down the
sublanes (96 = 6 tiles of 16 in bfloat16). Mamba-1: a channel's state
is N numbers and N = 16 is an eighth of a row of lanes (held [.., Di, N]
a slot of Jamba2-3B's 5 MB would be 40), so the CHANNELS lie on the
lanes and the state index down the sublanes: h [Lm, S, N, Di] (16 rows
are one tile of bfloat16, 5,120 channels 40 rows of lanes); its conv's
tail is over the Di channels of u alone. The DECLARED shape is then
what the memory holds, to the byte (`state_info`'s `whole_tiles` says
whether a geometry's is: a layout that the device pads says so). Every
write is a dynamic-update-slice of the carried buffer at the layer's
index, which XLA performs in place (a scatter into a scan carry copies
the whole buffer: cache/paged.py's window docs have the measurement).

Who computes the recurrence. Mamba-2: a DECODE row's one step is the
Pallas kernel ops/ssm_step.py when the engine's kernels are on and the
state's minor dims are whole tiles (the kernel takes the whole h,
aliased to its result, and the layer's index: one pass over layer m's
slots where they lie, y read from the float32 value before it is
rounded to the stored dtype); with kernels off (the CPU) or any other
state it is models.common.ssm_scan at T == 1 and the update in place,
which is also what the kernel is tested against. A CHUNK's is ssm_scan's
scan over its positions, either way. Gated DeltaNet, by the same rule:
a decode row's one step is the Pallas kernel ops/gdn_step.py when the
engine's kernels are on and the state's minor dims are whole tiles
(ops/gdn_step.py fits: the whole h aliased to its result and the
layer's index, ONE pass over layer m's slots where they lie, alpha S k,
alpha S q, the update and the readout from one float32 value of the
block); with kernels off (the CPU) or any other state it is
models.common.gdn_step (XLA's: one reduction over the layer's state and
the update in place, the state read twice), which is also what the
kernel is tested against. A chunk's is gdn_chunk (the chunkwise form),
either way. Mamba-1, by the same rule: a decode row's one step is the
Pallas kernel ops/mamba1_step.py when the engine's kernels are on and
the state's minor dims are whole tiles (ops/mamba1_step.py fits: the
whole h aliased to its result and the layer's index, ONE pass over
layer m's slots where they lie, the decay, the input and the readout
from one float32 value of a slot's state); with kernels off (the CPU)
or any other state it is models.common.mamba1_step (XLA's: the update
in place and a readout that forms it a second time, the state read
twice), which is also what the kernel is tested against. A chunk's is
mamba1_scan's scan over its positions, either way. Any kind: the
chunks read and write their own slots
FIRST, the state's and the tails', and the decode rows' step follows on
the result (a chunk's slot is no live decode row, so that step leaves
it as it is).
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from butterfly_tpu.core.config import ModelConfig
from butterfly_tpu.models.common import (
    RECURRENT_NAMES, conv_step, ffn_close, gdn_chunk, gdn_conv, gdn_gate_out,
    gdn_in_proj, gdn_step, gdn_step_inputs, mamba1_conv, mamba1_gate_out,
    mamba1_in_proj, mamba1_scan, mamba1_step, mamba1_step_inputs, ssm_conv,
    ssm_gate_out, ssm_in_proj, ssm_scan, ssm_skip, ssm_step_inputs,
    stream_read, stream_write)
from butterfly_tpu.ops import gdn_step as gdn_kernel
from butterfly_tpu.ops import mamba1_step as mamba1_kernel
from butterfly_tpu.ops.ssm_step import fits, ssm_step


class SSMState(NamedTuple):
    h: jax.Array      # by kind (state_shapes): [Ls, S, ...a slot's state]
    conv: jax.Array   # [Ls, K-1, S, Dc]

    @property
    def num_slots(self) -> int:
        return self.h.shape[1]


class StateRows(NamedTuple):
    """What advance_packed reads of a step's rows (cache/paged.py
    PackedRows holds these among its fields; the contiguous path,
    models/common.py forward, builds one of a batch's rows)."""
    active: jax.Array      # [S] the slots that decode this step
    ok: jax.Array          # [S + P*C] the rows that are real
    chunk_slot: jax.Array  # [P]
    chunk_ok: jax.Array    # [P] the chunk carries something
    chunk_pos: jax.Array   # [P, C]


def state_shapes(cfg: ModelConfig, slots: int) -> Dict[str, tuple]:
    """{"h", "conv"}: the shapes the state is held in, by the model's
    recurrent kind (the module's docstring has why)."""
    Ls = cfg.num_ssm_layers
    if cfg.recurrent_kind == "linear_attention":
        g = cfg.gdn_head_group
        return {"h": (Ls, slots, cfg.gdn_heads // g, cfg.gdn_key_dim,
                      g * cfg.gdn_value_dim),
                "conv": (Ls, cfg.gdn_conv - 1, slots, cfg.gdn_conv_dim)}
    if cfg.recurrent_kind == "mamba1":
        return {"h": (Ls, slots, cfg.mamba1_state, cfg.mamba1_inner),
                "conv": (Ls, cfg.mamba1_conv - 1, slots, cfg.mamba1_inner)}
    return {"h": (Ls, slots, cfg.ssm_heads, cfg.ssm_head_dim,
                  cfg.ssm_state),
            "conv": (Ls, cfg.ssm_conv - 1, slots, cfg.ssm_conv_dim)}


#: the dims of h as held (state_shapes), by kind: state_info's `layout`
_LAYOUTS = {"mamba": "layers, slots, heads, head_dim, state",
            "linear_attention": "layers, slots, heads/g, key_dim, g*value_dim",
            "mamba1": "layers, slots, state, channels"}


def _held(shape, itemsize: int) -> int:
    """Values a TPU holds for `shape`: the two minor dims in whole
    tiles (128 lanes; 8 sublanes of 32 bits, so 16 rows of bfloat16)."""
    *major, rows, lanes = shape
    sub = 8 * 4 // itemsize
    return math.prod(major) * -(-rows // sub) * sub * -(-lanes // 128) * 128


def bytes_per_slot(cfg: ModelConfig) -> int:
    """What one stream's state weighs, all recurrent layers: the
    declared values' bytes, which are what a TPU holds where the layout
    has whole tiles (state_info's `whole_tiles`: the three accepted
    geometries)."""
    shapes = state_shapes(cfg, 1)
    return (math.prod(shapes["h"]) + math.prod(shapes["conv"])) \
        * jnp.dtype(cfg.dtype).itemsize


def state_info(cfg: ModelConfig, slots: int) -> Optional[Dict]:
    """{kind, layers, layout, whole_tiles, bytes_per_slot, dtype, bytes},
    or None for a model without a recurrent state. `layout` names the
    dims of h as held (state_shapes); `whole_tiles`: the declared shapes
    are what a TPU's memory holds, to the byte (else it pads them)."""
    if not cfg.has_ssm:
        return None
    per = bytes_per_slot(cfg)
    size = jnp.dtype(cfg.dtype).itemsize
    shapes = state_shapes(cfg, slots)
    dims = _LAYOUTS[cfg.recurrent_kind]
    return {"kind": RECURRENT_NAMES[cfg.recurrent_kind],
            "layers": cfg.num_ssm_layers,
            "layout": f"h [{dims}] = {list(shapes['h'])}",
            "whole_tiles": all(_held(sh, size) == math.prod(sh)
                               for sh in shapes.values()),
            "bytes_per_slot": per,
            "dtype": str(jnp.dtype(cfg.dtype)), "bytes": per * slots}


def init_ssm_state(cfg: ModelConfig, slots: int,
                   sharding=None) -> Optional[SSMState]:
    """Zero state for `slots` slots, committed to `sharding` as the
    pool is (a block's outputs are: one executable for the first call
    and the rest)."""
    if not cfg.has_ssm:
        return None
    dt = jnp.dtype(cfg.dtype)
    shapes = state_shapes(cfg, slots)

    def build():
        return SSMState(h=jnp.zeros(shapes["h"], dt),
                        conv=jnp.zeros(shapes["conv"], dt))

    return jax.jit(build, out_shardings=sharding)()


def reset_slots(state: SSMState, slots) -> SSMState:
    """The state with `slots` (indices) zeroed, every layer."""
    slots = jnp.asarray(slots, jnp.int32)
    return SSMState(h=state.h.at[:, slots].set(0),
                    conv=state.conv.at[:, :, slots].set(0))


def decode_rows_step(h, m, u, dt, mp, cfg: ModelConfig, count,
                     use_kernel: bool = False):
    """The Mamba-2 recurrence of layer m's decode rows, one position: h
    [Lm, S, Nh, Hd, N] the whole carried state, u [S, 1, Dc] float32
    (ssm_conv), dt [S, 1, Nh], count [S] (1: the row decodes). With
    use_kernel and a state the kernel can cut (ops/ssm_step.py fits) one
    pass over the state where it lies; else ssm_scan's one step on a
    float32 view of the layer and an update in place: the kernel's
    reference. Returns (y [S, 1, Nh, Hd] float32, h)."""
    if use_kernel and fits(h):
        x, dA, dtx, Bg, Cg, real = ssm_step_inputs(u, dt, mp, cfg, count)
        y, h = ssm_step(h, m, dA[:, 0], dtx[:, 0], Bg[:, 0], Cg[:, 0],
                        real[:, 0])
        return ssm_skip(y[:, None], x, mp), h
    h_m = lax.dynamic_index_in_dim(h, m, 0, keepdims=False)
    y, new = ssm_scan(u, dt, mp, cfg, h_m.astype(jnp.float32), count)
    return y, lax.dynamic_update_index_in_dim(h, new.astype(h.dtype), m, 0)


def _slot_tail(conv, m, slot):
    """One slot's tail of layer m, [K-1, Dc], cut out of the carried
    conv [Ls, K-1, S, Dc] a ROW at a time: a row has one dim that is
    not 1 and so no layout to disagree about (asked for a slot's
    [K-1, 1, Dc] in one slice, XLA laid the WHOLE conv out slots-major
    for that reader's sake, a copy of all of it every layer-step:
    PERF.md, PR 64)."""
    Dc = conv.shape[3]
    return jnp.concatenate(
        [lax.dynamic_slice(conv, (m, k, slot, 0), (1, 1, 1, Dc)).reshape(1, Dc)
         for k in range(conv.shape[1])])


def decode_rows_conv(conv, m, x, mp, live):
    """The causal conv of layer m's decode rows, one position, any
    kind: conv [Ls, K-1, S, Dc] the whole carried tails, x [S, Dc] the
    rows' inputs, live [S] bool (the row decodes). One pass over layer
    m's planes WHERE THEY LIE (models.common.conv_step has the
    arithmetic), each new plane written in place at (m, k) by an update
    that holds its select. Returns (u [S, 1, Dc] float32, conv)."""
    planes = lax.dynamic_index_in_dim(conv, m, 0, keepdims=False)
    u, new = conv_step(planes, x, mp, live)
    for k, plane in enumerate(new):
        conv = lax.dynamic_update_slice(
            conv, plane.astype(conv.dtype)[None, None], (m, k, 0, 0))
    return u, conv


def gdn_heads_of(st: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Slots' Gated DeltaNet state as held, st [P, H/g, dk, g dv], a
    head at a time: [P, H, dv, dk], the reference's. For a reader of
    the state (tools/state_parity.py, the tests); no step transposes
    it (models.common.gdn_step and gdn_chunk read the held layout as
    it lies)."""
    P, J, dk, _ = st.shape
    g, dv = cfg.gdn_head_group, cfg.gdn_value_dim
    st = st.reshape(P, J, dk, g, dv).transpose(0, 1, 3, 4, 2)
    return st.reshape(P, J * g, dv, dk)


def gdn_lanes_of(st: jax.Array, cfg: ModelConfig) -> jax.Array:
    """gdn_heads_of's inverse: [P, H, dv, dk] -> [P, H/g, dk, g dv]."""
    P, H, dv, dk = st.shape
    g = cfg.gdn_head_group
    st = st.reshape(P, H // g, g, dv, dk).transpose(0, 1, 4, 2, 3)
    return st.reshape(P, H // g, dk, g * dv)


class _Mamba:
    """advance_packed's Mamba-2 layer: the pieces of models/common.py
    in the order the skeleton calls them."""
    conv = staticmethod(ssm_conv)

    @staticmethod
    def project(h, mp, cfg):
        z, xbc, dt = ssm_in_proj(h, mp, cfg)
        return xbc, (z, dt)

    @staticmethod
    def decode(h, m, u, aux, mp, cfg, count, use_kernel):
        return decode_rows_step(h, m, u, aux[1], mp, cfg, count, use_kernel)

    @staticmethod
    def chunk(st0, u, aux, mp, cfg, count):
        return ssm_scan(u, aux[1], mp, cfg, st0.astype(jnp.float32), count)

    @staticmethod
    def close(y, aux, mp, cfg):
        return ssm_gate_out(y, aux[0], mp, cfg)


class _DeltaNet:
    """advance_packed's Gated DeltaNet layer. A decode row's step: with
    use_kernel and a state the kernel can cut (ops/gdn_step.py fits)
    one pass over the state where it lies; else models.common.gdn_step,
    the kernel's reference."""
    conv = staticmethod(gdn_conv)

    @staticmethod
    def project(h, gp, cfg):
        qkv, z, a, b = gdn_in_proj(h, gp, cfg)
        return qkv, (z, a, b)

    @staticmethod
    def decode(h, m, u, aux, gp, cfg, count, use_kernel):
        q, k, v, la, beta = (a[:, 0] for a in gdn_step_inputs(
            u, aux[1], aux[2], gp, cfg, count))
        if use_kernel and gdn_kernel.fits(h, cfg.gdn_heads):
            o, h = gdn_kernel.gdn_step(h, m, q, k, v, la, beta, count > 0)
        else:
            o, h = gdn_step(h, m, q, k, v, la, beta, cfg)
        return o.reshape(o.shape[0], 1, cfg.gdn_heads, cfg.gdn_value_dim), h

    @staticmethod
    def chunk(st0, u, aux, gp, cfg, count):
        return gdn_chunk(*gdn_step_inputs(u, aux[1], aux[2], gp, cfg, count),
                         st0.astype(jnp.float32), cfg)

    @staticmethod
    def close(o, aux, gp, cfg):
        return gdn_gate_out(o, aux[0], gp, cfg)


class _Mamba1:
    """advance_packed's Mamba-1 layer: dt, B and C come from the conv's
    OUTPUT, so they are formed where the recurrence is (decode, chunk)
    and `aux` carries the gate alone. A decode row's step: with
    use_kernel and a state the kernel can cut (ops/mamba1_step.py fits)
    one pass over the state where it lies; else
    models.common.mamba1_step, the kernel's reference."""
    conv = staticmethod(mamba1_conv)

    @staticmethod
    def project(h, mp, cfg):
        u, z = mamba1_in_proj(h, mp, cfg)
        return u, (z,)

    @staticmethod
    def decode(h, m, u, aux, mp, cfg, count, use_kernel):
        dt, Bm, Cm = mamba1_step_inputs(u, mp, cfg)
        if not (use_kernel and mamba1_kernel.fits(h)):
            return mamba1_step(h, m, u, dt, Bm, Cm, mp, count)
        f32 = jnp.float32
        y, h = mamba1_kernel.mamba1_step(
            h, m, u[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0],
            -jnp.exp(mp["A_log"].astype(f32)), count > 0)
        return (y + mp["D"].astype(f32) * u[:, 0])[:, None], h

    @staticmethod
    def chunk(st0, u, aux, mp, cfg, count):
        return mamba1_scan(u, *mamba1_step_inputs(u, mp, cfg), mp,
                           st0.astype(jnp.float32), count)

    @staticmethod
    def close(y, aux, mp, cfg):
        return mamba1_gate_out(y, aux[0], mp)


_MIXERS = {"mamba": _Mamba, "linear_attention": _DeltaNet,
           "mamba1": _Mamba1}


def advance_packed(x, lp, mp, state: SSMState, m, rows, cfg: ModelConfig,
                   use_kernel: bool = False):
    """One recurrent layer (mixer, feed-forward, both residuals) of a
    packed mixed step over x [N, 1, D], N = S + P*C rows: the S decode
    rows first, then P chunks of C columns (`rows`: StateRows'
    fields). lp, mp: the layer's slices of params["layers"] and of its
    kind's stack (params["mamba"], "gdn" or "mamba1"); m: its index among
    the recurrent layers (traced). The layer's KIND (cfg.recurrent_kind)
    picks the mixer's pieces (_Mamba, _DeltaNet, _Mamba1); the order
    below is the same for all. use_kernel: the engine's kernel rule
    (ops/__init__.py); with it, and a state of whole tiles, the decode
    rows' recurrence is its kind's kernel (ssm_step, gdn_step,
    mamba1_step), one pass over the state where it lies.

    The projections, the gate and the feed-forward run once over all N
    rows (the weights stream once); the conv and the recurrence run on
    the decode rows as T == 1 against every slot's state (S is the
    state's slots, or 0: a step of chunks alone), and on each chunk as
    T == C against its own slot's, from zero where the chunk starts at
    position 0. Returns (x, state, load): load as
    models.common.ffn_close's."""
    mixer = _MIXERS[cfg.recurrent_kind]
    S, (P, C) = rows.active.shape[0], rows.chunk_pos.shape
    hin, mix = stream_read(x, lp, 1, cfg)
    xbc, aux = mixer.project(hin, mp, cfg)
    sdt = state.h.dtype
    h, conv, y_d, y_c = state.h, state.conv, None, None
    if P:
        # the chunks FIRST: each reads its own slot of the state as it
        # came and writes it back in place; the decode rows' step then
        # reads and writes the result, and leaves a chunk's slot as it
        # is (no live decode row: count 0). Behind the decode rows' step
        # a chunk's read was a second reader of that step's result, and
        # XLA computed the whole update twice in every mixed step
        # (`fusion.N.remat`: PERF.md, PR 56; with two chunks a step that
        # order's decode rows read 0.13 off the reference on the chip,
        # 5e-7 on the CPU); read from the state as it came while the
        # step wrote it, the state was copied whole. The conv's tail
        # goes the same way: a chunk's K-1 rows are cut out of layer m's
        # planes where they lie and written back there
        chunk_count = jnp.sum(rows.ok[S:].reshape(P, C), axis=1)
        fresh = (rows.chunk_pos[:, 0] == 0)[:, None, None]
        one = (1, 1) + h.shape[2:]
        at = [(m, rows.chunk_slot[p]) + (0,) * (h.ndim - 2)
              for p in range(P)]
        came = jnp.concatenate(
            [lax.dynamic_slice(h, at[p], one)[0] for p in range(P)])
        st0 = jnp.where(fresh.reshape((P,) + (1,) * (came.ndim - 1)), 0, came)
        tail_c0 = jnp.where(fresh, 0, jnp.stack(
            [_slot_tail(conv, m, rows.chunk_slot[p]) for p in range(P)]))
        u_c, tail_c = mixer.conv(xbc[S:].reshape(P, C, -1), tail_c0, mp,
                                 chunk_count)
        y_c, st_c = mixer.chunk(
            st0, u_c, tuple(a[S:].reshape(P, C, -1) for a in aux), mp, cfg,
            chunk_count)
        y_c = y_c.reshape((P * C, 1) + y_c.shape[2:])
        for p in range(P):
            # an idle chunk (argmax of nothing: slot 0) writes back what
            # is there NOW: read behind the chunks before it, one of
            # which may have written slot 0 this step
            h = lax.dynamic_update_slice(
                h, jnp.where(rows.chunk_ok[p],
                             st_c[p].astype(sdt)[None, None],
                             lax.dynamic_slice(h, at[p], one)), at[p])
            conv = lax.dynamic_update_slice(
                conv, jnp.where(rows.chunk_ok[p], tail_c[p].astype(sdt),
                                _slot_tail(conv, m, rows.chunk_slot[p])
                                )[None, :, None],
                (m, 0, rows.chunk_slot[p], 0))
    if S:
        # decode rows: slot s is row s; a row that does not decode this
        # step (free, dead, in prefill phase) has count 0 and keeps its
        # state and its tail
        u, conv = decode_rows_conv(conv, m, xbc[:S, 0], mp, rows.active)
        y_d, h = mixer.decode(h, m, u, tuple(a[:S] for a in aux), mp, cfg,
                              rows.active.astype(jnp.int32), use_kernel)
    y = jnp.concatenate([y for y in (y_d, y_c) if y is not None])
    x = stream_write(x, mixer.close(y, aux, mp, cfg), mix, cfg)
    x, load = ffn_close(x, lp, cfg, ok=rows.ok[:, None])
    return x, SSMState(h=h, conv=conv), load
