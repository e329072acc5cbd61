"""The recurrent state of a model with Mamba-2 layers: a second KIND of
per-stream memory beside the paged pool.

A page pool grows with a stream: a token adds a row to every attention
layer. A Mamba-2 layer keeps a FIXED-SIZE state a stream instead, the
same bytes at position 10 and at position 100,000: the state of each
head H [Nh, Hd, N] and the last K-1 inputs of the causal conv
[K-1, Dc]. It is never paged, hashed or exported; it belongs to a SLOT,
is read and written by every step, and starts from zero when a stream
starts (position 0), whatever the slot's last tenant left.

The interface is narrow on purpose (the scheduler learns nothing of it):

* `init_ssm_state(cfg, slots)`: allocate for S slots (None for a model
  without such layers);
* the value is a pytree (SSMState): the engine donates it to every
  block program and rebinds it from the result, exactly like the KV
  window, and it rides the block scan's CARRY (engine/serving.py);
* `advance_packed(...)`: one Mamba-2 layer of a packed mixed step: a
  decode row advances its slot's state by one position, a prefill chunk
  advances its OWN slot's state by its real columns, filler columns,
  idle chunks and dead rows advance nothing, and a chunk that starts at
  position 0 starts from zero INSIDE the program (a recomputed
  preemption or a reused slot needs no host edit);
* `reset_slots(state, slots)`: zero slots from the host (tests, and a
  caller that wants a scrubbed slot; serving never needs it);
* `state_info(cfg, slots)`: what /health and the ready line say.

Layout: h [Lm, S, Nh, Hd, N] (the two minor dims are whole tiles:
N on the lanes) and conv [Lm, K-1, S, Dc] (slots on the sublanes: a
[.., K-1, Dc] minor pair would pad K-1 = 3 to a tile of 16 rows in
bfloat16, five times the bytes). Every write is a dynamic-update-slice
of the carried buffer at the layer's index, which XLA performs in place
(a scatter into a scan carry copies the whole buffer: cache/paged.py's
window docs have the measurement).

Who computes the recurrence: a DECODE row's one step is the Pallas
kernel ops/ssm_step.py when the engine's kernels are on and the state's
minor dims are whole tiles (the kernel takes the whole h, aliased to
its result, and the layer's index: one pass over layer m's slots where
they lie, y read from the float32 value before it is rounded to the
stored dtype); with kernels off (the CPU) or any other state it is
models.common.ssm_scan at T == 1 and the update in place, which is also
what the kernel is tested against. A CHUNK's is ssm_scan's scan over its
positions, either way; it reads its slot after the decode rows' step (a
chunk's slot is no live decode row, so that step left it as it was).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from butterfly_tpu.core.config import ModelConfig
from butterfly_tpu.models.common import (
    ffn_close, ssm_conv, ssm_gate_out, ssm_in_proj, ssm_scan, ssm_skip,
    ssm_step_inputs, stream_read, stream_write)
from butterfly_tpu.ops.ssm_step import fits, ssm_step


class SSMState(NamedTuple):
    h: jax.Array      # [Lm, S, Nh, Hd, N]
    conv: jax.Array   # [Lm, K-1, S, Dc]

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


def bytes_per_slot(cfg: ModelConfig) -> int:
    """What one stream's state weighs, all Mamba layers."""
    per = cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state \
        + (cfg.ssm_conv - 1) * cfg.ssm_conv_dim
    return cfg.num_ssm_layers * per * jnp.dtype(cfg.dtype).itemsize


def state_info(cfg: ModelConfig, slots: int) -> Optional[Dict]:
    """{layers, bytes_per_slot, dtype, bytes}, or None for a model
    without a recurrent state."""
    if not cfg.has_ssm:
        return None
    per = bytes_per_slot(cfg)
    return {"layers": cfg.num_ssm_layers, "bytes_per_slot": per,
            "dtype": str(jnp.dtype(cfg.dtype)), "bytes": per * slots}


def init_ssm_state(cfg: ModelConfig, slots: int,
                   sharding=None) -> Optional[SSMState]:
    """Zero state for `slots` slots, committed to `sharding` as the
    pool is (a block's outputs are: one executable for the first call
    and the rest)."""
    if not cfg.has_ssm:
        return None
    dt = jnp.dtype(cfg.dtype)
    Lm = cfg.num_ssm_layers

    def build():
        return SSMState(
            h=jnp.zeros((Lm, slots, cfg.ssm_heads, cfg.ssm_head_dim,
                         cfg.ssm_state), dt),
            conv=jnp.zeros((Lm, cfg.ssm_conv - 1, slots, cfg.ssm_conv_dim),
                           dt))

    return jax.jit(build, out_shardings=sharding)()


def reset_slots(state: SSMState, slots) -> SSMState:
    """The state with `slots` (indices) zeroed, every layer."""
    slots = jnp.asarray(slots, jnp.int32)
    return SSMState(h=state.h.at[:, slots].set(0),
                    conv=state.conv.at[:, :, slots].set(0))


def decode_rows_step(h, m, u, dt, mp, cfg: ModelConfig, count,
                     use_kernel: bool = False):
    """The recurrence of layer m's decode rows, one position: h
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


def advance_packed(x, lp, mp, state: SSMState, m, rows, cfg: ModelConfig,
                   use_kernel: bool = False):
    """One Mamba-2 layer (mixer, feed-forward, both residuals) of a
    packed mixed step over x [N, 1, D], N = S + P*C rows: the S decode
    rows first, then P chunks of C columns (`rows`: StateRows'
    fields). lp, mp: the layer's slices of params["layers"] and
    params["mamba"]; m: its index among the Mamba layers (traced).
    use_kernel: the engine's kernel rule (ops/__init__.py); with it,
    and a state of whole tiles, the decode rows' recurrence is the
    ssm_step kernel, one pass over the state where it lies.

    The projections, the gate and the feed-forward run once over all N
    rows (the weights stream once); the conv and the recurrence run on
    the decode rows as T == 1 against every slot's state (S is the
    state's slots, or 0: a step of chunks alone), and on each chunk as
    T == C against its own slot's, from zero where the chunk starts at
    position 0. Returns (x, state, load): load as
    models.common.ffn_close's."""
    S, (P, C) = rows.active.shape[0], rows.chunk_pos.shape
    Nh, Hd, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    h, mix = stream_read(x, lp, 1, cfg)
    z, xbc, dt = ssm_in_proj(h, mp, cfg)
    h_all = lax.dynamic_index_in_dim(state.h, m, 0, keepdims=False)
    tails = lax.dynamic_index_in_dim(state.conv, m, 0, keepdims=False)
    tails = jnp.swapaxes(tails, 0, 1)                  # [slots, K-1, Dc]
    sdt = h_all.dtype
    h, tails_new, ys = state.h, tails, []
    if S:
        # decode rows: slot s is row s; a row that does not decode this
        # step (free, dead, in prefill phase) has count 0 and keeps its
        # state
        count = rows.active.astype(jnp.int32)
        u, tail_d = ssm_conv(xbc[:S], tails, mp, count)
        y, h = decode_rows_step(h, m, u, dt[:S], mp, cfg, count, use_kernel)
        if use_kernel:
            # a chunk's slot is no live decode row: the step left it as it
            # was, and reading it from the RESULT leaves no reader of the
            # buffer a kernel wrote in place (else: a copy of it)
            h_all = lax.dynamic_index_in_dim(h, m, 0, keepdims=False)
        tails_new = tail_d.astype(sdt)
        ys.append(y)
    if P:
        chunk_count = jnp.sum(rows.ok[S:].reshape(P, C), axis=1)
        fresh = (rows.chunk_pos[:, 0] == 0)[:, None, None]
        h_c0 = jnp.where(fresh[..., None], 0, h_all[rows.chunk_slot])
        tail_c0 = jnp.where(fresh, 0, tails[rows.chunk_slot])
        u_c, tail_c = ssm_conv(xbc[S:].reshape(P, C, -1), tail_c0, mp,
                               chunk_count)
        y_c, h_c = ssm_scan(u_c, dt[S:].reshape(P, C, -1), mp, cfg,
                            h_c0.astype(jnp.float32), chunk_count)
        ys.append(y_c.reshape(P * C, 1, Nh, Hd))
        for p in range(P):
            # the chunk's own slot, in place; an idle chunk (argmax of
            # nothing: slot 0) writes back what is there
            slot, ok = rows.chunk_slot[p], rows.chunk_ok[p]
            at = (m, slot, 0, 0, 0)
            old = lax.dynamic_slice(h, at, (1, 1, Nh, Hd, N))
            h = lax.dynamic_update_slice(
                h, jnp.where(ok, h_c[p].astype(sdt)[None, None], old), at)
            old_t = lax.dynamic_slice_in_dim(tails_new, slot, 1, axis=0)
            tails_new = lax.dynamic_update_slice_in_dim(
                tails_new, jnp.where(ok, tail_c[p].astype(sdt)[None], old_t),
                slot, axis=0)
    conv = lax.dynamic_update_index_in_dim(
        state.conv, jnp.swapaxes(tails_new, 0, 1), m, 0)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    x = stream_write(x, ssm_gate_out(y, z, mp, cfg), mix, cfg)
    x, load = ffn_close(x, lp, cfg, ok=rows.ok[:, None])
    return x, SSMState(h=h, conv=conv), load
