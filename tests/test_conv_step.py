"""The decode rows' causal conv in the layout the state holds (PR 64):
models/common.py conv_step against _causal_conv at one position, and a
packed step's state against the layer as PR 63 left it, in all three
recurrent kinds."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from butterfly_tpu.cache import ssm_state
from butterfly_tpu.cache.ssm_state import (
    SSMState, StateRows, advance_packed, decode_rows_conv, state_shapes)
from butterfly_tpu.core.config import tiny
from butterfly_tpu.models.common import (
    RECURRENT_STACKS, _causal_conv, conv_step, ffn_close, init_params,
    layer_at, stream_read, stream_write)

KINDS = {"mamba": "granite_hybrid", "linear_attention": "olmo_hybrid",
         "mamba1": "jamba"}


def same(a, b):
    """Bit for bit, whatever the dtype."""
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape \
        and a.tobytes() == b.tobytes()


def _case(dtype, S=12, Dc=256, bias=True, layers=3, seed=0):
    """Tails of `layers` layers over S slots, the rows' inputs, the
    taps, and `live`: a fifth of the slots (2 and 7) do not decode."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    conv = jax.random.normal(ks[0], (layers, 3, S, Dc)).astype(dtype)
    x = jax.random.normal(ks[1], (S, Dc)).astype(dtype)
    mp = {"conv_w": jax.random.normal(ks[2], (4, Dc)).astype(dtype)}
    if bias:
        mp["conv_b"] = jax.random.normal(ks[3], (Dc,)).astype(dtype)
    return conv, x, mp, jnp.arange(S) % 5 != 2


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_decode_arm_is_causal_conv_at_one_position(kind, dtype, bias):
    """conv_step on the planes where they lie against _causal_conv at
    T == 1 on the swapped tail, at each kind's toy width: u and the new
    tail bit for bit, rows that decode (count 1) and rows that do not
    (count 0) in one batch; and decode_rows_conv's update in place
    touches layer m alone."""
    cfg = tiny(KINDS[kind])
    Dc = state_shapes(cfg, 1)["conv"][3]
    conv, x, mp, live = _case(jnp.dtype(dtype), Dc=Dc, bias=bias)
    assert 0 < int((~live).sum()) < live.shape[0]
    m = 1
    want_u, want_tail = _causal_conv(
        x[:, None], jnp.swapaxes(conv[m], 0, 1), mp, live.astype(jnp.int32))
    u, new = conv_step(conv[m], x, mp, live)
    new = jnp.stack(new)
    assert u.dtype == jnp.float32 and new.dtype == conv.dtype
    assert same(u, want_u)
    assert same(new, jnp.swapaxes(want_tail, 0, 1))
    dead = np.flatnonzero(~np.asarray(live))
    assert same(new[:, dead], conv[m][:, dead])
    assert same(new[:2, 0], conv[m][1:, 0]) and same(new[2, 0], x[0])
    u2, conv2 = decode_rows_conv(conv, jnp.int32(m), x, mp, live)
    assert same(u2, want_u) and same(conv2[m], new)
    assert same(conv2[0], conv[0]) and same(conv2[2], conv[2])


def advance_packed_swapped(x, lp, mp, state, m, rows, cfg):
    """cache/ssm_state.py advance_packed as PR 63 left it: layer m's
    tails transposed to [S, K-1, Dc], the decode rows' conv
    _causal_conv's concatenate and gather, the chunks' tails written
    behind it, the whole transposed back. What PR 64's layer must
    equal."""
    mixer = ssm_state._MIXERS[cfg.recurrent_kind]
    S, (P, C) = rows.active.shape[0], rows.chunk_pos.shape
    hin, mix = stream_read(x, lp, 1, cfg)
    xbc, aux = mixer.project(hin, mp, cfg)
    tails = lax.dynamic_index_in_dim(state.conv, m, 0, keepdims=False)
    tails = jnp.swapaxes(tails, 0, 1)                  # [slots, K-1, Dc]
    sdt = state.h.dtype
    h, y_d, y_c = state.h, None, None
    if P:
        chunk_count = jnp.sum(rows.ok[S:].reshape(P, C), axis=1)
        fresh = (rows.chunk_pos[:, 0] == 0)[:, None, None]
        one = (1, 1) + h.shape[2:]
        at = [(m, rows.chunk_slot[p]) + (0,) * (h.ndim - 2)
              for p in range(P)]
        came = jnp.concatenate(
            [lax.dynamic_slice(h, at[p], one)[0] for p in range(P)])
        st0 = jnp.where(fresh.reshape((P,) + (1,) * (came.ndim - 1)), 0, came)
        tail_c0 = jnp.where(fresh, 0, tails[rows.chunk_slot])
        u_c, tail_c = mixer.conv(xbc[S:].reshape(P, C, -1), tail_c0, mp,
                                 chunk_count)
        y_c, st_c = mixer.chunk(
            st0, u_c, tuple(a[S:].reshape(P, C, -1) for a in aux), mp, cfg,
            chunk_count)
        y_c = y_c.reshape((P * C, 1) + y_c.shape[2:])
        for p in range(P):
            h = lax.dynamic_update_slice(
                h, jnp.where(rows.chunk_ok[p],
                             st_c[p].astype(sdt)[None, None],
                             lax.dynamic_slice(h, at[p], one)), at[p])
    tails_new = tails
    if S:
        count = rows.active.astype(jnp.int32)
        u, tail_d = mixer.conv(xbc[:S], tails, mp, count)
        y_d, h = mixer.decode(h, m, u, tuple(a[:S] for a in aux), mp, cfg,
                              count, False)
        tails_new = tail_d.astype(sdt)
    for p in range(P):
        slot = rows.chunk_slot[p]
        old_t = lax.dynamic_slice_in_dim(tails_new, slot, 1, axis=0)
        tails_new = lax.dynamic_update_slice_in_dim(
            tails_new, jnp.where(rows.chunk_ok[p],
                                 tail_c[p].astype(sdt)[None], old_t),
            slot, axis=0)
    conv = lax.dynamic_update_index_in_dim(
        state.conv, jnp.swapaxes(tails_new, 0, 1), m, 0)
    y = jnp.concatenate([y for y in (y_d, y_c) if y is not None])
    x = stream_write(x, mixer.close(y, aux, mp, cfg), mix, cfg)
    x, load = ffn_close(x, lp, cfg, ok=rows.ok[:, None])
    return x, SSMState(h=h, conv=conv), load


#: name -> (chunks a step, the slots that decode, [(slot, real columns,
#: first position, carries something)] a chunk); 4 slots, chunks of 4
SCENARIOS = {
    # a chunk writes slot 0 beside three decode rows
    "a-chunk-writes-slot-0": (1, (1, 2, 3), [(0, 4, 8, True)]),
    # two chunks, the second idle: its slot reads 0, where the first
    # writes; it writes back what the first LEFT
    "an-idle-chunk-behind-slot-0s": (2, (1, 3), [(0, 3, 4, True),
                                                 (0, 0, 0, False)]),
    # an idle chunk over slot 0 while slot 0 DECODES: the row's moved
    # tail is what stays
    "an-idle-chunk-over-a-live-slot-0": (2, (0, 1, 2), [(3, 4, 12, True),
                                                         (0, 0, 0, False)]),
    # a slot reused from position 0: what its last tenant left is not
    # read
    "a-slot-reused-from-position-0": (1, (0, 2), [(3, 2, 0, True)]),
    # two real chunks a step, one of them from position 0
    "two-chunks": (2, (1,), [(2, 4, 0, True), (3, 1, 5, True)]),
    # decode rows alone, some of them dead
    "decode-rows-alone": (1, (0, 3), None),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_packed_step_leaves_what_the_swapped_layer_left(kind, scenario):
    """Two packed steps of a recurrent layer over a state that every
    slot's last tenant left full: the real rows out, every layer's states and
    every layer's tails are the PR 63 layer's (advance_packed_swapped)
    bit for bit, in float32 and on the CPU where the same sums are the
    same bits; the layers the step does not name are untouched."""
    cfg = tiny(KINDS[kind], dtype="float32", param_dtype="float32")
    assert cfg.recurrent_kind == kind
    params = init_params(cfg, jax.random.PRNGKey(3))
    l = [i for i, k in enumerate(cfg.layer_types) if k == kind][-1]
    m = sum(k == kind for k in cfg.layer_types[:l])
    lp = layer_at(params["layers"], l, cfg)
    mp = layer_at(params[RECURRENT_STACKS[kind]], m, cfg)
    S, C = 4, 4
    P, decodes, chunks = SCENARIOS[scenario]
    shapes = state_shapes(cfg, S)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    state = SSMState(h=0.1 * jax.random.normal(ks[0], shapes["h"]),
                     conv=jax.random.normal(ks[1], shapes["conv"]))
    active = jnp.zeros((S,), bool).at[jnp.asarray(decodes)].set(True)
    if chunks is None:
        P, chunks = 0, []
    real = jnp.asarray([c[1] for c in chunks], jnp.int32).reshape(P)
    rows = StateRows(
        active=active,
        ok=jnp.concatenate([active, (jnp.arange(C)[None, :]
                                     < real[:, None]).reshape(-1)]),
        chunk_slot=jnp.asarray([c[0] for c in chunks], jnp.int32).reshape(P),
        chunk_ok=jnp.asarray([c[3] for c in chunks], bool).reshape(P),
        chunk_pos=(jnp.asarray([c[2] for c in chunks], jnp.int32).reshape(
            P, 1) + jnp.arange(C)[None, :]))
    new, old = state, state
    for step in range(2):
        x = jax.random.normal(ks[2 + step], (S + P * C, 1, cfg.hidden_size))
        x_new, new, _ = advance_packed(x, lp, mp, new, jnp.int32(m), rows,
                                       cfg)
        x_old, old, _ = advance_packed_swapped(x, lp, mp, old, jnp.int32(m),
                                               rows, cfg)
        # a row that is not real (the decode row of a slot in prefill
        # phase reads the tail its chunk just wrote, where PR 63's read
        # the tail as it came) feeds nothing: the real rows are compared
        real_rows = np.flatnonzero(np.asarray(rows.ok))
        assert same(x_new[real_rows], x_old[real_rows]), step
        assert same(new.h, old.h) and same(new.conv, old.conv), step
    assert not same(new.conv[m], state.conv[m])
    for other in range(state.conv.shape[0]):
        if other != m:
            assert same(new.conv[other], state.conv[other])
    idle = [s for s in range(S) if s not in decodes
            and all(not (c[0] == s and c[3]) for c in chunks)]
    for s in idle:      # a slot that neither decodes nor prefills
        assert same(new.conv[m][:, s], state.conv[m][:, s])
        assert same(new.h[m, s], state.h[m, s])
