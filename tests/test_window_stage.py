"""The staged window written in place (ISSUE 47): stage_window_layer
over the WHOLE window at a layer's index, by XLA's scatter and by the
Mosaic writer (ops/window_stage.py, interpreted here), against the
writes it replaced: a layer's slice staged by `.at[].set` and the slices
stacked again, which is what a layer scan with the window as xs and ys
computed. Bit for bit, every byte the step does not stage included."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.cache.paged import (
    KVWindow, scales_by_head, scales_by_step, stage_window_layer,
    window_leaves, window_runs)
from butterfly_tpu.models.common import quantize_kv


def _staged_until_pr47(wk, wv, k, v, win_len, wks=None, wvs=None, rows=None):
    """cache/paged.py stage_window_layer as it was: one layer's slices
    wk/wv [S, Kv, W, H] (scales [S, Kv, W]), token t of row b at window
    index win_len[b] + t of slot rows[b], an index of W or more dropped."""
    B, T = k.shape[0], k.shape[1]
    rows = (jnp.arange(B) if rows is None else rows)[:, None]
    idx = win_len[:, None] + jnp.arange(T)[None, :]
    k, v = (None if a is None else a.reshape(B, T, wk.shape[1], wk.shape[3])
            for a in (k, v))
    if wks is not None:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return (wk.at[rows, :, idx].set(kq, mode="drop"),
                wv.at[rows, :, idx].set(vq, mode="drop"),
                wks.at[rows, :, idx].set(ks, mode="drop"),
                wvs.at[rows, :, idx].set(vs, mode="drop"))
    wk = wk.at[rows, :, idx].set(k.astype(wk.dtype), mode="drop")
    if wv is not None:
        wv = wv.at[rows, :, idx].set(v.astype(wv.dtype), mode="drop")
    return wk, wv, None, None


def _index_staged_until_pr47(wki, ki, win_len, rows=None):
    B, T = ki.shape[:2]
    rows = (jnp.arange(B) if rows is None else rows)[:, None]
    idx = win_len[:, None] + jnp.arange(T)[None, :]
    return wki.at[rows, 0, idx].set(ki.astype(wki.dtype), mode="drop")


#: kind -> (window dtype, heads and width of a row as the window lays
#: it, values?, scales?, index keys' width)
KINDS = {
    "float": (jnp.float32, 2, 16, True, False, 0),
    "bf16": (jnp.bfloat16, 2, 16, True, False, 0),
    "int8": (jnp.int8, 2, 16, True, True, 0),
    "latent": (jnp.bfloat16, 1, 24, False, False, 0),
    "keye": (jnp.bfloat16, 1, 32, True, False, 8),
}

L, S = 3, 4


def _window(kind, W, seed=0):
    """A window full of random bytes, every layer's its own."""
    dt, Kv, H, values, quant, Hi = KINDS[kind]
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 8))

    def rnd(shape, dt):
        if dt == jnp.int8:
            return jax.random.randint(next(ks), shape, -127, 128, jnp.int8)
        return jax.random.normal(next(ks), shape, jnp.float32).astype(dt)

    sc = lambda: scales_by_step(                       # noqa: E731
        jax.random.uniform(next(ks), (L, S, Kv, W), jnp.float32))
    return KVWindow(
        k=rnd((L, S, Kv, W, H), dt),
        v=rnd((L, S, Kv, W, H), dt) if values else None,
        k_scale=sc() if quant else None, v_scale=sc() if quant else None,
        ki=rnd((L, S, 1, W, Hi), dt) if Hi else None)


def _fresh(kind, B, T, seed=1):
    """Fresh rows [B, T, ...] as a layer projects them: (k, v, ki)."""
    _, Kv, H, values, _, Hi = KINDS[kind]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    # a token-major row's heads arrive apart: [B, T, 2, H/2]
    shape = (B, T, Kv, H) if Kv > 1 else (B, T, 2, H // 2)
    return (jax.random.normal(ks[0], shape),
            jax.random.normal(ks[1], shape) if values else None,
            jax.random.normal(ks[2], (B, T, Hi)) if Hi else None)


def _want(window, layer, k, v, ki, win_len, rows):
    """The layer's slices staged as they were, the slices stacked."""
    Kv = window.k.shape[2]
    sl = [None if a is None else a[layer] for a in (
        window.k, window.v, window.k_scale, window.v_scale, window.ki)]
    if window.quantized:
        sl[2], sl[3] = (scales_by_head(a, Kv) for a in sl[2:4])
    # compiled, as the program under test is: XLA's fused absmax / 127
    # and the same ops one by one part in a scale's last bit
    new = list(jax.jit(_staged_until_pr47)(sl[0], sl[1], k, v, win_len,
                                           sl[2], sl[3], rows=rows))
    if window.quantized:
        new[2], new[3] = (scales_by_step(a) for a in new[2:4])
    if ki is not None:
        new.append(_index_staged_until_pr47(sl[4], ki, win_len, rows=rows))
    new = [a for a in new if a is not None]
    return [old.at[layer].set(a)
            for old, a in zip(window_leaves(window), new, strict=True)]


def _same(got: KVWindow, want):
    for a, b in zip(window_leaves(got), want, strict=True):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint8), np.asarray(b).view(np.uint8))


#: lanes: every slot stages T tokens at its count (rows=None: the
#: lane-wide forward of the speculative verify). name -> (W, T, counts
#: before)
LANES = {
    "one_token": (64, 1, (0, 31, 32, 63)),
    "a_few_and_a_dropped_tail": (64, 3, (0, 30, 62, 64)),
    "across_two_groups": (64, 40, (0, 5, 24, 40)),
    "a_window_of_one_group": (12, 3, (0, 4, 10, 12)),
}


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "mosaic"])
@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("case", sorted(LANES))
def test_a_lane_wide_stage_is_the_slices_staged_and_stacked(
        case, kind, use_kernel):
    """rows=None: every slot, dead ones too, stages T tokens from its
    count; what would pass W is dropped, entry by entry."""
    W, T, counts = LANES[case]
    window = _window(kind, W)
    k, v, ki = _fresh(kind, S, T)
    win_len = jnp.asarray(counts, jnp.int32)
    stage = jax.jit(lambda w, ly: stage_window_layer(
        w, ly, k, v, ki, jnp.repeat(jnp.arange(S), T),
        (win_len[:, None] + jnp.arange(T)[None, :]).reshape(-1),
        (window_runs(jnp.arange(S), win_len, T, W),), (T,), use_kernel))
    for layer in (0, 1, L - 1):
        _same(stage(window, jnp.int32(layer)),
              _want(window, layer, k, v, ki, win_len, None))


#: packed: S decode rows and P chunks of C columns. name -> (W, C,
#: counts before, the slots that decode, (chunk's slot, real columns)...)
PACKED = {
    "decode_rows_alone": (64, 4, (0, 31, 33, 63), (1, 1, 1, 1), ()),
    "a_dead_slot_and_a_full_window": (64, 4, (5, 9, 64, 63), (1, 0, 1, 1),
                                      ()),
    "a_chunk_inside_a_group": (64, 8, (3, 40, 7, 0), (1, 0, 1, 1),
                               ((1, 8),)),
    "a_chunk_astride_two_groups": (96, 40, (3, 20, 7, 0), (1, 0, 1, 1),
                                   ((1, 33),)),
    "a_chunk_that_ends_at_W": (64, 16, (3, 50, 7, 0), (1, 0, 1, 1),
                               ((1, 14),)),
    "a_chunk_whose_tail_drops": (64, 16, (3, 56, 7, 0), (1, 0, 1, 1),
                                 ((1, 16),)),
    "two_chunks_and_an_idle_one": (64, 8, (30, 0, 7, 0), (0, 0, 1, 1),
                                   ((0, 5), (1, 8), (0, 0))),
    "a_window_of_one_group": (12, 4, (0, 4, 9, 12), (1, 1, 0, 1),
                              ((2, 3),)),
}


def _packed_rows(case):
    """A case's rows as cache/paged.py packed_rows builds them: each
    row's slot and window index (W: dropped), and the same as runs."""
    W, C, counts, decodes, chunks = PACKED[case]
    P = len(chunks)
    win_len = jnp.asarray(counts, jnp.int32)
    active = jnp.asarray(decodes, bool)
    chunk_slot = jnp.asarray([c[0] for c in chunks], jnp.int32).reshape(P)
    count = jnp.asarray([c[1] for c in chunks], jnp.int32).reshape(P)
    col = jnp.arange(C)[None, :]
    slot = jnp.concatenate([jnp.arange(S), jnp.repeat(chunk_slot, C)])
    ok = jnp.concatenate([active, (col < count[:, None]).reshape(-1)])
    widx = jnp.where(ok, win_len[slot] + jnp.concatenate(
        [jnp.zeros((S,), jnp.int32),
         jnp.broadcast_to(col, (P, C)).reshape(-1)]), W)
    runs = (window_runs(jnp.arange(S), win_len, active, W),)
    if P:
        runs += (window_runs(chunk_slot, win_len[chunk_slot], count, W),)
    return slot, widx, runs


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "mosaic"])
@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("case", sorted(PACKED))
def test_a_packed_stage_is_the_slices_staged_and_stacked(
        case, kind, use_kernel):
    """The packed step's rows (packed_rows builds the same indices): a
    decode row a live slot, a chunk's real columns from its slot's
    count; a dead slot, a filler column and an idle chunk write nothing."""
    W, C, _, _, _ = PACKED[case]
    window = _window(kind, W)
    slot, widx, runs = _packed_rows(case)
    k, v, ki = _fresh(kind, slot.shape[0], 1)
    stage = jax.jit(lambda w, ly: stage_window_layer(
        w, ly, k, v, ki, slot, widx, runs, (1, C)[:len(runs)], use_kernel))
    for layer in (0, 1, L - 1):
        # as the packed step staged a slice: one entry a row at widx
        _same(stage(window, jnp.int32(layer)),
              _want(window, layer, k, v, ki, widx, slot))


def test_the_mosaic_writer_is_noted_under_its_own_name():
    """/health's record of kernels names the staging call apart from
    the reads (`paged_win`, `latent_win`), and says how it ran."""
    from butterfly_tpu.ops import record_kernels
    window = _window("bf16", 64)
    k, v, ki = _fresh("bf16", S, 1)
    zero = jnp.zeros((S,), jnp.int32)
    jax.clear_caches()      # the jitted call is traced anew, and noted
    with record_kernels({}) as log:
        jax.make_jaxpr(lambda w: stage_window_layer(
            w, 0, k, v, ki, jnp.arange(S), zero,
            (window_runs(jnp.arange(S), zero, 1, 64),), (1,), True))(window)
    assert log == {"stage_win:interpret": 1}


@pytest.mark.parametrize("kind", ["bf16", "int8", "keye"])
def test_the_mosaic_writer_under_a_mesh_stages_what_it_stages_alone(kind):
    """Slots over `data`, KV heads over `tensor`
    (ops/window_stage.py stage_window_sharded): a shard stages the runs
    of ITS slots, the heads it holds; the result is the unsharded one's,
    to the bit. A token-major window (Keye's) has no heads to shard by:
    there the wrapper hands the rows back to XLA's scatter."""
    from butterfly_tpu.core.config import MeshConfig
    from butterfly_tpu.core.mesh import make_mesh
    from butterfly_tpu.ops import record_kernels
    W, C, _, _, _ = PACKED["a_chunk_astride_two_groups"]
    window = _window(kind, W)
    slot, widx, runs = _packed_rows("a_chunk_astride_two_groups")
    k, v, ki = _fresh(kind, S + C, 1)

    def stage(w):
        return stage_window_layer(w, 1, k, v, ki, slot, widx, runs, (1, C),
                                  True)

    alone = jax.jit(stage)(window)
    jax.clear_caches()
    with jax.set_mesh(make_mesh(MeshConfig(data=2, tensor=2),
                                jax.devices()[:4])), \
            record_kernels({}) as log:
        _same(jax.jit(stage)(window), window_leaves(alone))
    assert bool(log) == (kind != "keye"), log
