"""Pallas kernel tests (interpret mode on CPU: the exact kernel code path).

flash_attention and paged_attention must match the dense XLA reference
bit-for-nearly-bit; the serving stack with use_kernels=True must produce
token-identical output to the gather path.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.core.config import RuntimeConfig, tiny
from butterfly_tpu.cache.ssm_state import (
    _DeltaNet, _Mamba1, decode_rows_step, state_shapes)
from butterfly_tpu.models.common import Model, attend
from butterfly_tpu.ops import gdn_step as gdn_kernel
from butterfly_tpu.ops import mamba1_step as mamba1_kernel
from butterfly_tpu.ops import record_kernels
from butterfly_tpu.ops.flash_attention import flash_attention
from butterfly_tpu.ops.paged_attention import paged_attention
from butterfly_tpu.ops.ssm_step import fits, heads_per_block, ssm_step


def causal_ref(q, k, v):
    B, T = q.shape[0], q.shape[1]
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    mask = pos[:, None, :] <= pos[:, :, None]
    return attend(q, k, v, mask, None)


@pytest.mark.parametrize("T,nq,kv,bq,bk", [
    (32, 8, 8, 16, 16),    # MHA, aligned blocks
    (50, 8, 2, 16, 16),    # GQA, ragged tail
    (17, 4, 4, 8, 8),      # tiny blocks, ragged
])
def test_flash_attention_parity(T, nq, kv, bq, bk):
    B, H = 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, nq, H))
    k = jax.random.normal(ks[1], (B, T, kv, H))
    v = jax.random.normal(ks[2], (B, T, kv, H))
    out = flash_attention(q, k, v, block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(causal_ref(q, k, v)),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    B, T, N, H = 1, 24, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(ks[i], (B, T, N, H)) for i in range(3))
    out = flash_attention(q, k, v, causal=False, block_q=8, block_k=8)
    ref = attend(q, k, v, jnp.ones((B, T, T), bool), None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    B, T, N, H = 2, 32, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(ks[i], (B, T, N, H), jnp.bfloat16)
               for i in range(3))
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    ref = causal_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                     v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def _gather_pool(pages, table):
    """[P,Kv,page,H] pool -> [S, MP*page, Kv, H] dense view."""
    S, MP = table.shape
    P, Kv, page, H = pages.shape
    return pages[table].transpose(0, 1, 3, 2, 4).reshape(S, MP * page, Kv, H)


def test_paged_attention_parity():
    S, Nq, Kv, H, page, P, MP = 3, 8, 2, 16, 4, 10, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (S, Nq, H))
    k_pages = jax.random.normal(ks[1], (P, Kv, page, H))
    v_pages = jax.random.normal(ks[2], (P, Kv, page, H))
    table = jnp.asarray([[0, 2, 9, 9], [3, 1, 4, 9], [5, 6, 7, 8]],
                        jnp.int32)
    lengths = jnp.asarray([6, 3, 15], jnp.int32)
    out = paged_attention(q, k_pages[None], v_pages[None], 0, table, lengths)

    kk = _gather_pool(k_pages, table)
    vv = _gather_pool(v_pages, table)
    mask = jnp.arange(MP * page)[None, None, :] < lengths[:, None, None]
    ref = attend(q[:, None], kk, vv, mask, None)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_int8_parity():
    """Quantized pools (codes + flat kv-major scale rows) match the dense
    int8 attend over the gathered view."""
    from butterfly_tpu.models.common import quantize_kv

    S, Nq, Kv, H, page, P, MP = 3, 8, 2, 16, 4, 10, 4
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (S, Nq, H))
    kf = jax.random.normal(ks[1], (P, Kv, page, H))
    vf = jax.random.normal(ks[2], (P, Kv, page, H))
    kq, ksc = quantize_kv(kf)   # codes [P,Kv,page,H], scales [P,Kv,page]
    vq, vsc = quantize_kv(vf)
    ksp = ksc.reshape(P, Kv * page)
    vsp = vsc.reshape(P, Kv * page)
    table = jnp.asarray([[0, 2, 9, 9], [3, 1, 4, 9], [5, 6, 7, 8]],
                        jnp.int32)
    lengths = jnp.asarray([6, 3, 15], jnp.int32)
    out = paged_attention(q, kq[None], vq[None], 0, table, lengths,
                          ksp[None], vsp[None])

    # dense reference: dequantize the gathered view, plain attend
    kk = _gather_pool(kq.astype(jnp.float32) * ksc[..., None], table)
    vv = _gather_pool(vq.astype(jnp.float32) * vsc[..., None], table)
    mask = jnp.arange(MP * page)[None, None, :] < lengths[:, None, None]
    ref = attend(q[:, None], kk, vv, mask, None)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _insert_window(view, win, lengths, counts):
    """Dense reference insert: window entry w of slot s lands at
    absolute position lengths[s] + w, entries past counts[s] dropped.
    view [S, MP*page, Kv, H]; win [S, Kv, W, H]."""
    out = np.asarray(view).copy()
    W = win.shape[2]
    for s in range(view.shape[0]):
        for w in range(min(int(counts[s]), W)):
            out[s, int(lengths[s]) + w] = np.asarray(win[s, :, w])
    return jnp.asarray(out)


def test_paged_attention_window_segment_parity():
    """The write-combined window segment (kv_write_combine): staged
    K/V [S, Kv, W, H] at absolute positions lengths..lengths+count-1
    folds into the online softmax exactly like an inserted dense view;
    entries past win_count must be invisible (they are recycled-buffer
    garbage by contract)."""
    S, Nq, Kv, H, page, P, MP, W = 3, 8, 2, 16, 4, 10, 4, 5
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(ks[0], (S, Nq, H))
    k_pages = jax.random.normal(ks[1], (P, Kv, page, H))
    v_pages = jax.random.normal(ks[2], (P, Kv, page, H))
    win_k = jax.random.normal(ks[3], (S, Kv, W, H))
    win_v = jax.random.normal(ks[4], (S, Kv, W, H))
    table = jnp.asarray([[0, 2, 9, 9], [3, 1, 4, 9], [5, 6, 7, 8]],
                        jnp.int32)
    lengths = jnp.asarray([6, 3, 9], jnp.int32)   # FLUSHED pool lengths
    counts = jnp.asarray([3, 5, 0], jnp.int32)    # staged entries/slot
    out = paged_attention(q, k_pages[None], v_pages[None], 0, table, lengths,
                          win_k=win_k[None], win_v=win_v[None],
                          win_count=counts)

    kk = _insert_window(_gather_pool(k_pages, table), win_k, lengths,
                        counts)
    vv = _insert_window(_gather_pool(v_pages, table), win_v, lengths,
                        counts)
    total = (lengths + counts)[:, None, None]
    mask = jnp.arange(MP * page)[None, None, :] < total
    ref = attend(q[:, None], kk, vv, mask, None)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # garbage past win_count must not leak into the output
    poisoned = win_k.at[:, :, 4:].set(1e3)
    out2 = paged_attention(q, k_pages[None], v_pages[None], 0, table, lengths,
                           win_k=poisoned[None], win_v=win_v[None],
                           win_count=jnp.minimum(counts, 4))
    ref2 = paged_attention(q, k_pages[None], v_pages[None], 0, table, lengths,
                           win_k=win_k[None], win_v=win_v[None],
                           win_count=jnp.minimum(counts, 4))
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(ref2))


def test_paged_attention_window_segment_int8_parity():
    """Quantized window segment: codes + scales (a step's one flat
    kv-major row, as the window stores them) dequantize inside the
    kernel's window step exactly like the pool blocks."""
    from butterfly_tpu.cache.paged import scales_by_step
    from butterfly_tpu.models.common import quantize_kv

    S, Nq, Kv, H, page, P, MP, W = 3, 8, 2, 16, 4, 10, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(17), 5)
    q = jax.random.normal(ks[0], (S, Nq, H))
    kf = jax.random.normal(ks[1], (P, Kv, page, H))
    vf = jax.random.normal(ks[2], (P, Kv, page, H))
    wkf = jax.random.normal(ks[3], (S, Kv, W, H))
    wvf = jax.random.normal(ks[4], (S, Kv, W, H))
    kq, ksc = quantize_kv(kf)
    vq, vsc = quantize_kv(vf)
    wkq, wks = quantize_kv(wkf)   # codes [S,Kv,W,H], scales [S,Kv,W]
    wvq, wvs = quantize_kv(wvf)
    table = jnp.asarray([[0, 2, 9, 9], [3, 1, 4, 9], [5, 6, 7, 8]],
                        jnp.int32)
    lengths = jnp.asarray([6, 3, 9], jnp.int32)
    counts = jnp.asarray([2, 4, 0], jnp.int32)
    out = paged_attention(q, kq[None], vq[None], 0, table, lengths,
                          ksc.reshape(1, P, Kv * page),
                          vsc.reshape(1, P, Kv * page),
                          win_k=wkq[None], win_v=wvq[None],
                          win_count=counts,
                          win_k_scale=scales_by_step(wks)[None],
                          win_v_scale=scales_by_step(wvs)[None])

    kk = _insert_window(_gather_pool(kq.astype(jnp.float32)
                                     * ksc[..., None], table),
                        wkq.astype(jnp.float32) * wks[..., None],
                        lengths, counts)
    vv = _insert_window(_gather_pool(vq.astype(jnp.float32)
                                     * vsc[..., None], table),
                        wvq.astype(jnp.float32) * wvs[..., None],
                        lengths, counts)
    total = (lengths + counts)[:, None, None]
    mask = jnp.arange(MP * page)[None, None, :] < total
    ref = attend(q[:, None], kk, vv, mask, None)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_zero_length_slot():
    """length 0 (inactive slot) visits no pages and returns zeros."""
    S, Nq, Kv, H, page, P = 2, 4, 4, 8, 4, 4
    q = jax.random.normal(jax.random.PRNGKey(4), (S, Nq, H))
    kp = jax.random.normal(jax.random.PRNGKey(5), (P, Kv, page, H))
    table = jnp.zeros((S, 2), jnp.int32)
    out = paged_attention(q, kp[None], kp[None], 0, table,
                          jnp.asarray([0, 4], jnp.int32))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)


@pytest.fixture(scope="module")
def mesh_dt():
    """data=2 x tensor=4 mesh for the sharded kernel wrappers."""
    from butterfly_tpu.core.config import MeshConfig
    from butterfly_tpu.core.mesh import make_mesh
    return make_mesh(MeshConfig(data=2, tensor=4))


def test_shardable_axes_engage(mesh_dt):
    """The eligibility gate must actually fire under a live mesh — the
    fallback is numerically identical, so parity tests alone can't tell
    shard_map engaged (round-3 review finding)."""
    from butterfly_tpu.ops.flash_attention import shardable_axes
    with jax.set_mesh(mesh_dt):
        assert shardable_axes(4, 8, 4) == ("data", "tensor")
        assert shardable_axes(3, 8, 4) == (None, "tensor")   # 3 % data=2
        assert shardable_axes(4, 6, 3) == ("data", None)     # heads % 4
    assert shardable_axes(4, 8, 4) == (None, None)           # no mesh


def test_flash_attention_sharded_parity(mesh_dt):
    """shard_map-wrapped kernel on a data x tensor mesh == plain kernel."""
    from butterfly_tpu.ops.flash_attention import flash_attention_sharded
    B, T, Nq, Kv, H = 4, 32, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (B, T, Nq, H))
    k = jax.random.normal(ks[1], (B, T, Kv, H))
    v = jax.random.normal(ks[2], (B, T, Kv, H))
    ref = flash_attention(q, k, v)
    with jax.set_mesh(mesh_dt):
        out = jax.jit(flash_attention_sharded)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_sharded_partial(mesh_dt):
    """Heads that don't divide tensor=4: shard_map engages on data only."""
    from butterfly_tpu.ops.flash_attention import flash_attention_sharded
    B, T, Nq, Kv, H = 2, 16, 3, 3, 8   # B%data=2 ok; heads 3%4 != 0
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, T, Nq, H))
    k = jax.random.normal(ks[1], (B, T, Kv, H))
    v = jax.random.normal(ks[2], (B, T, Kv, H))
    ref = flash_attention(q, k, v)
    with jax.set_mesh(mesh_dt):
        out = jax.jit(flash_attention_sharded)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sharded_wrappers_decline_when_nothing_divides(mesh_dt):
    """Live auto mesh + no shardable axis -> None (caller must go dense);
    a bare pallas_call under GSPMD is the failure the old engine guard
    prevented. The engine path must then still be token-correct."""
    from butterfly_tpu.ops.flash_attention import flash_attention_sharded
    B, T, Nq, Kv, H = 3, 16, 3, 3, 8   # 3 divides neither data=2 nor t=4
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    q = jax.random.normal(ks[0], (B, T, Nq, H))
    k = jax.random.normal(ks[1], (B, T, Kv, H))
    v = jax.random.normal(ks[2], (B, T, Kv, H))
    with jax.set_mesh(mesh_dt):
        assert flash_attention_sharded(q, k, v) is None

    # integration: indivisible-head model, meshed serving w/ kernels on
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    cfg = tiny("llama", dtype="float32", param_dtype="float32",
               num_heads=3, num_kv_heads=3, head_dim=8)
    params = Model(cfg).init(jax.random.PRNGKey(11))
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8)
    outs = {}
    for mesh in (None, mesh_dt):
        sched = Scheduler(ServingEngine(Model(cfg), params, rt, mesh=mesh,
                                        use_kernels=True))
        r = sched.submit([5, 7, 11], max_new_tokens=6)
        sched.run_until_done()
        outs[mesh is None] = r.output
    assert outs[True] == outs[False]


def test_paged_attention_sharded_parity(mesh_dt):
    from butterfly_tpu.ops.paged_attention import paged_attention_sharded
    S, Nq, Kv, H, page, P = 4, 8, 4, 16, 4, 12
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (S, Nq, H))
    kp = jax.random.normal(ks[1], (P, page, Kv, H))
    vp = jax.random.normal(ks[2], (P, page, Kv, H))
    table = jnp.asarray([[0, 2, 11], [3, 1, 11], [5, 6, 7], [8, 9, 10]],
                        jnp.int32)
    lengths = jnp.asarray([6, 3, 12, 9], jnp.int32)
    ref = paged_attention(q, kp[None], vp[None], 0, table, lengths)
    with jax.set_mesh(mesh_dt):
        out = jax.jit(paged_attention_sharded)(q, kp[None], vp[None], 0,
                                               table, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _pool_and_window(kf, vf, wkf, wvf, counts, quant):
    """bfloat16 pools [L, P, Kv, page, H] and windows [L, S, Kv, W, H]
    as the kernel takes them, whole (int8 codes and flat kv-major scale
    rows if `quant`, the window's a step a row): (kernel pool args
    [kp, vp, ksp, vsp], window kwargs, the four dense float32 views the
    reference attends)."""
    from butterfly_tpu.cache.paged import scales_by_step
    from butterfly_tpu.models.common import quantize_kv
    f32 = jnp.float32
    if not quant:
        return ([kf, vf, None, None],
                dict(win_k=wkf, win_v=wvf, win_count=counts),
                [a.astype(f32) for a in (kf, vf, wkf, wvf)])
    (kq, ksc), (vq, vsc) = quantize_kv(kf), quantize_kv(vf)
    (wkq, wks), (wvq, wvs) = quantize_kv(wkf), quantize_kv(wvf)
    flat = kf.shape[:2] + (-1,)                 # [L, P, Kv*page]
    return ([kq, vq, ksc.reshape(flat), vsc.reshape(flat)],
            dict(win_k=wkq, win_v=wvq, win_count=counts,
                 win_k_scale=scales_by_step(wks),
                 win_v_scale=scales_by_step(wvs)),
            [kq.astype(f32) * ksc[..., None], vq.astype(f32) * vsc[..., None],
             wkq.astype(f32) * wks[..., None],
             wvq.astype(f32) * wvs[..., None]])


def _layered_case(quant, window):
    """A pool of 3 layers, every layer's contents its own, for the
    kernel's whole-pool operand: (q, table, pool lengths, kernel pool
    args [kp, vp, ksp, vsp], window kwargs, and a function layer ->
    the dense float reference of THAT layer)."""
    L, S, Nq, Kv, H, page, P, W = 3, 4, 8, 4, 16, 4, 12, 3
    ks = jax.random.split(jax.random.PRNGKey(31), 5)
    q = jax.random.normal(ks[0], (S, Nq, H))
    table = jnp.asarray([[0, 2, 11], [3, 1, 11], [5, 6, 7], [8, 9, 10]],
                        jnp.int32)
    lengths = jnp.asarray([6, 3, 12, 9], jnp.int32)
    counts = jnp.asarray([2, 3, 0, 1], jnp.int32)
    kf = jax.random.normal(ks[1], (L, P, Kv, page, H), jnp.bfloat16)
    vf = jax.random.normal(ks[2], (L, P, Kv, page, H), jnp.bfloat16)
    wkf = jax.random.normal(ks[3], (L, S, Kv, W, H), jnp.bfloat16)
    wvf = jax.random.normal(ks[4], (L, S, Kv, W, H), jnp.bfloat16)
    pool, win, dense = _pool_and_window(kf, vf, wkf, wvf, counts, quant)
    if not window:
        win = {}

    def ref(layer):
        kk = _gather_pool(dense[0][layer], table)
        vv = _gather_pool(dense[1][layer], table)
        total = lengths
        if window:
            kk = _insert_window(kk, dense[2][layer], lengths, counts)
            vv = _insert_window(vv, dense[3][layer], lengths, counts)
            total = lengths + counts
        mask = jnp.arange(kk.shape[1])[None, None, :] < total[:, None, None]
        return attend(q[:, None], kk, vv, mask, None)[:, 0]

    return q, table, lengths, pool, win, ref


@pytest.mark.parametrize("via", ["plain", "sharded"])
@pytest.mark.parametrize("window", [False, True], ids=["nowin", "win"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_paged_attention_layer_of_whole_pool(layer, quant, window, via,
                                             mesh_dt):
    """The kernel over the WHOLE pool and the WHOLE window with the
    layer as a (traced) prefetched scalar: the dense reference of that
    layer, and to the bit what it gives over that layer's slices alone
    as a pool and a window of one (the operand of a caller that scans
    them). Every layer holds other values, pool and window, so a wrong
    layer of either fails both."""
    from butterfly_tpu.ops.paged_attention import paged_attention_sharded
    q, table, lengths, pool, win, ref = _layered_case(quant, window)

    def call(kp, vp, ly, ksp, vsp, win):
        fn = paged_attention if via == "plain" else paged_attention_sharded
        return fn(q, kp, vp, ly, table, lengths, ksp, vsp, **win)

    def run(*a):
        with contextlib.nullcontext() if via == "plain" \
                else jax.set_mesh(mesh_dt):
            return jax.jit(call)(*a)

    out = run(pool[0], pool[1], jnp.int32(layer), pool[2], pool[3], win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(layer)),
                               rtol=2e-5, atol=2e-5)
    alone = [None if a is None else a[layer][None] for a in pool]
    win1 = {k: a if k == "win_count" else a[layer][None]
            for k, a in win.items()}
    out1 = run(alone[0], alone[1], jnp.int32(0), alone[2], alone[3], win1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out1))
    other = np.asarray(ref((layer + 1) % 3))
    assert np.abs(np.asarray(out) - other).max() > 1e-2


@pytest.mark.parametrize("window", [False, True], ids=["nowin", "win"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_attention_grid_ends_with_longest_context(quant, window):
    """A slot's walk ends with its own context, a value, not with the
    table's width: the call's grid is over the slots alone and carries
    no bound at all (until PR 46: one dynamic bound, the longest live
    context in pages), and a table twice as wide, its new tail on pages
    full of huge values, gives the same output to the bit."""
    q, table, lengths, pool, win, ref = _layered_case(quant, window)
    layer = jnp.int32(1)

    def call(table, pool):
        return paged_attention(q, pool[0], pool[1], layer, table, lengths,
                               pool[2], pool[3], **win)

    out = call(table, pool)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(1)),
                               rtol=2e-5, atol=2e-5)
    # pages 12.. of a taller pool: never to be read
    hot = [None if a is None else jnp.concatenate(
        [a, jnp.full_like(a[:, :3], 100)], axis=1) for a in pool]
    wide = jnp.concatenate([table, jnp.full_like(table, 13)], axis=1)
    np.testing.assert_array_equal(np.asarray(call(wide, hot)),
                                  np.asarray(out))
    eqns = [e for e in jax.make_jaxpr(call)(wide, hot).jaxpr.eqns[0]
            .params["jaxpr"].eqns if e.primitive.name == "pallas_call"]
    grid = eqns[0].params["grid_mapping"]
    assert grid.grid == (q.shape[0],) and not grid.num_dynamic_grid_bounds


# -- the walk over a slot's own pages in chunks (ISSUE 46) -------------------

#: name -> (pool lengths of the four slots, the layer's sliding window);
#: pages of 4, a chunk of 4 pages (16 positions), a step of 2
WALKS = {
    # three chunks beside part of one
    "lengths_apart": ((3, 48, 20, 37), None),
    "ends_in_a_chunks_first_page": ((17, 18, 33, 35), None),
    "ends_on_a_chunks_last_page": ((16, 32, 48, 45), None),
    "nothing_beside_everything": ((0, 48, 0, 48), None),
    # lower bounds 29+, 37+, 14+ and 0: the second chunk, the third, the
    # first's last page, and a slot the window does not bind
    "slides_past_the_first_chunk": ((40, 48, 25, 9), 11),
}


@pytest.fixture
def small_chunks(monkeypatch):
    """The kernel with chunks of 4 pages multiplied 2 a step, traced anew
    (the jitted function would serve a trace made at another chunk)."""
    import butterfly_tpu.ops.paged_attention as pa
    monkeypatch.setattr(pa, "MAX_PAGES_PER_CHUNK", 4)
    monkeypatch.setattr(pa, "SUB_PAGES", 2)
    monkeypatch.setattr(pa, "paged_attention", pa.paged_attention.__wrapped__)
    return pa


def _walk_case(quant, window, lengths):
    """Four slots over a pool of one layer of 12 pages a slot: (q, table,
    kernel pool args, window kwargs, sliding window -> dense reference)."""
    S, Nq, Kv, H, page, MP, W = 4, 8, 4, 16, 4, 12, 3
    P = S * MP + 1
    ks = jax.random.split(jax.random.PRNGKey(46), 5)
    q = jax.random.normal(ks[0], (S, Nq, H))
    table = jnp.asarray(np.random.RandomState(46).permutation(P - 1)
                        .reshape(S, MP), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    counts = jnp.where(lengths > 0, jnp.asarray([2, 3, 0, 1]), 0) \
        if window else jnp.zeros((S,), jnp.int32)
    kf = jax.random.normal(ks[1], (1, P, Kv, page, H), jnp.bfloat16)
    vf = jax.random.normal(ks[2], (1, P, Kv, page, H), jnp.bfloat16)
    wkf = jax.random.normal(ks[3], (1, S, Kv, W, H), jnp.bfloat16)
    wvf = jax.random.normal(ks[4], (1, S, Kv, W, H), jnp.bfloat16)
    pool, win, dense = _pool_and_window(kf, vf, wkf, wvf, counts, quant)

    def ref(sw):
        kk = jnp.pad(_gather_pool(dense[0][0], table),
                     ((0, 0), (0, W), (0, 0), (0, 0)))
        vv = jnp.pad(_gather_pool(dense[1][0], table),
                     ((0, 0), (0, W), (0, 0), (0, 0)))
        if window:
            kk = _insert_window(kk, dense[2][0], lengths, counts)
            vv = _insert_window(vv, dense[3][0], lengths, counts)
        total = (lengths + counts)[:, None, None]
        pos = jnp.arange(kk.shape[1])[None, None, :]
        mask = (pos < total) & (pos >= total - (sw or kk.shape[1]))
        out = attend(q[:, None], kk, vv, mask, None)[:, 0]
        return jnp.where((total > 0), out, 0)

    return q, table, lengths, pool, (win if window else {}), ref


@pytest.mark.parametrize("via", ["plain", "sharded"])
@pytest.mark.parametrize("window", [False, True], ids=["nowin", "win"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_paged_attention_walks_its_own_pages_in_chunks(
        walk, quant, window, via, mesh_dt, small_chunks):
    """Every slot loops over ITS OWN pages, a chunk at a time: slots
    whose lengths lie chunks apart, a context that ends inside a chunk's
    first page and one that ends on a chunk's last, a slot with nothing
    beside one with everything, a sliding layer whose lower bound falls
    past the first chunk; the dense reference over the same pages."""
    lens, sw = WALKS[walk]
    q, table, lengths, pool, win, ref = _walk_case(quant, window, lens)
    fn = small_chunks.paged_attention if via == "plain" \
        else small_chunks.paged_attention_sharded
    kw = {} if sw is None else dict(sliding_window=jnp.int32(sw))
    with contextlib.nullcontext() if via == "plain" \
            else jax.set_mesh(mesh_dt):
        out = jax.jit(lambda q, *pool: fn(
            q, pool[0], pool[1], 0, table, lengths, *pool[2:], **win,
            **kw))(q, *[a for a in pool if a is not None])
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(sw)),
                               rtol=2e-5, atol=2e-5)


def _kernel_jaxpr(pages_per_chunk, monkeypatch):
    """The kernel body's jaxpr at a chunk of so many pages (Mistral-7B's
    heads, an int8 pool, window and sliding scalar on)."""
    import butterfly_tpu.ops.paged_attention as pa
    monkeypatch.setattr(pa, "_pages_per_chunk", lambda *a: pages_per_chunk)
    S, Nq, Kv, H, page, P, W = 2, 32, 8, 128, 16, 9, 8
    i8, f32 = jnp.int8, jnp.float32

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    jaxpr = jax.make_jaxpr(
        lambda *a: pa.paged_attention.__wrapped__(
            *a[:8], win_k=a[8], win_v=a[9], win_count=a[10],
            win_k_scale=a[11], win_v_scale=a[12], sliding_window=a[13],
            interpret=True))(
        sds((S, Nq, H), jnp.bfloat16), sds((1, P, Kv, page, H), i8),
        sds((1, P, Kv, page, H), i8), sds((), jnp.int32),
        sds((S, 64), jnp.int32), sds((S,), jnp.int32),
        sds((1, P, Kv * page), f32), sds((1, P, Kv * page), f32),
        sds((1, S, Kv, W, H), i8), sds((1, S, Kv, W, H), i8),
        sds((S,), jnp.int32), sds((1, S, 1, Kv * W), f32),
        sds((1, S, 1, Kv * W), f32), sds((), jnp.int32))
    call, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (S,)
    return call.params["jaxpr"]


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_paged_kernel_body_does_not_grow_with_the_chunk(monkeypatch):
    """Set-up by construction (ISSUE 46): a chunk's copies are issued and
    awaited by a rolled loop, so the body a serving program traces and
    lowers at every start is the same size at a chunk of 8 pages as at
    32 (the chunk is an argument of the kernel body, not of the public
    function), and small: three sites that start copies and one that
    awaits them, of three descriptors each, not three a page."""
    sizes = {n: sum(1 for _ in _eqns(_kernel_jaxpr(n, monkeypatch)))
             for n in (8, 32)}
    assert sizes[8] == sizes[32], sizes
    assert sizes[8] < 700, sizes
    text = str(_kernel_jaxpr(32, monkeypatch))
    assert text.count("dma_start") == 9 and text.count("dma_wait") == 3


def test_sliding_and_full_layers_share_one_paged_call():
    """Set-up by construction (ISSUE 46): the decode block of a model
    whose layers slide and do not slide holds ONE paged kernel: the
    layer's window is a prefetched scalar (0 = a full layer), not a
    branch between two bodies nor a second specialisation, so the model
    that mixes both kinds pays for one kernel as every other does."""
    from butterfly_tpu.cache.paged import (
        init_kv_window, init_paged_cache, paged_forward_packed)

    cfg = tiny("smallthinker", dtype="float32", param_dtype="float32")
    assert len(set(cfg.sliding_window_layout)) == 2
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8)
    params = Model(cfg).init(jax.random.PRNGKey(0))
    cache = init_paged_cache(cfg, rt)
    window = init_kv_window(cache, 8)

    def fn(params, cache, window):
        return paged_forward_packed(
            params, cfg, jnp.asarray([3, 5], jnp.int32), cache,
            jnp.zeros((1, 4), jnp.int32), jnp.asarray([1]), jnp.asarray([0]),
            jnp.ones((2,), bool), window, jnp.zeros((2,), jnp.int32),
            use_kernel=True)[:2]

    pool = cache.k_pages.shape
    calls = [e for e in _eqns(jax.make_jaxpr(fn)(params, cache, window).jaxpr)
             if e.primitive.name == "pallas_call"
             and [v.aval.shape for v in e.invars].count(pool) == 2]
    assert len(calls) == 1, len(calls)
    conds = [e for e in _eqns(calls[0].params["jaxpr"])
             if e.primitive.name == "cond"
             and len(e.params["branches"]) > 2]
    assert not conds


def test_serving_with_kernels_token_parity():
    """Full scheduler run with Pallas kernels == gather path, token-exact."""
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler

    cfg = tiny("llama", dtype="float32", param_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(42))
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8)

    outs = {}
    for use_k in (False, True):
        sched = Scheduler(ServingEngine(model, params, rt,
                                        use_kernels=use_k))
        r1 = sched.submit([5, 7, 11], max_new_tokens=6)
        r2 = sched.submit([3, 1], max_new_tokens=6)
        sched.run_until_done()
        outs[use_k] = (r1.output, r2.output)
    assert outs[False] == outs[True]


def test_engine_flash_prefill_token_parity():
    """InferenceEngine with flash prefill == dense prefill, token-exact."""
    from butterfly_tpu.engine import InferenceEngine, SamplingParams
    cfg = tiny("llama", dtype="float32", param_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    prompts = [[5, 7, 11, 2], [3]]
    sp = SamplingParams(max_new_tokens=6)
    a = InferenceEngine(model, params,
                        use_flash_prefill=False).generate(prompts, sp)
    b = InferenceEngine(model, params,
                        use_flash_prefill=True).generate(prompts, sp)
    np.testing.assert_array_equal(a.tokens, b.tokens)


# -- ssm_step: a decode row's Mamba-2 recurrence, one pass over the state -----

def _ssm_case(dtype, groups, seed=0):
    """A toy state of whole tiles (N = 128 on the lanes), three Mamba
    layers, four slots of which slot 2 does not decode, and what
    ssm_conv and the in-projection would hand one decode step."""
    cfg = tiny("granite_hybrid", ssm_state=128, ssm_groups=groups)
    Lm, S = 3, 4
    Nh, Hd, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    h = jax.random.normal(ks[0], (Lm, S, Nh, Hd, N)).astype(dtype)
    u = jax.random.normal(ks[1], (S, 1, cfg.ssm_conv_dim))
    dt = jax.random.normal(ks[2], (S, 1, Nh))
    mp = {"dt_bias": jax.random.normal(ks[3], (Nh,)),
          "A_log": jax.random.uniform(ks[4], (Nh,), minval=-1.0, maxval=1.0),
          "D": jax.random.normal(ks[5], (Nh,))}
    count = jnp.asarray([1, 1, 0, 1], jnp.int32)
    return cfg, h, u, dt, mp, count


@pytest.mark.parametrize("case", [
    "bf16-g1", "f32-g1", "bf16-g2", "f32-g4", "dead-row", "other-layers",
    "layer-in-a-scan", "a-state-that-does-not-fit"])
def test_ssm_step_is_the_jnp_step(case):
    """The kernel (interpreted) against ssm_scan(T == 1) + the update in
    place (cache/ssm_state.py decode_rows_step, kernels on and off): the
    state as stored in both dtypes, one group and several, a
    row that does not decode, the layers the call does not name, and
    the layer's index traced inside a scan as the engine's runs do."""
    dtype = jnp.float32 if case.startswith("f32") else jnp.bfloat16
    groups = int(case[-1]) if case[-2:-1] == "g" else 1
    cfg, h, u, dt, mp, count = _ssm_case(dtype, groups)
    # one bfloat16 ulp where a float32 sum in another order rounds the
    # other way; float32 to its own rounding
    tol = dict(rtol=8e-3, atol=1e-6) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-6, atol=1e-6)
    if case == "a-state-that-does-not-fit":
        small = h[..., :16]
        assert fits(h) and not fits(small)
        assert not fits(h[..., :8, :])             # bf16: 16 sublanes
        assert fits(h.astype(jnp.float32)[..., :8, :])
        # blocks of heads: 8 rows at a time or all of them, three pieces
        # of a block in the MXU's 128 lanes
        assert heads_per_block(jnp.zeros((1, 1, 128, 64, 128),
                                         jnp.bfloat16)) == 32
        assert heads_per_block(h) == 8
        assert not fits(jnp.zeros((1, 1, 44, 16, 128)))    # 44, 22, 11: none
        with pytest.raises(ValueError, match="whole tiles"):
            ssm_step(small, 0, *(jnp.zeros(()),) * 5)
        return
    if case == "layer-in-a-scan":
        def run(use_kernel):
            def body(h, m):
                y, h = decode_rows_step(h, m, u, dt, mp, cfg, count,
                                        use_kernel)
                return h, y
            return jax.jit(lambda h: jax.lax.scan(body, h, jnp.arange(3)))(h)
        (h_k, y_k), (h_j, y_j) = run(True), run(False)
        assert not np.array_equal(np.asarray(h_k[0], np.float32),
                                  np.asarray(h[0], np.float32))
    else:
        m = jnp.int32(1)
        y_k, h_k = decode_rows_step(h, m, u, dt, mp, cfg, count, True)
        y_j, h_j = decode_rows_step(h, m, u, dt, mp, cfg, count, False)
    assert h_k.dtype == h.dtype and y_k.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_j),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k, np.float32),
                               np.asarray(h_j, np.float32), **tol)
    if case == "dead-row":      # bit for bit, where the live rows moved
        assert np.array_equal(np.asarray(h_k[1, 2]), np.asarray(h[1, 2]))
        assert not np.array_equal(np.asarray(h_k[1, 1]), np.asarray(h[1, 1]))
    if case == "other-layers":
        assert np.array_equal(np.asarray(h_k[0]), np.asarray(h[0]))
        assert np.array_equal(np.asarray(h_k[2]), np.asarray(h[2]))


# -- gdn_step: a decode row's delta rule, one pass over the state -------------

def _gdn_case(dtype, dv, dk=32, seed=0):
    """A toy state of whole tiles (keys of 32 down the sublanes; values
    of 192, two heads a row of 384 lanes, or of 128, a head a row),
    three Gated DeltaNet layers, four slots of which slot 2 does not
    decode, and what gdn_conv and the in-projection would hand one
    decode step (b loud enough that beta spans the whole of (0, 2))."""
    cfg = tiny("olmo_hybrid", gdn_heads=4, gdn_key_dim=dk, gdn_value_dim=dv)
    Ls, S, H = 3, 4, cfg.gdn_heads
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    h = jax.random.normal(
        ks[0], (Ls,) + state_shapes(cfg, S)["h"][1:]).astype(dtype)
    u = jax.random.normal(ks[1], (S, 1, cfg.gdn_conv_dim))
    aux = (None, jax.random.normal(ks[2], (S, 1, H)),
           3 * jax.random.normal(ks[3], (S, 1, H)))
    gp = {"dt_bias": jax.random.normal(ks[4], (H,)),
          "A_log": jax.random.uniform(ks[5], (H,), minval=-1.0, maxval=1.0)}
    count = jnp.asarray([1, 1, 0, 1], jnp.int32)
    return cfg, h, u, aux, gp, count


@pytest.mark.parametrize("case", [
    "bf16-g2", "f32-g2", "bf16-g1", "f32-g1", "dead-row", "other-layers",
    "layer-in-a-scan", "beta-to-2", "a-state-that-does-not-fit"])
def test_gdn_step_is_the_jnp_step(case):
    """The kernel (interpreted) against models/common.py gdn_step
    (cache/ssm_state.py _DeltaNet.decode, kernels on and off): the state
    as stored in both dtypes, two heads' values a row of lanes and one,
    a row that does not decode, the layers the call does not name, the
    layer's index traced inside a scan as the engine's runs do, beta
    over the whole of (0, 2), and a state that is not whole tiles, which
    takes the `jnp` step."""
    dtype = jnp.float32 if case.startswith("f32") else jnp.bfloat16
    cfg, h, u, aux, gp, count = _gdn_case(dtype,
                                          128 if case.endswith("g1") else 192)
    H = cfg.gdn_heads
    assert cfg.gdn_head_group == (1 if case.endswith("g1") else 2)
    # one bfloat16 ulp where a float32 sum in another order rounds the
    # other way; float32 to its own rounding
    tol = dict(rtol=8e-3, atol=1e-6) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-6, atol=1e-6)

    def decode(h, m, use_kernel):
        return _DeltaNet.decode(h, m, u, aux, gp, cfg, count, use_kernel)

    if case == "a-state-that-does-not-fit":
        # keys of 8 are half a tile of bfloat16's 16 sublanes
        cfg, small, u, aux, gp, count = _gdn_case(dtype, 192, dk=8)
        assert gdn_kernel.fits(h, H) and not gdn_kernel.fits(small, H)
        assert gdn_kernel.fits(small.astype(jnp.float32), H)
        assert not gdn_kernel.fits(h[..., :64], H)
        # blocks of groups: all of them or 8 at a time, three pieces of a
        # block's heads in the MXU's 128 lanes, a block under its bytes
        olmo = jnp.zeros((1, 1, 15, 96, 384), jnp.bfloat16)
        assert gdn_kernel.groups_per_block(olmo, 30) == 15
        assert not gdn_kernel.fits(olmo.astype(jnp.float32), 30)
        assert gdn_kernel.groups_per_block(
            jnp.zeros((1, 1, 32, 16, 256), jnp.bfloat16), 64) == 16
        assert not gdn_kernel.fits(jnp.zeros((1, 1, 44, 16, 128)), 44)
        with pytest.raises(ValueError, match="whole tiles"):
            gdn_kernel.gdn_step(small, 0, *(jnp.zeros((1, H, 1)),) * 6)
        with record_kernels({}) as calls:   # the jnp step, asked or not
            o_k, h_k = decode(small, jnp.int32(1), True)
        assert not calls
        o_j, h_j = decode(small, jnp.int32(1), False)
        assert np.array_equal(np.asarray(o_k), np.asarray(o_j))
        assert np.array_equal(np.asarray(h_k, np.float32),
                              np.asarray(h_j, np.float32))
        return
    with record_kernels({}) as calls:
        if case == "layer-in-a-scan":
            def run(use_kernel):
                def body(h, m):
                    o, h = decode(h, m, use_kernel)
                    return h, o
                return jax.jit(
                    lambda h: jax.lax.scan(body, h, jnp.arange(3)))(h)
            (h_k, o_k), (h_j, o_j) = run(True), run(False)
            assert not np.array_equal(np.asarray(h_k[0], np.float32),
                                      np.asarray(h[0], np.float32))
        else:
            m = jnp.int32(1)
            o_k, h_k = decode(h, m, True)
            o_j, h_j = decode(h, m, False)
    assert calls == {"gdn_step:interpret": 1}
    assert h_k.dtype == h.dtype and o_k.dtype == jnp.float32
    # a row that does not decode is copied through and reads out zero
    # (the `jnp` step reads its state out; nothing takes that row's o)
    live = np.asarray(count) > 0
    np.testing.assert_allclose(np.asarray(o_k)[..., live, :, :, :],
                               np.asarray(o_j)[..., live, :, :, :],
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k, np.float32),
                               np.asarray(h_j, np.float32), **tol)
    if case == "dead-row":      # bit for bit, where the live rows moved
        assert np.array_equal(np.asarray(h_k[1, 2]), np.asarray(h[1, 2]))
        assert not np.array_equal(np.asarray(h_k[1, 1]), np.asarray(h[1, 1]))
        assert not np.asarray(o_k)[2].any() and np.asarray(o_j)[2].any()
    if case == "other-layers":
        assert np.array_equal(np.asarray(h_k[0]), np.asarray(h[0]))
        assert np.array_equal(np.asarray(h_k[2]), np.asarray(h[2]))
    if case == "beta-to-2":     # a reflection is among what was compared
        from butterfly_tpu.models.common import gdn_step_inputs
        beta = np.asarray(gdn_step_inputs(u, aux[1], aux[2], gp, cfg,
                                          count)[4])[count > 0]
        assert beta.min() < 0.2 and beta.max() > 1.8


# -- mamba1_step: a decode row's Mamba-1 recurrence, one pass over the state --

def _mamba1_case(dtype, inner=256, state=16, S=10, seed=0):
    """A toy state of whole tiles (16 state indices down the sublanes,
    `inner` channels on the lanes), three Mamba-1 layers, S slots of
    which a fifth (slots 2 and 7) do not decode, and what mamba1_conv
    would hand one decode step, over weights whose rates differ a
    channel and a state index."""
    cfg = tiny("jamba", mamba1_inner=inner, mamba1_state=state)
    N, Di, R = cfg.mamba1_state, cfg.mamba1_inner, cfg.mamba1_dt_rank
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    h = jax.random.normal(
        ks[0], (3,) + state_shapes(cfg, S)["h"][1:]).astype(dtype)
    u = jax.random.normal(ks[1], (S, 1, Di))
    mp = {"x_proj": 0.2 * jax.random.normal(ks[2], (Di, R + 2 * N)),
          "dt_proj": 0.5 * jax.random.normal(ks[3], (R, Di)),
          "dt_bias": jax.random.normal(ks[4], (Di,)),
          "A_log": jax.random.uniform(ks[5], (N, Di), minval=-1.0,
                                      maxval=1.0),
          "D": jax.random.normal(ks[6], (Di,))}
    count = (jnp.arange(S) % 5 != 2).astype(jnp.int32)
    return cfg, h, u, mp, count


@pytest.mark.parametrize("case", [
    "bf16", "f32", "one-lane-tile", "dead-rows", "other-layers",
    "layer-in-a-scan", "slots-that-do-not-divide-the-block",
    "lanes-in-two-blocks", "a-state-that-does-not-fit"])
def test_mamba1_step_is_the_jnp_step(case, monkeypatch):
    """The kernel (interpreted) against models/common.py mamba1_step
    (cache/ssm_state.py _Mamba1.decode, kernels on and off): the state
    as stored in both dtypes, two lane tiles a slot and one, a fifth of
    the slots not decoding, the layers the call does not name, the
    layer's index traced inside a scan as the engine's runs do, a last
    block of fewer slots than the others, a slot's channels in two
    blocks of lanes (a state wider than a block's tiles), and a state
    that is not whole tiles, which takes the `jnp` step."""
    dtype = jnp.float32 if case == "f32" else jnp.bfloat16
    cfg, h, u, mp, count = _mamba1_case(
        dtype, 128 if case == "one-lane-tile" else 256)
    # one bfloat16 ulp where a float32 sum in another order rounds the
    # other way; float32 to its own rounding
    tol = dict(rtol=8e-3, atol=1e-6) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-6, atol=1e-6)

    def decode(h, m, use_kernel):
        return _Mamba1.decode(h, m, u, (None,), mp, cfg, count, use_kernel)

    if case == "a-state-that-does-not-fit":
        # the cell's own geometry fits, eight slots a block of 1.3 MB
        cell = jnp.zeros((26, 128, 16, 5120), jnp.bfloat16)
        assert mamba1_kernel.fits(cell) and mamba1_kernel.fits(h)
        assert mamba1_kernel.slots_per_block(cell) == 8
        assert mamba1_kernel.lanes_per_block(cell) == 5120
        assert mamba1_kernel.slots_per_block(h) == h.shape[1]
        # 8 state indices are half a tile of bfloat16's 16 sublanes; 64
        # channels half a row of lanes; one slot over a block's bytes
        cfg, small, u, mp, count = _mamba1_case(dtype, state=8)
        assert not mamba1_kernel.fits(small)
        assert mamba1_kernel.fits(small.astype(jnp.float32))
        assert not mamba1_kernel.fits(h[..., :64])
        assert not mamba1_kernel.fits(jnp.zeros((1, 8, 16, 1 << 16)))
        with pytest.raises(ValueError, match="mamba1_step cannot cut"):
            mamba1_kernel.mamba1_step(small, 0, *(jnp.zeros((1, 1)),) * 6)
        with record_kernels({}) as calls:   # the jnp step, asked or not
            y_k, h_k = decode(small, jnp.int32(1), True)
        assert not calls
        y_j, h_j = decode(small, jnp.int32(1), False)
        assert np.array_equal(np.asarray(y_k), np.asarray(y_j))
        assert np.array_equal(np.asarray(h_k, np.float32),
                              np.asarray(h_j, np.float32))
        return
    if case == "slots-that-do-not-divide-the-block":
        # 10 slots in blocks of 8: the second block holds two
        monkeypatch.setattr(mamba1_kernel, "BLOCK_BYTES",
                            8 * h[0, 0].size * h.dtype.itemsize)
        assert mamba1_kernel.slots_per_block(h) == 8 < h.shape[1]
        jax.clear_caches()      # the wrapper's trace reads the constant
    if case == "lanes-in-two-blocks":
        monkeypatch.setattr(mamba1_kernel, "BLOCK_TILES", 1)
        assert mamba1_kernel.lanes_per_block(h) == 128 < h.shape[3]
        jax.clear_caches()
    with record_kernels({}) as calls:
        if case == "layer-in-a-scan":
            def run(use_kernel):
                def body(h, m):
                    y, h = decode(h, m, use_kernel)
                    return h, y
                return jax.jit(
                    lambda h: jax.lax.scan(body, h, jnp.arange(3)))(h)
            (h_k, y_k), (h_j, y_j) = run(True), run(False)
            assert not np.array_equal(np.asarray(h_k[0], np.float32),
                                      np.asarray(h[0], np.float32))
        else:
            m = jnp.int32(1)
            y_k, h_k = decode(h, m, True)
            y_j, h_j = decode(h, m, False)
    if case in ("slots-that-do-not-divide-the-block", "lanes-in-two-blocks"):
        jax.clear_caches()
    assert calls == {"mamba1_step:interpret": 1}
    assert h_k.dtype == h.dtype and y_k.dtype == jnp.float32
    assert y_k.shape == y_j.shape
    # a row that does not decode is copied through and reads out the
    # skip term alone (the `jnp` step reads its state out too; nothing
    # takes that row's y)
    live = np.asarray(count) > 0
    assert 0 < (~live).sum() == len(live) // 5
    np.testing.assert_allclose(np.asarray(y_k)[..., live, :, :],
                               np.asarray(y_j)[..., live, :, :],
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k, np.float32),
                               np.asarray(h_j, np.float32), **tol)
    if case == "dead-rows":     # bit for bit, where the live rows moved
        for s in np.flatnonzero(~live):
            assert np.array_equal(np.asarray(h_k[1, s]), np.asarray(h[1, s]))
            np.testing.assert_array_equal(
                np.asarray(y_k[s, 0]), np.asarray(mp["D"] * u[s, 0]))
        assert not np.array_equal(np.asarray(h_k[1, 1]), np.asarray(h[1, 1]))
    if case == "other-layers":
        assert np.array_equal(np.asarray(h_k[0]), np.asarray(h[0]))
        assert np.array_equal(np.asarray(h_k[2]), np.asarray(h[2]))
