"""Long-context serving tests (ISSUE 20).

Four layers, bottom up:

* ring block kernel: the Pallas leg (interpret mode — the jnp twin is
  what shard_map bodies run on CPU, so the kernel needs its own direct
  coverage) vs `ring_block_stats_ref` vs dense attention, float x int8,
  aligned x ragged chunk geometry;
* the stats algebra: a seq=4-style four-shard split merged with
  `merge_stats` must reproduce dense exactly (the running-max
  correction `exp(m_a - m)` is load-bearing here: without it the
  merge diverges from dense);
* engine surface: `sp_prefill_chunk` (seq=4 mesh, int8 KV) vs the
  lane-wide forward's logits on one device, chunk by chunk;
* scheduler: long prompts admitted through the seq-parallel lane
  (chunked SP prefill -> ordinary paged decode) match the scheduler
  without the lane token for token, and the pages the lane writes are
  prefix-registry-visible on resubmission.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.core.config import MeshConfig, ModelConfig, RuntimeConfig
from butterfly_tpu.core.mesh import make_mesh
from butterfly_tpu.engine.serving import ServingEngine
from butterfly_tpu.models.common import Model, init_params
from butterfly_tpu.ops.ring_attention import (
    finalize_stats, merge_stats, ring_block_stats, ring_block_stats_ref,
    zero_stats)
from butterfly_tpu.sched.scheduler import Scheduler
from packed_driver import lane_wide_chunk
from test_sched import _HostOnly


# ---------------------------------------------------------------------------
# kernel-level parity
# ---------------------------------------------------------------------------

def _dense_ref(q, k, v, q_pos, k_pos):
    """Full masked softmax attention. q [B,T,Nq,H]; k/v [B,S,Kv,H] float.

    GQA head order matches the ring contract: head n reads kv head n // G.
    """
    B, T, Nq, H = q.shape
    G = Nq // k.shape[2]
    kx = jnp.repeat(k, G, axis=2).astype(jnp.float32)
    vx = jnp.repeat(v, G, axis=2).astype(jnp.float32)
    s = jnp.einsum("btnh,bsnh->bnts", q.astype(jnp.float32), kx,
                   preferred_element_type=jnp.float32) / np.sqrt(H)
    mask = k_pos[:, None, None, :] <= q_pos[:, None, :, None]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnts,bsnh->btnh", p, vx)


def _make_block(T, S, start, seed=0):
    """A chunk of T queries at positions [start, start+T) over S keys."""
    B, Nq, Kv, H = 2, 8, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, Nq, H), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Kv, H), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Kv, H), jnp.float32)
    q_pos = jnp.broadcast_to(jnp.arange(start, start + T)[None], (B, T))
    k_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    return q, k, v, q_pos.astype(jnp.int32), k_pos.astype(jnp.int32)


def _quant_kv(x):
    """[B,S,Kv,H] float -> (codes [B,Kv,S,H] int8, scales [B,Kv,S])."""
    xt = jnp.moveaxis(x, 2, 1)                        # [B,Kv,S,H]
    scale = jnp.max(jnp.abs(xt), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    codes = jnp.round(xt / scale[..., None]).astype(jnp.int8)
    return codes, scale.astype(jnp.float32)


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("T,S,start", [(8, 32, 24), (5, 19, 11)],
                         ids=["aligned", "ragged"])
def test_ring_block_parity_grid(quant, T, S, start):
    """Pallas kernel (interpret) vs jnp twin vs dense, small blocks so the
    grid's reduction axis actually streams several K/V tiles through the
    scratch state (and the ragged case exercises the INVALID_POS pad)."""
    q, k, v, q_pos, k_pos = _make_block(T, S, start)
    if quant:
        kc, ks = _quant_kv(k)
        vc, vs = _quant_kv(v)
        ref_in = (q, kc, vc, q_pos, k_pos, ks, vs)
        k_dq = jnp.moveaxis(kc.astype(jnp.float32) * ks[..., None], 1, 2)
        v_dq = jnp.moveaxis(vc.astype(jnp.float32) * vs[..., None], 1, 2)
        dense = _dense_ref(q, k_dq, v_dq, q_pos, k_pos)
    else:
        ref_in = (q, k, v, q_pos, k_pos)
        dense = _dense_ref(q, k, v, q_pos, k_pos)

    twin = finalize_stats(ring_block_stats_ref(*ref_in), jnp.float32)
    kern = finalize_stats(
        ring_block_stats(*ref_in, block_q=8, block_k=8, interpret=True),
        jnp.float32)

    np.testing.assert_allclose(np.asarray(twin), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(twin),
                               rtol=2e-5, atol=2e-5)


def test_ring_merge_four_shards_matches_dense():
    """seq=4 ring decomposition, one device: per-shard partial stats
    merged left-to-right (seeded with the zero_stats identity) must equal
    dense. Each shard has a different score max, so the running-max
    rescale `exp(m_a - m)` in merge_stats is what makes this pass."""
    T, S, start = 8, 32, 24
    q, k, v, q_pos, k_pos = _make_block(T, S, start, seed=3)
    B, _, Nq, H = q.shape
    parts = []
    for i in range(4):
        sl = slice(i * 8, (i + 1) * 8)
        parts.append(ring_block_stats_ref(
            q, k[:, sl], v[:, sl], q_pos, k_pos[:, sl]))
    merged = functools.reduce(merge_stats, parts, zero_stats(B, Nq, T, H))
    out = finalize_stats(merged, jnp.float32)
    dense = _dense_ref(q, k, v, q_pos, k_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# engine + scheduler surfaces (tiny model, seq=4 x data=2 mesh)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    cfg = ModelConfig(vocab_size=256, hidden_size=64, num_layers=2,
                      num_heads=8, num_kv_heads=2, head_dim=8,
                      intermediate_size=128, max_seq_len=256,
                      dtype="float32")
    return Model(cfg), init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def sp_mesh():
    return make_mesh(MeshConfig(seq=4, data=2))


LONG = [int(t) for t in (np.arange(100) * 7 + 3) % 256]
SHORT = [int(t) for t in (np.arange(12) * 5 + 1) % 256]


def test_sp_chunk_prefill_int8_logits_parity(tiny_model, sp_mesh):
    """Fast-tier anchor: seq-parallel chunk prefill with int8 KV matches
    the lane-wide forward's logits chunk for chunk (dequant happens inside
    the ring blocks — the engine-level guard that used to reject this
    combination is gone)."""
    model, params = tiny_model
    rt = RuntimeConfig(max_batch_size=2, page_size=16, max_seq_len=128,
                       kv_quant="int8")
    dense = ServingEngine(model, params, runtime=rt)
    sp = ServingEngine(model, params, runtime=rt, mesh=sp_mesh)
    assert sp.supports_seq_parallel and sp.sp_degree == 4

    prompt = [int(t) for t in (np.arange(40) * 11 + 5) % 256]
    pages = list(range(-(-len(prompt) // 16)))
    dense.set_table_row(0, pages)
    sp.set_table_row(0, pages)
    for lo, hi in ((0, 24), (24, 40)):
        ld = lane_wide_chunk(dense, 0, prompt[lo:hi])
        ls = sp.sp_prefill_chunk(0, prompt[lo:hi], lo)
        np.testing.assert_allclose(np.asarray(ls), np.asarray(ld),
                                   rtol=3e-4, atol=3e-4)
    assert int(np.asarray(jax.device_get(sp.cache.lengths))[0]) == 40


@pytest.mark.parametrize("kvq", ["none", "int8"], ids=["float", "int8"])
def test_sp_sched_long_prefill_parity(tiny_model, sp_mesh, kvq):
    """A long prompt (above seq_parallel_threshold) admitted through the
    scheduler's SP lane plus a concurrent short prompt on the normal
    path: both must match the scheduler without the lane (the long
    prompt a block's chunks) token for token, and
    the lane must actually have dispatched SP chunks."""
    model, params = tiny_model
    rt = RuntimeConfig(max_batch_size=2, page_size=16, max_seq_len=160,
                       kv_quant=kvq, prefill_chunk=16,
                       seq_parallel_threshold=64)
    sp = Scheduler(ServingEngine(model, params, rt, mesh=sp_mesh), seed=0)
    assert sp._sp_enabled
    dn = Scheduler(ServingEngine(
        model, params, rt.replace(seq_parallel_threshold=0)), seed=0)

    r_sp = sp.submit(list(LONG), max_new_tokens=8, temperature=0.0)
    s_sp = sp.submit(list(SHORT), max_new_tokens=8, temperature=0.0)
    sp.run_until_done()
    r_dn = dn.submit(list(LONG), max_new_tokens=8, temperature=0.0)
    s_dn = dn.submit(list(SHORT), max_new_tokens=8, temperature=0.0)
    dn.run_until_done()

    assert r_sp.output == r_dn.output
    assert s_sp.output == s_dn.output
    assert sp._c_sp_tokens.value > 0


def test_prefix_hit_after_long_prefill(tiny_model, sp_mesh):
    """KV written by SP chunk prefill lands in the paged pool like any
    other prefill: resubmitting the long prompt must hit the prefix
    registry (cached pages at admit) and still decode identically."""
    model, params = tiny_model
    rt = RuntimeConfig(max_batch_size=2, page_size=16, max_seq_len=160,
                       kv_quant="none", prefill_chunk=16,
                       seq_parallel_threshold=64, prefix_caching=True)
    s = Scheduler(ServingEngine(model, params, rt, mesh=sp_mesh), seed=0)
    a = s.submit(list(LONG), max_new_tokens=4, temperature=0.0)
    s.run_until_done()
    b = s.submit(list(LONG), max_new_tokens=4, temperature=0.0)
    s.run_until_done()
    assert b.cached_at_admit > 0
    assert a.output == b.output


# ---------------------------------------------------------------------------
# the lane's first token: sampled on the device where its last chunk
# ran, queued (Scheduler._pending_first) and fetched with the next drain
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sp2_mesh():
    return make_mesh(MeshConfig(seq=2), jax.devices()[:2])


def _lane_sched(tiny_model, mesh, **rt_kw):
    model, params = tiny_model
    rt = RuntimeConfig(max_batch_size=2, page_size=8, max_seq_len=64,
                       prefill_chunk=16, seq_parallel_chunk=16,
                       seq_parallel_threshold=12 if mesh else 0, **rt_kw)
    return Scheduler(ServingEngine(model, params, rt, mesh=mesh), seed=0)


def test_lane_first_token_is_pending_until_the_next_drain(tiny_model,
                                                          sp2_mesh):
    """The (id, preemptions)-keyed index over undrained first tokens is
    filled where the lane's prompt completes and cleared by the drain
    that fetches them; until then every prompt token's K/V is written
    and _written subtracts nothing (it would lose a page of prefix
    registration at a page boundary: the prompt is exactly two pages)."""
    sched = _lane_sched(tiny_model, sp2_mesh, inflight_blocks=1)
    req = sched.submit(LONG[:16], max_new_tokens=4)
    sched.tick()    # one lane chunk takes the whole prompt
    assert req.state == "running" and req.output == []
    assert (req.id, req.preemptions) in sched._pending_first_keys
    assert [f[0] for f in sched._pending_first] == [req]
    assert sched._written(req) == 16    # the whole prompt, no -1
    sched.tick()    # the drain: the first token lands on the host
    assert len(req.output) >= 1
    assert not sched._pending_first_keys and not sched._pending_first
    # once drained, the last sampled token's K/V is indeed unwritten
    assert sched._written(req) == len(req.all_tokens) - 1
    sched.run_until_done()
    assert req.state == "finished" and len(req.output) == 4


def test_lane_first_tokens_ride_the_drain_s_one_fetch(tiny_model, sp2_mesh):
    """A pending first token rides the same fetch as the blocks: read as
    it is in the drain's one `jax.device_get`, no program launched to
    read it; and the tokens are the scheduler's without the lane."""
    sched = _lane_sched(tiny_model, sp2_mesh)
    req = sched.submit(LONG[:16], max_new_tokens=6)
    for _ in range(4):
        if sched._pending_first:
            break
        sched.tick()
    assert len(sched._pending_first) == 1

    sched._pending_first = [f[:3] + (_HostOnly(f[3]),)
                            for f in sched._pending_first]
    calls, real = [], jax.device_get
    try:
        jax.device_get = lambda x: calls.append(x) or real(x)
        sched._drain_inflight("idle")
    finally:
        jax.device_get = real
    assert len(calls) == 1 and len(calls[0][0]) == 1
    sched.run_until_done()
    plain = _lane_sched(tiny_model, None)
    assert not plain._sp_enabled
    want = plain.submit(LONG[:16], max_new_tokens=6)
    plain.run_until_done()
    assert req.output == want.output
