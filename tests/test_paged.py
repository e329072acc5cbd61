"""Paged KV cache tests: parity with the contiguous cache + allocator
bookkeeping (SURVEY.md §7 stage 4; BASELINE.json configs[4])."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.cache.allocator import PageAllocator
from butterfly_tpu.cache.paged import (
    PagedKVCache, gather_paged_layer, init_paged_cache, paged_forward,
    write_paged_layer)
from butterfly_tpu.core.config import RuntimeConfig, tiny
from butterfly_tpu.models.common import Model, forward, init_cache


CFG = tiny("llama", dtype="float32", param_dtype="float32")
RT = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8)


def seq_table(cache, batch, pages_per_seq):
    """Identity block tables: slot b owns pages [b*p .. (b+1)*p)."""
    table = np.full(np.asarray(cache.page_table).shape, cache.null_page,
                    np.int32)
    for b in range(batch):
        table[b, :pages_per_seq] = np.arange(
            b * pages_per_seq, (b + 1) * pages_per_seq)
    return cache._replace(page_table=jnp.asarray(table))


def test_paged_forward_matches_contiguous():
    """Prefill + 4 decode steps: logits equal the contiguous-cache path."""
    params = Model(CFG).init(jax.random.PRNGKey(0))
    cache_c = init_cache(CFG, batch=2, max_seq=64)
    cache_p = seq_table(init_paged_cache(CFG, RT), 2, 64 // RT.page_size)

    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, CFG.vocab_size, (2, 9)))
    ref, cache_c = jax.jit(lambda p, t, c: forward(p, CFG, t, c))(
        params, tokens, cache_c)
    out, cache_p = jax.jit(lambda p, t, c: paged_forward(p, CFG, t, c))(
        params, tokens, cache_p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    for step in range(4):
        nxt = jnp.argmax(ref[:, -1, :], axis=-1)[:, None]
        ref, cache_c = jax.jit(lambda p, t, c: forward(p, CFG, t, c))(
            params, nxt, cache_c)
        out, cache_p = jax.jit(
            lambda p, t, c: paged_forward(p, CFG, t, c))(params, nxt, cache_p)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_inactive_slots_frozen():
    """active=False slots keep their length and never corrupt others."""
    params = Model(CFG).init(jax.random.PRNGKey(0))
    cache = seq_table(init_paged_cache(CFG, RT), 2, 8)
    tokens = jnp.asarray(
        np.random.RandomState(1).randint(0, CFG.vocab_size, (2, 5)))
    _, cache = paged_forward(params, CFG, tokens, cache)

    active = jnp.asarray([True, False])
    tok = jnp.asarray([[7], [9]])
    out_a, cache2 = paged_forward(params, CFG, tok, cache, active=active)
    assert int(cache2.lengths[0]) == 6 and int(cache2.lengths[1]) == 5

    # slot 1's pages are untouched by slot 0's step
    p1 = np.asarray(cache.page_table)[1, :1]
    np.testing.assert_array_equal(np.asarray(cache2.k_pages[:, p1]),
                                  np.asarray(cache.k_pages[:, p1]))


def test_write_gather_roundtrip():
    k_pages = jnp.zeros((6, 2, 4, 3))  # [P, Kv, page, H]
    v_pages = jnp.zeros((6, 2, 4, 3))
    table = jnp.asarray([[0, 2], [3, 1]], jnp.int32)  # interleaved pages
    k = jnp.arange(2 * 5 * 2 * 3, dtype=jnp.float32).reshape(2, 5, 2, 3)
    start = jnp.asarray([0, 3], jnp.int32)
    # slot1 writing at start=3 spills onto its second page (page id 1)
    kp, vp, _, _ = write_paged_layer(k_pages, v_pages, table, k, k * 2, start)
    got = gather_paged_layer(kp, table)
    np.testing.assert_allclose(np.asarray(got[0, 0:5]), np.asarray(k[0]))
    np.testing.assert_allclose(np.asarray(got[1, 3:8]), np.asarray(k[1]))


def test_write_gather_roundtrip_int8():
    """Quantized write/gather: dequantized roundtrip within int8 error."""
    from butterfly_tpu.cache.paged import gather_paged_layer_q

    P, Kv, page, H = 6, 2, 4, 8
    k_pages = jnp.zeros((P, Kv, page, H), jnp.int8)
    v_pages = jnp.zeros((P, Kv, page, H), jnp.int8)
    ksp = jnp.zeros((P, Kv * page))
    vsp = jnp.zeros((P, Kv * page))
    table = jnp.asarray([[0, 2], [3, 1]], jnp.int32)
    k = jax.random.normal(jax.random.PRNGKey(0), (2, 5, Kv, H))
    start = jnp.asarray([0, 3], jnp.int32)
    kp, vp, ksp, vsp = write_paged_layer(k_pages, v_pages, table, k, k * 2,
                                         start, None, ksp, vsp)
    codes, scales = gather_paged_layer_q(kp, ksp, table)  # [B,Kv,S,*]
    got = (codes.astype(jnp.float32) *
           scales[..., None]).transpose(0, 2, 1, 3)       # [B,S,Kv,H]
    np.testing.assert_allclose(np.asarray(got[0, 0:5]), np.asarray(k[0]),
                               atol=2e-2)
    np.testing.assert_allclose(np.asarray(got[1, 3:8]), np.asarray(k[1]),
                               atol=2e-2)


def test_paged_forward_int8_close_to_fp():
    """int8 paged serving cache tracks the fp paged path closely and
    EXACTLY matches the contiguous int8 cache's numerics contract
    (scores scaled output-side, probs carry the V scale)."""
    params = Model(CFG).init(jax.random.PRNGKey(0))
    rt_q = RT.replace(kv_quant="int8")
    cache_f = seq_table(init_paged_cache(CFG, RT), 2, 64 // RT.page_size)
    cache_q = seq_table(init_paged_cache(CFG, rt_q), 2, 64 // RT.page_size)
    assert cache_q.quantized and cache_q.k_pages.dtype == jnp.int8

    tokens = jnp.asarray(
        np.random.RandomState(3).randint(0, CFG.vocab_size, (2, 9)))
    ref, cache_f = paged_forward(params, CFG, tokens, cache_f)
    out, cache_q = paged_forward(params, CFG, tokens, cache_q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0.1, atol=0.15)

    for _ in range(3):
        nxt = jnp.argmax(ref[:, -1, :], axis=-1)[:, None]
        ref, cache_f = paged_forward(params, CFG, nxt, cache_f)
        out, cache_q = paged_forward(params, CFG, nxt, cache_q)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0.1, atol=0.15)


def test_allocator_grow_release():
    a = PageAllocator(num_pages=10, page_size=4, max_pages_per_seq=4)
    assert a.grow(0, 9) is not None       # 3 pages
    assert a.free_pages == 7
    assert a.grow(0, 12) == []            # fits in current pages
    assert a.pages_needed(0, 13) == 1
    assert a.grow(1, 16) is not None      # 4 pages
    assert a.free_pages == 3
    assert a.grow(0, 16) is not None      # 1 more page
    assert a.free_pages == 2
    assert a.grow(0, 17) is None          # over max_pages_per_seq
    assert a.grow(2, 9) is None           # needs 3 > 2 free, all-or-nothing
    assert a.free_pages == 2
    assert a.release(1) and a.free_pages == 6
    a.release(0)
    assert a.free_pages == 10


@pytest.mark.parametrize("lengths", [[1, 17, 8], [32, 1, 5]])
def test_allocator_property_accounting(lengths):
    """Σ owned + free == total, and no page owned twice."""
    a = PageAllocator(num_pages=32, page_size=4, max_pages_per_seq=16)
    for slot, ln in enumerate(lengths):
        assert a.grow(slot, ln) is not None
    owned = [p for s in range(len(lengths)) for p in a.pages_of(s)]
    assert len(owned) == len(set(owned))
    assert len(owned) + a.free_pages == 32
    for s in range(len(lengths)):
        a.release(s)
    assert a.free_pages == 32


def test_near_capacity_prompt_bucket_padding_no_corruption():
    """A prompt whose prefill bucket pads past the block-table capacity
    must not corrupt the slot's own pages.

    max_seq=96 (not a power of two), page=8 -> 12-entry rows. A 90-token
    prompt owns all 12 pages; its bucket pads to 128 positions, so the
    writer sees positions 96..127 with no table entry. write_paged_layer
    routes them to the null page explicitly; this pins greedy parity
    with the contiguous engine so that contract can never regress."""
    import numpy as np
    from butterfly_tpu.core.config import RuntimeConfig, tiny
    from butterfly_tpu.engine import InferenceEngine, SamplingParams
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.sched.scheduler import Scheduler

    cfg = tiny("llama", dtype="float32", param_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    prompt = [int(t) for t in
              np.random.RandomState(0).randint(0, cfg.vocab_size, 90)]

    rt = RuntimeConfig(max_batch_size=2, max_seq_len=96, page_size=8,
                       prefill_chunk=512)  # whole-prompt bucket: 128 > 96
    sched = Scheduler(ServingEngine(model, params, rt, use_kernels=False))
    req = sched.submit(prompt, max_new_tokens=5)
    sched.run_until_done()

    ref = InferenceEngine(model, params).generate(
        [prompt], SamplingParams(max_new_tokens=5))
    want = ref.tokens[0, :int(ref.lengths[0])].tolist()
    assert req.output == want


# -- the read-only pool reaches the kernel whole (ISSUE 31) -----------------

def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
@pytest.mark.parametrize("forward", ["packed", "window"])
def test_windowed_forward_hands_the_kernel_the_whole_pool(forward, kv_quant):
    """With the window on and kernels on, the paged kernel's call takes
    the cache's own k_pages / v_pages, every layer of them, and nothing
    in the program cuts one layer's [P, Kv, page, H] out of the pool: a
    custom call's operand is a buffer, so on the chip such a slice was
    a copy of the layer in every layer of every step. Read off the
    jaxpr, so it holds on any backend."""
    from butterfly_tpu.cache.paged import (
        init_kv_window, paged_forward_packed, paged_forward_window)

    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8,
                       kv_quant=kv_quant)
    params = Model(CFG).init(jax.random.PRNGKey(0))
    cache = seq_table(init_paged_cache(CFG, rt), 2, 64 // rt.page_size)
    window = init_kv_window(cache, 8)
    win_len = jnp.zeros((2,), jnp.int32)
    active = jnp.ones((2,), bool)
    tokens = jnp.asarray([3, 5], jnp.int32)
    if forward == "packed":
        def fn(params, cache, window):
            return paged_forward_packed(
                params, CFG, tokens, cache, jnp.zeros((1, 4), jnp.int32),
                jnp.asarray([1]), jnp.asarray([0]), active, window, win_len,
                use_kernel=True)[:2]
    else:
        def fn(params, cache, window):
            return paged_forward_window(params, CFG, tokens[:, None], cache,
                                        window, win_len, active,
                                        use_kernel=True)
    eqns = list(_eqns(jax.make_jaxpr(fn)(params, cache, window).jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert calls
    pool = cache.k_pages.shape
    for e in calls:
        shapes = [v.aval.shape for v in e.invars]
        assert shapes.count(pool) == 2, shapes
    cut = [e for e in eqns if e.primitive.name in ("dynamic_slice", "gather")
           and e.outvars[0].aval.shape[-4:] == pool[1:]]
    assert not cut, cut


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_gather_indexes_layer_and_page_at_once(quant):
    """gather_paged_layer(_q) over the whole pool with a traced layer is
    the gather over that layer's slice, to the bit."""
    from butterfly_tpu.cache.paged import gather_paged_layer_q
    L, P, Kv, page, H = 3, 7, 2, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    pool = jax.random.normal(ks[0], (L, P, Kv, page, H))
    scales = jax.random.uniform(ks[1], (L, P, Kv * page))
    table = jnp.asarray([[0, 3, 6], [5, 1, 6]], jnp.int32)
    for layer in range(L):
        ly = jnp.int32(layer)
        if quant:
            codes = (pool * 20).astype(jnp.int8)
            got = jax.jit(gather_paged_layer_q)(codes, scales, table, ly)
            want = gather_paged_layer_q(codes[layer], scales[layer], table)
        else:
            got = (jax.jit(gather_paged_layer)(pool, table, ly),)
            want = (gather_paged_layer(pool[layer], table),)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
