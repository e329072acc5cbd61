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
    pool, leaf = cache.k_pages.shape, window.k.shape
    # the writer of the staged rows and the reader: both take the
    # window's leaves whole, the reader the pool's too
    reads = [e for e in calls if pool in [v.aval.shape for v in e.invars]]
    assert reads and len(calls) > len(reads)
    for e in calls:
        shapes = [v.aval.shape for v in e.invars]
        assert shapes.count(leaf) == 2, shapes
        assert shapes.count(pool) == (2 if e in reads else 0), shapes
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


# -- the window flush writes what was staged, in place (ISSUE 35) -----------

def _flush_by_scatter(cache, window, win_len):
    """The flush as it was until PR 35, kept as the oracle: ONE scatter
    per pool tensor over ALL S x W window entries, the entries at or
    past win_len (and the positions past the table) routed to the null
    page. On the chip it copied and relaid the whole pool to land a few
    hundred rows; its result, on every page but the null page, is what
    the flush must still leave."""
    L, Pp, Kv, page, H = cache.k_pages.shape
    S = win_len.shape[0]
    W = window.width
    mp = cache.page_table.shape[1]
    pos = cache.lengths[:, None] + jnp.arange(W)[None, :]     # [S, W]
    valid = jnp.arange(W)[None, :] < win_len[:, None]
    page_idx = jnp.take_along_axis(cache.page_table,
                                   jnp.clip(pos // page, 0, mp - 1), axis=1)
    page_idx = jnp.where(valid & (pos < mp * page), page_idx, Pp - 1)
    flat_pages = page_idx.reshape(-1)                          # [S*W]
    flat_off = (pos % page).reshape(-1)
    kv_vals = window.k.transpose(1, 3, 0, 2, 4).reshape(S * W, L, Kv, H)
    vv_vals = window.v.transpose(1, 3, 0, 2, 4).reshape(S * W, L, Kv, H)
    k_pages = cache.k_pages.at[:, flat_pages, :, flat_off].set(kv_vals)
    v_pages = cache.v_pages.at[:, flat_pages, :, flat_off].set(vv_vals)
    ksp, vsp = cache.k_scale_pages, cache.v_scale_pages
    if window.quantized:
        cols = jnp.arange(Kv)[None, :] * page + flat_off[:, None]
        from butterfly_tpu.cache.paged import scales_by_head
        ks_vals, vs_vals = (
            scales_by_head(a, Kv).transpose(0, 1, 3, 2).reshape(L, S * W, Kv)
            for a in (window.k_scale, window.v_scale))
        ksp = ksp.at[:, flat_pages[:, None], cols].set(ks_vals)
        vsp = vsp.at[:, flat_pages[:, None], cols].set(vs_vals)
    cache = cache._replace(k_pages=k_pages, v_pages=v_pages,
                           k_scale_pages=ksp, v_scale_pages=vsp,
                           lengths=cache.lengths + win_len)
    return cache, jnp.zeros_like(win_len), win_len.sum()


def _staged_case(quant, lengths, W=8, L=3, Kv=2, page=4, H=8, mp=3,
                 seed=0):
    """A pool and a window full of random bytes (stale rows past
    win_len included), each slot on distinct pages in shuffled order."""
    from butterfly_tpu.cache.paged import KVWindow, scales_by_step
    S = len(lengths)
    P = S * mp + 2                              # one page spare, one null
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    if quant:
        rnd = lambda k, sh: jax.random.randint(k, sh, -127, 128, jnp.int8)
        sc = lambda k, sh: jax.random.uniform(k, sh, jnp.float32)
    else:
        rnd = lambda k, sh: jax.random.normal(k, sh, jnp.float32)
        sc = lambda k, sh: None
    by_step = lambda a: None if a is None else scales_by_step(a)
    table = np.random.RandomState(seed).permutation(P - 1)[:S * mp]
    cache = PagedKVCache(
        k_pages=rnd(ks[0], (L, P, Kv, page, H)),
        v_pages=rnd(ks[1], (L, P, Kv, page, H)),
        page_table=jnp.asarray(table.reshape(S, mp), jnp.int32),
        lengths=jnp.asarray(lengths, jnp.int32),
        k_scale_pages=sc(ks[2], (L, P, Kv * page)),
        v_scale_pages=sc(ks[3], (L, P, Kv * page)))
    window = KVWindow(
        k=rnd(ks[4], (L, S, Kv, W, H)), v=rnd(ks[5], (L, S, Kv, W, H)),
        k_scale=by_step(sc(ks[6], (L, S, Kv, W))),
        v_scale=by_step(sc(ks[7], (L, S, Kv, W))))
    return cache, window


def _pools(cache):
    return [np.asarray(p) for p in (cache.k_pages, cache.v_pages,
                                    cache.k_scale_pages, cache.v_scale_pages)
            if p is not None]


#: (flushed lengths, staged entries) a slot; pages of 4, a window of 8,
#: a table of 3 pages (12 positions)
FLUSH_CASES = {
    "nothing": ([0, 5, 3, 7], [0, 0, 0, 0]),
    "one": ([0, 5, 3, 7], [1, 0, 1, 1]),
    "straddling": ([2, 3, 7, 1], [3, 5, 2, 8]),
    "whole-window": ([0, 1, 4, 3], [8, 8, 8, 8]),
    # slot 0 lands 8..11 of 8..15, slot 1 10..11 of 10..13, slot 2
    # none (it is full), slot 3 5..11 of 5..12: the rest is dropped
    "past-the-table": ([8, 10, 12, 5], [8, 4, 3, 8]),
}


@pytest.mark.parametrize("case", list(FLUSH_CASES))
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_flush_equals_the_scatter_it_replaced(quant, case):
    """To the byte on every page but the null page, codes and scales;
    the null page keeps what it held (an entry at or past win_len goes
    nowhere); lengths advance, win_len comes back zeroed, and the count
    is the entries staged."""
    from butterfly_tpu.cache.paged import flush_paged_window
    lengths, staged = FLUSH_CASES[case]
    cache, window = _staged_case(quant, lengths)
    win_len = jnp.asarray(staged, jnp.int32)
    want, _, _ = jax.jit(_flush_by_scatter)(cache, window, win_len)
    got, zeroed, count = jax.jit(flush_paged_window)(cache, window, win_len)
    before = _pools(cache)
    for new, old, was in zip(_pools(got), _pools(want), before):
        np.testing.assert_array_equal(new[:, :-1], old[:, :-1])
        np.testing.assert_array_equal(new[:, -1], was[:, -1])
    np.testing.assert_array_equal(np.asarray(got.lengths),
                                  np.asarray(lengths) + np.asarray(staged))
    np.testing.assert_array_equal(np.asarray(got.page_table),
                                  np.asarray(cache.page_table))
    assert not np.asarray(zeroed).any() and zeroed.dtype == win_len.dtype
    assert int(count) == sum(staged)
    # the rows that changed are the rows that were staged and land
    landed = sum(min(n, max(0, cache.max_seq - ln))
                 for ln, n in zip(lengths, staged))
    rows = (_pools(got)[0] != before[0]).any(axis=(0, 2, 4)).sum()
    assert rows == landed <= sum(staged), (rows, landed)


@pytest.mark.parametrize("W", [1, 2, 16])
def test_flush_of_a_window_narrower_or_wider_than_a_page(W):
    """One step a tick keeps a window of 1 or 2 entries beside pages of
    4; a long block one of several pages."""
    from butterfly_tpu.cache.paged import flush_paged_window
    cache, window = _staged_case(True, [3, 0, 6, 11], W=W, mp=8)
    win_len = jnp.asarray([W, 0, max(1, W - 1), W], jnp.int32)
    want, _, _ = jax.jit(_flush_by_scatter)(cache, window, win_len)
    got, _, count = jax.jit(flush_paged_window)(cache, window, win_len)
    for new, old in zip(_pools(got), _pools(want)):
        np.testing.assert_array_equal(new[:, :-1], old[:, :-1])
    assert int(count) == int(win_len.sum())


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_flush_holds_no_scatter_into_the_pool_and_no_transpose_of_it(quant):
    """Read off the jaxpr, so it holds on any backend: nothing scatters
    into a pool (one scatter over all S x W entries made XLA move the
    layers and the in-page offset beside the page dim and back: two
    copies and two relayouts of the whole pool on the chip), nothing
    transposes or reshapes one, and the only operations whose result
    has a pool's shape are the loop that carries it and the
    dynamic-update-slice that writes one page of it in place."""
    from butterfly_tpu.cache.paged import flush_paged_window
    cache, window = _staged_case(quant, [2, 3, 7, 1])
    win_len = jnp.asarray([3, 5, 2, 8], jnp.int32)
    eqns = list(_eqns(jax.make_jaxpr(flush_paged_window)(
        cache, window, win_len).jaxpr))
    pools = {p.shape for p in (cache.k_pages, cache.k_scale_pages)
             if p is not None}
    assert window.k.shape not in pools
    makes = {e.primitive.name for e in eqns
             if any(v.aval.shape in pools for v in e.outvars)}
    assert makes == {"while", "dynamic_update_slice"}, makes
    takes = {e.primitive.name for e in eqns
             if any(getattr(v, "aval", None) is not None
                    and v.aval.shape in pools for v in e.invars)}
    assert takes <= {"while", "dynamic_update_slice", "dynamic_slice"}, takes
    # and nothing is as large as the window's S x W entries either: the
    # work follows what was staged
    S, W = win_len.shape[0], window.width
    entries = window.k.size // (S * W)
    big = [e.primitive.name for e in eqns if e.primitive.name != "while"
           and any(v.aval.size >= S * W * entries and
                   v.aval.shape not in pools for v in e.outvars)]
    assert not big, big


def test_flush_under_a_mesh_equals_the_unsharded_flush():
    """Four CPU devices on the `tensor` axis, the pools and the window
    sharded over their KV heads as the serving engine lays them out:
    the same bytes as on one device, and the pools keep their layout."""
    from butterfly_tpu.cache.paged import flush_paged_window
    from butterfly_tpu.core.config import MeshConfig
    from butterfly_tpu.core.mesh import make_mesh
    from butterfly_tpu.parallel.partition import (
        kv_window_specs, paged_cache_specs, to_shardings)
    mesh = make_mesh(MeshConfig(tensor=4), jax.devices()[:4])
    cfg = tiny("llama", num_heads=8, num_kv_heads=4, head_dim=8)
    for quant in (False, True):
        cache, window = _staged_case(quant, [2, 3, 7, 1], Kv=4)
        win_len = jnp.asarray([3, 5, 2, 8], jnp.int32)
        want, _, n = jax.jit(flush_paged_window)(cache, window, win_len)
        csh = to_shardings(paged_cache_specs(cfg, mesh, 4, quant=quant), mesh)
        wsh = to_shardings(kv_window_specs(cfg, mesh, 4, quant=quant), mesh)
        with mesh:
            got, zeroed, m = jax.jit(flush_paged_window,
                                     donate_argnums=(0, 2))(
                jax.device_put(cache, csh), jax.device_put(window, wsh),
                win_len)
        assert got.k_pages.sharding.is_equivalent_to(csh.k_pages, 5)
        for new, old in zip(_pools(got), _pools(want)):
            np.testing.assert_array_equal(new, old)
        np.testing.assert_array_equal(np.asarray(got.lengths),
                                      np.asarray(want.lengths))
        assert int(m) == int(n) and not np.asarray(zeroed).any()


# -- the token-major pool of a model with an indexer (ISSUE 37) ---------------
#
# cache/paged.py pool_row: [L, P, 1, page, Kv*H], a token's KV heads
# contiguous. The code that is indifferent to the layout (writes, staging,
# the flush, the table gathers, the window's insert and permutation) must
# leave in it the values it leaves in a head-major pool.

def _token_major(a):
    """[.., N, Kv, R, H] -> [.., N, 1, R, Kv*H]: the same values with a
    token's KV heads contiguous (a pool, a window, or one layer of
    either)."""
    *lead, Kv, R, H = a.shape
    return jnp.swapaxes(a, -3, -2).reshape(*lead, 1, R, Kv * H)


def _both_layouts(lengths, **kw):
    cache, window = _staged_case(False, lengths, **kw)
    return (cache, window), (
        cache._replace(k_pages=_token_major(cache.k_pages),
                       v_pages=_token_major(cache.v_pages)),
        window._replace(k=_token_major(window.k), v=_token_major(window.v)))


@pytest.mark.parametrize("case", list(FLUSH_CASES))
def test_flush_of_a_token_major_pool_lands_the_head_major_values(case):
    from butterfly_tpu.cache.paged import flush_paged_window
    lengths, staged = FLUSH_CASES[case]
    head, token = _both_layouts(lengths)
    win_len = jnp.asarray(staged, jnp.int32)
    want, _, n = jax.jit(flush_paged_window)(*head, win_len)
    got, zeroed, m = jax.jit(flush_paged_window)(*token, win_len)
    assert got.k_pages.shape == token[0].k_pages.shape
    for new, old in ((got.k_pages, want.k_pages), (got.v_pages, want.v_pages)):
        np.testing.assert_array_equal(np.asarray(new),
                                      np.asarray(_token_major(old)))
    np.testing.assert_array_equal(np.asarray(got.lengths),
                                  np.asarray(want.lengths))
    assert int(m) == int(n) == sum(staged) and not np.asarray(zeroed).any()


def test_flush_of_a_token_major_pool_copies_and_relays_no_pool():
    """test_flush_holds_no_scatter_into_the_pool_and_no_transpose_of_it,
    for the layout whose page is [1, page, Kv*H]."""
    from butterfly_tpu.cache.paged import flush_paged_window
    _, (cache, window) = _both_layouts([2, 3, 7, 1])
    win_len = jnp.asarray([3, 5, 2, 8], jnp.int32)
    eqns = list(_eqns(jax.make_jaxpr(flush_paged_window)(
        cache, window, win_len).jaxpr))
    pool = cache.k_pages.shape
    assert pool[2] == 1 and window.k.shape != pool
    makes = {e.primitive.name for e in eqns
             if any(v.aval.shape == pool for v in e.outvars)}
    assert makes == {"while", "dynamic_update_slice"}, makes
    takes = {e.primitive.name for e in eqns
             if any(getattr(v, "aval", None) is not None
                    and v.aval.shape == pool for v in e.invars)}
    assert takes <= {"while", "dynamic_update_slice", "dynamic_slice"}, takes


def _write(pool, table, k, v, start):
    kp, vp, _, _ = write_paged_layer(pool.k_pages[1], pool.v_pages[1], table,
                                     k, v, start)
    return kp, vp


def _stage(window, k, v, wlen):
    from butterfly_tpu.cache.paged import stage_window_layer, window_runs
    slots, T = jnp.asarray([2, 0, 3]), k.shape[1]
    new = stage_window_layer(
        window, 1, k, v, None, jnp.repeat(slots, T),
        (wlen[:, None] + jnp.arange(T)[None, :]).reshape(-1),
        (window_runs(slots, wlen, T, window.width),), (T,), False)
    return new.k[1], new.v[1]


INDIFFERENT = ["write", "stage", "gather", "insert", "permute"]


@pytest.mark.parametrize("what", INDIFFERENT)
def test_code_indifferent_to_the_layout_leaves_the_same_values(what):
    """Each function on a head-major pool or window and on the
    token-major one of the same values, given the same fresh rows
    [B, T, Kv, H]: the results are each other's relayout, to the bit."""
    from butterfly_tpu.cache.paged import (
        insert_window_view, permute_window_tail)
    (hc, hw), (tc, tw) = _both_layouts([2, 3, 7, 1], Kv=4)
    Kv, H = hc.k_pages.shape[2], hc.k_pages.shape[4]
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    k = jax.random.normal(ks[0], (3, 2, Kv, H))
    v = jax.random.normal(ks[1], (3, 2, Kv, H))
    if what == "write":
        start, table = jnp.asarray([2, 3, 7]), hc.page_table[:3]
        want = _write(hc, table, k, v, start)
        got = _write(tc, table, k, v, start)
    elif what == "stage":
        wlen = jnp.asarray([1, 6, 7])       # row 2's second entry drops
        want, got = _stage(hw, k, v, wlen), _stage(tw, k, v, wlen)
    elif what == "gather":
        want = [gather_paged_layer(hc.k_pages, hc.page_table, 2)]
        got = [gather_paged_layer(tc.k_pages, tc.page_table, 2)]
        assert got[0].shape == (4, 12, 1, Kv * H)
        want = [want[0].reshape(got[0].shape)]
    elif what == "insert":
        view = gather_paged_layer(hc.k_pages, hc.page_table, 0)
        want = [insert_window_view(view, hw.k[0], hc.lengths)]
        got = [insert_window_view(view.reshape(4, 12, 1, Kv * H), tw.k[0],
                                  hc.lengths)]
        want = [want[0].reshape(got[0].shape)]
    else:
        perm = jnp.asarray([[2, 0, 1]] * 4, jnp.int32)
        wlen = jnp.asarray([1, 4, 0, 5], jnp.int32)
        want = permute_window_tail(hw, wlen, perm)[:2]
        got = permute_window_tail(tw, wlen, perm)[:2]
    for new, old in zip(got, want):
        if what in ("write", "stage", "permute"):
            old = _token_major(old)
        assert new.shape == old.shape
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
