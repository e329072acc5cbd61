"""The cache by kind (cache/paged.py ring_pages): a sliding layer's rows
in a RING of pages a slot owns, beside the full layers' pages under the
page table and the free list. Toys whose window (8) binds, pages of 4, so
that a stream of 40 wraps its ring: the ring's invariants (a cell holds
the last position written to it, a chunk that straddles the wrap, a row
never read after it is overwritten), the packed step against the plain
reference (the family's: tests/test_trinity.py reads the same through
its own toy) and against a cache of ONE kind, the kernel's walk of a
ring, the scheduler's release, preemption and recompute, pages counted by
kind, and what must NOT change: a model without sliding layers, and one
whose table is no longer than its ring, build today's cache to the leaf."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.cache import paged
from butterfly_tpu.cache.paged import (
    KVWindow, flush_paged_window, gather_paged_layer, init_kv_window,
    init_paged_cache, pool_kinds, ring_pages, ring_positions, staged_most)
from butterfly_tpu.core.config import RuntimeConfig, tiny
from butterfly_tpu.engine.serving import ServingEngine
from butterfly_tpu.models.common import Model, layer_runs
from butterfly_tpu.ops.paged_attention import paged_attention
from butterfly_tpu.sched.scheduler import Scheduler
from servebench.references import smallthinker_f32
from packed_driver import err, leaf_of, scripted_run

CFG = tiny("smallthinker", dtype="float32", param_dtype="float32")
PAGE, WINDOW = 4, CFG.sliding_window
RT = RuntimeConfig(max_batch_size=3, max_seq_len=64, page_size=PAGE)


def ring_for(width: int) -> int:
    """R of a ring that serves a window of `width` staged rows."""
    return -(-(WINDOW + width) // PAGE) + 1


# -- which caches are by kind, and what the others are ------------------------

SERVE = dict(max_batch_size=4, max_seq_len=256, page_size=4,
             decode_steps_per_tick=2, prefill_inline_budget=4)


def test_a_ring_holds_the_window_what_is_staged_and_a_page_more():
    rt = RuntimeConfig(**SERVE)
    assert staged_most(rt) == 2 * 2 * 4
    assert ring_pages(CFG, rt) == (WINDOW + 16) // PAGE + 1 == 7
    big = tiny("smallthinker", sliding_window=4096)
    real = RuntimeConfig(max_batch_size=24, max_seq_len=16384, page_size=16,
                         decode_steps_per_tick=4)
    assert ring_pages(big, real) == (4096 + 256) // 16 + 1 == 273


@pytest.mark.parametrize("why, cfg, over", [
    ("no layer slides", tiny("llama"), {}),
    ("the table is no longer than the ring", CFG, {"max_seq_len": 24}),
    ("max_seq under the window: smallthinker-21b-a3b's cell",
     tiny("smallthinker", sliding_window=4096), {"max_seq_len": 2048}),
    ("no write-combined window", CFG, {"kv_write_combine": False}),
    ("an int8 pool", CFG, {"kv_quant": "int8"}),
    ("speculation", CFG, {"speculative_gamma": 2}),
])
def test_a_cache_of_one_kind_is_today_s_to_the_leaf(why, cfg, over):
    rt = RuntimeConfig(**{**SERVE, **over})
    assert ring_pages(cfg, rt) == 0, why
    cache = init_paged_cache(cfg, rt, ring=ring_pages(cfg, rt))
    assert not cache.by_kind and pool_kinds(cache) is None
    assert all(getattr(cache, n) is None for n in (
        "k_ring", "v_ring", "ring_table", "full_layers", "ring_layers"))
    L = cfg.num_layers
    assert cache.k_pages.shape[0] == L == cache.num_layers
    assert cache.k_pages.shape[1] == 4 * -(-rt.max_seq_len // 4) + 1
    # what rides a block's programs is what it was: five places, no more
    assert len(paged.pool_leaves(cache, absent=True)) == 5


def test_a_mesh_keeps_one_kind():
    assert ring_pages(CFG, RuntimeConfig(**SERVE), meshed=True) == 0
    assert ring_pages(CFG, RuntimeConfig(**SERVE)) == 7


def test_a_cache_by_kind_holds_each_kind_s_layers_apart():
    rt = RuntimeConfig(**SERVE)
    cache = init_paged_cache(CFG, rt, ring=7)
    # the toy's pattern [0, 1, 1, 1]: one full layer, three that slide
    assert cache.k_pages.shape == (1, 4 * 64 + 1, 2, 4, 16)
    assert cache.k_ring.shape == cache.v_ring.shape == (3, 4 * 7 + 1, 2, 4, 16)
    assert cache.full_layers.tolist() == [0]
    assert cache.ring_layers.tolist() == [1, 2, 3]
    # a slot's ring is its own, always: no free list hands it out
    assert cache.ring_table.tolist() == [list(range(s * 7, s * 7 + 7))
                                         for s in range(4)]
    assert cache.num_layers == 4
    assert init_kv_window(cache, 16).k.shape == (4, 4, 2, 16, 16)
    kinds = pool_kinds(cache)
    assert kinds["slide"] == {"layers": 3, "pages": 28, "ring_pages": 7,
                              "bytes": 2 * 3 * 29 * 2 * 4 * 16 * 4}
    assert kinds["full"]["layers"] == 1 and kinds["full"]["pages"] == 256


def test_layer_runs_end_where_the_kinds_meet():
    assert layer_runs(CFG) == [("attention", 0, 4, 0)]
    assert layer_runs(CFG, by_window=True) == [
        ("attention", 0, 1, 0, 0, False), ("attention", 1, 3, 1, 0, True)]
    tr = tiny("trinity")        # S S S F S behind a leading dense layer
    assert layer_runs(tr, by_window=True) == [
        ("attention", 0, 1, 0, 0, True), ("attention", 1, 2, 1, 1, True),
        ("attention", 3, 1, 3, 0, False), ("attention", 4, 1, 4, 3, True)]
    # without the cache's kinds: the dense layer, then the experts'
    assert layer_runs(tr) == [("attention", 0, 1, 0), ("attention", 1, 4, 1)]


# -- the ring's cells ----------------------------------------------------------

@pytest.mark.parametrize("written", [0, 1, 5, 28, 29, 61, 200])
def test_a_cell_holds_the_last_position_written_to_it(written):
    cells = 28
    got = np.asarray(ring_positions(jnp.asarray([written]), cells))[0]
    want = np.full(cells, -1)
    for p in range(written):
        want[p % cells] = p
    assert (np.where(got < 0, -1, got) == want).all()


@pytest.mark.parametrize("chunks", [
    (4,) * 12,              # whole pages
    (6, 6, 5, 1, 1, 7, 3),  # runs that start and end inside pages
    (8, 8, 8, 8, 8, 8),     # the window's whole width, across the wrap
    (7, 8, 8, 3, 8, 8, 8),  # a chunk that STRADDLES the wrap (cell 32 -> 0)
])
def test_the_flush_writes_a_row_to_the_pool_of_its_layer_s_kind(chunks):
    """Slot 1 takes a stream in chunks, each staged and flushed: a row's
    values are its position + 100 x its layer. Afterwards the full
    layer's pages hold every position and each sliding layer's ring the
    last 32 (R = 8 pages of 4), every cell the position ring_positions
    says."""
    W, R = 8, 8
    rt = RuntimeConfig(max_batch_size=3, max_seq_len=64, page_size=PAGE)
    cache = init_paged_cache(CFG, rt, ring=R)
    S, mp = cache.page_table.shape
    cache = cache._replace(page_table=jnp.arange(
        S * mp, dtype=jnp.int32).reshape(S, mp))
    flush = jax.jit(flush_paged_window)
    at = 0
    for n in chunks:
        vals = np.zeros(init_kv_window(cache, W).k.shape, np.float32)
        for l in range(4):
            vals[l, 1, :, :n, :] = (at + np.arange(n) + 100 * l)[None, :, None]
        win = KVWindow(k=jnp.asarray(vals), v=jnp.asarray(-vals))
        wlen = jnp.zeros((S,), jnp.int32).at[1].set(n)
        cache, wlen, flushed = flush(cache, win, wlen)
        assert int(flushed) == n and int(wlen.sum()) == 0
        at += n
    assert cache.lengths.tolist() == [0, at, 0]
    full = np.asarray(gather_paged_layer(cache.k_pages, cache.page_table, 0))
    assert (full[1, :at, :, 0] == np.arange(at)[:, None]).all()
    assert (full[0] == 0).all() and (full[2] == 0).all()
    where = np.asarray(ring_positions(jnp.asarray([at]), R * PAGE))[0]
    for i, layer in enumerate((1, 2, 3)):
        ring = np.asarray(gather_paged_layer(cache.k_ring, cache.ring_table, i))
        vring = np.asarray(gather_paged_layer(cache.v_ring, cache.ring_table,
                                              i))
        held = where >= 0
        assert (ring[1, held, :, 0] == (where[held] + 100 * layer)[:, None]).all()
        assert (vring[1, held, 0, 0] == -(where[held] + 100 * layer)).all()
        # a ring is its slot's own: the neighbours' cells were not written
        assert (ring[0] == 0).all() and (ring[2] == 0).all()
        # the window's rows are all there: no query of the stream reaches
        # further back, and nothing it reaches was overwritten
        assert set(range(max(0, at - WINDOW - W), at)) <= set(where[held])


# -- the packed step by kind ---------------------------------------------------

@pytest.fixture(scope="module")
def params():
    return Model(CFG).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(5).randint(1, CFG.vocab_size, (3, 40))


def reference(params, tokens):
    file = dict(
        rms_norm_eps=CFG.norm_eps, rope_theta=CFG.rope_theta,
        num_hidden_layers=CFG.num_layers,
        moe_num_active_primary_experts=CFG.num_experts_per_tok,
        sliding_window_size=CFG.sliding_window,
        sliding_window_layout=list(CFG.sliding_window_layout),
        rope_layout=list(CFG.rope_layout))
    return np.stack([np.asarray(smallthinker_f32.logits(
        t, leaf_of(params), file)) for t in tokens])


@pytest.fixture(scope="module")
def want(params, tokens):
    return reference(params, tokens)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel"])
def test_the_packed_step_over_rings_is_the_reference(params, tokens, want,
                                                    use_kernel):
    """Chunks of 6 and decode rows to position 30, a window of 8: every
    sliding layer's ring (8 pages of 4) wraps, a slot is handed to a
    second stream, and every row the head read is the reference's."""
    out, drv, _ = scripted_run(params, tokens, CFG, use_kernel=use_kernel,
                               ring=ring_for(18))
    assert drv.cache.by_kind and len(out) > 30
    assert max(p for _, p, _ in out) >= 3 * WINDOW
    # float32 on both sides on the CPU: a row that read an overwritten
    # cell, or missed one, reads a thousand times this
    assert max(err(row, want[s, p]) for s, p, row in out) < 2e-5
    # the counts: what the sliding layers' decode rows read against what
    # they would have read with no window (three sliding layers)
    load = np.stack([l for l in drv.loads])
    assert load.shape[1] == 5 and (load[:, 3] <= load[:, 4]).all()
    assert load[-1, 3] < load[-1, 4] and load[-1, 3] % 3 == 0


def test_a_cache_of_one_kind_serves_the_same_rows(params, tokens):
    by_kind, _, _ = scripted_run(params, tokens, CFG, ring=ring_for(18))
    one, drv, _ = scripted_run(params, tokens, CFG)
    assert not drv.cache.by_kind
    for (s, p, a), (s2, p2, b) in zip(by_kind, one):
        assert (s, p) == (s2, p2)
        assert float(np.max(np.abs(a - b))) < 2e-5


@pytest.mark.parametrize("total, staged", [(9, 0), (30, 3), (33, 1), (61, 7)])
def test_the_kernel_walks_a_ring_as_the_mask_reads_it(total, staged):
    """paged_attention over a ring of 8 pages of 4 (interpreted) against
    softmax attention over the last `window` positions, the stream's
    `total` positions written in ring order and `staged` of them still
    in the write-combined window."""
    R, Kv, H, Nq, sw = 8, 2, 16, 4, 8
    rng = np.random.RandomState(total)
    k = rng.randn(total, Kv, H).astype(np.float32)
    v = rng.randn(total, Kv, H).astype(np.float32)
    q = rng.randn(2, Nq, H).astype(np.float32)
    flushed = total - staged
    pool_k = np.zeros((2, 2 * R + 1, Kv, PAGE, H), np.float32)
    pool_v = np.zeros_like(pool_k)
    table = np.arange(2 * R, dtype=np.int32).reshape(2, R)
    for p in range(flushed):        # slot 1's ring, layer 1 of the pool
        pg = table[1, (p // PAGE) % R]
        pool_k[1, pg, :, p % PAGE], pool_v[1, pg, :, p % PAGE] = k[p], v[p]
    W = 8
    win_k = np.zeros((3, 2, Kv, W, H), np.float32)
    win_v = np.zeros_like(win_k)
    win_k[2, 1, :, :staged] = k[flushed:].transpose(1, 0, 2)
    win_v[2, 1, :, :staged] = v[flushed:].transpose(1, 0, 2)
    got = paged_attention(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v), 1,
        jnp.asarray(table), jnp.asarray([0, flushed], jnp.int32),
        win_k=jnp.asarray(win_k), win_v=jnp.asarray(win_v),
        win_count=jnp.asarray([0, staged], jnp.int32), sliding_window=sw,
        win_layer=2, ring=True)
    lo = max(0, total - sw)
    kk = np.repeat(k[lo:], Nq // Kv, axis=1)
    vv = np.repeat(v[lo:], Nq // Kv, axis=1)
    sc = np.einsum("nh,snh->ns", q[1], kk) / np.sqrt(H)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    assert np.allclose(np.asarray(got[1]), np.einsum("ns,snh->nh", pr, vv),
                       atol=2e-5)


# -- through the engine and the scheduler --------------------------------------

def engine(params, **over):
    rt = RuntimeConfig(**{**SERVE, **over})
    return ServingEngine(Model(CFG), params, rt, use_kernels=False)


def serve(params, prompts, new=24, **over):
    sched = Scheduler(engine(params, **over))
    reqs = [sched.submit(p, max_new_tokens=new) for p in prompts]
    sched.run_until_done()
    return sched, [r.output for r in reqs]


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(11)
    return [rng.randint(1, CFG.vocab_size, n).tolist()
            for n in (37, 9, 50, 21, 30)]


@pytest.fixture(scope="module")
def served(params, prompts):
    return serve(params, prompts)


def test_the_scheduler_serves_over_rings_what_one_table_serves(
        params, prompts, served, want):
    sched, outs = served
    assert sched.engine.cache.by_kind
    # a table of 64 pages against a ring of 7: by kind. The same requests
    # under ONE table (the window off leaves the cache of one kind): the
    # same greedy tokens
    one, same = serve(params, prompts, kv_write_combine=False)
    assert not one.engine.cache.by_kind
    assert outs == same
    # five requests through four slots: one slot was released and handed
    # on, its ring with it, and nothing of the first tenant was read
    assert all(len(o) == 24 for o in outs)


def test_pages_are_counted_by_kind_in_the_tick_record(served):
    sched, _ = served
    ticks = sched.ticklog.dump()["ticks"]
    assert all(t["kv_pages_slide"] is not None for t in ticks)
    busy = [t for t in ticks if t["batch"]]
    # a slot with a request holds its ring's 7 pages, whatever it wrote
    assert {t["kv_pages_slide"] % 7 for t in busy} == {0}
    assert max(t["kv_pages_slide"] for t in busy) == 4 * 7
    # the full kind's count is the free list's: the pool less pages_free
    assert all(t["kv_pages_full"] == 4 * 64 - t["pages_free"] for t in ticks)
    drained = [t for t in ticks if t["swa_rows_whole"]]
    assert drained and all(t["swa_rows_read"] <= t["swa_rows_whole"]
                           for t in drained)
    # contexts of 60-odd under a window of 8: most rows are left unread
    assert sum(t["swa_rows_read"] for t in drained) \
        < 0.5 * sum(t["swa_rows_whole"] for t in drained)
    # a stream of 74 passes its ring of 28 rows twice
    assert sum(t["ring_wraps"] for t in ticks) >= 4
    m = sched.metrics()
    assert m["preemptions_total"] == 0


def test_preemption_recomputes_into_the_slot_s_ring(params, prompts, served):
    """A pool of 20 pages for the full layer: four streams of 60 do not
    fit (15 pages each), the youngest is preempted and recomputed, and
    every answer is what the unpressed run gave. A ring has nothing to
    give back but itself: preemption learns of no page but the table's."""
    _, calm = served
    sched, outs = serve(params, prompts, num_pages=20)
    assert sched.engine.cache.by_kind
    assert sched.metrics()["preemptions_total"] >= 1
    assert outs == calm


def test_prefix_caching_beside_a_ring_is_refused_by_name(params):
    with pytest.raises(NotImplementedError, match="prefix caching.*ring of 7"):
        engine(params, prefix_caching=True)
    # at a max_seq the ring would not shorten it is what it always was
    assert not engine(params, prefix_caching=True,
                      max_seq_len=24).cache.by_kind


def test_the_lane_wide_forward_and_page_export_refuse_a_ring(params):
    eng = engine(params)
    with pytest.raises(NotImplementedError, match="ring of their own"):
        paged.paged_forward(params, CFG, jnp.zeros((4, 1), jnp.int32),
                            eng.cache)
    with pytest.raises(NotImplementedError, match="ring of their own"):
        eng.read_pages([0])


def test_a_window_wider_than_the_ring_allows_is_refused(params):
    eng = engine(params)
    eng._ensure_window(8)           # two blocks of 2 steps x 4: 16 rows
    assert eng._kv_window.width == 16 == staged_most(eng.runtime)
    eng._kv_window = None
    with pytest.raises(ValueError, match="ring was sized for 16"):
        eng._ensure_window(9)


def test_health_names_the_pool_s_kinds(params):
    from butterfly_tpu.serve.server import runtime_report
    rep = runtime_report(Scheduler(engine(params)))
    assert rep["pool_kinds"]["slide"]["ring_pages"] == 7
    assert rep["pool_kinds"]["full"]["layers"] == 1
    assert runtime_report(Scheduler(engine(
        params, max_seq_len=24)))["pool_kinds"] is None
