"""Keye-VL-2.0's language model on every path the server runs, against
its plain reference (servebench/references/keye_f32.py): a toy of the
model's shape (hidden 64, 2 layers, 4 queries over 2 KV heads of 16 with
norms on heads, 8 experts of 32 with 2 a token, an indexer of 2 heads of
16 whose top 8 binds), seeded random weights, float32, contexts to 40 so
that the selection leaves positions out. Logits, not tokens."""
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import numpy as np
import pytest

from butterfly_tpu.cache import paged
from butterfly_tpu.cache.paged import (
    flush_paged_window, init_kv_window, init_paged_cache, paged_forward,
    paged_forward_packed, paged_forward_window, pool_layout, pool_leaves,
    window_leaves)
from butterfly_tpu.core.config import (
    MeshConfig, ModelConfig, RuntimeConfig, keye_vl2_30b_a3b, tiny)
from butterfly_tpu.core.mesh import make_mesh
from servebench.references import keye_f32 as ref
from butterfly_tpu.models.common import (
    Model, index_scores, layer_stack, select_mask, select_topk)
from butterfly_tpu.parallel.partition import (
    kv_window_specs, paged_cache_specs, shard_params, to_shardings)
from butterfly_tpu.quant.int8 import is_quantized_leaf, quantize_int8

CFG = tiny("keye", hidden_size=64, num_layers=2, num_heads=4,
           num_kv_heads=2, head_dim=16, dtype="float32",
           param_dtype="float32")
T = 40
TOPK = CFG.index_topk
#: float32 on both sides on the CPU
TOL = 5e-5


def file_config(cfg: ModelConfig, **over) -> dict:
    """The published keys the reference reads, as a configuration file
    of `cfg` would hold them."""
    return dict(
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        num_hidden_layers=cfg.num_layers, num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        sa_config={"topk": cfg.index_topk,
                   "indexer_num_heads": cfg.index_heads,
                   "indexer_head_dim": cfg.index_head_dim}, **over)


def leaf_of(params):
    def leaf(path, layer=None):
        node = params
        for key in path.split("/"):
            node = node[key]
        if is_quantized_leaf(node):
            q8, s = node["q8"], node["s"]
            if layer is not None:
                q8, s = q8[layer], s[layer]
            return q8.astype(jnp.float32) * s.astype(jnp.float32)
        return (node if layer is None else node[layer]).astype(jnp.float32)
    return leaf


def seeded_params(cfg=CFG):
    p = Model(cfg).init(jax.random.PRNGKey(0))
    # norms that are not all ones, so that a norm put in the wrong place
    # shows, and index weights large enough for the selection to matter
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))

    def jitter(a):
        return 1 + 0.3 * jax.random.normal(next(keys), a.shape)

    lay = p["layers"]
    for g in (lay["ln1"], lay["ln2"], lay["attn"]["q_norm"],
              lay["attn"]["k_norm"], lay["index"]["k_norm"]):
        g["scale"] = jitter(g["scale"])
    lay["index"]["k_norm"]["bias"] = 0.1 * jax.random.normal(
        next(keys), lay["index"]["k_norm"]["bias"].shape)
    # keys and values that differ enough between positions for a wrong
    # selection to move the logits
    lay["attn"]["wv"] = lay["attn"]["wv"] * 20
    lay["attn"]["wk"] = lay["attn"]["wk"] * 20
    lay["attn"]["wq"] = lay["attn"]["wq"] * 20
    return p


@pytest.fixture(scope="module")
def params():
    return seeded_params()


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(3).randint(1, CFG.vocab_size, (2, T))


def reference(params, tokens, cfg=CFG):
    return np.stack([np.asarray(ref.logits(t, leaf_of(params),
                                           file_config(cfg)))
                     for t in tokens])


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's full forward of both sequences: [2, T, V]."""
    return reference(params, tokens)


def worst(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)))


# -- the contiguous cache ---------------------------------------------------

def test_contiguous_forward_whole(params, tokens, want):
    model = Model(CFG)
    got, _ = model(params, jnp.asarray(tokens), model.init_cache(2, 64))
    assert worst(got, want) < TOL


def test_prefill_then_decode_through_the_contiguous_cache(params, tokens, want):
    model = Model(CFG)
    cache = model.init_cache(2, 64)
    got, cache = model(params, jnp.asarray(tokens[:, :6]), cache)
    rows = [got]
    for t in range(6, T):
        got, cache = model(params, jnp.asarray(tokens[:, t:t + 1]), cache)
        rows.append(got)
    assert worst(jnp.concatenate(rows, axis=1), want) < TOL


# -- the paged path ---------------------------------------------------------

RT = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=4)


def paged_cache(cfg=CFG, rt=RT, mesh=None):
    """mesh: every leaf in its mesh layout (paged_cache_specs)."""
    sh = None if mesh is None else to_shardings(
        paged_cache_specs(cfg, mesh, rt.max_batch_size), mesh)
    cache = init_paged_cache(cfg, rt, shardings=sh)
    per = rt.max_seq_len // rt.page_size
    table = np.full(np.asarray(cache.page_table).shape, cache.null_page,
                    np.int32)
    for b in range(2):
        # a slot's pages lie scattered and out of order in the pool
        table[b, :per] = np.arange(per)[::-1] * 2 + b
    return cache._replace(page_table=jax.device_put(
        table, None if sh is None else sh.page_table))


def test_paged_prefill_chunks_then_decode(params, tokens, want):
    """A fresh chunk inside topk, a warm chunk that crosses it, then
    decode steps one token at a time (each reads only what it selected)."""
    cache = paged_cache()
    assert cache.ki_pages.shape == (2, 33, 1, 4, paged.index_row(CFG))
    a, cache = paged_forward(params, CFG, jnp.asarray(tokens[:, :5]), cache,
                             fresh=True)
    b, cache = paged_forward(params, CFG, jnp.asarray(tokens[:, 5:24]), cache)
    rows = [a, b]
    for t in range(24, T):
        got, cache = paged_forward(params, CFG, jnp.asarray(tokens[:, t:t + 1]),
                                   cache)
        rows.append(got)
    assert worst(jnp.concatenate(rows, axis=1), want) < TOL


def test_windowed_decode_across_a_flush(params, tokens, want):
    """Prefill, then decode through the write-combined window, a flush
    in the middle: a row's selection spans pool and window."""
    cache = paged_cache()
    a, cache = paged_forward(params, CFG, jnp.asarray(tokens[:, :20]), cache,
                             fresh=True)
    window = init_kv_window(cache, 8)
    assert window.ki.shape == (2, 2, 1, 8, paged.index_row(CFG))
    wlen = jnp.zeros((2,), jnp.int32)
    rows = [a]
    for t in range(20, T):
        if t in (27, 35):
            cache, wlen, n = flush_paged_window(cache, window, wlen)
            assert int(n) == 2 * (7 if t == 27 else 8)
        got, window = paged_forward_window(
            params, CFG, jnp.asarray(tokens[:, t:t + 1]), cache, window, wlen)
        wlen = wlen + 1
        rows.append(got)
    assert worst(jnp.concatenate(rows, axis=1), want) < TOL


def packed_run(params, tokens, cfg=CFG, windowed=True, C=6, mesh=None):
    """Sequence 0 decodes from position 20 while sequence 1's prompt is
    fed in chunks of C from position 3 (crossing topk) through the
    packed mixed step; a flush every third step. Returns the logits
    [(sequence, position, row [V])] and the loads. mesh: the pool and
    the window in their mesh layouts, the step and the flush jitted
    (GSPMD partitions them), and the pool and window as they ended."""
    cache = paged_cache(cfg, mesh=mesh)
    step, flush = paged_forward_packed, flush_paged_window
    if mesh is not None:
        step = jax.jit(paged_forward_packed, static_argnums=(1,))
        flush = jax.jit(flush_paged_window)
    _, cache = paged_forward(
        params, cfg, jnp.asarray(tokens[:, :20]), cache, fresh=True,
        active=jnp.asarray([True, False]))
    _, cache = paged_forward(
        params, cfg, jnp.asarray(tokens[:, :3]), cache, fresh=True,
        active=jnp.asarray([False, True]))
    window = init_kv_window(cache, 3 * C, None if mesh is None else
                            to_shardings(kv_window_specs(cfg, mesh, 2), mesh)
                            ) if windowed else None
    wlen = jnp.zeros((2,), jnp.int32) if windowed else None
    out, loads = [], []
    t0, t1 = 20, 3
    n = 0
    while t0 < T:
        count = min(C, T - t1)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :count] = tokens[1, t1:t1 + count]
        if windowed and n % 3 == 0:
            cache, wlen, _ = flush(cache, window, wlen)
        logits, state, load = step(
            params, cfg, jnp.asarray(tokens[:, t0]), cache,
            jnp.asarray(chunk), jnp.asarray([1]), jnp.asarray([count]),
            jnp.asarray([True, False]), window, wlen)
        adv = jnp.asarray([1, count], jnp.int32)
        if windowed:
            window, wlen = state, wlen + adv
        else:
            cache = state._replace(lengths=cache.lengths + adv)
        out.append((0, t0, logits[0]))
        if count:
            out.append((1, t1 + count - 1, logits[1]))
        loads.append(np.asarray(load))
        t0, t1, n = t0 + 1, t1 + count, n + 1
    if mesh is not None:
        return out, loads, cache, window
    return out, loads


@pytest.mark.parametrize("windowed", [False, True], ids=["pool", "window"])
def test_packed_step_with_a_chunk_that_crosses_topk(params, tokens, want,
                                                    windowed):
    out, _ = packed_run(params, tokens, windowed=windowed)
    assert len(out) > 20
    for seq, pos, row in out:
        assert worst(row, want[seq, pos]) < TOL, (seq, pos)


def test_kv_rows_counted_by_hand(params, tokens):
    """`load`'s last four: the step's live decode rows, the positions
    they could attend, those they attended and the rows the read moved
    (on the gather's path the selected ones). Sequence 0 decodes alone
    from position 20: it could attend t + 1 positions and read topk."""
    _, loads = packed_run(params, tokens)
    for i, load in enumerate(loads):
        assert load.shape == (7,)
        np.testing.assert_allclose(load[3:], [1, 20 + i + 1, TOPK, TOPK])
    assert loads[0][0] > 0          # the experts' counts ride as they were


def test_int8_weights_leave_the_indexer_in_float(tokens):
    p = quantize_int8(seeded_params(), CFG)
    assert not any(is_quantized_leaf(v) for v in (
        p["layers"]["index"]["w_qi"], p["layers"]["index"]["w_ki"],
        p["layers"]["index"]["w_w"], p["layers"]["moe"]["router"]))
    assert is_quantized_leaf(p["layers"]["attn"]["wq"])
    want = reference(p, tokens)
    model = Model(CFG)
    got, _ = model(p, jnp.asarray(tokens), model.init_cache(2, 64))
    assert worst(got, want) < 20 * TOL
    cache = paged_cache()
    a, cache = paged_forward(p, CFG, jnp.asarray(tokens[:, :24]), cache,
                             fresh=True)
    b, cache = paged_forward(p, CFG, jnp.asarray(tokens[:, 24:25]), cache)
    assert worst(a, want[:, :24]) < 20 * TOL
    assert worst(b, want[:, 24:25]) < 20 * TOL


# -- on both sides of topk ---------------------------------------------------

def paged_logits(params, tokens, cfg, n, select=None, monkeypatch=None):
    """Logits of the first n positions: a prefill of n - 4 through the
    paged cache, then 4 decode steps."""
    if select is not None:
        monkeypatch.setattr(paged, "sparse_paged_attend", partial(
            paged.sparse_paged_attend, select=select))
    cache = paged_cache(cfg)
    a, cache = paged_forward(params, cfg, jnp.asarray(tokens[:, :n - 4]),
                             cache, fresh=True)
    rows = [a]
    for t in range(n - 4, n):
        got, cache = paged_forward(params, cfg,
                                   jnp.asarray(tokens[:, t:t + 1]), cache)
        rows.append(got)
    return jnp.concatenate(rows, axis=1)


@pytest.mark.parametrize("select", ["index", "all", "recent"])
def test_both_sides_of_topk(params, tokens, want, select, monkeypatch):
    """At most topk positions: the logits are those of the same model
    with the indexer switched off, whatever selects. Past topk the
    indexer's choice agrees with the reference and the two controls
    (attend everything; attend the last topk) do NOT."""
    sel = None if select == "index" else select
    got = paged_logits(params, tokens, CFG, T, sel, monkeypatch)
    assert worst(got[:, :TOPK], want[:, :TOPK]) < TOL
    past = worst(got[:, TOPK:], want[:, TOPK:])
    if select == "index":
        assert past < TOL
        off = CFG.replace(index_heads=0, index_head_dim=0, index_topk=0)
        p_off = {**params, "layers": {k: v for k, v in params["layers"].items()
                                      if k != "index"}}
        plain = paged_logits(p_off, tokens, off, TOPK)
        assert worst(plain, want[:, :TOPK]) < TOL
    else:
        assert past > 100 * TOL


def test_selection_is_topk_with_ties_to_the_lower_position():
    scores = jnp.asarray([[1.0, 3.0, 3.0, 2.0, 3.0, 0.5, 9.0, 9.0]])
    valid = jnp.asarray([[True] * 6 + [False] * 2])
    idx, ok, mask = select_topk(scores, valid, 2, with_mask=True)
    assert sorted(np.asarray(idx[0]).tolist()) == [1, 2] and bool(ok.all())
    assert np.asarray(mask[0]).tolist() == [False, True, True] + [False] * 5
    assert np.asarray(select_mask(scores, valid, 4)[0]).tolist() == \
        [False, True, True, True, True, False, False, False]
    # fewer valid positions than k: all of them, and the rest marked
    idx, ok, mask = select_topk(scores, valid, 8, with_mask=True)
    assert int(ok.sum()) == 6 and bool((mask == valid).all())
    qi = jnp.ones((1, 1, 2, 4))
    ki = jnp.asarray([[[1.0] * 4, [-1.0] * 4]])
    w = jnp.asarray([[[2.0, -0.5]]])
    # relu kills the second key; the first scores (2 - 0.5) * 4
    np.testing.assert_allclose(index_scores(qi, w, ki)[0, 0], [6.0, 0.0])


# -- the third pool: staged, flushed, freed with its pages -------------------

def staged_case(lengths, W=8, L=3, Kv=2, page=4, H=8, Hi=6, mp=3, seed=0):
    """A pool and a window full of random values (stale rows past
    win_len included), index keys beside keys and values, each slot on
    distinct pages in shuffled order."""
    S = len(lengths)
    P = S * mp + 2                              # one page spare, one null
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    rnd = lambda k, sh: jax.random.normal(k, sh, jnp.float32)  # noqa: E731
    table = np.random.RandomState(seed).permutation(P - 1)[:S * mp]
    cache = paged.PagedKVCache(
        k_pages=rnd(ks[0], (L, P, Kv, page, H)),
        v_pages=rnd(ks[1], (L, P, Kv, page, H)),
        page_table=jnp.asarray(table.reshape(S, mp), jnp.int32),
        lengths=jnp.asarray(lengths, jnp.int32),
        ki_pages=rnd(ks[2], (L, P, 1, page, Hi)))
    window = paged.KVWindow(
        k=rnd(ks[3], (L, S, Kv, W, H)), v=rnd(ks[4], (L, S, Kv, W, H)),
        ki=rnd(ks[5], (L, S, 1, W, Hi)))
    return cache, window


def flush_by_scatter(cache, window, win_len):
    """The oracle the flush tests have (tests/test_paged.py), for every
    tensor the pool has: ONE scatter over all S x W window entries, those
    at or past win_len, or past the table, routed to the null page."""
    page, mp = cache.page_size, cache.page_table.shape[1]
    S, W = win_len.shape[0], window.width
    pos = cache.lengths[:, None] + jnp.arange(W)[None, :]
    valid = jnp.arange(W)[None, :] < win_len[:, None]
    pg = jnp.take_along_axis(cache.page_table,
                             jnp.clip(pos // page, 0, mp - 1), axis=1)
    pg = jnp.where(valid & (pos < mp * page), pg, cache.null_page).reshape(-1)
    off = (pos % page).reshape(-1)
    out = [pool.at[:, pg, :, off].set(
        staged.transpose(1, 3, 0, 2, 4).reshape(S * W, *pool.shape[:1],
                                                *pool.shape[2:3],
                                                *pool.shape[4:]))
        for pool, staged in zip(pool_leaves(cache), window_leaves(window))]
    return pool_leaves(cache, out)


#: (flushed lengths, staged entries) a slot; pages of 4, a window of 8,
#: a table of 3 pages (12 positions)
FLUSH_CASES = {
    "nothing": ([0, 5, 3, 7], [0, 0, 0, 0]),
    "one": ([0, 5, 3, 7], [1, 0, 1, 1]),
    "straddling": ([2, 3, 7, 1], [3, 5, 2, 8]),
    "whole-window": ([0, 1, 4, 3], [8, 8, 8, 8]),
    "past-the-table": ([8, 10, 12, 5], [8, 4, 3, 8]),
}


@pytest.mark.parametrize("case", list(FLUSH_CASES))
def test_flush_of_three_pools_equals_the_scatter(case):
    """Keys, values AND index keys, to the bit on every page but the
    null page, which keeps what it held."""
    lengths, staged = FLUSH_CASES[case]
    cache, window = staged_case(lengths)
    assert len(pool_leaves(cache)) == 3 == len(window_leaves(window))
    win_len = jnp.asarray(staged, jnp.int32)
    want = jax.jit(flush_by_scatter)(cache, window, win_len)
    got, zeroed, count = jax.jit(flush_paged_window)(cache, window, win_len)
    for new, old, was in zip(pool_leaves(got), pool_leaves(want),
                             pool_leaves(cache)):
        np.testing.assert_array_equal(np.asarray(new[:, :-1]),
                                      np.asarray(old[:, :-1]))
        np.testing.assert_array_equal(np.asarray(new[:, -1]),
                                      np.asarray(was[:, -1]))
    assert int(count) == sum(staged) and not np.asarray(zeroed).any()
    landed = sum(min(n, max(0, cache.max_seq - ln))
                 for ln, n in zip(lengths, staged))
    changed = (np.asarray(got.ki_pages) != np.asarray(cache.ki_pages))
    assert changed.any(axis=(0, 2, 4)).sum() == landed


@pytest.mark.parametrize("arch", ["llama", "mixtral", "smallthinker"])
def test_a_model_without_an_indexer_has_no_third_pool(arch):
    """Mistral's and SmallThinker's programs hold what they held: two
    pool tensors, two window tensors, no norm on heads, no index
    weights, and the scans carry no leaf for what is absent."""
    cfg = tiny(arch, dtype="float32", param_dtype="float32")
    assert not cfg.has_indexer and not cfg.qk_norm
    cache = init_paged_cache(cfg, RT)
    assert cache.ki_pages is None and len(pool_leaves(cache)) == 2
    assert pool_leaves(cache, absent=True)[2:] == (None, None, None)
    window = init_kv_window(cache, 4)
    assert window.ki is None and len(window_leaves(window)) == 2
    p = Model(cfg).init(jax.random.PRNGKey(0))
    assert "index" not in p["layers"] and "q_norm" not in p["layers"]["attn"]
    assert "index" not in layer_stack(p["layers"], cfg)
    assert len(jax.tree.leaves(cache)) == 4
    # the pool, the window and their sharding specs as they were before
    # a token-major layout existed: a row a KV head, heads on dim 2
    L, Kv, H = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    pages = RT.max_batch_size * (RT.max_seq_len // RT.page_size) + 1
    assert pool_layout(cfg) == "head"
    assert cache.k_pages.shape == cache.v_pages.shape == \
        (L, pages, Kv, RT.page_size, H)
    assert window.k.shape == window.v.shape == (L, 2, Kv, 4, H)
    mesh = make_mesh(MeshConfig(tensor=2), jax.devices()[:2])
    assert Kv % 2 == 0
    specs = paged_cache_specs(cfg, mesh, 2)
    assert specs.k_pages == specs.v_pages == P(None, None, "tensor", None,
                                                None)
    assert specs.page_table == P(None, None) and specs.lengths == P(None)
    assert specs.ki_pages is None and specs.k_scale_pages is None
    assert paged_cache_specs(cfg, mesh, 2, quant=True).k_scale_pages == \
        P(None, None, "tensor")
    wspecs = kv_window_specs(cfg, mesh, 2)
    assert wspecs.k == wspecs.v == P(None, None, "tensor", None, None)
    assert wspecs.ki is None and wspecs.k_scale is None
    assert kv_window_specs(cfg, mesh, 2, quant=True).k_scale == \
        P(None, None, None, "tensor")


# -- the token-major pool: a selected token is ONE row ------------------------

def test_a_model_with_an_indexer_holds_a_token_as_one_row():
    """Keys and values [L, P, 1, page, Kv*H], the window beside them,
    the index keys one head of a whole lane tile; under a tensor mesh
    the row's minor dim is what is sharded, a chip's KV heads contiguous
    in it."""
    L, Kv, H = CFG.num_layers, CFG.num_kv_heads, CFG.head_dim
    assert pool_layout(CFG) == "token" and paged.pool_row(CFG) == (1, Kv * H)
    cache = init_paged_cache(CFG, RT)
    assert cache.k_pages.shape == cache.v_pages.shape == (L, 33, 1, 4, Kv * H)
    assert paged.index_row(CFG) == paged.LANES > CFG.index_head_dim
    assert cache.ki_pages.shape == (L, 33, 1, 4, paged.LANES)
    assert cache.page_size == 4 and cache.null_page == 32
    window = init_kv_window(cache, 8)
    assert window.k.shape == window.v.shape == (L, 2, 1, 8, Kv * H)
    mesh = make_mesh(MeshConfig(tensor=2), jax.devices()[:2])
    specs, wspecs = (f(CFG, mesh, 2) for f in (paged_cache_specs,
                                               kv_window_specs))
    assert specs.k_pages == specs.v_pages == P(None, None, None, None,
                                                "tensor")
    assert wspecs.k == wspecs.v == P(None, None, None, None, "tensor")
    assert specs.ki_pages == wspecs.ki == P(None, None, None, None, None)
    # KV heads the mesh cannot divide: every chip holds the rows whole
    odd = make_mesh(MeshConfig(tensor=4), jax.devices()[:4])
    assert paged_cache_specs(CFG, odd, 2).k_pages == P(*[None] * 5)


def head_major_rows(pages, layer, pg, off):
    """The read of a head-major pool [L, P, Kv, page, H] by (page,
    offset) as PR 36 made it: Kv rows of H a token out of the pool seen
    flat [L*P*Kv*page, H] -> [B, K, Kv, H]."""
    L, Pn, Kv, page, H = pages.shape
    row = ((layer * Pn + pg)[..., None] * Kv + jnp.arange(Kv)) * page \
        + off[..., None]
    return jnp.take(pages.reshape(L * Pn * Kv * page, H), row, axis=0)


@pytest.mark.parametrize("seed", range(6))
def test_a_token_row_is_the_head_major_gather_to_the_bit(seed):
    """_pool_rows by _row_addresses on a token-major pool against the
    head-major read of the same values: random pools, layers, tables
    (null-page entries among them) and positions, in any order and
    repeated; an entry that is not in the pool (address -1, `ok` false
    in sparse_paged_attend) reads a row of the pool, whichever."""
    rng = np.random.RandomState(seed)
    L, Pn, Kv, page, H = 2 + seed % 3, 9 + seed, 1 + seed % 4, 4, 8
    B, mp, K = 3, 5, 11
    head = jnp.asarray(rng.randn(L, Pn, Kv, page, H), jnp.float32)
    head = head.astype(jnp.bfloat16 if seed % 2 else jnp.float32)
    head = head.at[:, -1].set(0)                         # the null page
    token = head.transpose(0, 1, 3, 2, 4).reshape(L, Pn, 1, page, Kv * H)
    table = rng.randint(0, Pn, (B, mp)).astype(np.int32)
    table[:, -1] = Pn - 1                                # not allocated
    idx = rng.randint(0, mp * page, (B, K)).astype(np.int32)
    layer = jnp.int32(rng.randint(L))
    addr = paged._row_addresses(jnp.asarray(table), Pn, page, layer)
    assert addr.shape == (B, mp * page) and addr.dtype == jnp.int32
    row = np.take_along_axis(np.asarray(addr), idx, axis=1)
    ok = rng.rand(B, K) < 0.8
    row = np.where(ok, row, -1)
    got = jax.jit(paged._pool_rows)(token, jnp.asarray(row))
    want = head_major_rows(head, layer, np.take_along_axis(
        table, idx // page, axis=1), idx % page)
    assert got.shape == (B, K, Kv * H) and got.dtype == head.dtype
    got = np.asarray(got.astype(jnp.float32)).reshape(B, K, Kv, H)
    want = np.asarray(want.astype(jnp.float32))
    np.testing.assert_array_equal(got[ok], want[ok])
    null = np.take_along_axis(table, idx // page, axis=1) == Pn - 1
    assert null.any() and not got[ok & null].any()
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(
        got[~ok], np.broadcast_to(np.asarray(token.astype(
            jnp.float32))[0, 0, 0, 0].reshape(Kv, H), got[~ok].shape))


@pytest.mark.parametrize("k", [1, 5, 8, 12])
def test_a_payload_rides_the_selection_in_top_k_s_order(k):
    """select_topk with a payload: the payload of the positions that
    lax.top_k picks, in its order (ties to the lower position, the
    entries past the valid ones included), and the same mask."""
    rng = np.random.RandomState(k)
    scores = jnp.asarray(np.round(rng.randn(4, 12), 1), jnp.float32)
    valid = jnp.asarray(rng.rand(4, 12) < 0.7)
    payload = jnp.asarray(rng.permutation(48).reshape(4, 12), jnp.int32)
    idx, ok, mask = select_topk(scores, valid, k, with_mask=True)
    got, ok2, mask2 = select_topk(scores, valid, k, with_mask=True,
                                  payload=payload)
    np.testing.assert_array_equal(
        got, np.take_along_axis(np.asarray(payload), np.asarray(idx), 1))
    np.testing.assert_array_equal(ok, ok2)
    np.testing.assert_array_equal(mask, mask2)
    assert len(np.unique(np.asarray(scores))) < scores.size   # ties


def test_packed_step_on_a_tensor_mesh_shards_a_token_s_row(params, tokens,
                                                           want):
    """The packed step, window on, over two chips (one KV head each):
    the single-device logits, from a pool and a window whose rows are
    sharded on their minor dim."""
    mesh = make_mesh(MeshConfig(tensor=2), jax.devices()[:2])
    out, _, cache, window = packed_run(shard_params(params, CFG, mesh),
                                       tokens, mesh=mesh)
    assert len(out) > 20
    for seq, pos, row in out:
        assert worst(row, want[seq, pos]) < TOL, (seq, pos)
    half = CFG.num_kv_heads * CFG.head_dim // 2
    for a in (cache.k_pages, cache.v_pages, window.k, window.v):
        assert a.sharding.spec == P(None, None, None, None, "tensor")
        assert a.sharding.shard_shape(a.shape)[2:] == (1, a.shape[3], half)
    assert cache.ki_pages.sharding.is_fully_replicated


def test_served_tokens_on_a_tensor_mesh_are_the_single_device_s(params):
    """Through the scheduler (mixed blocks, the window, the flush) on
    two chips: the tokens one chip serves."""
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    rt = RuntimeConfig(max_batch_size=3, max_seq_len=64, page_size=4,
                       decode_steps_per_tick=2, prefill_inline_budget=8)
    rng = np.random.RandomState(12)
    prompts = [rng.randint(1, CFG.vocab_size, n).tolist() for n in (7, 19)]

    def run(mesh):
        eng = ServingEngine(Model(CFG), params, rt, mesh=mesh)
        sched = Scheduler(eng, seed=0)
        reqs = [sched.submit(p, max_new_tokens=12) for p in prompts]
        sched.run_until_done()
        return [r.output for r in reqs], eng

    ref_out, one = run(None)
    got, eng = run(make_mesh(MeshConfig(tensor=2), jax.devices()[:2]))
    assert got == ref_out and all(len(o) == 12 for o in got)
    assert one.cache.k_pages.shape == eng.cache.k_pages.shape
    assert eng.cache.k_pages.sharding.spec[4] == "tensor"


# -- what cannot take the third pool refuses the model by name ----------------

def _engine(**rt):
    from butterfly_tpu.engine.serving import ServingEngine
    mesh = rt.pop("mesh", None)
    return ServingEngine(Model(CFG), seeded_params(), RuntimeConfig(
        max_batch_size=2, max_seq_len=64, page_size=4, **rt), mesh=mesh)


def _mesh(axis):
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:2]), (axis,))


def _stages():
    from butterfly_tpu.parallel.pipeline import paged_pipeline_packed
    paged_pipeline_packed(None, CFG, None, None, None, None, None, None,
                          mesh=_mesh("stage"))


REFUSALS = {
    "prefix caching": lambda: _engine(prefix_caching=True),
    "host KV tier": lambda: _engine(prefix_caching=True, host_kv_tier_mb=1),
    "export": lambda: _engine().read_pages([0]),
    "import": lambda: _engine().write_pages([0], None, None),
    "pipeline serving": lambda: _engine(mesh=_mesh("stage")),
    "pipeline": _stages,
    "sequence-parallel": lambda: _engine(mesh=_mesh("seq")),
    "speculative": lambda: _engine(speculative_gamma=2),
    "int8 KV": lambda: _engine(kv_quant="int8"),
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_refused_by_name(what):
    with pytest.raises(NotImplementedError, match=what) as e:
        REFUSALS[what]()
    assert "sparse-attention indexer" in str(e.value)


# -- through the scheduler: the server's own path -----------------------------

def test_served_tokens_are_the_reference_s_greedy_tokens(params):
    """Three requests through the continuous scheduler (mixed blocks, the
    write-combined window and its flush), prompts admitted while others
    decode, contexts past topk: every served token is the argmax of the
    reference's logits over the tokens before it. The tick records carry
    what a decode row could attend and what it read, and a finished
    request's pages, index keys and all, go back to the free list."""
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    rt = RuntimeConfig(max_batch_size=3, max_seq_len=64, page_size=4,
                       decode_steps_per_tick=2, prefill_inline_budget=8)
    sched = Scheduler(ServingEngine(Model(CFG), params, rt), seed=0)
    free = sched.alloc.free_pages
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, CFG.vocab_size, n).tolist() for n in (5, 21, 13)]
    reqs = [sched.submit(prompts[0], max_new_tokens=14)]
    for _ in range(2):
        sched.tick()
    reqs += [sched.submit(p, max_new_tokens=10) for p in prompts[1:]]
    sched.run_until_done()
    leaf, fc = leaf_of(params), file_config(CFG)
    for prompt, req in zip(prompts, reqs):
        seq = list(prompt)
        for tok in req.output:
            top = np.asarray(ref.logits(np.asarray(seq), leaf, fc)[-1])
            order = np.argsort(top)
            assert top[order[-1]] - top[order[-2]] > 1e-5
            assert tok == order[-1]
            seq.append(tok)
    assert sched.alloc.free_pages == free
    ticks = [t for t in sched.ticklog.dump()["ticks"]
             if t["kv_rows_live"] is not None]
    assert ticks
    for t in ticks:
        assert t["kv_rows_selected"] == min(TOPK, t["kv_rows_selected"])
        assert t["kv_rows_live"] >= t["kv_rows_selected"] > 0
        # kernels off: the selected rows are gathered, and no others move
        assert t["kv_rows_moved"] == t["kv_rows_selected"]
        assert t["experts_touched"] is not None
    assert any(t["kv_rows_live"] > TOPK == t["kv_rows_selected"]
               for t in ticks)
    assert sched._g_kv_rows_selected.value == TOPK
    assert sched._g_kv_rows_moved.value == TOPK
    assert sched._g_kv_rows_live.value > TOPK


@pytest.mark.parametrize("arch, layout", [("keye", "token"),
                                          ("mixtral", "head")])
def test_the_runtime_report_says_which_pool_serves(arch, layout):
    """What /health and the ready line report beside the kernels."""
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    from butterfly_tpu.serve.server import runtime_report
    cfg = tiny(arch, dtype="float32", param_dtype="float32")
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8)
    sched = Scheduler(ServingEngine(
        Model(cfg), Model(cfg).init(jax.random.PRNGKey(0)), rt), seed=0)
    assert runtime_report(sched)["pool_layout"] == layout


def test_a_model_without_an_indexer_s_ticks_carry_no_kv_rows():
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    cfg = tiny("mixtral", dtype="float32", param_dtype="float32")
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8)
    sched = Scheduler(ServingEngine(
        Model(cfg), Model(cfg).init(jax.random.PRNGKey(0)), rt), seed=0)
    sched.submit([5, 7, 11], max_new_tokens=6)
    sched.run_until_done()
    ticks = sched.ticklog.dump()["ticks"]
    assert any(t["experts_touched"] is not None for t in ticks)
    assert all(t["kv_rows_live"] is None and t["kv_rows_selected"] is None
               and t["kv_rows_moved"] is None
               for t in ticks)


# -- the preset, the files ----------------------------------------------------

def test_preset_is_the_published_model():
    cfg = keye_vl2_30b_a3b()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.intermediate_size, cfg.vocab_size) == \
        (48, 2048, 32, 4, 128, 768, 151936)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.act,
            cfg.router_input, cfg.qk_norm) == (128, 8, "silu", "ffn", True)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == \
        (16, 64, 2048)
    assert cfg.rope_theta == 1e7 and not cfg.tie_embeddings
    with pytest.raises(ValueError, match="come together"):
        ModelConfig(index_topk=8)
    with pytest.raises(ValueError, match="per-layer attention pattern"):
        tiny("smallthinker", index_heads=2, index_head_dim=16, index_topk=8)


# -- tools/sparse_parity.py, rehearsed ----------------------------------------

def toy_file(**serve) -> dict:
    """A configuration file of the toy, with the source's key names."""
    return dict(
        file_config(CFG), name="toy-keye", model_type="KeyeVL2",
        hidden_size=CFG.hidden_size, head_dim=CFG.head_dim,
        num_attention_heads=CFG.num_heads,
        num_key_value_heads=CFG.num_kv_heads, vocab_size=CFG.vocab_size,
        intermediate_size=4 * CFG.hidden_size,
        moe_intermediate_size=CFG.intermediate_size,
        max_position_embeddings=128, tie_word_embeddings=False,
        hidden_act="silu", torch_dtype="float32", reference="keye_f32",
        model=dict(arch="keye", intermediate_size=CFG.intermediate_size,
                   qk_norm=True, index_heads=CFG.index_heads,
                   index_head_dim=CFG.index_head_dim,
                   index_topk=CFG.index_topk),
        serve=dict(quant="none", kv_quant="none", max_batch=2, max_seq=128,
                   page_size=4, decode_steps_per_tick=2,
                   prefill_inline_budget=2, **serve))


def test_sparse_parity_tool_separates_its_controls_on_the_toy():
    """The check of the chip (a stream of several times topk through the
    packed step, against the reference in blocks), at a toy's size on
    the CPU: the clean run agrees on both sides of topk; attending
    everything, or the last topk, shows only past it."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    import sparse_parity
    out = sparse_parity.check(toy_file(), toy=True, stream=60, decode=12)
    assert out["evidence"] == "cpu toy", out
    assert out["decode_read"] == "gather"       # kernels off on the CPU
    assert out["rows_before"] >= 1 and out["rows_after"] >= 12
    assert out["clean"]["after_max"] < 1e-4 > out["clean"]["before_max"]
    for control in ("select_all", "select_recent"):
        assert out[control]["before_max"] < 1e-4
        assert out[control]["after_median"] > 100 * out["clean"]["after_max"]
    # every selection of the programs is held to the plain path's as
    # it runs (PR 55): none differs but under the planted threshold,
    # one ulp too high, which the logits' limit is not asked about
    counted = out["selection"]
    assert set(counted) == set(sparse_parity.FAULTS)
    assert all(c["calls"] > 0 for c in counted.values())
    assert [f for f, c in counted.items() if c["rows_differ"]] == \
        ["threshold_ulp"] and out["ok"]
