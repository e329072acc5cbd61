"""ops/sparse_attention.py in interpret mode against the gather branch
of cache/paged.py sparse_paged_attend on the same pools: a decode row
of a model with an indexer reads its slot's live pages through the
kernel with the selection as a mask, and attends what the gather
attends. A toy's geometry (2 KV heads of 8 under 4 queries, pages of 4,
index keys of 6, top 48 of a table of 192) at the kernel's own chunk of
32 pages; float32, so the two sums differ by their order alone."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.cache import paged
from butterfly_tpu.cache.paged import MASKED_READ_SPAN, sparse_paged_attend
from butterfly_tpu.core.config import tiny
from butterfly_tpu.models.common import attend_token_rows
from butterfly_tpu.ops import record_kernels, sparse_attention as sa

TOPK = 48
CFG = tiny("keye", hidden_size=64, num_layers=2, num_heads=4,
           num_kv_heads=2, head_dim=8, index_topk=TOPK, dtype="float32",
           param_dtype="float32")
L, KV, H, NQ, PAGE, HI, NI, W = 2, 2, 8, 4, 4, 6, 2, 8
MP = MASKED_READ_SPAN * TOPK // PAGE          # a table of 192 positions
CHUNK = sa.PAGES_PER_CHUNK * PAGE             # 128 rows
#: contexts (the decode row's position + 1), a slot each: under topk
#: with a last page partly live, past it, nothing (a dead slot), exactly
#: one chunk, a chunk and one page, the whole table
CONTEXTS = (30, 100, 0, CHUNK, CHUNK + PAGE, MP * PAGE)
TOL = 2e-5


def case(contexts=CONTEXTS, staged=None, mp=MP, dtype=jnp.float32, seed=0,
         ties=False):
    """Pools, a window and one decode row a slot at position context - 1
    (context 0: a slot that is not live). staged [S]: rows of the window
    a slot holds BEFORE its current token, which is then staged behind
    them (None: no window, everything in the pool). ties: every index
    key is the same, so every position scores alike."""
    S, P = len(contexts), len(contexts) * mp + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    rnd = lambda k, sh, dt=dtype: jax.random.normal(k, sh, dt)  # noqa: E731
    ctx = np.asarray(contexts)
    active = jnp.asarray(ctx > 0)
    pos = jnp.asarray(np.maximum(ctx - 1, 0), jnp.int32)[:, None]
    table = np.random.RandomState(seed).permutation(P - 1) + 1
    kip = rnd(ks[2], (L, P, 1, PAGE, HI), jnp.float32)
    args = dict(
        q=rnd(ks[3], (S, 1, NQ, H)),
        qi=rnd(ks[4], (S, 1, NI, HI), jnp.float32),
        w=rnd(ks[5], (S, 1, NI), jnp.float32),
        kp=rnd(ks[0], (L, P, 1, PAGE, KV * H)),
        vp=rnd(ks[1], (L, P, 1, PAGE, KV * H)),
        kip=jnp.ones_like(kip) if ties else kip, layer=1, cfg=CFG,
        page_table=jnp.asarray(table.reshape(S, mp), jnp.int32),
        positions=pos, active=active,
        mask=(jnp.arange(mp * PAGE)[None, None, :] <= pos[:, :, None])
        & active[:, None, None])
    if staged is not None:
        win_len = jnp.asarray(np.minimum(staged, np.maximum(ctx - 1, 0)),
                              jnp.int32)
        wki = rnd(ks[8], (L, S, 1, W, HI), jnp.float32)
        args["win"] = (paged.KVWindow(
            k=rnd(ks[6], (L, S, 1, W, KV * H)),
            v=rnd(ks[7], (L, S, 1, W, KV * H)),
            ki=jnp.ones_like(wki) if ties else wki), win_len, None)
    return args


def both(args, **kw):
    """(kernel branch, gather branch) of sparse_paged_attend, each
    (out, count)."""
    return (sparse_paged_attend(**args, use_kernel=True, **kw),
            sparse_paged_attend(**args, use_kernel=False, **kw))


def agree(got, want, contexts=CONTEXTS, tol=TOL):
    (out, count), (ref, ref_count) = got, want
    live = np.asarray(contexts) > 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               atol=tol, rtol=tol)
    # a slot with nothing to attend: zeros, whatever lies in its pages
    assert not np.asarray(out, np.float32)[~live].any()
    # rows, positions attendable, positions ATTENDED: what the model
    # attends is what it attended; the kernel MOVES its live rows
    np.testing.assert_allclose(count[:3], ref_count[:3])
    assert count[3] == count[1]


@pytest.mark.parametrize("staged", [None, (0, 3, 0, 7, 2, 5)],
                         ids=["pool", "window"])
def test_the_masked_read_attends_what_the_gather_attends(staged):
    """Contexts under topk (everything selected) and past it, a dead
    slot beside live ones, a last page partly live, exactly one chunk, a
    chunk and a page, the whole table; with the window, staged rows of
    which the indexer selects some and not others."""
    args = case(staged=staged)
    got, want = both(args)
    agree(got, want)
    attended = sum(min(c, TOPK) for c in CONTEXTS)
    np.testing.assert_allclose(
        got[1], [5, sum(CONTEXTS), attended, sum(CONTEXTS)])
    # the gather moves the rows it selected and no others
    np.testing.assert_allclose(
        want[1], [5, sum(CONTEXTS), attended, attended])


def test_staged_rows_are_attended_only_where_selected():
    """The window's rows ride under the selection at their positions:
    with the scores set by position (select="recent" attends the last
    topk), a window that holds MORE staged rows than topk leaves its
    oldest out, and the kernel with them."""
    contexts = (20, 12)
    args = case(contexts, staged=(7, 7), mp=MASKED_READ_SPAN * 5 // PAGE)
    args["cfg"] = CFG.replace(index_topk=5)      # of 8 staged, 5 attended
    got, want = both(args, select="recent")
    agree(got, want, contexts)
    assert got[1][2] == 2 * 5
    # and they are the LAST five: attend_token_rows over the window alone
    window, win_len, _ = args["win"]
    keep = jnp.arange(W)[None, None, :] >= 8 - 5
    alone = attend_token_rows(args["q"], window.k[1, :, 0], window.v[1, :, 0],
                              jnp.broadcast_to(keep, (2, 1, W)))
    np.testing.assert_allclose(got[0], alone, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("staged", [None, (4, 4)], ids=["pool", "window"])
def test_equal_scores_at_the_kth_place_go_to_the_lower_position(staged):
    """Every position scores alike: the selection is the first topk
    positions, in the kernel's read as in the gather's sort."""
    contexts = (TOPK + 4, 150)
    args = case(contexts, staged=staged, ties=True)
    got, want = both(args)
    agree(got, want, contexts)
    # the first topk positions and no others: the values at positions
    # TOPK.. overwritten, in the pool and in the window (slot 0's staged
    # rows lie at TOPK - 1 .., its first still selected), and nothing moves
    poked = dict(args)
    later = (args["page_table"][:, TOPK // PAGE:]).reshape(-1)
    poked["vp"] = args["vp"].at[:, later].set(7.0)
    if staged is not None:
        window, win_len, _ = args["win"]
        poked["win"] = (window._replace(v=window.v.at[:, :, :, 1:].set(7.0)),
                        win_len, None)
    again = sparse_paged_attend(**poked, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(again[0]))


@pytest.mark.parametrize("select", ["all", "recent"])
def test_the_controls_reach_the_masked_read(select):
    """tools/sparse_parity.py plants `select`: the kernel's branch
    attends every position, or the last topk, as the gather's does."""
    args = case(staged=(0, 3, 0, 7, 2, 5))
    got, want = both(args, select=select)
    agree(got, want)
    attended = sum(CONTEXTS) if select == "all" \
        else sum(min(c, TOPK) for c in CONTEXTS)
    assert got[1][2] == attended
    # and not what the indexer's choice gives past topk
    index, _ = sparse_paged_attend(**args, use_kernel=True)
    assert np.abs(np.asarray(got[0][1] - index[1])).max() > 1e-3
    np.testing.assert_allclose(got[0][0], index[0], atol=TOL, rtol=TOL)


def test_bfloat16_pools_as_the_cell_holds_them():
    """bfloat16 rows and products, the softmax in float32, on both
    sides: they part by the order of their sums."""
    args = case(staged=(0, 3, 0, 7, 2, 5), dtype=jnp.bfloat16)
    got, want = both(args)
    assert got[0].dtype == jnp.bfloat16
    agree(got, want, tol=3e-2)


#: the gather's sort with the row address as its payload, in a jaxpr
SORT = " sort["


def traced(args, **kw):
    """The jaxpr of sparse_paged_attend and the kernels its trace noted."""
    log = {}
    arrays = {k: v for k, v in args.items()
              if k not in ("cfg", "layer", "win")}
    # a wrapper notes its kernel when it is TRACED, and the selection's
    # operands have one shape with a window and without
    jax.clear_caches()
    with record_kernels(log):
        jaxpr = jax.make_jaxpr(lambda a: sparse_paged_attend(
            **a, cfg=args["cfg"], layer=args["layer"], win=args.get("win"),
            **kw))(arrays)
    return str(jaxpr), log


@pytest.mark.parametrize("staged", [None, (1, 2, 0)], ids=["pool", "window"])
def test_the_branch_is_chosen_by_the_table_and_topk_alone(staged):
    """The rule between the two reads is a shape: a table of up to
    MASKED_READ_SPAN x topk positions goes through the kernel (no sort
    with a payload, no gather of rows), one page more lowers to the
    gather and notes no read kernel; kernels off is the gather at any
    size. The index SCORES of such a row come from their own call
    wherever kernels are on, whatever the table (ops/index_scores.py,
    PR 53), and the masked read's selection from the counting call
    (ops/select_mask.py, PR 55: no sort at all). (Three slots, as no other test here has: the wrapper notes
    its kernel when it is TRACED, and jit keeps a trace of shapes it saw.)"""
    text, log = traced(case((100, 60, 9), staged=staged), use_kernel=True)
    assert log == {"index_scores:interpret": 1, "select_mask:interpret": 1,
                   "sparse_attention:interpret": 1}
    assert text.count("pallas_call") == 3 and SORT not in text
    assert "top_k" not in text and "cumsum" not in text
    text, log = traced(case((100, 60, 9), staged=staged, mp=MP + 1),
                       use_kernel=True)
    assert log == {"index_scores:interpret": 1}
    assert text.count("pallas_call") == 1 and SORT in text
    text, log = traced(case((100, 60, 9), staged=staged), use_kernel=False)
    assert log == {} and "pallas_call" not in text and SORT in text


def _kernel_body(pages_per_chunk, monkeypatch):
    """The kernel body's jaxpr at a chunk of so many pages (the cell's
    32 queries over 4 KV heads of 128, the window on)."""
    monkeypatch.setattr(sa, "PAGES_PER_CHUNK", pages_per_chunk)
    S, Nq, R, page, P, Wd, mp = 2, 32, 512, 16, 9, 16, 64
    bf = jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    jaxpr = jax.make_jaxpr(partial(
        sa.sparse_attention.__wrapped__.__wrapped__, interpret=True))(
        sds((S, Nq, 128), bf), sds((1, P, 1, page, R), bf),
        sds((1, P, 1, page, R), bf), sds((), jnp.int32),
        sds((S, mp), jnp.int32), sds((S,), jnp.int32),
        sds((S, mp * page), jnp.bool_), sds((1, S, 1, Wd, R), bf),
        sds((1, S, 1, Wd, R), bf), sds((S,), jnp.int32))
    call, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (S,)
    assert call.params["out_avals"][0].shape == (S, 4, 1, 8, 128)
    return call.params["jaxpr"]


def test_the_kernel_body_does_not_grow_with_the_chunk(monkeypatch):
    """Set-up by construction, as tests/test_joyai.py holds the latent
    kernel's: a chunk's copies are a rolled loop over groups of
    GROUP_PAGES pages, so the body a serving program traces and lowers
    at every start is the same size at a chunk of 8 pages as at 32, and
    holds a group's starts of both pools twice (a slot's own first chunk
    or a dead slot's hand-on; the next chunk's or the next slot's) and
    one wait a pool."""
    from test_kernels import _eqns
    bodies = {n: _kernel_body(n, monkeypatch) for n in (8, 32)}
    sizes = {n: sum(1 for _ in _eqns(b)) for n, b in bodies.items()}
    assert sizes[8] == sizes[32] < 700, sizes
    text = str(bodies[32])
    assert text.count("dma_start") == 2 * 2 * sa.GROUP_PAGES
    assert text.count("dma_wait") == 2


def test_fits_says_what_the_kernel_serves():
    """A token-major pool of whole tiles whose rows split into heads of
    whole lanes; a head-major pool, a pool of fewer pages than a group
    or rows that are no whole heads take the gather."""
    pool = jax.ShapeDtypeStruct((2, 9, 1, 16, 512), jnp.bfloat16)
    assert sa.fits(pool, 128) and sa.fits(pool, 128, 256)
    # a window is no wider than a chunk of 32 pages
    assert sa.fits(pool, 128, 512) and not sa.fits(pool, 128, 528)
    for shape, head in (((2, 9, 4, 16, 128), 128), ((2, 7, 1, 16, 512), 128),
                        ((2, 9, 1, 16, 512), 96)):
        assert not sa.fits(jax.ShapeDtypeStruct(shape, jnp.bfloat16), head)


def test_the_parity_tool_s_stream_through_the_masked_read(monkeypatch):
    """tools/sparse_parity.py at a toy's size with kernels ON (the
    engine's switch forced, the span widened to the toy's table of 16 x
    topk): a stream of several times topk through the packed step, its
    window and its flushes, every decode row read by the kernel
    (interpreted), against the plain reference; the clean run agrees on
    both sides of topk, both controls show past it, and the output says
    which read served."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    import sparse_parity
    from butterfly_tpu.engine.serving import ServingEngine
    from test_keye import toy_file

    real_init = ServingEngine.__init__

    def init(self, *args, **kw):
        real_init(self, *args, **kw)
        self._use_kernels = True

    monkeypatch.setattr(ServingEngine, "__init__", init)
    monkeypatch.setattr(paged, "MASKED_READ_SPAN", 16)
    out = sparse_parity.check(toy_file(), toy=True, stream=60, decode=12)
    assert out["decode_read"].startswith("masked"), out["kernels"]
    assert out["kernels"]["sparse_attention:interpret"] >= 1
    assert out["clean"]["after_max"] < 1e-4 > out["clean"]["before_max"]
    for control in ("select_all", "select_recent"):
        assert out[control]["before_max"] < 1e-4
        assert out[control]["after_median"] > 100 * out["clean"]["after_max"]
