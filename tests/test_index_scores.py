"""ops/index_scores.py, interpreted: a decode row's index scores from a
walk of its slot's live index-key pages against models/common.py
index_scores over the gathered view, which it stands in for
(cache/paged.py _index_selection)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.cache import paged
from butterfly_tpu.models import common
from butterfly_tpu.ops import index_scores as walk
from butterfly_tpu.ops import record_kernels

PAGE, MP, L, W, TOPK = 16, 144, 2, 128, 96
ROWS = walk.PAGES_PER_CHUNK * PAGE      # 1,024 positions a chunk
S_MAX = MP * PAGE                       # 2,304: two chunks and a part
#: the two indexers the benchmark's cells hold: index heads, key width
GEOMETRIES = {"keye": (16, 64), "glm5": (32, 128)}
#: the flushed length of the slot under test: a dead slot, one position,
#: a page's edge and one past it, a chunk's edge and one past it, the
#: table less a window's staged rows, and the whole table
LENGTHS = (0, 1, PAGE, PAGE + 1, ROWS, ROWS + 1, S_MAX - 3, S_MAX)


def operands(Ni, Hi, length, distinct, seed=53):
    """Three slots (the one under test between a short and a long
    neighbour, so its first chunk is started by another slot and it
    starts another's), their pages shuffled over the pool. Index keys as
    the pool caches them, zeros behind Hi (index_row). distinct: how
    many different keys there are (0: every key its own): a few make
    scores that TIE, position against position, at every level."""
    S, P = 3, 3 * MP + 1
    width = -(-Hi // paged.LANES) * paged.LANES
    rs = np.random.RandomState(seed + length)

    def keys(*shape):
        a = rs.randn(distinct, Hi)[rs.randint(0, distinct, shape)] \
            if distinct else rs.randn(*shape, Hi)
        return jnp.pad(jnp.asarray(a, jnp.bfloat16),
                       [(0, 0)] * len(shape) + [(0, width - Hi)])

    kip, wki = keys(L, P, 1, PAGE), keys(L, S, 1, W)
    qi = jnp.asarray(rs.randn(S, 1, Ni, Hi), jnp.float32)
    w = jnp.asarray(rs.randn(S, 1, Ni), jnp.float32)
    table = jnp.asarray(rs.permutation(P - 1).reshape(S, MP), jnp.int32)
    lens = jnp.asarray([PAGE + 5, length, S_MAX - 200], jnp.int32)
    return qi, w, kip, wki, table, lens


def view_scores(qi, w, kip, wki, table, lens, layer):
    """What the call stands in for: the table's keys as a view, the
    staged keys inserted at the flushed length, scored by XLA."""
    Hi = qi.shape[-1]
    kiv = paged.gather_paged_layer(kip, table, layer)[..., :Hi]
    if wki is not None:
        kiv = paged.insert_window_view(kiv, wki[layer][..., :Hi], lens)
    return common.index_scores(qi, w, kiv[:, :, 0])


@pytest.mark.parametrize("length", LENGTHS)
@pytest.mark.parametrize("window", [False, True], ids=["pool", "window"])
@pytest.mark.parametrize("model", sorted(GEOMETRIES))
def test_the_walk_scores_what_the_view_scores(model, window, length):
    """At every position a row may attend the two agree to float32
    rounding (the query keeps its float32: three bfloat16 pieces against
    keys that are bfloat16; one bfloat16 pass would read 4e-3), the
    staged rows' scores stand at the staged rows' positions, what lies
    past them is finite, and select_mask over either is THE SAME mask,
    on keys of which only six are different, so that the k-th score is
    tied many times over."""
    Ni, Hi = GEOMETRIES[model]
    for distinct in (0, 6):
        qi, w, kip, wki, table, lens = operands(Ni, Hi, length, distinct)
        staged = jnp.asarray([2, 3, 1], jnp.int32) if window else 0
        got = walk.index_scores(qi[:, 0], w[:, 0], kip, 1, table, lens,
                                wki if window else None)[:, None]
        want = view_scores(qi, w, kip, wki if window else None, table, lens, 1)
        assert got.shape == want.shape == (3, 1, S_MAX)
        assert got.dtype == jnp.float32 and np.isfinite(np.asarray(got)).all()
        # a row attends its slot's flushed positions and its staged rows
        valid = jnp.arange(S_MAX)[None, None, :] < (lens + staged)[:, None,
                                                                   None]
        # rounding is of the SUMMANDS, relu(s) w a head, whose signs
        # cancel in a score: the same sum under |w| is their size
        size = view_scores(qi, jnp.abs(w), kip, wki if window else None,
                           table, lens, 1)
        err = np.where(valid, np.abs(np.asarray(got - want))
                       / (1 + np.asarray(size)), 0)
        assert err.max() < 2e-6, (distinct, err.max())
        picked = [np.asarray(common.select_mask(a, valid, TOPK))
                  for a in (got, want)]
        np.testing.assert_array_equal(*picked)
        if distinct and length + int(window) > TOPK:
            s = np.where(np.asarray(valid), np.asarray(want), -np.inf)[1, 0]
            assert (s == np.sort(s)[-TOPK]).sum() > 1   # a tie at the k-th
        if not window and length == 0:
            assert not np.asarray(got)[1].any()     # a dead slot: the filler


def test_the_call_notes_itself_and_the_table_may_end_inside_a_chunk():
    """`index_scores:interpret` in an engine's record of its kernels (on
    the chip `:compiled`: /health's kernels.calls), one call a trace;
    the result is S_max wide whatever the chunk (a table of two chunks
    and a part: the columns behind it are cut)."""
    qi, w, kip, _, table, lens = operands(16, 64, 40, 0)
    log = {}
    with record_kernels(log):
        jaxpr = jax.make_jaxpr(lambda *a: walk.index_scores(*a))(
            qi[:, 0], w[:, 0], kip, 0, table, lens)
    assert log == {"index_scores:interpret": 1}
    assert S_MAX % ROWS and jaxpr.out_avals[0].shape == (3, S_MAX)


def test_fits_says_which_pools_the_call_serves(monkeypatch):
    """Interpreted, any token-major pool of a group's pages or more and a
    window no wider than a chunk; compiled, whole tiles besides: a page
    and a window of whole sublane tiles, a key of whole lanes."""
    bf = jnp.bfloat16
    pool = jax.ShapeDtypeStruct((L, 17, 1, PAGE, 128), bf)
    assert walk.fits(pool) and walk.fits(pool, ROWS)
    assert not walk.fits(pool, ROWS + PAGE)
    assert not walk.fits(jax.ShapeDtypeStruct((L, 15, 1, PAGE, 128), bf))
    assert not walk.fits(jax.ShapeDtypeStruct((L, 17, 2, PAGE, 128), bf))
    small = jax.ShapeDtypeStruct((L, 17, 1, 4, 128), bf)
    assert walk.fits(small, 8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert walk.fits(pool, 256) and walk.fits(pool, 64)
    assert not walk.fits(pool, 8) and not walk.fits(small, 128)
    assert not walk.fits(jax.ShapeDtypeStruct((L, 17, 1, PAGE, 64), bf))


@pytest.mark.parametrize("config, reader", [
    ("keye-vl2-30b-a3b", "sparse"), ("glm-5-ep16", "dsa")])
def test_the_benchmark_s_readers_count_the_call(config, reader):
    """`sparse_attn_share` and `dsa_share` sum the selecting path's
    operations, which they tell by a dim of max_seq in a result
    (servebench/sparse_peaks.py, servebench/dsa_peaks.py: not this PR's
    to edit): the call's name as a device trace prints it, with its
    result [slots, max_seq], is one of them in both cells, so the
    shares' seconds include the call's and the rooflines are not
    flattered by a path that dropped out of sight."""
    import json
    from pathlib import Path

    from servebench import dsa_peaks, sparse_peaks
    from servebench.xplane import clean
    cfg = json.loads((Path(sparse_peaks.__file__).parent / "configs"
                      / f"{config}.json").read_text())
    S, M = cfg["serve"]["max_batch"], cfg["serve"]["max_seq"]
    name = clean(f"%index_scores.15 = f32[{S},{M}]{{1,0:T(8,128)}} "
                 "custom-call(%bitcast.3, %reshape.1)")
    if reader == "sparse":
        assert sparse_peaks.is_sparse_op(
            name, sparse_peaks.sparse_patterns(cfg))
    else:
        assert dsa_peaks.is_dsa_op(name, dsa_peaks.dsa_patterns(cfg))
        # and it is not mistaken for the selecting READ, whose seconds
        # `dsa_roofline`'s call-only sum takes
        assert not dsa_peaks.dsa_patterns(cfg)["call"].search(name)
