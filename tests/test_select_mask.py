"""ops/select_mask.py, interpreted: a row's k highest scores found by
COUNTING, against models/common.py select_mask (lax.top_k and a running
count: the plain path it stands in for, cache/paged.py _selection) and
against numpy's stable sort, as EQUAL masks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.cache import paged
from butterfly_tpu.models import common
from butterfly_tpu.ops import record_kernels
from butterfly_tpu.ops import select_mask as counted

#: what a row of scores can hold at its cut: name -> (scores, valid) of
#: one row of n positions for a selection of k, from a RandomState
KINDS = {}


def kind(fn):
    KINDS[fn.__name__] = fn
    return fn


def _live(rs, n, least=0):
    """A causal row: the positions up to a drawn length."""
    return np.arange(n) < rs.randint(least, n + 1)


@kind
def distinct(rs, n, k):
    return rs.randn(n), _live(rs, n, k + 1)


@kind
def ties_astride_the_cut(rs, n, k):
    """A few levels: the k-th score is shared by positions on both
    sides of the cut, and the lower ones are taken."""
    return np.round(rs.randn(n) * 2) / 2, _live(rs, n, k + 1)


@kind
def all_equal(rs, n, k):
    return np.full(n, 0.25), np.ones(n, bool)


@kind
def signed_zeros(rs, n, k):
    """+0.0 beside -0.0 compare equal: one level, taken by position."""
    s = np.where(rs.rand(n) < 0.5, 0.0, -0.0)
    s[rs.rand(n) < 0.1] = -1.0
    return s, np.ones(n, bool)


@kind
def minus_inf_at_valid_positions(rs, n, k):
    """More -inf among the valid than the cut leaves out: some are taken,
    the lower first, and no position that is not valid."""
    s = rs.randn(n)
    s[rs.rand(n) < 0.8] = -np.inf
    return s, rs.rand(n) < 0.9


@kind
def infinities(rs, n, k):
    s = np.round(rs.randn(n))
    s[rs.rand(n) < 0.3] = np.inf
    s[rs.rand(n) < 0.1] = -np.inf
    return s, _live(rs, n, k + 1)


@kind
def denormals(rs, n, k):
    return rs.randint(-3, 4, n) * np.float32(1e-42), np.ones(n, bool)


@kind
def fewer_valid_than_k(rs, n, k):
    return rs.randn(n), np.arange(n) < rs.randint(0, k)


@kind
def exactly_k_valid(rs, n, k):
    return np.round(rs.randn(n)), np.arange(n) < k


@kind
def nothing_valid(rs, n, k):
    return rs.randn(n), np.zeros(n, bool)


@kind
def valid_here_and_there(rs, n, k):
    """No causal prefix: what may be attended lies anywhere."""
    return np.round(rs.randn(n) * 3) / 3, rs.rand(n) < 0.7


def rows(R, n, k, kinds=None, seed=55):
    """scores [R, n] float32 and valid [R, n]: a row a kind, in turn."""
    rs = np.random.RandomState(seed)
    kinds = list(kinds or KINDS)
    made = [KINDS[kinds[r % len(kinds)]](rs, n, k) for r in range(R)]
    return (np.stack([s for s, _ in made]).astype(np.float32),
            np.stack([v for _, v in made]))


def oracle(scores, valid, k):
    """The k valid positions that score highest, equal scores by
    position, the lower first: numpy's stable sort of the valid
    positions' scores, a row at a time."""
    out = np.zeros(scores.shape, bool)
    n = out.shape[-1]
    flat, ok = out.reshape(-1, n), valid.reshape(-1, n)
    for r, s in enumerate(scores.reshape(-1, n)):
        at = np.flatnonzero(ok[r])
        zeroed = s[at] + np.float32(0.0)                    # -0.0 is 0.0
        flat[r, at[np.argsort(-zeroed, kind="stable")[:k]]] = True
    return out


def plain(scores, valid, k):
    return np.asarray(common.select_mask(jnp.asarray(scores),
                                         jnp.asarray(valid), k))


def call(scores, valid, k):
    got = counted.select_mask(jnp.asarray(scores), jnp.asarray(valid), k)
    assert got.dtype == jnp.int32 and got.shape == scores.shape
    got = np.asarray(got)
    assert set(np.unique(got)) <= {0, 1}
    return got != 0


@pytest.mark.parametrize("k", [1, 37, 299], ids=lambda k: f"k{k}")
@pytest.mark.parametrize("name", sorted(KINDS))
def test_the_call_selects_what_the_plain_path_selects(name, k):
    """A small geometry, no whole lanes (interpreted, any will do): five
    rows of one kind, k = 1, a cut inside and k = n - 1."""
    scores, valid = rows(5, 300, k, [name])
    got = call(scores, valid, k)
    assert (got == oracle(scores, valid, k)).all()
    if name == "denormals":
        # the CPU backend's float compares flush them (every score then
        # EQUALS the k-th lax.top_k found) and its sort does not: the
        # plain path's own top_k is what the call agrees with
        idx, ok, _ = common.select_topk(jnp.asarray(scores),
                                        jnp.asarray(valid), k)
        want = np.zeros_like(got)
        np.put_along_axis(want, np.asarray(idx), np.asarray(ok), axis=-1)
        assert (got == want).all()
    else:
        assert (got == plain(scores, valid, k)).all()
    assert (got.sum(-1) == np.minimum(valid.sum(-1), k)).all()


#: the three arrays of scores the cells' programs select over (PERF.md,
#: PR 55): Keye's decode rows, a chunk's rows of one slot, GLM-5's
#: decode rows; and a small one
GEOMETRIES = {
    "keye_decode": ((32, 7168), 2048),
    "chunk": ((1, 32, 7168), 2048),
    "glm5_decode": ((32, 1, 7168), 2048),
    "small": ((2, 3, 256), 40),
}


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_at_the_cells_geometries_through_the_cache_manager(name):
    """cache/paged.py _selection with kernels on, every kind of row
    among its rows: ONE call over the rows flattened to [R, n], the
    selection back in the scores' shape as int32, the plain path's mask
    (kernels off: bool) position for position."""
    shape, k = GEOMETRIES[name]
    n = shape[-1]
    R = int(np.prod(shape[:-1]))
    kinds = [x for x in sorted(KINDS) if x != "denormals"]
    scores, valid = rows(R, n, k, kinds)
    scores, valid = jnp.asarray(scores.reshape(shape)), \
        jnp.asarray(valid.reshape(shape))
    log = {}
    jax.clear_caches()      # the wrapper notes its kernel when it is TRACED
    with record_kernels(log):
        jaxpr = jax.make_jaxpr(
            lambda s, v: paged._selection(s, v, k, True))(scores, valid)
    assert log == {"select_mask:interpret": 1}
    call_, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "jit"
              and e.params["name"] == "select_mask"]
    assert [v.aval.shape for v in call_.outvars] == [(R, n)]
    assert "top_k" not in str(jaxpr) and "cumsum" not in str(jaxpr)
    got = paged._selection(scores, valid, k, True)
    want = paged._selection(scores, valid, k, False)
    assert got.dtype == jnp.int32 and want.dtype == jnp.bool_
    assert got.shape == want.shape == shape
    assert (np.asarray(got != 0) == np.asarray(want)).all()
    assert (np.asarray(want)
            == oracle(np.asarray(scores), np.asarray(valid), k)).all()


@pytest.mark.parametrize("bits", [(1, 1), (2, 2), (4, 4), (2, 1)],
                         ids=lambda b: f"key{b[0]}pos{b[1]}")
def test_the_pass_width_changes_no_selection(bits, monkeypatch):
    """1, 2 or 4 bits a pass (the module's constants, which the builder
    measured): the same k-th key and the same cut."""
    monkeypatch.setattr(counted, "KEY_BITS", bits[0])
    monkeypatch.setattr(counted, "POS_BITS", bits[1])
    k = 100
    scores, valid = rows(len(KINDS), 640, k)
    got = np.asarray(counted._call(
        counted.sort_keys(jnp.asarray(scores), jnp.asarray(valid)), k,
        True)) != 0
    assert (got == oracle(scores, valid, k)).all()


def test_keys_are_in_the_scores_order():
    """sort_keys: int32 keys in float32's order, +-0.0 one key, and a
    position that is not valid under every score, -inf's too."""
    s = np.asarray([-np.inf, -3.5, -1e-42, -0.0, 0.0, 1e-42, 2.0, np.inf],
                   np.float32)
    keys = np.asarray(counted.sort_keys(jnp.asarray(s),
                                        jnp.ones(s.shape, bool)))
    assert keys[3] == keys[4] and (np.diff(np.delete(keys, 3)) > 0).all()
    out = np.asarray(counted.sort_keys(jnp.asarray(s),
                                       jnp.zeros(s.shape, bool)))
    assert (out < keys.min()).all()


def test_fits_is_a_matter_of_shapes(monkeypatch):
    """Interpreted, any rows where there is something to leave out;
    compiled, whole lanes, and a block of rows that fast memory holds.
    Refused, the cache manager keeps lax.top_k and says so."""
    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)
    assert counted.fits(sds(5, 300), 37)
    assert not counted.fits(sds(5, 300), 300)       # nothing to leave out
    monkeypatch.setattr(counted, "resolve_interpret", lambda i: False)
    assert counted.fits(sds(32, 7168), 2048) and counted.fits(sds(4, 128), 8)
    assert not counted.fits(sds(32, 7100), 2048)    # no whole lanes
    assert not counted.fits(sds(32, 7168), 7168)
    assert not counted.fits(sds(20, 1 << 20), 2048)  # no block that fits
    assert counted._block_rows(64, 7168) == 32
    assert counted._block_rows(24, 7168) == 8
    assert counted._block_rows(256, 32768) == 16
    scores, valid = rows(4, 200, 9)
    log = {}
    with record_kernels(log):
        got = paged._selection(jnp.asarray(scores), jnp.asarray(valid), 9,
                               True)
    assert log == {"dense_fallback": 1} and got.dtype == jnp.bool_
    assert (np.asarray(got) == oracle(scores, valid, 9)).all()


@pytest.mark.parametrize("config, reader", [
    ("keye-vl2-30b-a3b", "sparse"), ("glm-5-ep16", "dsa")])
def test_the_benchmark_s_readers_count_the_call(config, reader):
    """`sparse_attn_share` and `dsa_share` sum the selecting path's
    operations, which they tell by a dim of max_seq in a result
    (servebench/sparse_peaks.py, servebench/dsa_peaks.py: not this PR's
    to edit): the call as a device trace prints it, and the fusion that
    makes its keys, are among them in both cells, as the sort and the
    running count were. The path did not drop out of the shares' sight
    with them."""
    import json
    from pathlib import Path

    from servebench import dsa_peaks, sparse_peaks
    from servebench.xplane import clean
    cfg = json.loads((Path(sparse_peaks.__file__).parent / "configs"
                      / f"{config}.json").read_text())
    S, M = cfg["serve"]["max_batch"], cfg["serve"]["max_seq"]
    for text in (f"%select_mask.15 = s32[{S},{M}]{{1,0:T(8,128)S(1)}} "
                 "custom-call(%xor_select_fusion.3)",
                 f"%xor_select_fusion.3 = s32[{S},{M}]{{1,0:T(8,128)S(1)}}"
                 " fusion(%index_scores.15, %iota.1)"):
        name = clean(text)
        if reader == "sparse":
            assert sparse_peaks.is_sparse_op(
                name, sparse_peaks.sparse_patterns(cfg))
        else:
            assert dsa_peaks.is_dsa_op(name, dsa_peaks.dsa_patterns(cfg))
            # and neither is mistaken for the selecting READ, whose
            # seconds `dsa_roofline`'s call-only sum takes
            assert not dsa_peaks.dsa_patterns(cfg)["call"].search(name)
