"""GLM-5's family on the CPU at a toy's size with every mechanism
present: latent attention whose key part, rotary part and values have
three widths, a lightning indexer fed from the QUERY LATENT whose heads
rotate only their first rotary-width dims and whose top-k binds inside
the toy's contexts, the latent pool and the index-key pool side by side,
a leading dense layer, sigmoid routing with a selection bias and a
scale, a shared expert, and ONE chip's share of the experts. LOGITS
against the plain float32 reference (servebench/references/glm5_f32.py),
which shares no code with the program and takes the EXPANDED form."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import butterfly_tpu.models.common as common
from butterfly_tpu.cache.paged import (
    LANES, init_kv_window, init_paged_cache, paged_forward, pool_layout,
    pool_row)
from butterfly_tpu.core.config import (
    PRESETS, ModelConfig, RuntimeConfig, glm5, tiny)
from butterfly_tpu.models.common import (
    Model, expert_load, init_cache, mlp_block, moe_block)
from butterfly_tpu.quant.int8 import (
    init_params_by_leaf, is_quantized_leaf, quantize_int8)
from servebench.references import glm5_f32 as ref
from test_joyai import (RT, T, Packed, err, forward,  # noqa: F401
                        leaf_of, scripted_run)

ROOT = Path(__file__).resolve().parent.parent
CFG = tiny("glm5", dtype="float32", param_dtype="float32")
#: rms difference over the standard deviation of the reference's logits
#: at the position. float32 on both sides on the CPU reads 1e-7 to 1e-6
#: (sums in another order: the absorbed products against the expanded);
#: ONE position selected in place of another reads 1e-3 and more (the
#: mutations below), a bfloat16 program 1e-2
TOL = 2e-5


def file_config(cfg: ModelConfig, **over) -> dict:
    """The published keys the reference reads, as a configuration file
    of `cfg` would hold them (and its `model` group's share)."""
    out = dict(
        rms_norm_eps=cfg.norm_eps, num_hidden_layers=cfg.num_layers,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        kv_lora_rank=cfg.kv_lora_rank,
        rope_parameters={"rope_theta": cfg.rope_theta},
        index_topk=cfg.index_topk,
        num_experts_per_tok=cfg.num_experts_per_tok,
        first_k_dense_replace=cfg.first_k_dense,
        routed_scaling_factor=cfg.routed_scaling_factor,
        model={"experts_held": cfg.experts_held,
               "experts_first": cfg.experts_first})
    out.update(over)
    return out


def seeded_params(cfg=CFG):
    p = Model(cfg).init(jax.random.PRNGKey(0))
    # norms that are not all ones, sublayers loud enough to move the
    # stream off the embedding, attention scores spread enough that a
    # wrong rotation, scale or mask moves the logits, and an indexer
    # loud enough that its order of the positions is no near-tie
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))

    def jitter(a):
        return 1 + 0.3 * jax.random.normal(next(keys), a.shape)

    at, ix = p["layers"]["attn"], p["layers"]["index"]
    for g in (p["layers"]["ln1"], p["layers"]["ln2"], at["q_norm"],
              at["kv_norm"], p["final_norm"], ix["k_norm"]):
        g["scale"] = jitter(g["scale"])
    ix["k_norm"]["bias"] = 0.2 * jax.random.normal(
        next(keys), ix["k_norm"]["bias"].shape)
    at["wo"] = at["wo"] * 40
    at["w_uq"] = at["w_uq"] * 20
    at["w_uk"] = at["w_uk"] * 20
    at["w_dkv"] = at["w_dkv"] * 20
    ix["w_qi"] = ix["w_qi"] * 40
    ix["w_w"] = ix["w_w"] * 40
    p["dense"]["mlp"]["w_down"] = p["dense"]["mlp"]["w_down"] * 40
    p["sparse"]["moe"]["w_down"] = p["sparse"]["moe"]["w_down"] * 40
    p["sparse"]["shared"]["w_down"] = p["sparse"]["shared"]["w_down"] * 40
    return p


@pytest.fixture(scope="module")
def params():
    return seeded_params()


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(3).randint(1, CFG.vocab_size, (3, T))


def reference(params, tokens, cfg=CFG, **over):
    return np.asarray(ref.logits(np.asarray(tokens), leaf_of(params),
                                 file_config(cfg, **over)))


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's full forward of the three sequences: [3, T, V]."""
    return np.stack([reference(params, t) for t in tokens])


def worst(got, want, rows=range(T)):
    return max(err(got[p], want[p]) for p in rows)


# -- the contiguous cache -----------------------------------------------------

def test_the_selection_binds_and_moves_the_reference(params, tokens, want):
    """Past the toy's index_topk 8 the reference's rows depend on WHICH
    positions were selected: with every position attended (topk past
    the context) the rows up to position 7 stay and the later ones
    move."""
    assert np.std(want) > 0.05 and CFG.index_topk == 8 < T
    dense = reference(params, tokens[0], index_topk=1000)
    assert worst(dense, want[0], range(8)) < 1e-6
    assert min(err(dense[p], want[0, p]) for p in range(12, T)) > 1e-3


@pytest.mark.parametrize("fresh", [False, True], ids=["absorbed", "expanded"])
def test_contiguous_forward_whole(params, tokens, want, fresh):
    cache = init_cache(CFG, 3, 64)
    got, cache = forward(params, CFG, jnp.asarray(tokens), cache, fresh=fresh)
    for s in range(3):
        assert worst(got[s], want[s]) < TOL, s
    assert cache.v is None and cache.k.shape == (3, 3, 64, 1, CFG.latent_row)
    assert cache.ki.shape == (3, 3, 64, CFG.index_head_dim)


def test_prefill_then_decode_through_the_cache_past_topk(params, tokens,
                                                         want):
    """servebench/refcheck.py's drive, on past the toy's index_topk: a
    prefill of 6 (no selection yet), then decode calls of one token,
    each ABSORBED over the cached rows its indexer selected among the
    cached index keys."""
    cache = init_cache(CFG, 3, 64)
    got, cache = forward(params, CFG, jnp.asarray(tokens[:, :6]), cache)
    assert err(got[1, -1], want[1, 5]) < TOL
    for j in range(6, 30):
        got, cache = forward(params, CFG, jnp.asarray(tokens[:, j:j + 1]),
                             cache)
        for s in range(3):
            assert err(got[s, 0], want[s, j]) < TOL, (s, j)


def _from_h(monkeypatch):
    """The indexer's queries fed from the layer's normed input h in
    place of the query latent c_q (Keye's form)."""
    orig = common.index_proj
    monkeypatch.setattr(common, "index_proj", lambda x, lp, cfg, cos, sin,
                        cq=None: orig(x, lp, cfg, cos, sin, x))
    return {}


def _all_rotated(monkeypatch):
    """All the index head's dims rotated by pairs, as if the head were
    one rotary part (the REFERENCE is mutated: the program then differs
    from it as a program that did so would from the true one)."""
    monkeypatch.setattr(ref, "rotate_first",
                        lambda x, rope, theta: ref.rotate_pairs(x, theta))
    return {}


def _topk_minus_1(monkeypatch):
    return {"index_topk": CFG.index_topk - 1}


@pytest.mark.parametrize("fault", [_from_h, _all_rotated, _topk_minus_1],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_a_wrong_indexer_fails_the_tolerance(fault, monkeypatch):
    """TOL is tight enough that an indexer fed from h instead of c_q,
    all index dims rotated, or a selection of topk - 1 fails it: at a
    query latent as wide as the hidden size (so that the same w_qi can
    read either), the clean program is the reference's and each fault
    is not, past index_topk and nowhere before."""
    cfg = CFG.replace(q_lora_rank=CFG.hidden_size)
    p = seeded_params(cfg)
    seq = np.random.RandomState(5).randint(1, cfg.vocab_size, (1, 30))

    def run(**over):
        got, _ = common.forward(p, cfg, jnp.asarray(seq),
                                init_cache(cfg, 1, 32))
        return got[0], reference(p, seq[0], cfg, **over)

    got, want = run()
    assert worst(got, want, range(30)) < TOL
    got, want = run(**fault(monkeypatch))
    assert worst(got, want, range(cfg.index_topk - 1)) < TOL
    assert worst(got, want, range(cfg.index_topk, 30)) > 50 * TOL


# -- the two pools ------------------------------------------------------------

def test_the_pool_holds_a_latent_row_and_an_index_key_a_token():
    cache = init_paged_cache(CFG, RT)
    assert pool_row(CFG) == (1, LANES) and pool_layout(CFG) == "latent"
    assert cache.k_pages.shape == (3, 3 * 16 + 1, 1, 4, LANES)
    assert cache.ki_pages.shape == (3, 3 * 16 + 1, 1, 4, LANES)
    assert cache.v_pages is None and cache.k_scale_pages is None
    win = init_kv_window(cache, 8)
    assert win.v is None and win.k.shape == (3, 3, 1, 8, LANES)
    assert win.ki.shape == (3, 3, 1, 8, LANES)
    # the published sizes: a row of 576 values in five lane tiles, an
    # index key of 128
    big = glm5()
    assert big.latent_row == 576 and pool_row(big) == (1, 640)
    assert big.index_head_dim * 2 == 256 and big.index_rope_dim == 64
    with pytest.raises(NotImplementedError, match="int8 KV cache"):
        init_paged_cache(CFG, dataclasses.replace(RT, kv_quant="int8"))


# -- the packed step ----------------------------------------------------------

@pytest.fixture(scope="module")
def scripted(params, tokens):
    return scripted_run(params, tokens, cfg=CFG)


@pytest.mark.parametrize("windowed", [False, True], ids=["pool", "window"])
def test_packed_steps_chunks_filler_decode_rows_and_a_reused_slot(
        params, tokens, want, windowed, scripted):
    """Chunks and decode rows in ONE step, through both pools, the
    window (its rows and its index keys) and its flush, and with the
    window off straight into the pools: every row the head read is the
    reference's, contexts to 34 against a top-k of 8, in a slot that
    held another stream before."""
    out, drv, read = scripted if windowed \
        else scripted_run(params, tokens, windowed, cfg=CFG)
    assert len(out) > 30 and {s for s, _, _ in out} == {0, 1, 2}
    for s, pos, row in out:
        assert err(row, want[s, pos]) < TOL, (s, pos)
    # the load: three of the experts', then the decode rows' four: the
    # rows that had anything to attend, what they could attend, what
    # they attended (8 at most each) and what the read moved (kernels
    # off: the slot's whole view), the mean over the three layers
    loads = np.stack(drv.loads)
    assert loads.shape[1] == 7
    live = loads[:, 4]
    np.testing.assert_array_equal(live, np.asarray(read))
    assert live[:4].sum() == 0 and live[4] == 21
    np.testing.assert_array_equal(
        loads[:, 5], [min(8, 21) if i == 4 else s for i, s in enumerate(
            loads[:, 5])])
    assert (loads[:, 5] <= 8 * loads[:, 3]).all()
    np.testing.assert_array_equal(loads[:, 6], 64 * loads[:, 3])


def test_the_masked_call_is_the_jnp_read_through_the_packed_run(
        params, tokens, want):
    """Kernels on (interpreted on the CPU): a decode row's read is the
    Pallas call latent_select_attention over the slot's live pages and
    the window, its selection a mask, and every row is still the
    reference's; what it moves is the LIVE rows."""
    from butterfly_tpu.ops import record_kernels
    log = {}
    with record_kernels(log):
        out, drv, read = scripted_run(params, tokens, cfg=CFG,
                                      use_kernel=True)
    assert log.get("latent_select_win:interpret") \
        and "dense_fallback" not in log and "latent_win:interpret" not in log
    for s, pos, row in out:
        assert err(row, want[s, pos]) < TOL, (s, pos)
    loads = np.stack(drv.loads)
    np.testing.assert_array_equal(loads[:, 6], loads[:, 4])


@pytest.mark.parametrize("windowed", [False, True], ids=["pool", "window"])
def test_the_masked_call_alone_against_the_gathered_read(windowed):
    """ops/latent_attention.py latent_select_attention in interpret mode
    at ONE geometry (64 heads over rows of 256 lanes, pages of 16, a
    chunk of 4 pages; slots dead, short, a chunk and a page, two chunks
    and the whole table) against the gathered rows under the same mask:
    a selection of every third live position, and the window's staged
    rows masked by the selection at THEIR positions. With every
    position selected it is latent_attention to the bit."""
    import butterfly_tpu.ops.latent_attention as la
    lens, wc = [0, 5, 80, 130, 144], [0, 8, 1, 3, 0]
    L, page, R, rank, Nq, S, W, mp = 2, 16, 256, 128, 64, 5, 8, 9
    P = S * mp + 1
    rs = np.random.RandomState(0)
    pool = jnp.asarray(rs.randn(L, P, 1, page, R), jnp.float32)
    q = jnp.asarray(rs.randn(S, Nq, R), jnp.float32)
    table = jnp.asarray(rs.permutation(P - 1).reshape(S, mp), jnp.int32)
    lens, wc = jnp.asarray(lens, jnp.int32), jnp.asarray(wc, jnp.int32)
    win = jnp.asarray(rs.randn(L, S, 1, W, R), jnp.float32)
    sel = jnp.asarray(rs.rand(S, mp * page) < 0.34)
    assert la.fits(pool, rank, True, W)
    assert not la.fits(pool, rank, True, la.PAGES_PER_CHUNK * page + 1)
    extra = (win, wc) if windowed else ()
    pos = jnp.arange(mp * page)[None]
    for layer in range(L):
        got = la.latent_select_attention(
            q, pool, layer, table, lens, sel, *extra, rank=rank, scale=0.1)
        rows = pool[layer][table][:, :, 0].reshape(S, mp * page, R)
        live = pos < lens[:, None]
        if windowed:
            # the staged rows stand at positions lens .. lens + wc - 1
            at = lens[:, None] + jnp.arange(W)[None]
            rows = rows.at[jnp.arange(S)[:, None], at].set(
                win[layer, :, 0], mode="drop")
            live = pos < (lens + wc)[:, None]
        live = live & sel
        s = jnp.einsum("snr,scr->snc", q, rows) * 0.1
        p = jax.nn.softmax(jnp.where(live[:, None], s, -1e30), -1) \
            * live[:, None]
        want = jnp.einsum("snc,scr->snr", p, rows[..., :rank])
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5
        dead = ~np.asarray(live).any(1)
        assert not np.asarray(got)[dead].any() and dead.any()
        everything = la.latent_select_attention(
            q, pool, layer, table, lens, jnp.ones_like(sel), *extra,
            rank=rank, scale=0.1)
        plain = la.latent_attention(q, pool, layer, table, lens, *extra,
                                    rank=rank, scale=0.1)
        np.testing.assert_array_equal(np.asarray(everything),
                                      np.asarray(plain))


@pytest.mark.parametrize("select", ["index", "all", "recent"])
def test_both_callers_of_the_indexer_s_read_side_score_alike(select):
    """cache/paged.py _index_selection is ONE function for the selection
    over keys and values (Keye's: the staged index keys INSERTED into
    the table's view, then scored) and over latent rows (`scatter`: the
    staged keys scored where they lie, their scores taking their
    positions). The same scores to the bit, the same count of attended
    positions and of live ones, under the parity tool's controls too;
    a staged run that passes the table's end is dropped by both."""
    from butterfly_tpu.cache import paged
    L, page, Hi, Ni, S, W, mp, Tq = 2, 4, 16, 2, 3, 5, 6, 3
    P, S_max = S * mp + 1, mp * page
    rs = np.random.RandomState(5)
    kip = jnp.asarray(rs.randn(L, P, 1, page, Hi), jnp.float32)
    table = jnp.asarray(rs.permutation(P - 1).reshape(S, mp), jnp.int32)
    qi = jnp.asarray(rs.randn(S, Tq, Ni, Hi), jnp.float32)
    w = jnp.asarray(rs.randn(S, Tq, Ni), jnp.float32)
    wki = jnp.asarray(rs.randn(S, 1, W, Hi), jnp.float32)
    base = jnp.asarray([0, 9, S_max - 2], jnp.int32)
    mask = jnp.arange(S_max)[None, None] < (
        base[:, None, None] + 1 + jnp.arange(Tq)[None, :, None])
    # the window whole, as the function takes it: layer 1 holds the keys
    window = paged.KVWindow(
        k=jnp.zeros((L, S, 1, W, 1)), v=None,
        ki=jnp.stack([jnp.zeros_like(wki), wki]))

    def selection(win, scatter):
        return paged._index_selection(
            (qi, w, kip), win, 1, page_table=table, positions=base[:, None],
            mask=mask, active=None, topk=8, select=select, scatter=scatter,
            use_kernel=False)

    staged_at_base = (window, jnp.zeros((S,), jnp.int32), None)
    got = [selection(staged_at_base, scatter) for scatter in (False, True)]
    for a, b in zip(*got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    scores, topk, live = got[0]
    assert topk == (S_max if select == "all" else 8)
    np.testing.assert_array_equal(np.asarray(live), np.asarray(mask).sum(-1))
    if select == "index":
        # the staged keys' scores stand at base .. base + W - 1, and a
        # window leaves every other position's score as the pool's
        pool, _, _ = selection(None, True)
        staged = np.asarray(common.index_scores(qi, w, wki[:, 0]))
        for s_, b_ in enumerate(np.asarray(base)):
            n = min(W, S_max - b_)
            np.testing.assert_array_equal(
                np.asarray(scores)[s_, :, b_:b_ + n], staged[s_, :, :n])
            rest = np.r_[0:b_, b_ + n:S_max]
            np.testing.assert_array_equal(np.asarray(scores)[s_][:, rest],
                                          np.asarray(pool)[s_][:, rest])


# -- int8 ---------------------------------------------------------------------

def test_int8_weights_serve_the_reference_over_the_same_codes(tokens):
    """Weight-only int8: the reference reads the same codes times
    scales, so what is left is the program's arithmetic; the indexer's
    leaves stay float by their path, as a router's."""
    p = quantize_int8(seeded_params(), CFG)
    for name in ("w_qi", "w_ki", "w_w"):
        assert not is_quantized_leaf(p["layers"]["index"][name])
    assert is_quantized_leaf(p["sparse"]["moe"]["w_gate"])
    want = reference(p, tokens[0])
    cache = init_cache(CFG, 1, 64)
    got, cache = forward(p, CFG, jnp.asarray(tokens[:1, :20]), cache)
    assert err(got[0, 19], want[19]) < TOL
    got, _ = forward(p, CFG, jnp.asarray(tokens[:1, 20:21]), cache)
    assert err(got[0, 0], want[20]) < TOL


def test_weights_built_leaf_by_leaf_have_the_share_s_tree():
    cfg = CFG.replace(experts_held=2, experts_first=4)
    want = jax.eval_shape(lambda: Model(cfg).init(jax.random.PRNGKey(0)))
    got = init_params_by_leaf(cfg, jax.random.PRNGKey(0), quant="int8")
    moe = got["sparse"]["moe"]
    assert moe["w_gate"]["q8"].shape == (2, 2, 64, 32)
    assert moe["router"].shape == (2, 64, 8) == want["sparse"]["moe"][
        "router"].shape
    assert got["layers"]["index"]["w_qi"].shape == (3, 48, 2, 16)
    assert got["layers"]["index"]["w_qi"].dtype == jnp.float32


# -- the share ----------------------------------------------------------------

SHARES = 4      # of 8 experts, two each


def share_of(params, cfg, i, n=SHARES):
    """(cfg, params) of chip i of n: the same router and bias, its own
    experts' leaves."""
    held = cfg.num_experts // n
    cut = dict(params["sparse"]["moe"])
    for name in ("w_gate", "w_up", "w_down"):
        cut[name] = cut[name][:, i * held:(i + 1) * held]
    return cfg.replace(experts_held=held, experts_first=i * held), \
        {**params, "sparse": {**params["sparse"], "moe": cut}}


def test_the_shares_add_up_to_the_uncut_layer_of_the_reference(params):
    """The share tied to the model: the four shares' routed parts (the
    program's expert layer, told which two of the eight it holds) and
    the shared expert counted ONCE are the reference's uncut layer, and
    each chip's layer is the reference's given the same share."""
    h = jax.random.normal(jax.random.PRNGKey(7), (1, 24, CFG.hidden_size))
    j = 1
    at = lambda tree: jax.tree.map(lambda a: a[j], tree)  # noqa: E731
    whole = np.asarray(ref.feed_forward(h[0], leaf_of(params), j,
                                        file_config(CFG)))
    shared = mlp_block(h, at(params["sparse"]["shared"]), CFG)[0]
    total, parts = np.asarray(shared, np.float64), []
    for i in range(SHARES):
        cfg, p = share_of(params, CFG, i)
        routed = moe_block(h, at(p["sparse"]["moe"]), cfg)[0]
        parts.append(float(jnp.abs(routed).max()))
        total = total + np.asarray(routed)
        one = np.asarray(ref.feed_forward(h[0], leaf_of(p), j,
                                          file_config(cfg)))
        assert err(np.asarray(routed + shared), one) < TOL, i
    assert err(total, whole) < TOL and sum(p > 0 for p in parts) >= 3
    # and the uncut layer of the program is the same sum
    full = moe_block(h, at(params["sparse"]["moe"]), CFG)[0] + shared
    assert err(np.asarray(full), whole) < TOL


def test_a_share_s_logits_are_the_reference_s_given_the_same_share(
        params, tokens, want):
    """The partial result goes on to the next layer: the whole model
    with experts 2-3 of 8 held, contiguous and through the packed step,
    against the reference handed the same share; it is NOT the uncut
    model's."""
    cfg, p = share_of(params, CFG, 1)
    mine = reference(p, tokens[0], cfg)
    assert err(mine[30], want[0, 30]) > 1e-2
    got, _ = forward(p, cfg, jnp.asarray(tokens[:1]), init_cache(cfg, 1, 64))
    assert worst(got[0], mine) < TOL
    drv = Packed(p, cfg)
    for lo in range(0, 18, 6):
        row = drv.step({}, (0, tokens[0, lo:lo + 6]))
    assert err(row[0], mine[17]) < TOL
    for pos in range(18, 24):
        row = drv.step({0: tokens[0, pos]})
        assert err(row[0], mine[pos]) < TOL, pos
    # the load: the experts' three over the two HELD, the read's four,
    # then of the step's rows x 3 assignments those that fell on a held
    # expert, and all
    loads = np.stack(drv.loads)
    assert loads.shape[1] == 9
    np.testing.assert_array_equal(loads[:, 8], [18, 18, 18] + [3] * 6)
    assert (loads[:, 7] <= loads[:, 8]).all() and loads[:, 7].sum() > 0
    assert (loads[:, 0] <= 2).all()
    np.testing.assert_allclose(loads[:, 2] * 2, loads[:, 7], rtol=1e-6)


def test_expert_load_counts_over_the_held():
    logits = jnp.asarray(np.random.RandomState(0).randn(2, 5, 8), jnp.float32)
    ok = jnp.ones((2, 5), bool).at[1, 3:].set(False)
    whole = np.asarray(expert_load(logits, 3, ok))
    parts = [np.asarray(expert_load(logits, 3, ok, held=(f, 2)))
             for f in (0, 2, 4, 6)]
    assert all(p[4] == 8 * 3 for p in parts)
    assert sum(p[3] for p in parts) == 8 * 3
    assert sum(p[0] for p in parts) == whole[0]
    assert max(p[1] for p in parts) == whole[1]


#: one family a kind of expert layer: softmax routing, an indexer beside
#: it, sigmoid routing with a bias and a shared expert over layer runs
#: (the others' layers are these with another mixer or residual path:
#: tracing them unjitted costs 10 s each)
MOE_ARCHS = ["mixtral", "keye", "joyai", "glm5"]


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_every_expert_held_is_the_whole_layer_bit_for_bit(arch):
    """Held count 0 is every other preset's program as it was: no code
    of the share runs. And a share that holds ALL the experts gives the
    same logits to the bit, so the share's path is the whole layer's
    arithmetic and nothing else."""
    cfg = tiny(arch, dtype="float32")
    assert cfg.experts_held == 0 and cfg.local_experts == cfg.num_experts
    p = Model(cfg).init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.RandomState(1).randint(1, 258, (2, 12)))
    base, _ = common.forward(p, cfg, toks, init_cache(cfg, 2, 16))
    full = cfg.replace(experts_held=cfg.num_experts)
    assert jax.tree.map(jnp.shape, Model(full).init(jax.random.PRNGKey(0))) \
        == jax.tree.map(jnp.shape, p)
    got, _ = common.forward(p, full, toks, init_cache(full, 2, 16))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


# -- what carries neither kind of row refuses the model by name ---------------

def _engine(**rt):
    from butterfly_tpu.engine.serving import ServingEngine
    mesh = rt.pop("mesh", None)
    return ServingEngine(Model(CFG), seeded_params(), RuntimeConfig(
        max_batch_size=2, max_seq_len=64, page_size=4, **rt), mesh=mesh)


def _mesh(axis):
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:2]), (axis,))


def _lane_wide(forward):
    """cache/paged.py's lane-wide forwards refuse the model before they
    read an argument: what still calls them (the speculative block's
    verify) does not carry what this model caches."""
    return forward(None, CFG, *[None] * 4)


REFUSALS = {
    "int8 KV cache": lambda: _engine(kv_quant="int8"),
    "a device mesh \\(tensor=2\\)": lambda: _engine(mesh=_mesh("tensor")),
    "a device mesh \\(stage=2\\)": lambda: _engine(mesh=_mesh("stage")),
    "a device mesh \\(seq=2\\)": lambda: _engine(mesh=_mesh("seq")),
    "export": lambda: _engine().read_pages([0]),
    "import": lambda: _engine().write_pages([0], None, None),
    "host KV tier": lambda: _engine(prefix_caching=True, host_kv_tier_mb=1),
    "prefix caching": lambda: _engine(prefix_caching=True),
    "speculative": lambda: _engine(speculative_gamma=2),
    "paged_forward, paged_forward_window": lambda: _lane_wide(paged_forward),
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_refused_by_name(what):
    with pytest.raises(NotImplementedError, match=what):
        REFUSALS[what]()


FIELD_ERRORS = [
    (dict(experts_first=2), "experts_first without experts_held"),
    (dict(experts_held=3, experts_first=6), "lies inside the experts"),
    (dict(experts_held=9), "lies inside the experts"),
    (dict(experts_held=2, moe_impl="ep", router_score="softmax",
          router_bias=False, routed_scaling_factor=0.0, first_k_dense=0),
     "moe_impl 'ep'\\) does not carry experts_held"),
    (dict(index_head_dim=4), "index_head_dim 4 under qk_rope_head_dim 8"),
    (dict(index_heads=0), "come together"),
]


@pytest.mark.parametrize("kw, what", FIELD_ERRORS,
                         ids=[w[:24] for _, w in FIELD_ERRORS])
def test_the_new_fields_are_checked_together(kw, what):
    with pytest.raises(ValueError, match=what):
        CFG.replace(**kw)


def test_a_dense_model_has_no_share():
    with pytest.raises(ValueError, match="lies inside the experts"):
        tiny("llama", experts_held=2)


def test_the_ep_branch_adds_the_shared_expert():
    """models/common.py ffn_block: the expert-parallel branch returned
    before the shared expert was added. On one device moe_block_ep is
    the dense block, so the two implementations now agree on a model
    with a shared expert."""
    cfg = tiny("granite_hybrid", dtype="float32")
    p = Model(cfg).init(jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda a: a[0], p["layers"])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 6, cfg.hidden_size))
    dense = common.ffn_block(h, lp, cfg)
    ep = common.ffn_block(h, lp, cfg.replace(moe_impl="ep"))
    np.testing.assert_allclose(np.asarray(ep), np.asarray(dense), rtol=1e-5,
                               atol=1e-6)
    shared = mlp_block(h, lp["shared"], cfg)
    assert float(jnp.abs(shared).max()) > 1e-3


# -- through the scheduler: the server's own path -----------------------------

def test_served_tokens_and_the_tick_record_of_a_share(params):
    """Two requests over two slots through the continuous scheduler
    (mixed blocks, the window with both kinds of staged row, its
    flush), experts 4-5 of 8 held: every served token is the greedy
    token of the reference given the same share, past the toy's top-k,
    and the tick records carry the indexer's three and the share's
    two."""
    from test_joyai import served
    cfg, p = share_of(params, CFG, 2)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, cfg.vocab_size, n).tolist() for n in (5, 13)]
    # sequences of T tokens, as `want`'s: on the CPU the reference's
    # unjitted operations compile once a shape, and this test is
    # otherwise the file's dearest
    new = (T - 5, T - 13)
    sched, reqs = served(p, prompts, new, cfg=cfg)
    for prompt, req, n in zip(prompts, reqs, new):
        assert len(req.output) == n
        seq = list(prompt) + list(req.output)
        rows = reference(p, seq, cfg)
        for i, tok in enumerate(req.output):
            row = rows[len(prompt) + i - 1]
            order = np.argsort(row)
            if row[order[-1]] - row[order[-2]] > 1e-4 * np.std(row):
                assert tok == order[-1], i
    ticks = [t for t in sched.ticklog.dump()["ticks"]
             if t["expert_rows_routed"] is not None]
    assert ticks and all(t["latent_rows"] is None for t in ticks)
    assert all(t["expert_rows_local"] <= t["expert_rows_routed"]
               for t in ticks)
    assert sum(t["expert_rows_routed"] for t in ticks) >= 3 * (
        sum(len(q) for q in prompts) + sum(new) - 2)
    seen = [t for t in ticks if t["kv_rows_live"] is not None]
    assert seen and all(t["kv_rows_selected"] <= 8 for t in seen)
    assert max(t["kv_rows_live"] for t in seen) > 8
    assert all(t["experts_touched"] <= 2 for t in seen)
    snap = sched.registry.snapshot()
    assert snap["expert_rows_local_total"] == pytest.approx(
        sum(t["expert_rows_local"] for t in ticks))
    assert snap["latent_rows_read"] == 0 and snap["kv_rows_selected"] > 0


def test_a_whole_model_s_ticks_carry_no_share():
    from test_joyai import served
    cfg = tiny("joyai", dtype="float32")
    sched, _ = served(Model(cfg).init(jax.random.PRNGKey(0)),
                      [[1, 2, 3, 4, 5]], (6,), cfg=cfg)
    ticks = sched.ticklog.dump()["ticks"]
    assert ticks and all(t["expert_rows_local"] is None
                         and t["expert_rows_routed"] is None for t in ticks)
    assert any(t["latent_rows"] for t in ticks)
    assert sched.registry.snapshot()["expert_rows_local_total"] == 0


def test_the_checkpoint_loader_refuses_the_family_by_name(tmp_path):
    from butterfly_tpu.ckpt import load_checkpoint
    with pytest.raises(ValueError, match="no checkpoint converter for "
                       "arch 'glm5'"):
        load_checkpoint(str(tmp_path), CFG)


# -- the preset, the file, its arithmetic -------------------------------------

def the_file() -> dict:
    return json.loads((ROOT / "servebench" / "configs"
                       / "glm-5-ep16.json").read_text())


def test_preset_is_the_published_model():
    cfg = PRESETS["glm-5"]()
    pub = json.loads((ROOT / "servebench" / "pins" / "glm-5-ep16.json")
                     .read_text())["published"]
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads) == (
        pub["num_hidden_layers"], pub["hidden_size"],
        pub["num_attention_heads"]) == (78, 6144, 64)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.q_lora_rank, cfg.kv_lora_rank) == tuple(
        pub[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                         "q_lora_rank", "kv_lora_rank"))
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (
        pub["index_n_heads"], pub["index_head_dim"], pub["index_topk"])
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.first_k_dense,
            cfg.expert_width, cfg.intermediate_size, cfg.vocab_size) == (
        pub["n_routed_experts"], pub["num_experts_per_tok"],
        pub["first_k_dense_replace"], pub["moe_intermediate_size"],
        pub["intermediate_size"], pub["vocab_size"])
    assert cfg.rope_theta == pub["rope_parameters"]["rope_theta"]
    assert cfg.norm_eps == pub["rms_norm_eps"] and cfg.experts_held == 0
    assert cfg.attn_scale == pytest.approx(256 ** -0.5)


def test_the_file_s_arithmetic():
    """What the configuration file says one chip holds, reckoned from
    its own keys in this repo's bytes (weight-only int8: a byte a
    parameter for projections, experts, shared expert and head; two for
    the router and its bias, the indexer, norms and embedding): 9.05 GB
    of weights and 3.88 GB of cache, 81 % of a v5e's 16 GB."""
    from servebench.launcher import model_fields
    f = the_file()
    cfg = ModelConfig(**model_fields(f))
    D, L, Ld = cfg.hidden_size, cfg.num_layers, cfg.first_k_dense
    assert (L, Ld, cfg.num_experts, cfg.experts_held, cfg.experts_first,
            cfg.vocab_size) == (11, 1, 256, 16, 0, 19360)
    attn = D * cfg.q_lora_rank \
        + cfg.q_lora_rank * cfg.num_heads * cfg.qk_head_dim \
        + D * cfg.latent_row + cfg.kv_lora_rank * cfg.num_heads * (
            cfg.qk_nope_head_dim + cfg.v_head_dim) \
        + cfg.num_heads * cfg.v_head_dim * D
    assert attn == 165_019_648                              # 165.0 M
    index = cfg.q_lora_rank * cfg.index_heads * cfg.index_head_dim \
        + D * cfg.index_head_dim + D * cfg.index_heads
    assert index == 9_371_648                               # 9.37 M
    expert = 3 * D * cfg.expert_width
    assert expert == 37_748_736 and 3 * D * cfg.intermediate_size \
        == 226_492_416
    floats = 2 * (index + D * cfg.num_experts + cfg.num_experts
                  + 2 * D + cfg.q_lora_rank + cfg.kv_lora_rank
                  + 2 * cfg.index_head_dim)
    sparse = attn + expert * (1 + cfg.experts_held) + floats
    assert sparse / 1e6 == pytest.approx(828.6, abs=0.1)
    dense = attn + 3 * D * cfg.intermediate_size + 2 * (
        index + 2 * D + cfg.q_lora_rank + cfg.kv_lora_rank
        + 2 * cfg.index_head_dim)
    assert dense / 1e6 == pytest.approx(410.3, abs=0.1)
    head, embed = D * cfg.vocab_size, 2 * D * cfg.vocab_size
    assert round(head / 1e6, 1) == 118.9 and round(embed / 1e6, 1) == 237.9
    weights = Ld * dense + (L - Ld) * sparse + head + embed
    assert round(weights / 1e9, 2) == 9.05
    serve = f["serve"]
    pages = serve["max_batch"] * serve["max_seq"] // serve["page_size"]
    assert pages == 14336
    row = pool_row(cfg)[1] * 2 + cfg.index_head_dim * 2
    assert row == 1280 + 256
    cache = (pages + 1) * serve["page_size"] * L * row
    assert round(cache / 1e9, 2) == 3.88
    assert 0.80 < (weights + cache) / 16e9 < 0.82
    # a whole expert layer is no chip's: 9.66 G parameters
    assert round(expert * 256 / 1e9, 2) == 9.66


# -- tools/sparse_parity.py, rehearsed on the latent family -------------------

def test_sparse_parity_tool_separates_its_controls_on_the_toy():
    """The check of the chip (ONE stream of several times topk through
    the packed step, against the reference in blocks), at a toy's size
    on the CPU, for a selection over LATENT rows: the clean run agrees
    on both sides of topk; attending everything, or the last topk,
    shows only past it. The toy's file states a share (experts 4-5 of
    8), which the reference is handed too."""
    import sys
    sys.path.insert(0, str(ROOT / "tools"))
    import sparse_parity
    config = json.loads((ROOT / "tests" / "servebench" / "files_dsa"
                         / "configs" / "tiny-glm5.json").read_text())
    config["serve"].update(max_batch=2, page_size=4, prefill_inline_budget=2)
    # the limit is the FILE's, stated beside the readings it was set
    # from, and the tool's own where a file states none; never by name
    assert "parity_tolerance" not in config
    config["parity_tolerance"] = 0.01
    # a stream of T tokens: the reference's unjitted operations are
    # compiled for that length already (`want`)
    out = sparse_parity.check(config, toy=True, stream=T, decode=12)
    assert out["limit"] == 0.01 and out["ok"], out
    cell = json.loads((ROOT / "servebench" / "configs"
                       / "glm-5-ep16.json").read_text())
    assert sparse_parity.LIMIT < cell["parity_tolerance"] < 1
    assert all(reading in cell["parity_tolerance_why"]
               for reading in ("0.132", "1.063", "1.219"))
    assert not hasattr(sparse_parity, "LIMITS")
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
