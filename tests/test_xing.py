"""Xing4.0-29B-A4B's family on the CPU at a toy's size with every
mechanism present: FOUR residual streams mixed by manifold-constrained
hyper-connections (20 Sinkhorn rounds a sublayer) around latent
attention under a YaRN rotation, TWO leading dense layers, sigmoid
routing with a selection bias and a scale, a shared expert, an untied
head. LOGITS against the plain float32 reference
(servebench/references/xing_f32.py), which shares no code with the
program: the residual path is never compared only with itself. Beside
it, what the one pair (models/common.py stream_read / stream_write)
owes every OTHER family: hc_mult 0 is pre_norm and x + y to the bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import butterfly_tpu.models.common as common
from butterfly_tpu.cache.paged import (
    init_kv_window, init_paged_cache, paged_forward, paged_forward_packed)
from butterfly_tpu.core.config import (
    PRESETS, ModelConfig, RuntimeConfig, tiny, xing4_29b_a4b)
from butterfly_tpu.models.common import (
    Model, forward, init_cache, layer_runs, stream_read, stream_write)
from butterfly_tpu.quant.int8 import (
    init_params_by_leaf, is_quantized_leaf, quantize_int8)
from servebench.references import xing_f32 as ref

from test_joyai import (chunk_of, err,  # noqa: F401 (a fixture)
                        kernel_read_through_the_packed_run, leaf_of,
                        scripted_run)

#: the toy at FOUR Sinkhorn rounds for the bulk of the file, and at the
#: published twenty where the count is what is tested (CFG20): XLA's
#: CPU backend takes two seconds to compile a round of the unrolled
#: iteration a program, 40 s a program at twenty
CFG20 = tiny("xing", dtype="float32", param_dtype="float32")
CFG = CFG20.replace(hc_sinkhorn_iters=4)
T = 40
#: rms difference over the standard deviation of the reference's logits
#: at the position. float32 on both sides on the CPU reads 3e-7 (the
#: median row) to 3.4e-6 (the worst of 120): sums in another order. What
#: it must catch, at THESE weights (seeded_params: the Sinkhorn logits
#: spread five times wider than the initializer's, so that 20 rounds
#: have not long converged): 19 rounds for 20 reads 2.1e-4, the mixing
#: in bfloat16 and a term left out 1e-2 and more
#: (test_what_the_limit_catches)
TOL = 2e-5

#: seeded_params' multipliers: sublayers' outputs, and the queries' and
#: keys' expansions (20 in tests/test_joyai.py; under m^2 = 2 and four
#: streams that makes a toy whose softmax is an argmax and whose float32
#: noise reads 2e-4)
LOUD, SHARP = 40, 5
_forward = jax.jit(forward, static_argnums=(1,), static_argnames=("fresh",))


def file_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, as a configuration file
    of `cfg` would hold them."""
    return dict(
        rms_norm_eps=cfg.norm_eps, num_hidden_layers=cfg.num_layers,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta,
        rope_scaling=dict(cfg.rope_scaling),
        num_experts_per_tok=cfg.num_experts_per_tok,
        n_routed_experts=cfg.num_experts,
        first_k_dense_replace=cfg.first_k_dense,
        routed_scaling_factor=cfg.routed_scaling_factor,
        hc_mult=cfg.hc_mult, hc_sinkhorn_iters=cfg.hc_sinkhorn_iters,
        hc_eps=cfg.hc_eps, mhc_h_res_clamp_min=cfg.hc_clamp_min,
        mhc_h_res_clamp_max=cfg.hc_clamp_max)


def seeded_params(cfg=CFG):
    p = Model(cfg).init(jax.random.PRNGKey(0))
    # norms that are not all ones, sublayers loud enough to move the
    # streams off the embedding, scores spread enough that a wrong
    # rotation, scale or mask moves the logits (tests/test_joyai.py)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))

    def jitter(a):
        return 1 + 0.3 * jax.random.normal(next(keys), a.shape)

    at = p["layers"]["attn"]
    for g in (p["layers"]["ln1"], p["layers"]["ln2"], at["q_norm"],
              at["kv_norm"], p["final_norm"]):
        g["scale"] = jitter(g["scale"])
    at["wo"] = at["wo"] * LOUD
    at["w_uq"] = at["w_uq"] * SHARP
    at["w_uk"] = at["w_uk"] * SHARP
    at["w_dkv"] = at["w_dkv"] * 20
    p["dense"]["mlp"]["w_down"] = p["dense"]["mlp"]["w_down"] * LOUD
    p["sparse"]["moe"]["w_down"] = p["sparse"]["moe"]["w_down"] * LOUD
    p["sparse"]["shared"]["w_down"] = p["sparse"]["shared"]["w_down"] * LOUD
    # the mixing: H_res's logits five times wider (a Sinkhorn that 20
    # rounds have only just settled), and alpha large enough that the
    # token's own streams, not b alone, decide the coefficients
    n = cfg.hc_mult
    for sub in ("hc1", "hc2"):
        hc = p["layers"][sub]
        hc["b"] = hc["b"].at[:, 2 * n:].multiply(5.0)
        hc["alpha"] = hc["alpha"] * 30
    return p


@pytest.fixture(scope="module")
def params():
    return seeded_params()


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(3).randint(1, CFG.vocab_size, (3, T))


def reference(params, tokens, cfg=CFG, **kw):
    return np.asarray(ref.logits(np.asarray(tokens), leaf_of(params),
                                 file_config(cfg), **kw))


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's full forward of the three sequences: [3, T, V]."""
    return np.stack([reference(params, t) for t in tokens])


def worst(got, want):
    return max(err(got[s, pos], want[s, pos])
               for s in range(got.shape[0]) for pos in range(got.shape[1]))


# -- the reference ------------------------------------------------------------

def test_the_reference_is_not_trivial_and_its_mixing_is_alive(params, tokens,
                                                              want):
    """Rows differ by position, an earlier token moves a later row, and
    the mixing is no identity: the same weights with every H_res the
    identity (b's res part 30 on the diagonal and -30 off it), or with
    the token's own share of the coefficients switched off (alpha 0),
    give other logits."""
    assert np.std(want) > 0.05
    assert err(want[0, 5], want[0, 20]) > 0.1
    seq = tokens[0].copy()
    seq[3] = (seq[3] + 7) % CFG.vocab_size
    assert err(reference(params, seq)[30], want[0, 30]) > 1e-3
    n = CFG.hc_mult
    eye = (60.0 * jnp.eye(n) - 30.0).reshape(-1)

    def with_hc(change):
        q = jax.tree.map(lambda a: a, params)
        for sub in ("hc1", "hc2"):
            q["layers"][sub] = change(dict(params["layers"][sub]))
        return reference(q, tokens[0])

    identity = with_hc(lambda hc: dict(
        hc, b=hc["b"].at[:, 2 * n:].set(eye)))
    deaf = with_hc(lambda hc: dict(hc, alpha=hc["alpha"] * 0))
    assert err(identity[30], want[0, 30]) > 0.02
    assert err(deaf[30], want[0, 30]) > 1e-3


# -- the contiguous cache -----------------------------------------------------

@pytest.mark.parametrize("fresh", [False, True], ids=["absorbed", "expanded"])
def test_contiguous_forward_whole(params, tokens, want, fresh):
    cache = init_cache(CFG, 3, 64)
    got, cache = _forward(params, CFG, jnp.asarray(tokens), cache,
                          fresh=fresh)
    assert worst(np.asarray(got), want) < TOL
    assert cache.v is None and cache.k.shape == (4, 3, 64, 1, CFG.latent_row)


def test_prefill_then_decode_through_the_cache(params, tokens, want):
    """servebench/refcheck.py's drive: a prefill of 12, then decode
    calls of one token through the cache."""
    cache = init_cache(CFG, 3, 64)
    got, cache = _forward(params, CFG, jnp.asarray(tokens[:, :12]), cache)
    assert err(got[1, -1], want[1, 11]) < TOL
    for j in range(12, 24):
        got, cache = _forward(params, CFG, jnp.asarray(tokens[:, j:j + 1]),
                              cache)
        for s in range(3):
            assert err(got[s, 0], want[s, j]) < TOL, (s, j)


def test_twenty_rounds_are_the_references_and_nineteen_are_not(params,
                                                               tokens):
    """At the published count: the program's 20 rounds against the
    reference's Python loop of 20, under TOL; the program with 19
    against the same reference, five times over it (the Sinkhorn's
    logits are spread wide enough in seeded_params that the twentieth
    round still moves H_res)."""
    want = np.stack([reference(params, t, CFG20) for t in tokens[:2]])
    toks, cache = jnp.asarray(tokens[:2]), init_cache(CFG20, 2, 64)
    assert CFG20.hc_sinkhorn_iters == 20 == tiny("xing").hc_sinkhorn_iters
    got, _ = _forward(params, CFG20, toks, cache)
    assert worst(np.asarray(got), want) < TOL
    got, _ = _forward(params, CFG20.replace(hc_sinkhorn_iters=19), toks,
                      cache)
    assert worst(np.asarray(got), want) > 5 * TOL


FAULTS = {
    # (what is planted, the least it must read)
    "mix_bfloat16": 100 * TOL,
    "no_h_post_factor": 100 * TOL,
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_what_the_limit_catches(params, tokens, want, fault, monkeypatch):
    """TOL is tight enough: the program with the mixing's arithmetic in
    bfloat16 (the streams stay float32: only what _MIX governs), or
    with H_post's factor 2 left out, reads over it against the same
    reference."""
    cfg = CFG
    if fault == "mix_bfloat16":
        monkeypatch.setattr(common, "_MIX", jnp.bfloat16)
    else:
        real = common.stream_read

        def read(x, lp, sub, c):
            h, (m, post) = real(x, lp, sub, c)
            return h, (m, post / 2)
        monkeypatch.setattr(common, "stream_read", read)
    got, _ = jax.jit(lambda p, t, c: forward(p, cfg, t, c))(
        params, jnp.asarray(tokens), init_cache(CFG, 3, 64))
    assert worst(np.asarray(got), want) > FAULTS[fault]


# -- the packed step ----------------------------------------------------------

@pytest.mark.parametrize("windowed", [False, True], ids=["pool", "window"])
def test_packed_steps_chunks_filler_decode_rows_and_a_reused_slot(
        params, tokens, want, windowed):
    """tests/test_joyai.py's script through the packed mixed step: a
    chunk beside decode rows in ONE step, filler columns, the window and
    its flush, a slot given to another stream; the streams ride
    [4, N, 1, D] beside the window through both runs' scans. Every row
    the head read is the reference's. The load ends in the cached rows
    read (four layers) and, LAST, the positions mixed: every real row of
    the step, decode rows and a chunk's real columns, no filler."""
    out, drv, read = scripted_run(params, tokens, windowed, cfg=CFG)
    assert len(out) > 30 and {s for s, _, _ in out} == {0, 1, 2}
    for s, pos, row in out:
        assert err(row, want[s, pos]) < TOL, (s, pos)
    loads = np.stack(drv.loads)
    assert loads.shape[1] == 5
    np.testing.assert_array_equal(loads[:, 3], 4 * np.asarray(read))
    # the script's steps: four chunks alone (6, 6, 6, 2), three chunks
    # beside one decode row, then two decode rows
    np.testing.assert_array_equal(loads[:9, 4], [6, 6, 6, 2, 7, 7, 4, 2, 2])
    assert 0 < loads[:, 0].max() <= CFG.num_experts


@pytest.mark.parametrize("pages", [32, 2], ids=["one_chunk", "chunks_of_2"])
def test_the_kernel_read_is_the_jnp_read_through_the_packed_run(
        params, tokens, want, pages, chunk_of):
    kernel_read_through_the_packed_run(params, tokens, want, CFG, pages,
                                       chunk_of)


# -- weights ------------------------------------------------------------------

def test_weights_built_leaf_by_leaf_have_the_same_tree():
    cfg = CFG.replace(dtype="bfloat16")
    p = init_params_by_leaf(cfg, jax.random.PRNGKey(0), quant="int8")
    q = quantize_int8(Model(cfg).init(jax.random.PRNGKey(0)), cfg)
    assert jax.tree.structure(p) == jax.tree.structure(q)
    assert jax.tree.map(lambda a: a.shape, p) == \
        jax.tree.map(lambda a: a.shape, q)
    assert is_quantized_leaf(p["dense"]["mlp"]["w_up"])
    assert is_quantized_leaf(p["sparse"]["moe"]["w_down"])
    assert p["dense"]["mlp"]["w_up"]["q8"].shape == (2, 64, 96)
    assert set(p["layers"]) == {"ln1", "ln2", "attn", "hc1", "hc2"}
    # the mixing leaves stay float by leaf path, as routers do, and are
    # seeded as init_params seeds them: alpha the constant, b ~ N(0, 1)
    # (a leaf named `b` elsewhere is a bias, zeros)
    for tree in (p, q):
        for sub in ("hc1", "hc2"):
            hc = tree["layers"][sub]
            assert {k: v.shape for k, v in hc.items()} == {
                "phi": (4, 4 * 64, 24), "b": (4, 24), "alpha": (4, 3)}
            if tree is p:       # born in the compute dtype
                assert all(v.dtype == jnp.bfloat16 for v in hc.values())
            np.testing.assert_allclose(np.asarray(hc["alpha"], np.float32),
                                       0.01, rtol=1e-2)
            assert 0.6 < float(jnp.std(hc["b"].astype(jnp.float32))) < 1.4
            assert 0.015 < float(jnp.std(hc["phi"].astype(jnp.float32))) \
                < 0.025


def test_int8_weights_serve_the_reference_over_the_same_codes(tokens):
    """Weight-only int8: the reference reads the same codes times
    scales and the same float mixing leaves, so what is left is the
    program's arithmetic."""
    p = quantize_int8(seeded_params(), CFG)
    want = reference(p, tokens[0])
    cache = init_cache(CFG, 1, 64)
    got, cache = _forward(p, CFG, jnp.asarray(tokens[:1, :20]), cache)
    assert err(got[0, 19], want[19]) < TOL
    got, _ = _forward(p, CFG, jnp.asarray(tokens[:1, 20:21]), cache)
    assert err(got[0, 0], want[20]) < TOL


# -- the pair: hc_mult 0 is pre_norm and x + y, to the bit --------------------

def _old_read(x, lp, sub, cfg):
    return common.pre_norm(x, lp[f"ln{sub}"], cfg), None


def _old_write(x, y, mix, cfg):
    if cfg.residual_multiplier:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


ARCHS = ["gpt2", "llama", "mixtral", "smallthinker", "keye",
         "granite_hybrid", "joyai"]


@pytest.mark.parametrize("arch", ARCHS)
def test_one_stream_is_bit_identical_in_every_other_family(arch, monkeypatch):
    """Every family without hc_mult through the pair against the same
    programs with the residual path as it stood before the pair (the
    norm, then x + y, written out here): a prefill, a decode call
    through the cache and a packed mixed step with a chunk beside decode
    rows over the window, in bfloat16, where an op moved is a bit moved.
    The same bits."""
    import butterfly_tpu.cache.paged as paged
    import butterfly_tpu.cache.ssm_state as ssm_state
    cfg = tiny(arch, dtype="bfloat16")
    assert cfg.hc_mult == 0
    p = Model(cfg).init(jax.random.PRNGKey(0))
    toks = np.random.RandomState(3).randint(1, cfg.vocab_size, (2, 24))

    def run():
        fwd = jax.jit(lambda p, t, c: forward(p, cfg, t, c))
        a, cache = fwd(p, jnp.asarray(toks[:, :16]), init_cache(cfg, 2, 64))
        b, _ = fwd(p, jnp.asarray(toks[:, 16:17]), cache)
        rt = RuntimeConfig(max_batch_size=3, max_seq_len=64, page_size=4)
        pc = init_paged_cache(cfg, rt)
        S, mp = pc.page_table.shape
        pc = pc._replace(page_table=jnp.arange(
            S * mp, dtype=jnp.int32).reshape(S, mp))
        kw = {}
        if cfg.has_ssm:
            kw["state"] = ssm_state.init_ssm_state(cfg, S)
        out = jax.jit(lambda p, *a, **k: paged_forward_packed(
            p, cfg, *a, **k))(
            p, jnp.asarray([5, 6, 7]), pc, jnp.asarray(toks[:1, :6]),
            jnp.asarray([1]), jnp.asarray([6]),
            jnp.asarray([True, False, True]), init_kv_window(pc, 12),
            jnp.zeros((S,), jnp.int32), **kw)
        return [np.asarray(x, np.float32) for x in (a, b, out[0])]

    got = run()
    for mod in (common, paged, ssm_state):
        monkeypatch.setattr(mod, "stream_read", _old_read)
        monkeypatch.setattr(mod, "stream_write", _old_write)
    for new, old in zip(got, run()):
        np.testing.assert_array_equal(new, old)


def test_streams_ride_every_forward_of_a_family_without_latent_attention():
    """The pair is in every layer body, so n streams ride the paths the
    latent family never takes too: the one-token decode fast path, the
    fresh prefill that keeps the cache out of its scan, the general
    scan and the alternating paged forward (what speculation's verify
    forward is) agree on a Llama toy of two streams."""
    cfg = tiny("llama", dtype="float32", hc_mult=2, hc_sinkhorn_iters=5)
    p = Model(cfg).init(jax.random.PRNGKey(0))
    assert p["layers"]["hc1"]["phi"].shape == (2, 2 * 64, 2 * 4)
    toks = jnp.asarray(np.random.RandomState(5).randint(1, 258, (2, 12)))
    whole, _ = forward(p, cfg, toks, init_cache(cfg, 2, 32))    # general
    fresh, cache = forward(p, cfg, toks[:, :8], init_cache(cfg, 2, 32),
                           fresh=True)
    np.testing.assert_allclose(fresh, whole[:, :8], atol=2e-5)
    for j in range(8, 12):                                      # decode
        got, cache = forward(p, cfg, toks[:, j:j + 1], cache)
        np.testing.assert_allclose(got[:, 0], whole[:, j], atol=2e-5)
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=32, page_size=4)
    pc = init_paged_cache(cfg, rt)
    pc = pc._replace(page_table=jnp.arange(16, dtype=jnp.int32).reshape(2, 8))
    paged, pc = paged_forward(p, cfg, toks[:, :8], pc, fresh=True)
    np.testing.assert_allclose(paged, whole[:, :8], atol=2e-5)
    paged, _ = paged_forward(p, cfg, toks[:, 8:9], pc)
    np.testing.assert_allclose(paged[:, 0], whole[:, 8], atol=2e-5)
    # and the streams are not one stream in disguise
    one, _ = forward(p, cfg.replace(hc_mult=0), toks, init_cache(cfg, 2, 32))
    assert float(jnp.max(jnp.abs(one - whole))) > 1e-2


# -- the Sinkhorn ----------------------------------------------------------------

def h_res(logits, cfg=CFG20):
    """stream_read's H_res [n, n] for ONE token whose res~ is `logits`
    (phi zero: b alone decides)."""
    n, D = cfg.hc_mult, cfg.hidden_size
    b = jnp.concatenate([jnp.zeros((2 * n,)), jnp.asarray(
        logits, jnp.float32).reshape(-1)])
    lp = {"ln1": {"scale": jnp.ones((D,))},
          "hc1": {"phi": jnp.zeros((n * D, n * (2 + n))), "b": b,
                  "alpha": jnp.ones((3,))}}
    _, (m, post) = stream_read(jnp.ones((n, 1, 1, D)), lp, 1, cfg)
    assert m.shape == (n, n, 1) and post.shape == (n, 1)
    return np.asarray(m[..., 0], np.float64)


EXTREMES = {
    "all_high": np.full((4, 4), 30.0),
    "all_low": np.full((4, 4), -30.0),
    "permutation": 60.0 * np.eye(4)[[2, 0, 3, 1]] - 30.0,
    "blocks": 60.0 * np.kron(np.eye(2), np.ones((2, 2))) - 30.0,
    "past_the_clamp": 400.0 * np.eye(4)[[1, 2, 3, 0]] - 200.0,
}


@pytest.mark.parametrize("name", list(EXTREMES))
def test_h_res_is_doubly_stochastic_at_the_clamped_extremes(name):
    """20 rounds from logits at the clamp's ends (and past them: +-200
    is clipped to +-30, where exp stays finite in float32): rows and
    columns sum to 1 within 1e-4, whatever hc_eps adds to a denominator
    of 4e-13."""
    m = h_res(EXTREMES[name])
    assert np.all(np.isfinite(m)) and m.min() >= 0
    np.testing.assert_allclose(m.sum(axis=0), 1.0, atol=1e-4)
    np.testing.assert_allclose(m.sum(axis=1), 1.0, atol=1e-4)
    if name == "permutation":
        np.testing.assert_allclose(m, np.eye(4)[[2, 0, 3, 1]], atol=1e-4)
    if name == "all_low":
        np.testing.assert_allclose(m, 0.25, atol=1e-4)


def test_a_triangular_pattern_is_where_20_rounds_do_not_reach():
    """The known limit of the iteration, held so that nobody reads the
    test above as more than it says: a matrix whose large entries form
    a triangle has no doubly stochastic scaling with that support, the
    iteration approaches the identity as 1/t, and after 20 rounds the
    rows sum to 1 (they were normalised last) while a column is still
    4 % off."""
    m = h_res(60.0 * np.triu(np.ones((4, 4))) - 30.0)
    np.testing.assert_allclose(m.sum(axis=1), 1.0, atol=1e-4)
    off = np.abs(m.sum(axis=0) - 1.0).max()
    assert 0.01 < off < 0.1


def test_the_write_is_h_res_times_the_streams_plus_h_post_times_the_output():
    n, D = 4, 8
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, 2, 3, D), jnp.float32)
    y = jnp.asarray(rng.randn(2, 3, D), jnp.float32)
    m = jnp.asarray(rng.rand(n, n, 6), jnp.float32)
    post = jnp.asarray(rng.rand(n, 6), jnp.float32)
    got = stream_write(x, y, (m, post), CFG)
    want = np.einsum("ijr,jrd->ird", m, np.asarray(x).reshape(n, 6, D)) \
        + np.asarray(post)[:, :, None] * np.asarray(y).reshape(6, D)
    np.testing.assert_allclose(np.asarray(got).reshape(n, 6, D), want,
                               rtol=1e-5, atol=1e-6)


# -- YaRN ---------------------------------------------------------------------

def test_yarn_rates_and_the_scale_worked_by_hand():
    """The 32 rotation rates of the published model: pairs 0-10 keep
    theta^(-2i/64), pairs 23-31 take it over 64, a linear ramp between
    (the correction range of beta_fast 32 and beta_slow 1 over 4,096:
    32 ln(4096 / (2 pi t)) / ln(10000) = 10.47 and 22.51, floor and
    ceil); m^2 = (0.1 ln 64 + 1)^2 = 1.41589^2 = 2.0047 on the softmax
    scale (ISSUE 49 writes 2.0048, m rounded to 1.4159 first)."""
    cfg = xing4_29b_a4b()
    rates = cfg.yarn_inv_freq()
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    assert rates.shape == (32,) and rates.dtype == np.float32
    np.testing.assert_allclose(rates[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(rates[23:], plain[23:] / 64, rtol=1e-6)
    # by hand: pair 10 = 10^-1.25, pair 31 = 10^-3.875 / 64, pair 16
    # (r = 6/13) = 0.01 x (7/13) + 0.01 / 64 x (6/13)
    assert rates[0] == 1.0
    assert rates[10] == pytest.approx(0.0562341, rel=1e-5)
    assert rates[31] == pytest.approx(2.083627e-6, rel=1e-5)
    assert rates[16] == pytest.approx(0.005456731, rel=1e-5)
    assert rates[11] == pytest.approx(
        plain[11] * (12 / 13) + plain[11] / 64 / 13, rel=1e-5)
    assert np.all(np.diff(rates) < 0)
    np.testing.assert_allclose(rates, ref.yarn_rates(dict(
        qk_rope_head_dim=64, rope_theta=10000,
        rope_scaling=dict(cfg.rope_scaling))), rtol=1e-6)
    assert cfg.attn_scale_mult == pytest.approx(2.0047, abs=5e-5)
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * 2.00474, rel=1e-5)
    assert ref.score_scale(dict(
        qk_nope_head_dim=128, qk_rope_head_dim=64,
        rope_scaling=dict(cfg.rope_scaling))) == pytest.approx(cfg.attn_scale)
    # a model without the group rotates and scales as ever
    joy = PRESETS["joyai-llm-flash"]()
    assert joy.rope_scaling == () and joy.attn_scale == 192 ** -0.5
    cos, _ = common.rope_freqs(cfg, jnp.asarray([[1]]))
    np.testing.assert_allclose(np.asarray(cos[0, 0]), np.cos(rates), rtol=1e-6)


# -- the preset, the fields, the refusals -------------------------------------

def test_preset_is_the_published_model():
    cfg = PRESETS["xing4.0-29b-a4b"]()
    assert cfg == xing4_29b_a4b() and cfg.arch == "xing"
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size) == \
        (40, 3584, 131072)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (512, 768, 128, 64, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.expert_width,
            cfg.shared_intermediate_size, cfg.intermediate_size) == \
        (64, 4, 1024, 1024, 9216)
    assert cfg.first_k_dense == 2 and cfg.routed_scaling_factor == 2.0
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_clamp_min, cfg.hc_clamp_max) == (4, 20, 1e-6, -30, 30)
    assert hash(cfg) == hash(xing4_29b_a4b())       # a static jit argument
    # the benchmark's cut: TWO dense layers as one run, 8 expert layers
    cut = cfg.replace(num_layers=10)
    assert layer_runs(cut) == [("attention", 0, 2, 0), ("attention", 2, 8, 2)]
    shapes = jax.eval_shape(Model(cut).init, jax.random.PRNGKey(0))
    at = shapes["layers"]["attn"]
    assert sum(int(np.prod(at[n].shape[1:])) for n in (
        "w_dq", "w_uq", "w_dkv", "w_uk", "w_uv", "wo")) == 28_409_856
    assert shapes["layers"]["hc1"]["phi"].shape == (10, 14336, 24)
    assert shapes["dense"]["mlp"]["w_gate"].shape == (2, 3584, 9216)
    assert shapes["sparse"]["moe"]["w_gate"].shape == (8, 64, 3584, 1024)


def test_leading_dense_layers_beyond_one_run_as_one_run(params):
    assert layer_runs(CFG) == [("attention", 0, 2, 0), ("attention", 2, 2, 2)]
    assert params["dense"]["mlp"]["w_gate"].shape == (2, 64, 96)
    assert params["sparse"]["moe"]["w_gate"].shape == (2, 8, 64, 32)
    assert layer_runs(tiny("xing", first_k_dense=3))[0] == \
        ("attention", 0, 3, 0)


FIELD_ERRORS = [
    (dict(hc_mult=1), "hc_mult 1"),
    (dict(hc_sinkhorn_iters=0), "one round"),
    (dict(hc_clamp_min=30.0), "an ordered clamp"),
    (dict(hc_eps=0.0), "a positive eps"),
    (dict(rope_scaling={"type": "linear", "factor": 2}), "type 'linear'"),
    (dict(rope_scaling={"type": "yarn", "factor": 64}),
     "without \\['original_max_position_embeddings'\\]"),
    (dict(first_k_dense=4), "first_k_dense 4 of 4 layers"),
]


@pytest.mark.parametrize("kw, what", FIELD_ERRORS,
                         ids=[w[:20] for _, w in FIELD_ERRORS])
def test_the_new_fields_are_checked_together(kw, what):
    with pytest.raises(ValueError, match=what):
        tiny("xing", **kw)


def test_older_families_reject_what_they_cannot_carry_beside_streams():
    for arch, what in (("granite_hybrid", "layer_types beside hc_mult"),
                       ("keye", "index_topk beside hc_mult"),
                       ("smallthinker", "router_input 'attn' beside hc_mult"),
                       ("gpt2", "arch 'gpt2' beside hc_mult")):
        with pytest.raises(ValueError, match=what):
            tiny(arch, hc_mult=4)
    with pytest.raises(ValueError, match="mscale_all_dim scales the softmax"):
        tiny("llama", rope_scaling=dict(CFG.rope_scaling))
    with pytest.raises(ValueError, match="does not rotate every layer"):
        tiny("granite_hybrid", rope_scaling={
            "type": "yarn", "factor": 4,
            "original_max_position_embeddings": 64})
    with pytest.raises(ValueError, match="mscale 2 beside mscale_all_dim 1"):
        tiny("xing", rope_scaling=dict(dict(CFG.rope_scaling), mscale=2))


def _mesh(axis):
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:2]), (axis,))


def _engine(cfg, **rt):
    from butterfly_tpu.engine.serving import ServingEngine
    mesh = rt.pop("mesh", None)
    return ServingEngine(Model(cfg), Model(cfg).init(jax.random.PRNGKey(0)),
                         RuntimeConfig(max_batch_size=2, max_seq_len=64,
                                       page_size=4, **rt), mesh=mesh)


LLAMA_HC = tiny("llama", hc_mult=2)


def _pipeline():
    from butterfly_tpu.parallel.pipeline import pipeline_forward
    pipeline_forward(None, LLAMA_HC, jnp.zeros((2, 4), jnp.int32),
                     init_cache(LLAMA_HC, 2, 8), _mesh("stage"))


def _sequence():
    from butterfly_tpu.parallel.sequence import sp_forward
    sp_forward(None, LLAMA_HC, None, None)


REFUSALS = {
    "pipeline serving": lambda: _engine(LLAMA_HC, mesh=_mesh("stage")),
    "the sequence-parallel prefill lane":
        lambda: _engine(LLAMA_HC, mesh=_mesh("seq")),
    "pipeline parallelism": _pipeline,
    "sequence parallelism": _sequence,
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_paths_that_carry_one_stream_refuse_n_by_name(what):
    with pytest.raises(NotImplementedError, match=what) as e:
        REFUSALS[what]()
    assert "hc_mult" in str(e.value) and "the 2 mixed" in str(e.value)


def test_the_family_itself_is_refused_where_a_latent_model_is():
    """Xing is latent: a mesh, speculation and the lane-wide forwards
    refuse it by that name first (tests/test_joyai.py has the list);
    the checkpoint loader knows no converter for the family."""
    from butterfly_tpu.ckpt.load import load_checkpoint
    with pytest.raises(NotImplementedError, match="speculative"):
        _engine(CFG, speculative_gamma=2)
    with pytest.raises(NotImplementedError, match="a device mesh"):
        _engine(CFG, mesh=_mesh("stage"))
    for cfg in (CFG, tiny("joyai")):    # before a byte is read
        with pytest.raises(ValueError, match="no checkpoint converter for "
                                             f"arch '{cfg.arch}'"):
            load_checkpoint("/nonexistent", cfg)


# -- through the scheduler: the server's own path -----------------------------

def test_served_tokens_are_the_references_and_the_ticks_count_the_mixing(
        params):
    """Three requests over two slots through the continuous scheduler
    (mixed blocks, the window and its flush, a slot reused): every
    served token is the reference's greedy token, and the tick records
    count the positions mixed: every prompt token and every decode
    step once, over that at most the steps a finished stream's blocks
    in flight ran on."""
    from test_joyai import served
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, CFG.vocab_size, n).tolist() for n in (5, 13, 9)]
    new = (12, 10, 6)
    sched, reqs = served(params, prompts, new, cfg=CFG)
    for prompt, req, n in zip(prompts, reqs, new):
        assert len(req.output) == n
        rows = reference(params, list(prompt) + list(req.output))
        for i, tok in enumerate(req.output):
            row = rows[len(prompt) + i - 1]
            order = np.argsort(row)
            assert row[order[-1]] - row[order[-2]] > 1e-4 * np.std(row), i
            assert tok == order[-1], i
    ticks = [t for t in sched.ticklog.dump()["ticks"]
             if t["hc_rows"] is not None]
    assert ticks and all(t["latent_rows"] is not None
                         and t["experts_touched"] is not None for t in ticks)
    assert all(t["hc_steps"] % 2 == 0 and t["hc_steps"] > 0 for t in ticks)
    least = sum(len(p) + n - 1 for p, n in zip(prompts, new))
    rows = sum(t["hc_rows"] for t in ticks)
    assert least <= rows <= least + 2 * 2 * len(reqs)
    assert sched.registry.snapshot()["hc_rows_mixed_total"] == rows


@pytest.mark.parametrize("arch", ["llama", "joyai"])
def test_a_model_of_one_stream_counts_no_mixing(arch):
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    cfg = tiny(arch, dtype="float32")
    eng = ServingEngine(Model(cfg), Model(cfg).init(jax.random.PRNGKey(0)),
                        RuntimeConfig(max_batch_size=2, max_seq_len=64,
                                      page_size=4, decode_steps_per_tick=2))
    sched = Scheduler(eng, seed=0)
    sched.submit([1, 2, 3, 4, 5], max_new_tokens=6)
    sched.run_until_done()
    ticks = sched.ticklog.dump()["ticks"]
    assert ticks and all(t["hc_rows"] is None and t["hc_steps"] is None
                         for t in ticks)
    assert sched.registry.snapshot()["hc_rows_mixed_total"] == 0


def test_a_dense_model_of_streams_is_served_and_counted():
    """No experts, no latent rows: the load is three zeros and the
    positions mixed, and the scheduler reads the count from its end."""
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    cfg = tiny("llama", dtype="float32", hc_mult=2, hc_sinkhorn_iters=5)
    params = Model(cfg).init(jax.random.PRNGKey(0))
    eng = ServingEngine(Model(cfg), params, RuntimeConfig(
        max_batch_size=2, max_seq_len=64, page_size=4,
        decode_steps_per_tick=2))
    sched = Scheduler(eng, seed=0)
    prompt = [1, 2, 3, 4, 5]
    req = sched.submit(prompt, max_new_tokens=6)
    sched.run_until_done()
    seq = jnp.asarray([prompt + list(req.output)])
    want, _ = forward(params, cfg, seq, init_cache(cfg, 1, 32))
    assert list(req.output) == [int(t) for t in jnp.argmax(
        want[0, len(prompt) - 1:-1], axis=-1)]
    ticks = [t for t in sched.ticklog.dump()["ticks"]
             if t["hc_rows"] is not None]
    assert ticks and all(t["experts_touched"] is None for t in ticks)
    assert sum(t["hc_rows"] for t in ticks) >= len(prompt) + 5


# -- the parity tool, rehearsed -----------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_parity_tool_takes_the_family_and_its_mixing_faults(dtype):
    """tools/latent_parity.py at the toy's size (a stream of 120 tokens,
    24 of them decoded, through the packed step, the window and a flush
    every second step): the clean run under the limit, its two faults
    as for JoyAI's family, and for a model of n streams the mixing's
    coefficients themselves (`mixing`, part of `ok`): `stream_read` over
    30 rows of 4 streams against the reference's `mixing`, clean under
    MIX_LIMIT, with bfloat16 arithmetic and with one round fewer (three
    of the toy file's four) over it. The served logits hold neither on
    the chip (PERF.md, PR 49), which is why the coefficients are read."""
    import json
    from pathlib import Path

    import tools.latent_parity as lp
    config = json.loads((Path(__file__).parent / "servebench" / "files"
                         / "configs" / "tiny-xing.json").read_text())
    config["torch_dtype"] = dtype
    assert config["hc_sinkhorn_iters"] == 4         # the toy file's
    out = lp.check(config, toy=True, stream=120, decode=24, past=64)
    assert out["evidence"] == "cpu toy" and out["ok"], out
    clean, astray, blind = (out[f] for f in lp.FAULTS)
    assert max(clean["chunks_median"], clean["decoded_median"]) \
        < (1e-4 if dtype == "float32" else lp.LIMIT)
    assert astray["decoded_median"] > lp.LIMIT
    assert min(blind["chunks_median"], blind["decoded_median"]) > lp.LIMIT
    mix = out["mixing"]
    assert set(mix) == set(lp.MIXING) | {"limit", "rows", "alpha_res"}
    assert mix["rows"] == 30 and mix["limit"] == lp.MIX_LIMIT
    # float32 against float32: rounding alone, thirty times under the limit
    assert mix["clean"] < 1e-5
    assert min(mix["mix_bfloat16"], mix["sinkhorn_19"]) > 3 * lp.MIX_LIMIT
