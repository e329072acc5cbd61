"""Jamba's family on the CPU at a toy's size with every mechanism present:
two Mamba-1 layers (128 channels with a state of 16 each, dt through a
bottleneck of 8, the three inner norms, a conv with a bias over the inner
stream alone) around an attention layer of 4 heads over ONE key-value
head without rotation, one "expert" that is a dense feed-forward, a tied
head. LOGITS (and states) against the plain float32 reference
(butterfly_tpu/models/jamba_f32.py), which shares no code with the
program. ONE serving engine for the module."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import packed_driver
from packed_driver import err, forward, leaf_of
from butterfly_tpu.cache.paged import paged_forward, paged_forward_window
from butterfly_tpu.cache.ssm_state import (
    _held, bytes_per_slot, init_ssm_state, state_info, state_shapes)
from butterfly_tpu.core.config import (
    PRESETS, ModelConfig, RuntimeConfig, jamba2_3b, tiny)
from butterfly_tpu.models import jamba_f32 as ref
from butterfly_tpu.models.common import (
    Model, init_cache, layer_runs, mamba1_conv, mamba1_scan, mamba1_step,
    mamba1_step_inputs, ssm_conv, ssm_scan)
from butterfly_tpu.quant.int8 import (
    init_params_by_leaf, is_quantized_leaf, quantize_int8)

ROOT = Path(__file__).resolve().parent.parent
CFG = tiny("jamba", dtype="float32", param_dtype="float32")
T = 40
#: rms difference over the standard deviation of the reference's logits
#: at the position. float32 on both sides on the CPU reads 1e-7 to 1e-6
#: (sums in another order); a bfloat16 program reads 1e-2, a term left
#: out or a state leaked 1e-1
TOL = 2e-5


def file_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, as a configuration file
    of `cfg` would hold them."""
    attn = [l for l, k in enumerate(cfg.layer_types) if k == "attention"]
    return dict(
        rms_norm_eps=cfg.norm_eps, num_hidden_layers=cfg.num_layers,
        hidden_size=cfg.hidden_size, attn_layer_offset=attn[0],
        attn_layer_period=(attn[1] - attn[0]) if len(attn) > 1
        else cfg.num_layers,
        mamba_expand=cfg.mamba1_inner // cfg.hidden_size,
        mamba_d_state=cfg.mamba1_state, mamba_dt_rank=cfg.mamba1_dt_rank,
        mamba_d_conv=cfg.mamba1_conv, mamba_conv_bias=True,
        mamba_proj_bias=False, num_experts=cfg.num_experts)


def seeded_params(cfg=CFG):
    p = Model(cfg).init(jax.random.PRNGKey(0))
    # norms that are not all ones, so that a norm left out or put over
    # the wrong width shows; projections loud enough that SiLU and
    # softplus bend; a conv bias and a dt bias away from zero; decays
    # slow enough that a state is remembered for tens of positions, and
    # unlike from channel to channel and from state index to state index
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 12))
    m = p["mamba1"]

    def jitter(a):
        return 1 + 0.3 * jax.random.normal(next(keys), a.shape)

    for g in (p["layers"]["ln1"], p["layers"]["ln2"], p["final_norm"],
              m["dt_norm"], m["b_norm"], m["c_norm"]):
        g["scale"] = jitter(g["scale"])
    m["in_proj"] = m["in_proj"] * 10
    m["x_proj"] = m["x_proj"] * 10
    m["conv_b"] = 0.3 * jax.random.normal(next(keys), m["conv_b"].shape)
    m["dt_bias"] = -2 + 0.5 * jax.random.normal(next(keys),
                                                m["dt_bias"].shape)
    m["A_log"] = jax.random.normal(next(keys), m["A_log"].shape)
    p["attn"]["wq"] = p["attn"]["wq"] * 20
    p["attn"]["wk"] = p["attn"]["wk"] * 20
    return p


@pytest.fixture(scope="module")
def params():
    return seeded_params()


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(3).randint(1, CFG.vocab_size, (3, 72))


def reference(params, tokens, cfg=CFG, states=None):
    return np.asarray(ref.logits(np.asarray(tokens), leaf_of(params),
                                 file_config(cfg), states=states))


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's full forward of the three sequences: [3, T, V]."""
    return np.stack([reference(params, t[:T]) for t in tokens])


# -- the configuration --------------------------------------------------------

def test_the_preset_is_the_published_model():
    cfg = PRESETS["jamba2-3b"]()
    assert cfg == jamba2_3b() and cfg.arch == "jamba"
    assert [l for l, k in enumerate(cfg.layer_types) if k == "attention"] \
        == [7, 21] and set(cfg.layer_types) == {"mamba1", "attention"}
    assert (cfg.recurrent_kind, cfg.num_ssm_layers, cfg.num_attn_layers) \
        == ("mamba1", 26, 2)
    assert (cfg.q_per_kv, cfg.num_kv_heads) == (20, 1)
    assert cfg.num_heads * cfg.head_dim == 2560 and cfg.mamba1_inner == 5120
    assert layer_runs(cfg) == [
        ("mamba1", 0, 7, 0), ("attention", 7, 1, 0), ("mamba1", 8, 13, 7),
        ("attention", 21, 1, 1), ("mamba1", 22, 6, 20)]
    # ONE "expert" is the feed-forward itself: no router is built
    assert cfg.num_experts == 1 and cfg.is_moe and not cfg.routed
    shapes = jax.eval_shape(lambda: Model(cfg).init(jax.random.PRNGKey(0)))
    assert sorted(shapes["layers"]) == ["ln1", "ln2", "mlp"]
    assert shapes["layers"]["mlp"]["w_gate"].shape == (28, 2560, 8192)
    m = shapes["mamba1"]
    assert m["in_proj"].shape == (26, 2560, 10240)
    assert m["x_proj"].shape == (26, 5120, 192)
    assert m["dt_proj"].shape == (26, 160, 5120)
    assert m["A_log"].shape == (26, 16, 5120)       # as the state is held
    assert m["conv_w"].shape == (26, 4, 5120) and m["conv_b"].shape == (26, 5120)
    assert {k: v["scale"].shape for k, v in m.items() if "norm" in k} == {
        "dt_norm": (26, 160), "b_norm": (26, 16), "c_norm": (26, 16)}
    # a mixer to the parameter, and the model: ISSUE 58's arithmetic
    assert sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(m)) \
        == 41_241_792
    assert shapes["attn"]["wk"].shape == (2, 2560, 1, 128)
    assert "lm_head" not in shapes
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 3_029_337_472


def test_the_state_is_held_as_declared_in_whole_tiles():
    """Channels on the lanes, the state index down the sublanes:
    [26, S, 16, 5120] is whole tiles of bfloat16 (16 rows one tile, 40
    rows of lanes), so a slot's 5,058,560 B, two bytes a value, are
    what the memory holds; [.., 5120, 16] would be held padded eight
    times."""
    cfg = jamba2_3b()
    shapes = state_shapes(cfg, 128)
    assert shapes == {"h": (26, 128, 16, 5120), "conv": (26, 3, 128, 5120)}
    assert shapes["h"][-1] % 128 == 0 and shapes["h"][-2] % 16 == 0
    values = 26 * (5120 * 16 + 3 * 5120)
    assert bytes_per_slot(cfg) == 2 * values == 5_058_560
    info = state_info(cfg, 128)
    assert info["kind"] == "Mamba-1" and info["layers"] == 26
    assert "layers, slots, state, channels" in info["layout"]
    assert "[26, 128, 16, 5120]" in info["layout"]
    assert info["bytes"] == 128 * 5_058_560 and info["whole_tiles"]
    assert info["bytes_per_slot"] == 5_058_560
    assert _held((26, 128, 5120, 16), 2) == 8 * 26 * 128 * 5120 * 16
    # a geometry the device pads says so
    odd = cfg.replace(mamba1_inner=5000)
    assert not state_info(odd, 128)["whole_tiles"]
    assert bytes_per_slot(odd) == 2 * 26 * (5000 * 16 + 3 * 5000)
    assert state_info(tiny("granite_hybrid"), 2)["kind"] == "Mamba-2"
    assert state_info(tiny("olmo_hybrid"), 2)["kind"] == "Gated DeltaNet"
    assert state_info(tiny("llama"), 2) is None


BAD = {
    "two recurrent kinds": dict(layer_types=("mamba", "mamba1", "attention"),
                                ssm_heads=2, ssm_head_dim=4, ssm_state=4),
    "mamba-1 beside the delta rule": dict(
        layer_types=("linear_attention", "mamba1", "attention"),
        gdn_heads=2, gdn_key_dim=4, gdn_value_dim=4),
    "no inner width": dict(mamba1_inner=0),
    "no state": dict(mamba1_state=0),
    "no rank for dt": dict(mamba1_dt_rank=0),
    "a conv of one tap": dict(mamba1_conv=1),
    "an unknown kind": dict(layer_types=("mamba1", "mamba-1", "attention")),
    "too few kinds": dict(layer_types=("mamba1", "attention")),
}


@pytest.mark.parametrize("what", list(BAD))
def test_the_configuration_refuses(what):
    with pytest.raises(ValueError):
        tiny("jamba", **BAD[what])


# -- what makes it Mamba-1 ----------------------------------------------------

def _layer(params, m=0):
    return jax.tree.map(lambda a: a[m], params["mamba1"])


def _mamba2_twin():
    """A Mamba-2 layer of the same 128 channels (8 heads of 16, a state
    of 16) with seeded weights: the control each of the three tests
    below must FAIL for."""
    cfg = tiny("granite_hybrid", dtype="float32", param_dtype="float32")
    assert cfg.ssm_inner == CFG.mamba1_inner and cfg.ssm_state == 16
    p = Model(cfg).init(jax.random.PRNGKey(0))
    return cfg, jax.tree.map(lambda a: a[0], p["mamba"])


def test_two_channels_with_unlike_rows_of_a_decay_unlike_under_one_dt(params):
    """A state of ones, no input (u = 0), the SAME dt in every channel:
    what is left of h[n, c] is exp(dt A[n, c]), a different number for
    each channel and each state index. A Mamba-2 step under the same dt
    leaves ONE number a head: all 16 channels of a head and all 16
    state indices decay alike."""
    mp = _layer(params)
    Di, N = CFG.mamba1_inner, CFG.mamba1_state
    h = jnp.ones((1, 1, N, Di))
    dt = jnp.full((1, 1, Di), 0.5)
    zero = jnp.zeros((1, 1, Di))
    y, h1 = mamba1_step(h, 0, zero, dt, jnp.ones((1, 1, N)),
                        jnp.ones((1, 1, N)), mp, jnp.asarray([1]))
    left = np.asarray(h1[0, 0])                              # [N, Di]
    A = -np.exp(np.asarray(mp["A_log"]))
    np.testing.assert_allclose(left, np.exp(0.5 * A), rtol=1e-6)
    # two channels, one state index; two state indices, one channel
    assert abs(left[0, 0] - left[0, 1]) > 1e-2
    assert abs(left[0, 0] - left[1, 0]) > 1e-2
    assert len(np.unique(np.round(left, 6))) > 0.9 * N * Di
    # the readout sums what is left over n (C = 1) and nothing else
    np.testing.assert_allclose(np.asarray(y[0, 0]), left.sum(0), rtol=1e-5)
    # the control: Mamba-2's scan under one dt leaves one number a head
    cfg2, mp2 = _mamba2_twin()
    mp2 = dict(mp2, A_log=jnp.linspace(-1, 1, cfg2.ssm_heads))
    u2 = jnp.zeros((1, 1, cfg2.ssm_conv_dim))
    _, h2 = ssm_scan(u2, jnp.full((1, 1, cfg2.ssm_heads), 0.5)
                     - mp2["dt_bias"], mp2, cfg2,
                     jnp.ones((1, cfg2.ssm_heads, cfg2.ssm_head_dim, 16)),
                     jnp.asarray([1]))
    left2 = np.asarray(h2[0]).reshape(cfg2.ssm_heads, -1)
    assert np.ptp(left2, axis=1).max() < 1e-6                # a head: alike
    assert len(np.unique(np.round(left2, 6))) == cfg2.ssm_heads


def test_b_and_c_change_when_the_conv_s_bias_changes(params):
    """dt, B and C are projected from the conv's OUTPUT: a bias on the
    conv's channels moves all three. Mamba-2 projects B and C from the
    layer's input beside x and convolves each in its own channels: a
    bias on x's channels moves neither."""
    mp = _layer(params)
    rng = np.random.RandomState(5)
    u_in = jnp.asarray(rng.randn(1, 6, CFG.mamba1_inner), jnp.float32)
    tail = jnp.zeros((1, 3, CFG.mamba1_inner))
    count = jnp.asarray([6])

    def inputs(mp):
        u, _ = mamba1_conv(u_in, tail, mp, count)
        return mamba1_step_inputs(u, mp, CFG)

    dt0, B0, C0 = inputs(mp)
    dt1, B1, C1 = inputs(dict(mp, conv_b=mp["conv_b"] + 0.5))
    for a, b in ((dt0, dt1), (B0, B1), (C0, C1)):
        assert np.abs(np.asarray(a - b)).max() > 1e-2
    # the control
    cfg2, mp2 = _mamba2_twin()
    Di, N = cfg2.ssm_inner, cfg2.ssm_state
    x_in = jnp.asarray(rng.randn(1, 6, cfg2.ssm_conv_dim), jnp.float32)
    tail2 = jnp.zeros((1, 3, cfg2.ssm_conv_dim))
    moved = dict(mp2, conv_b=mp2["conv_b"].at[:Di].add(0.5))
    u0, _ = ssm_conv(x_in, tail2, mp2, count)
    u1, _ = ssm_conv(x_in, tail2, moved, count)
    assert np.abs(np.asarray(u0 - u1))[..., :Di].max() > 1e-2
    assert np.abs(np.asarray(u0 - u1))[..., Di:].max() == 0.0  # B and C


@pytest.mark.parametrize("norm", ["dt_norm", "b_norm", "c_norm"])
def test_dropping_an_inner_norm_fails_against_the_reference(params, tokens,
                                                            want, norm):
    """The family norms dt's bottleneck, B and C, each with a weight of
    its own (Mamba-1 as published, and a Mamba-2 step, norm none of
    them). A program without any one of the three is another model."""
    mixers = {k: v for k, v in params["mamba1"].items() if k != norm}
    got, _ = forward({**params, "mamba1": mixers}, CFG,
                     jnp.asarray(tokens[:1, :12]), init_cache(CFG, 1, 32))
    assert min(err(got[0, t], want[0, t]) for t in range(4, 12)) > 1000 * TOL
    # and cfg.mamba1_norms False builds none of the three
    bare = jax.eval_shape(lambda: Model(CFG.replace(
        mamba1_norms=False)).init(jax.random.PRNGKey(0)))["mamba1"]
    assert not any("norm" in k for k in bare) and "dt_bias" in bare


@pytest.mark.parametrize("T_,count", [(1, 1), (6, 4), (32, 32), (70, 41)])
def test_a_chunks_recurrence_is_the_per_position_loop(params, T_, count):
    """mamba1_scan (a scan over a row's positions) against the
    reference's own `position` a position at a time, and against
    mamba1_step a position a call over the HELD layout: outputs and
    final state; a row's positions past `count` advance nothing."""
    mp = _layer(params, 1)
    Di, N = CFG.mamba1_inner, CFG.mamba1_state
    rng = np.random.RandomState(T_ + count)
    u_in = jnp.asarray(rng.randn(2, T_, Di), jnp.float32)
    tail = jnp.asarray(rng.randn(2, 3, Di), jnp.float32)
    s0 = jnp.asarray(rng.randn(2, N, Di), jnp.float32)
    cnt = jnp.asarray([count, count])
    u, tail1 = mamba1_conv(u_in, tail, mp, cnt)
    dt, Bm, Cm = mamba1_step_inputs(u, mp, CFG)
    y, st = jax.jit(mamba1_scan)(u, dt, Bm, Cm, mp, s0, cnt)
    assert y.shape == (2, T_, Di)
    # the loop, the reference's own position
    w = {k: leaf_of({"mamba1": params["mamba1"]})(p, 1)
         for k, p in ref.MAMBA_LEAVES.items()
         if k not in ("in_proj", "out_proj")}
    full = np.concatenate([np.asarray(tail), np.asarray(u_in)], axis=1)
    for r in range(2):
        h, rows = s0[r].T, []
        for t in range(count):
            h, y_t = ref.position(h, jnp.asarray(full[r, t:t + 4]), w,
                                  CFG.norm_eps)
            rows.append(np.asarray(y_t))
        want_y = np.stack(rows)
        scale = np.abs(want_y).max()
        assert np.abs(np.asarray(y[r, :count]) - want_y).max() < 2e-5 * scale
        assert np.abs(np.asarray(st[r]).T - np.asarray(h)).max() \
            < 2e-5 * np.abs(np.asarray(h)).max()
        np.testing.assert_allclose(np.asarray(tail1[r]),
                                   full[r, count:count + 3], rtol=1e-6)
    # and the decode rows' step over the held layout, a position a call
    h = s0[None]
    step = jax.jit(lambda h, *a: mamba1_step(h, 0, *a, mp,
                                             jnp.asarray([1, 1])))
    for t in range(min(count, 8)):
        y_t, h = step(h, u[:, t:t + 1], dt[:, t:t + 1], Bm[:, t:t + 1],
                      Cm[:, t:t + 1])
        assert np.abs(np.asarray(y_t[:, 0] - y[:, t])).max() \
            < 2e-5 * float(jnp.abs(y[:, t]).max()), t


# -- the contiguous cache ---------------------------------------------------

def test_the_reference_is_in_the_repo_twice_and_is_not_trivial(want):
    assert (ROOT / "butterfly_tpu/models/jamba_f32.py").read_text() \
        == (ROOT / "servebench/references/jamba_f32.py").read_text()
    top = want.argmax(-1)
    assert len(np.unique(top)) > 10
    # the logits move with the context: the same token at two positions
    assert np.abs(want[0, 5] - want[0, 25]).max() > 0.05


def test_contiguous_forward_whole(params, tokens, want):
    got, cache = forward(params, CFG, jnp.asarray(tokens[:, :T]),
                         init_cache(CFG, 3, 64))
    for s in range(3):
        for t in range(T):
            assert err(got[s, t], want[s, t]) < TOL, (s, t)
    assert cache.k.shape[:1] == (1,) and cache.k.shape[3] == 1   # ONE KV head
    assert cache.ssm.h.shape == (2, 3, 16, 128)


def test_prefill_then_decode_through_the_cache_and_the_state(params, tokens,
                                                             want):
    """A padded prefill (the engine's last_index contract: 12 real
    tokens in a bucket of 16, the state advanced by 12 and no further),
    then decode calls of one token through the cache and the state."""
    cache = init_cache(CFG, 3, 64)
    padded = np.zeros((3, 16), np.int32)
    padded[:, :12] = tokens[:, :12]
    got, cache = forward(params, CFG, jnp.asarray(padded), cache,
                         last_index=jnp.full((3,), 11))
    cache = cache._replace(length=jnp.full((3,), 12, jnp.int32))
    rows = [got]
    for t in range(12, T):
        got, cache = forward(params, CFG, jnp.asarray(tokens[:, t:t + 1]),
                             cache)
        rows.append(got)
    got = jnp.concatenate(rows, axis=1)
    for s in range(3):
        for i, t in enumerate(range(11, T)):
            assert err(got[s, i], want[s, t]) < TOL, (s, t)


# -- the packed mixed step ----------------------------------------------------

@pytest.fixture(scope="module")
def scripted(params, tokens):
    """The scripted run through the window and its flush, once."""
    return packed_driver.scripted_run(params, tokens, CFG)


@pytest.mark.parametrize("windowed", [False, True], ids=["pool", "window"])
def test_packed_steps_chunks_filler_decode_rows_and_a_reused_slot(
        params, tokens, want, windowed, scripted):
    out, drv, _ = scripted if windowed \
        else packed_driver.scripted_run(params, tokens, CFG, windowed)
    assert len(out) > 30
    assert {s for s, _, _ in out} == {0, 1, 2}
    for s, pos, row in out:
        assert err(row, want[s, pos]) < TOL, (s, pos)
    # ONE "expert" routes nothing: three zeros where experts' loads would
    # be, then the positions pushed through a recurrence and the slots
    # that started from zero (three streams began)
    loads = np.stack(drv.loads)
    assert loads.shape[1] == 5 and not loads[:, :3].any()
    assert loads[0, 3] == 6 and loads[3, 3] == 2
    assert loads[4, 3] == 1 + 6 and loads[:, 4].sum() == 3


def held_channels(state, m, slot):
    """Layer m's state of one slot as the reference holds it, [Di, N]."""
    return np.asarray(state.h[m, slot]).T


def states_are_the_loops(drv, params, seq, slot, n):
    held = []
    reference(params, seq[:n], states=held)
    assert len(held) == CFG.num_ssm_layers == 2
    for m, (h, tail) in enumerate(held):
        scale = np.abs(np.asarray(h)).max()
        assert scale > 1e-3             # a state worth comparing
        assert np.abs(held_channels(drv.state, m, slot) - h).max() \
            < 2e-5 * scale, (slot, m)
        got_t = np.asarray(drv.state.conv[m, :, slot])
        assert np.abs(got_t - tail).max() < 1e-5 * np.abs(tail).max()


def test_the_recurrence_is_tied_to_the_loop(params, tokens, scripted):
    """After a prompt fed as chunks of C, the last partly filler, then
    decode steps, each slot's state IS the state the reference's
    position-by-position loop holds after the same tokens: filler
    columns advanced nothing, a slot given to a second stream STARTED
    FROM ZERO and holds that stream's state and nothing of the first's,
    a slot that never held a stream is zero."""
    _, drv, seen = scripted
    for slot, (s, n) in seen.items():
        states_are_the_loops(drv, params, tokens[s], slot, n)
    assert seen[1][0] == 2                  # slot 1's second tenant
    assert not np.asarray(drv.state.h[:, 2]).any()
    assert not np.asarray(drv.state.conv[:, :, 2]).any()


def test_kernels_on_takes_mamba1_step_for_the_decode_rows(params, tokens,
                                                          scripted):
    """The scripted run (chunks, filler, decode rows beside a chunk, a
    reused slot), kernels on (interpreted here; the toy's state [.., 16,
    128] is whole tiles of float32 and of bfloat16): the decode rows'
    recurrence is the mamba1_step kernel, a chunk's is the scan over its
    positions (no other kernel of a recurrent kind; the attention
    layer's paged read is the kernel's too), and logits and states are
    the `jnp` run's."""
    from butterfly_tpu.ops import mamba1_step as mamba1_kernel
    from butterfly_tpu.ops import record_kernels
    out_j, drv_j, _ = scripted
    for dtype in (jnp.float32, jnp.bfloat16):
        assert mamba1_kernel.fits(drv_j.state.h.astype(dtype))
    with record_kernels({}) as calls:
        out_k, drv_k, _ = packed_driver.scripted_run(params, tokens, CFG,
                                                     use_kernel=True)
    # a call site is a traced run's body, in the one program the driver
    # steps
    assert calls["mamba1_step:interpret"] >= 1
    assert not any(k.startswith(("ssm_step", "gdn_step")) for k in calls)
    assert "dense_fallback" not in calls
    assert [(s, pos) for s, pos, _ in out_k] == \
        [(s, pos) for s, pos, _ in out_j] and len(out_k) > 30
    for (s, pos, row_k), (_, _, row_j) in zip(out_k, out_j):
        assert err(row_k, row_j) < TOL, (s, pos)
    for got, ref_ in ((drv_k.state.h, drv_j.state.h),
                      (drv_k.state.conv, drv_j.state.conv)):
        got, ref_ = np.asarray(got), np.asarray(ref_)
        assert np.abs(ref_).max() > 1e-3
        assert np.abs(got - ref_).max() < 1e-5 * np.abs(ref_).max()
    assert not np.asarray(drv_k.state.h[:, 2]).any()


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip (no chip attached: the TPU's compiler is
    installed here); skipped where none can be described. The library
    reads where to log when it loads: told not to, for the module's
    tests only."""
    import os
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    env = pytest.MonkeyPatch()
    if "TPU_LOG_DIR" not in os.environ:
        env.setenv("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        env.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    env.undo()


def test_the_decode_rows_step_compiles_for_the_chip_as_one_pass_in_place(
        one_chip, monkeypatch):
    """A mixed step's Mamba-1 layers at the cell's own shapes (128
    decode rows beside one chunk of 32 at the published widths, the
    state riding two scans as the engine's block carries it, donated),
    kernels on, compiled for the TPU: the decode rows' recurrence is the
    Mosaic call `mamba1_step` behind the chunk's write in place, nothing
    copies the state, whole or a layer of it, nothing computes its
    update a second time (`.remat`: PERF.md, PR 56), no result sits a
    row a tile (`T(1,128)`: PERF.md, PR 53), and the call's HLO text,
    which is all a device trace knows of it, is caught by the
    benchmark's reader of the mixers (servebench/mamba1_peaks.py) and
    not by the paged kernel's.
    Since PR 64 the conv's tails are read and written where they lie:
    no value of a tail's swapped shape, no conv laid out slots-major, no
    row-a-tile value a slot and Dc wide (packed_driver.swapped_tails),
    no copy of the tails, and every operation whose result carries the
    planes is one the benchmark's reader of the mixers counts
    (packed_driver.planes_unread)."""
    import json
    import re
    import sys

    from butterfly_tpu.cache.ssm_state import StateRows, advance_packed
    from butterfly_tpu.models.common import layer_at
    from servebench.mamba1_peaks import mamba1_patterns
    from servebench.xplane import clean
    sys.path.insert(0, str(ROOT / "tools"))
    try:
        from chip_kernels import state_copies
    finally:
        sys.path.remove(str(ROOT / "tools"))
    # the published pattern's first seven layers: mixers all (147 MB of
    # state: four layers' 84 MB the compiler moved whole into its fast
    # memory and back around every step, which no cell's 545 MB fit)
    cfg = jamba2_3b().replace(
        num_layers=7, layer_types=jamba2_3b().layer_types[:7],
        dtype="bfloat16")
    S, P, C, Lm = 128, 1, 32, cfg.num_ssm_layers
    assert Lm == 7

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: init_params_by_leaf(
        cfg, jax.random.PRNGKey(0))))
    state = on_chip(jax.eval_shape(lambda: init_ssm_state(cfg, S)))
    assert state.h.shape == (Lm, S, 16, 5120)
    rows = on_chip(StateRows(
        active=jnp.zeros((S,), bool), ok=jnp.zeros((S + P * C,), bool),
        chunk_slot=jnp.zeros((P,), jnp.int32), chunk_ok=jnp.zeros((P,), bool),
        chunk_pos=jnp.zeros((P, C), jnp.int32)))

    def prog(x, state, params, rows):
        def layer(carry, i):
            x, st = carry
            x, st, _ = advance_packed(
                x, layer_at(params["layers"], i, cfg),
                layer_at(params["mamba1"], i, cfg), st, i, rows, cfg,
                use_kernel=True)
            return (x, st), None

        def step(carry, _):     # a block is steps of a run of layers
            return jax.lax.scan(layer, carry, jnp.arange(Lm))[0], None
        return jax.lax.scan(step, (x, state), jnp.arange(4))[0]

    jax.clear_caches()          # no interpreted trace of the kernel is met
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        with jax.disable_jit(False):
            compiled = jax.jit(prog, donate_argnums=1).lower(
                on_chip(jnp.zeros((S + P * C, 1, cfg.hidden_size),
                                  jnp.bfloat16)), state, params, rows
            ).compile()
    except Exception as e:  # the TPU library is one process's at a time
        if "Mosaic" in str(e):      # the kernel refused is no skip
            raise
        pytest.skip(f"the TPU compiler could not be used here: {e}")
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    hlo = compiled.as_text()
    assert state_copies(hlo, state.h) == []
    whole = ",".join(map(str, state.h.shape))
    assert not re.findall(rf"%\S*remat\S* = \(?\w+\[{whole}\]", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 256e6
    calls = [line.strip() for line in hlo.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert calls and all(c.startswith("%mamba1_step") for c in calls)
    # the conv's tails are read and written where they lie (PR 64)
    assert packed_driver.swapped_tails(
        hlo, state.conv, S + P * C, state_copies(hlo, state.conv)) == []
    # a row a tile: the call's own results, and any value of a row a
    # slot and Di or 2 Di wide that XLA forms around it (a weight's one
    # row [1, Di] is held so by right)
    for c in calls:
        assert "T(1,128)" not in c.split(" custom-call(")[0], c[:200]
    assert not re.findall(
        rf"= \w+\[(?:{S + P * C}|{S}),(?:1,)?(?:5120|10240)\]"
        rf"\{{[^}}]*T\(1,128\)",
        hlo)
    config = json.loads((ROOT / "servebench" / "configs"
                         / "jamba2-3b.json").read_text())
    mixers = mamba1_patterns(config)
    for name in map(clean, calls):  # as xplane.py names an operation
        assert mixers.search(name) and "paged_att" not in name, name
    assert packed_driver.planes_unread(hlo, state.conv, mixers) == []


def test_a_prompt_of_70_as_chunks_of_32_32_6_is_the_loop(params, tokens):
    """The cell's chunk width: 32 + 32 + 6 (26 of filler) through the
    packed step, then decode rows; every head row's logits and the
    final state are the reference's loop's over the same 72 tokens."""
    rt = RuntimeConfig(max_batch_size=3, max_seq_len=96, page_size=4)
    drv = packed_driver.Packed(params, CFG, width=32, rt=rt)
    seq = tokens[1]
    want = reference(params, seq)
    for lo, n in ((0, 32), (32, 32), (64, 6)):
        got = drv.step({}, (2, seq[lo:lo + n]))
        assert err(got[2], want[lo + n - 1]) < TOL, lo
    for t in (70, 71):
        got = drv.step({2: seq[t]})
        assert err(got[2], want[t]) < TOL, t
    states_are_the_loops(drv, params, seq, 2, 72)


def test_an_idle_chunk_leaves_what_slot_0s_chunk_wrote(params, tokens):
    """Two chunks a step (prefill_inline_budget over the chunk's width),
    the second idle: its slot reads 0, and slot 0 is where the real
    chunk writes. What it writes back is the state as the real chunk
    LEFT it, not as the step found it: logits and states are the loop's."""
    seq = tokens[0]
    out, drv = packed_driver.idle_chunk_run(params, seq, CFG)
    want = reference(params, seq[:19])
    for slot, pos, row in out:
        assert err(row, want[pos]) < TOL, (slot, pos)
    for slot, n in ((0, 19), (1, 5)):
        states_are_the_loops(drv, params, seq, slot, n)


# -- precisions ---------------------------------------------------------------

def test_a_bfloat16_program_fails_the_limit_float32_passes(params, tokens,
                                                          want):
    cfg = CFG.replace(dtype="bfloat16")
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    cache = init_cache(cfg, 1, 64)
    assert cache.ssm.h.dtype == jnp.bfloat16
    got, cache = forward(p, cfg, jnp.asarray(tokens[:1, :12]), cache)
    rows = [got[0, -1]]
    for t in range(12, 20):
        got, cache = forward(p, cfg, jnp.asarray(tokens[:1, t:t + 1]), cache)
        rows.append(got[0, 0])
    errs = [err(r, want[0, 11 + i]) for i, r in enumerate(rows)]
    assert min(errs) > 50 * TOL
    assert max(errs) < 0.2          # and it is the same model


def test_int8_weights_quantize_the_two_wide_projections_by_path(tokens):
    """`--quant int8` on this family: weight-only codes for the mixer's
    in- and out-projection, the attention, the feed-forward and the
    head (a tied head is held a second time as codes); the x- and
    dt-projection, the conv, A_log, D, dt's bias, the three inner norms
    and the embedding stay float. Against the reference over the SAME
    codes times scales (and the head's own) the program differs by
    float32 rounding."""
    p = quantize_int8(seeded_params(), CFG)
    assert {k for k, v in p["mamba1"].items() if is_quantized_leaf(v)} \
        == {"in_proj", "out_proj"}
    assert {k for k, v in p["attn"].items() if is_quantized_leaf(v)} \
        == {"wq", "wk", "wv", "wo"}
    assert all(is_quantized_leaf(v) for v in p["layers"]["mlp"].values())
    assert is_quantized_leaf(p["lm_head"])
    assert not is_quantized_leaf(p["embed"]["tok"])
    # one scale an output channel: u's and z's own
    assert p["mamba1"]["in_proj"]["s"].shape == (2, 1, 2 * CFG.mamba1_inner)
    got, _ = forward(p, CFG, jnp.asarray(tokens[:1, :T]),
                     init_cache(CFG, 1, 64))
    # the reference ties its head to the embedding; the program's head
    # is the quantized copy: hand the reference that copy as its
    # embedding's transpose at the head alone
    head = np.asarray(leaf_of(p)("lm_head"))
    base = leaf_of(p)

    class Tied:
        """E whose rows are the float embedding's and whose transpose
        is the quantized head."""
        def __init__(self, E):
            self.E, self.T = E, head

        def __getitem__(self, i):
            return self.E[i]

    want = np.asarray(ref.logits(
        tokens[0, :T], lambda path, layer=None: Tied(base(path))
        if path == "embed/tok" else base(path, layer), file_config(CFG)))
    for t in range(T):
        assert err(got[0, t], want[t]) < TOL, t
    # and born leaf by leaf (cli.load_params' path) it is the same tree
    cfg = CFG.replace(dtype="bfloat16")
    born = init_params_by_leaf(cfg, jax.random.PRNGKey(0), quant="int8")
    made = quantize_int8(Model(cfg).init(jax.random.PRNGKey(0)), cfg)
    assert jax.tree.structure(born) == jax.tree.structure(made)
    assert jax.tree.map(lambda a: a.shape, born) == \
        jax.tree.map(lambda a: a.shape, made)


# -- through the scheduler: the server's own path, ONE engine -----------------

@pytest.fixture(scope="module")
def engine(params):
    from butterfly_tpu.engine.serving import ServingEngine
    return ServingEngine(Model(CFG), params, RuntimeConfig(
        max_batch_size=2, max_seq_len=64, page_size=4, num_pages=16,
        decode_steps_per_tick=2, prefill_inline_budget=4))


def greedy_of_the_reference(params, prompt, output):
    """Every served token is the argmax of the reference's logits over
    the tokens before it, by a margin a rounding cannot close."""
    rows = reference(params, list(prompt) + list(output))
    for i, tok in enumerate(output):
        row = rows[len(prompt) + i - 1]
        order = np.argsort(row)
        assert row[order[-1]] - row[order[-2]] > 1e-4 * np.std(row), i
        assert tok == order[-1], i


def test_served_tokens_slot_reuse_and_a_recomputed_preemption(
        params, engine, monkeypatch):
    """Four requests over two slots through the continuous scheduler
    (mixed blocks, the lazy drain, the window and its flush), a pool of
    16 pages that the first two streams outgrow together: the younger
    is preempted MID-DECODE and recomputed from position 0 (its slot's
    state starts from zero inside the program), both slots are reused
    after a finish, and every served token is the reference's greedy
    token. The tick records count what went through a recurrence and
    the states that started from zero."""
    from butterfly_tpu.sched.scheduler import Scheduler
    victims = []
    preempt = Scheduler._preempt
    monkeypatch.setattr(Scheduler, "_preempt", lambda self, req: (
        victims.append((req.state, len(req.output))), preempt(self, req))[1])
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, CFG.vocab_size, n).tolist()
               for n in (5, 6, 13, 9)]
    new = (40, 40, 10, 6)
    sched = Scheduler(engine, seed=0)
    reqs = [sched.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
    sched.run_until_done()
    for prompt, req, n in zip(prompts, reqs, new):
        assert len(req.output) == n
        greedy_of_the_reference(params, prompt, req.output)
    assert sched.alloc.free_pages == 16
    begun = [made for state, made in victims if state == "running"]
    assert begun and max(begun) > 8
    assert int(sched.metrics()["preemptions_total"]) == len(victims)
    ticks = [t for t in sched.ticklog.dump()["ticks"]
             if t["ssm_rows"] is not None]
    assert ticks and all(t["experts_touched"] is None for t in ticks)
    assert sum(t["state_resets"] for t in ticks) == len(reqs) + len(begun)
    once = sum(len(p) for p in prompts) + sum(new) - len(new)
    assert once + sum(begun) <= sum(t["ssm_rows"] for t in ticks) \
        <= once + sum(begun) + 6 * len(begun) + 16
    assert all(t["ssm_steps"] % 2 == 0 and t["ssm_rows"]
               <= t["ssm_steps"] * (2 + 4) for t in ticks)
    assert sched.registry.snapshot()["ssm_state_bytes"] == \
        2 * bytes_per_slot(CFG)
    # the engine's state is the held layout
    assert engine._ssm_state.h.shape == state_shapes(CFG, 2)["h"] \
        == (2, 2, 16, 128)


# -- what cannot take the state refuses the model by name ---------------------

#: four layers, for the meshes of two that divide a model's layers
CFG4 = CFG.replace(num_layers=4, layer_types=CFG.layer_types + ("mamba1",))


def _engine(**rt):
    from butterfly_tpu.engine.serving import ServingEngine
    mesh = rt.pop("mesh", None)
    return ServingEngine(Model(CFG4), None, RuntimeConfig(
        max_batch_size=2, max_seq_len=64, page_size=4, **rt), mesh=mesh)


def _mesh(axis):
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:2]), (axis,))


def _stages():
    from butterfly_tpu.parallel.pipeline import paged_pipeline_packed
    paged_pipeline_packed(None, CFG, None, None, None, None, None, None,
                          mesh=_mesh("stage"))


def _seq_parallel():
    from butterfly_tpu.parallel.sequence import sp_forward
    sp_forward(None, CFG, jnp.zeros((1, 8), jnp.int32), _mesh("seq"))


def _fused_generate():
    from butterfly_tpu.models.common import decode_step_win
    decode_step_win(None, CFG, None, None, [], 0)


def _lane_wide(forward):
    """cache/paged.py's lane-wide forwards refuse the model before they
    read an argument: what still calls them (the speculative block's
    verify) does not carry what this model caches."""
    return forward(None, CFG, *[None] * 4)


#: name -> the call; those that take `engine` run on the module's one
REFUSALS = {
    "prefix caching": lambda e: _engine(prefix_caching=True),
    "host KV tier": lambda e: _engine(prefix_caching=True,
                                      host_kv_tier_mb=1),
    "export": lambda e: e.read_pages([0]),
    "import": lambda e: e.write_pages([0], None, None),
    "pipeline serving": lambda e: _engine(mesh=_mesh("stage")),
    "pipeline": lambda e: _stages(),
    "sequence-parallel prefill lane": lambda e: _engine(mesh=_mesh("seq")),
    "sequence parallelism": lambda e: _seq_parallel(),
    "tensor parallelism": lambda e: _engine(mesh=_mesh("tensor")),
    "data-parallel mesh": lambda e: _engine(mesh=_mesh("data")),
    "speculative": lambda e: _engine(speculative_gamma=2),
    "paged_forward_window": lambda e: _lane_wide(paged_forward_window),
    "lane-wide forward \\(paged_forward": lambda e: _lane_wide(paged_forward),
    "int8 contiguous KV cache":
        lambda e: init_cache(CFG, 1, 16, quant="int8"),
    "write-combined fused generate": lambda e: _fused_generate(),
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_refused_by_name(what, engine):
    with pytest.raises(NotImplementedError, match=what) as e:
        REFUSALS[what](engine)
    assert "recurrent state" in str(e.value)
    assert "Mamba-1" in str(e.value)
    assert "2 of 3" in str(e.value) or "3 of 4" in str(e.value)


def test_no_checkpoint_converter_refuses_the_family_by_name(tmp_path):
    from butterfly_tpu.ckpt.load import load_checkpoint
    with pytest.raises(ValueError, match="no checkpoint converter for arch "
                                         "'jamba'"):
        load_checkpoint(str(tmp_path), CFG)


def _tool(name):
    import importlib
    import sys
    sys.path.insert(0, str(ROOT / "tools"))
    try:
        return importlib.import_module(name)
    finally:
        sys.path.remove(str(ROOT / "tools"))


def _toy_file():
    import json
    return json.loads((ROOT / "tests/servebench/files_mamba1/configs/"
                       "tiny-jamba.json").read_text())


def test_state_parity_tool_reads_the_new_kind_on_the_toy():
    """tools/state_parity.py over the toy file of the family (three
    requests through the scheduler over two slots, each slot's final
    state, a channel at a time, against the reference's loop), bfloat16
    as the cell keeps it: the clean run holds the loop's states beside
    the loop that rounds as the program does; a slot that is not reset,
    or filler that advances, shows in the reused slot; `drift` is what
    bfloat16 costs beside the float32 loop."""
    state_parity = _tool("state_parity")
    toy = _toy_file()
    out = state_parity.check(dict(toy, torch_dtype="bfloat16"), toy=True,
                             long_short=40,
                             requests=((40, 48), (10, 6), (20, 14)))
    assert out["evidence"] == "cpu toy" and out["kind"] == "Mamba-1"
    assert out["clean"]["slots"] == [0, 1, 1]
    assert out["clean"]["long"]["positions"] == 87
    for name in ("long", "second"):
        got = out["clean"][name]
        assert max(got["h_worst"], got["conv_worst"]) < 3e-2, got
        assert got["drift_worst"] < 5e-2
    for fault in ("no_reset", "filler_advances"):
        assert out[fault]["second"]["h_worst"] > \
            3 * out["clean"]["second"]["h_worst"], out[fault]
    assert out["limit"] == state_parity.LIMITS["mamba1"] == 0.17


def test_mixed_parity_tool_reads_the_family_against_its_reference():
    """tools/mixed_parity.py reads the packed step against the plain
    reference, with a prompt of 70 that crosses two chunk edges and a
    flush and then decodes; a chunk fed one token late passes its
    limit."""
    mixed_parity = _tool("mixed_parity")
    toy = _toy_file()
    from servebench.launcher import model_fields
    cfg = ModelConfig(**model_fields(toy))
    params = init_params_by_leaf(cfg, jax.random.PRNGKey(0))
    out = mixed_parity.run_recurrent(cfg, params, toy["serve"], toy, P=1)
    assert out["chunk_prompts"] == [70] and out["chunk_width"] == 32
    assert out["against"].endswith("jamba_f32")
    assert out["clean"]["rows"] >= 10 and \
        out["clean"]["argmax_agree"] == out["clean"]["rows"]
    limit = mixed_parity.LIMITS.get(cfg.recurrent_kind, mixed_parity.LIMIT)
    assert out["clean"]["max"] < 1e-5 < limit < out["chunk_shift"]["max"]


def test_the_mixers_scopes_are_in_the_compiled_step(params):
    """`mamba1_proj`, `mamba1_conv`, `mamba1_inputs` (x-projection, the
    three norms, dt), `mamba1_step` (a decode row's recurrence),
    `mamba1_scan` (a chunk's) and `mamba1_gate` name the mixer's
    operations in a compiled packed step's metadata, beside `attn` and
    `mlp`, where Mamba-2's `ssm_*` stand in granite's and `gdn_*` in
    olmo's (a device trace names an operation by its HLO text and keeps
    the scope in the operation's metadata: the benchmark tells the
    mixers by shapes, servebench/mamba1_peaks.py)."""
    drv = packed_driver.Packed(params, CFG, width=8)
    S = drv.cache.num_slots
    text = packed_driver._packed_step.lower(
        params, CFG, jnp.zeros((S,), jnp.int32), drv.cache,
        jnp.zeros((1, 8), jnp.int32), jnp.asarray([0]), jnp.asarray([8]),
        jnp.ones((S,), bool), drv.window, drv.wlen, state=drv.state,
        use_kernel=False).compile().as_text()
    for scope in ("mamba1_proj", "mamba1_conv", "mamba1_inputs",
                  "mamba1_step", "mamba1_scan", "mamba1_gate", "attn",
                  "mlp"):
        assert f"/{scope}/" in text, scope
    assert "/ssm_proj/" not in text and "/gdn_proj/" not in text
    assert "/moe" not in text           # ONE "expert": no router, no mix


# -- the seeded mixer is a LIVE Mamba-1, and the check sees it ---------------

def test_a_seeded_mixer_has_a_rate_of_its_own_a_channel_and_an_index():
    """models/common.py MAMBA1_SEEDS, by leaf (the chip's path) as in
    init_params: taps N(0, .5), the skip N(0, 1), A uniform in 1-16 and
    dt log-uniform in .001-.1 with a number of its own for every channel
    and state index, dt's projection at R^-1/2. At N(0, .02) each, every
    rate was -1 and a mixer added a fiftieth of a feed-forward's output:
    nothing of it reached a comparison with the reference."""
    from butterfly_tpu.models.common import MAMBA1_SEEDS
    from butterfly_tpu.quant.int8 import _leaf_kind
    cfg = tiny("jamba", dtype="float32", param_dtype="float32")
    R = cfg.mamba1_dt_rank
    for p in (Model(cfg).init(jax.random.PRNGKey(0)),
              init_params_by_leaf(cfg, jax.random.PRNGKey(0))):
        m = jax.tree.map(np.asarray, p["mamba1"])
        A = np.exp(m["A_log"])                              # [Lm, N, Di]
        assert 1 <= A.min() < 1.5 and 15 < A.max() <= 16
        assert A.std(axis=1).min() > 2 and A.std(axis=2).min() > 2
        dt = np.log1p(np.exp(m["dt_bias"]))
        assert 1e-3 * .99 <= dt.min() < 2e-3 and .05 < dt.max() <= .1 * 1.01
        assert 0.4 < m["conv_w"].std() < 0.6 and 0.8 < m["D"].std() < 1.2
        assert 0.8 < m["dt_proj"].std() * R ** 0.5 < 1.2
        for name in ("in_proj", "x_proj", "out_proj", "conv_b"):
            assert 0.015 < m[name].std() < 0.025, name
    stream = (0.02, 1.0)
    assert {n: _leaf_kind(["mamba1", n], stream) for n in MAMBA1_SEEDS} \
        == MAMBA1_SEEDS
    # the other two kinds' stacks are seeded by leaf as they were: their
    # cells' checks and limits were read on those weights
    for stack in ("mamba", "gdn"):
        for name in ("conv_w", "A_log", "dt_bias", "D", "in_proj"):
            assert _leaf_kind([stack, name], stream) == "normal"


@pytest.fixture(scope="module")
def faults_read():
    return _tool("mixer_faults").check(_toy_file(), [2 ** 31 + 5800])


def test_the_check_passes_the_sound_program(faults_read):
    assert faults_read["limit"] == 1e-4 and faults_read["unseen"] == []
    assert max(faults_read["readings"]["clean"]) < 1e-5 and faults_read["ok"]


@pytest.mark.parametrize("fault", [
    "one_rate_a_channel", "one_step_a_layer", "no_dt_norm", "no_b_norm",
    "no_c_norm", "tail_lost", "state_lost"])
def test_the_check_sees_a_mixer_that_is_not_mamba1(faults_read, fault):
    """tools/mixer_faults.py: servebench/refcheck.py's own number (what
    decides `correct`) for the program over weights with ONE Mamba-1
    property taken away, against the reference over the sound weights;
    PERF.md has the chip's readings at the published widths."""
    assert fault in _tool("mixer_faults").FAULTS
    assert min(faults_read["readings"][fault]) > 10 * faults_read["limit"]
