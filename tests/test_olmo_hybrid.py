"""Olmo-Hybrid's family on the CPU at a toy's size with every mechanism
present: two Gated DeltaNet layers and a full one, values twice as wide
as keys, four heads' values in one row of lanes, beta to 2, one query a
KV head, the projection-wide norms, no rotation, the norm on each
sublayer's output, an untied head. LOGITS (and states) against the plain
float32 reference (butterfly_tpu/models/olmo_hybrid_f32.py), which
shares no code with the program. ONE serving engine for the module."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import packed_driver
from packed_driver import err, forward, leaf_of
from butterfly_tpu.cache.paged import paged_forward, paged_forward_window
from butterfly_tpu.cache.ssm_state import (
    bytes_per_slot, gdn_heads_of, gdn_lanes_of, init_ssm_state, state_info,
    state_shapes)
from butterfly_tpu.core.config import (
    PRESETS, ModelConfig, RuntimeConfig, olmo_hybrid_7b, tiny)
from butterfly_tpu.models import olmo_hybrid_f32 as ref
from butterfly_tpu.models.common import (
    Model, gdn_chunk, gdn_step, gdn_step_inputs, init_cache, layer_runs)
from butterfly_tpu.quant.int8 import (
    init_params_by_leaf, is_quantized_leaf, quantize_int8)

ROOT = Path(__file__).resolve().parent.parent
CFG = tiny("olmo_hybrid", dtype="float32", param_dtype="float32")
T = 40
#: rms difference over the standard deviation of the reference's logits
#: at the position. float32 on both sides on the CPU reads 1e-7 to 1e-6
#: (sums in another order; the chunkwise form against the loop); a
#: bfloat16 program reads 1e-2, a term left out or a state leaked 1e-1
TOL = 2e-5


def file_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, as a configuration file
    of `cfg` would hold them."""
    return dict(
        rms_norm_eps=cfg.norm_eps, num_hidden_layers=cfg.num_layers,
        layer_types=["full_attention" if k == "attention" else k
                     for k in cfg.layer_types],
        linear_num_key_heads=cfg.gdn_heads,
        linear_num_value_heads=cfg.gdn_heads,
        linear_key_head_dim=cfg.gdn_key_dim,
        linear_value_head_dim=cfg.gdn_value_dim,
        linear_conv_kernel_dim=cfg.gdn_conv,
        linear_allow_neg_eigval=cfg.gdn_neg_eigval)


def seeded_params(cfg=CFG):
    p = Model(cfg).init(jax.random.PRNGKey(0))
    # norms that are not all ones, so that a norm put in the wrong place
    # or over the wrong width shows; projections loud enough that SiLU
    # bends and beta leaves 1 (b = 0) for both halves of (0, 2); decays
    # slow enough that a state is remembered for tens of positions
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))

    def jitter(a):
        return 1 + 0.3 * jax.random.normal(next(keys), a.shape)

    for g in (p["layers"]["ln1"], p["layers"]["ln2"], p["gdn"]["norm"],
              p["final_norm"], p["attn"]["q_norm"], p["attn"]["k_norm"]):
        g["scale"] = jitter(g["scale"])
    p["gdn"]["in_proj"] = p["gdn"]["in_proj"] * 10
    p["gdn"]["ab_proj"] = p["gdn"]["ab_proj"] * 20
    p["gdn"]["A_log"] = p["gdn"]["A_log"] - 2.0
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
    cfg = PRESETS["olmo-hybrid-7b"]()
    assert cfg == olmo_hybrid_7b() and cfg.arch == "olmo_hybrid"
    assert cfg.layer_types == (("linear_attention",) * 3
                               + ("attention",)) * 8
    assert (cfg.recurrent_kind, cfg.num_ssm_layers, cfg.num_attn_layers) \
        == ("linear_attention", 24, 8)
    assert (cfg.gdn_key_width, cfg.gdn_value_width, cfg.gdn_conv_dim) \
        == (2880, 5760, 11520) and cfg.gdn_key_width * 4 == 3 * 3840
    assert cfg.q_per_kv == 1 and cfg.num_heads * cfg.head_dim == 3840
    assert layer_runs(cfg) == [
        (kind, 4 * i + (3 if kind == "attention" else 0), n, at)
        for i in range(8)
        for kind, n, at in (("linear_attention", 3, 3 * i),
                            ("attention", 1, i))]
    # the mixer's five projections, 6 x hidden^2, and the feed-forward:
    # the catalog's "about 208M a layer"
    shapes = jax.eval_shape(lambda: Model(cfg).init(jax.random.PRNGKey(0)))
    gdn = shapes["gdn"]
    wide = sum(np.prod(gdn[k].shape[1:]) for k in ("in_proj", "out_proj"))
    assert wide == 6 * 3840 ** 2 == 88_473_600
    assert gdn["ab_proj"].shape == (24, 3840, 60)
    assert gdn["conv_w"].shape == (24, 4, 11520) and "conv_b" not in gdn
    assert shapes["attn"]["q_norm"]["scale"].shape == (8, 30, 128)
    assert shapes["lm_head"].shape == (3840, 100352)


def test_the_state_is_held_as_declared_in_whole_tiles():
    """Two heads' values share a row of 384 lanes and the keys lie down
    96 sublanes: [24, S, 15, 96, 384] is whole tiles of bfloat16, so a
    slot's 28,200,960 B are what the memory holds; a head's own
    [192, 96] (or [96, 192]) would be padded by a third."""
    cfg = olmo_hybrid_7b()
    assert cfg.gdn_head_group == 2
    shapes = state_shapes(cfg, 64)
    assert shapes == {"h": (24, 64, 15, 96, 384),
                      "conv": (24, 3, 64, 11520)}
    assert shapes["h"][-1] % 128 == 0 and shapes["h"][-2] % 16 == 0
    assert bytes_per_slot(cfg) == 24 * (30 * 192 * 96 + 3 * 11520) * 2 \
        == 28_200_960
    info = state_info(cfg, 64)
    assert info["kind"] == "Gated DeltaNet" and info["layers"] == 24
    assert "[24, 64, 15, 96, 384]" in info["layout"]
    assert info["bytes"] == 64 * 28_200_960 and info["whole_tiles"]
    # a geometry whose values fill no whole lanes is held padded, and
    # the report says so
    odd = cfg.replace(gdn_heads=3, gdn_value_dim=40)
    assert odd.gdn_head_group == 1 and odd.gdn_conv_dim == 696
    assert not state_info(odd, 64)["whole_tiles"]
    assert bytes_per_slot(odd) == 24 * (3 * 96 * 40 + 3 * 696) * 2
    # a head's own [96, 192] would be padded by a third
    from butterfly_tpu.cache.ssm_state import _held
    assert _held((30, 96, 192), 2) * 3 == 30 * 96 * 192 * 4
    assert state_info(tiny("granite_hybrid"), 2)["kind"] == "Mamba-2"


BAD = {
    "two recurrent kinds": dict(layer_types=("mamba", "linear_attention",
                                             "attention"), ssm_heads=2,
                                ssm_head_dim=4, ssm_state=4),
    "no gdn sizes": dict(gdn_heads=0),
    "a conv of one tap": dict(gdn_conv=1),
    "both norms on q and k": dict(qk_norm=True),
    "post_norm without a recurrent kind": dict(layer_types=()),
    "post_norm beside streams": dict(hc_mult=2),
    "an unknown kind": dict(layer_types=("linear_attention", "full", "x")),
}


@pytest.mark.parametrize("what", list(BAD))
def test_the_configuration_refuses(what):
    with pytest.raises(ValueError):
        tiny("olmo_hybrid", **BAD[what])


# -- the delta rule itself ----------------------------------------------------

def _unit_state(cfg, slots=1):
    return init_ssm_state(cfg.replace(num_layers=1, layer_types=(
        "linear_attention",)), slots).h


def _write(h, cfg, key, value, beta=1.0, alpha=1.0, additive=False):
    """One position through gdn_step for every head: (what the state
    then answers for `key`, h). additive: the control, an update that
    adds beta v k^T without reading what the state holds."""
    H, dk, dv = cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    k = jnp.broadcast_to(key, (1, H, dk))
    v = jnp.tile(value, H)[None]
    if additive:
        # v + S k in place of v: the delta rule's read, cancelled
        _, held = _read(h, cfg, key)
        v = v + alpha * held.reshape(1, H * dv)
    la = jnp.full((1, H), np.log(alpha), jnp.float32)
    _, h = gdn_step(h, 0, k, k, v, la, jnp.full((1, H), beta), cfg)
    return _read(h, cfg, key)[1], h


def _read(h, cfg, key):
    """(h, S k [H, dv]) for every head of slot 0."""
    S = gdn_heads_of(h[0, :1].astype(jnp.float32), cfg)[0]   # [H, dv, dk]
    return h, jnp.einsum("hvk,k->hv", S, key)


def test_a_second_write_to_a_key_overwrites_the_first():
    """alpha = 1, beta = 1, a unit key: after (k, v) and then (k, v')
    the state answers S k = v'. An additive update answers v + v'."""
    cfg = CFG
    rng = np.random.RandomState(0)
    key = rng.randn(cfg.gdn_key_dim)
    key = jnp.asarray(key / np.linalg.norm(key), jnp.float32)
    other = jnp.asarray(np.eye(cfg.gdn_key_dim)[0] - key[0] * key,
                        jnp.float32)
    other = other / jnp.linalg.norm(other)                   # orthogonal
    v1, v2 = (jnp.asarray(rng.randn(cfg.gdn_value_dim), jnp.float32)
              for _ in range(2))
    h = _unit_state(cfg)
    got, h = _write(h, cfg, key, v1)
    np.testing.assert_allclose(got, np.tile(v1, (cfg.gdn_heads, 1)),
                               atol=1e-6)
    got, h = _write(h, cfg, key, v2)
    np.testing.assert_allclose(got, np.tile(v2, (cfg.gdn_heads, 1)),
                               atol=1e-6)
    # what an orthogonal key holds is left as it was
    _, h = _write(h, cfg, other, v1)
    np.testing.assert_allclose(_read(h, cfg, key)[1],
                               np.tile(v2, (cfg.gdn_heads, 1)), atol=1e-6)
    # the control: the same two writes, added
    h = _unit_state(cfg)
    _, h = _write(h, cfg, key, v1, additive=True)
    got, _ = _write(h, cfg, key, v2, additive=True)
    np.testing.assert_allclose(got, np.tile(v1 + v2, (cfg.gdn_heads, 1)),
                               atol=1e-5)
    assert np.abs(np.asarray(got) - np.asarray(v2)).max() > 0.1


def test_beta_zero_leaves_only_the_decay_and_beta_two_reflects():
    cfg = CFG
    key = jnp.asarray(np.eye(cfg.gdn_key_dim)[2], jnp.float32)
    v = jnp.arange(1.0, cfg.gdn_value_dim + 1)
    h = _unit_state(cfg)
    _, h = _write(h, cfg, key, v)
    before = np.asarray(h, np.float64)
    got, h0 = _write(h, cfg, key, 5 * v, beta=0.0, alpha=0.5)
    np.testing.assert_allclose(np.asarray(h0), 0.5 * before, atol=1e-6)
    np.testing.assert_allclose(got[0], 0.5 * np.asarray(v), atol=1e-6)
    # beta = 2 with nothing to write: S (I - 2 k k^T), the eigenvalue -1
    got, _ = _write(h, cfg, key, 0 * v, beta=2.0)
    np.testing.assert_allclose(got[0], -np.asarray(v), atol=1e-6)


@pytest.mark.parametrize("neg", [True, False], ids=["to_2", "to_1"])
def test_beta_spans_0_2_only_under_neg_eigval(neg):
    cfg = CFG.replace(gdn_neg_eigval=neg)
    H = cfg.gdn_heads
    gp = {"dt_bias": jnp.zeros((H,)), "A_log": jnp.zeros((H,))}
    b = jnp.asarray([-20.0, 0.0, 1.0, 20.0])[None, :, None] * jnp.ones((H,))
    u = jnp.ones((1, 4, cfg.gdn_conv_dim))
    q, k, _, la, beta = gdn_step_inputs(u, 0 * b, b, gp, cfg,
                                        jnp.asarray([3]))
    top = 2.0 if neg else 1.0
    np.testing.assert_allclose(beta[0, :3, 0], top * np.asarray(
        [0.0, 0.5, 1 / (1 + np.exp(-1.0))]), atol=1e-6)
    assert (float(beta[0, 2, 0]) > 1.0) == neg
    # a position that is not real passes the state on: alpha 1, beta 0
    assert float(beta[0, 3, 0]) == 0.0 and float(la[0, 3, 0]) == 0.0
    assert float(la[0, 0, 0]) < 0
    np.testing.assert_allclose(jnp.sum(k * k, -1), 1.0, atol=1e-4)
    np.testing.assert_allclose(jnp.sum(q * q, -1), 1 / cfg.gdn_key_dim,
                               atol=1e-5)


@pytest.mark.parametrize("T_,count,alone", [
    (1, 1, False), (6, 4, False), (32, 32, False), (70, 70, False),
    (70, 41, False), (40, 35, True)])
def test_the_chunkwise_form_is_the_per_position_loop(T_, count, alone):
    """gdn_chunk (pieces of 32 solved at once, the state handed on)
    against the reference's loop of delta_step, and against gdn_step a
    position at a time over the HELD layout: outputs and final state; a
    row's positions past `count` advance nothing. `alone`: a geometry
    whose heads fill no whole lanes together (three of 40 values), so
    that a head's values lie in a row of their own (gdn_head_group 1)."""
    cfg = CFG.replace(gdn_heads=3, gdn_value_dim=40) if alone else CFG
    assert cfg.gdn_head_group == (1 if alone else 4)
    H, dk, dv = cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    rng = np.random.RandomState(T_ + count)
    u = jnp.asarray(rng.randn(2, T_, cfg.gdn_conv_dim), jnp.float32)
    a, b = (jnp.asarray(2 * rng.randn(2, T_, H), jnp.float32)
            for _ in range(2))
    gp = {"dt_bias": jnp.asarray(rng.randn(H), jnp.float32),
          "A_log": jnp.asarray(rng.randn(H) - 1, jnp.float32)}
    q, k, v, la, beta = gdn_step_inputs(u, a, b, gp, cfg,
                                        jnp.asarray([count, count]))
    s0 = jnp.asarray(rng.randn(2, H, dv, dk), jnp.float32)
    o, st = jax.jit(gdn_chunk, static_argnums=6)(
        q, k, v, la, beta, gdn_lanes_of(s0, cfg), cfg)
    st = gdn_heads_of(st, cfg)
    assert o.shape == (2, T_, H, dv)
    # the loop, the reference's own step
    S, rows = s0, []
    for t in range(T_):
        new = [ref.delta_step(S[r], q[r, t], k[r, t],
                              v[r, t].reshape(H, dv), jnp.exp(la[r, t]),
                              beta[r, t]) for r in range(2)]
        S = jnp.stack([n[0] for n in new])
        rows.append(jnp.stack([n[1] for n in new]))
    want_o = np.asarray(jnp.stack(rows, axis=1))
    scale = np.abs(want_o).max()
    assert np.abs(np.asarray(o)[:, :count] - want_o[:, :count]).max() \
        < 2e-5 * scale
    assert np.abs(np.asarray(st - S)).max() < 2e-5 * np.abs(S).max()
    # and the decode rows' step over the held layout, a position a call
    h = gdn_lanes_of(s0, cfg)[None]
    step = jax.jit(lambda h, *a: gdn_step(h, 0, *a, cfg))
    for t in range(min(T_, 8)):
        o_t, h = step(h, q[:, t], k[:, t], v[:, t], la[:, t], beta[:, t])
        if t < count:
            assert np.abs(np.asarray(o_t).reshape(2, H, dv)
                          - want_o[:, t]).max() < 2e-5 * scale, t


# -- the contiguous cache ---------------------------------------------------

def test_the_reference_is_in_the_repo_twice_and_is_not_trivial(want):
    assert (ROOT / "butterfly_tpu/models/olmo_hybrid_f32.py").read_text() \
        == (ROOT / "servebench/references/olmo_hybrid_f32.py").read_text()
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
    assert cache.k.shape[0] == 1 and cache.ssm.h.shape[0] == 2


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
    # a dense model: three zeros where experts' loads would be, then the
    # positions pushed through a recurrence and the slots that started
    # from zero (three streams began)
    loads = np.stack(drv.loads)
    assert loads.shape[1] == 5 and not loads[:, :3].any()
    assert loads[0, 3] == 6 and loads[3, 3] == 2
    assert loads[4, 3] == 1 + 6 and loads[:, 4].sum() == 3


def held_heads(state, m, slot, cfg=CFG):
    """Layer m's state of one slot a head at a time, [H, dv, dk]."""
    return np.asarray(gdn_heads_of(
        jnp.asarray(state.h[m, slot])[None].astype(jnp.float32), cfg)[0])


def test_the_recurrence_is_tied_to_the_loop(params, tokens, scripted):
    """After a prompt fed as chunks of C, the last partly filler, then
    decode steps, each slot's state IS the state the reference's
    position-by-position loop holds after the same tokens: filler
    columns advanced nothing, a slot given to a second stream holds
    that stream's state and nothing of the first's, a slot that never
    held a stream is zero."""
    _, drv, seen = scripted
    for slot, (s, n) in seen.items():
        held = []
        reference(params, tokens[s, :n], states=held)
        assert len(held) == CFG.num_ssm_layers == 2
        for m, (S, tail) in enumerate(held):
            scale = np.abs(np.asarray(S)).max()
            assert scale > 1e-3         # a state worth comparing
            assert np.abs(held_heads(drv.state, m, slot) - S).max() \
                < 2e-5 * scale, (slot, m)
            got_t = np.asarray(drv.state.conv[m, :, slot])
            assert np.abs(got_t - tail).max() < 1e-5 * np.abs(tail).max()
    assert not np.asarray(drv.state.h[:, 2]).any()
    assert not np.asarray(drv.state.conv[:, :, 2]).any()


def test_the_kernel_step_is_the_jnp_step_through_the_packed_run(
        params, tokens, scripted):
    """The scripted run (chunks, filler, decode rows beside a chunk that
    reads ITS slot's old state, a reused slot), kernels on (interpreted
    here; the toy's state is whole tiles of float32: four heads' values
    a row of 128 lanes): the decode rows' recurrence is the gdn_step
    kernel, every other row's is the chunkwise form, and logits and
    states are the `jnp` run's to float32 rounding."""
    from butterfly_tpu.ops import record_kernels
    out_j, drv_j, _ = scripted
    with record_kernels({}) as calls:
        out_k, drv_k, _ = packed_driver.scripted_run(params, tokens, CFG,
                                                     use_kernel=True)
    # a call site is a traced run's body, in the one program the driver
    # steps
    assert calls["gdn_step:interpret"] >= 1 and "dense_fallback" not in calls
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
    """A mixed step's Gated DeltaNet layers at the cell's own shapes (64
    decode rows beside one chunk of 32 at the published widths, the
    state riding two scans as the engine's block carries it, donated),
    kernels on, compiled for the TPU: the decode rows' recurrence is the
    Mosaic call `gdn_step`, nothing copies the state, whole or a layer
    of it, nothing computes its update a second time (`.remat`: PERF.md,
    PR 56), and the call's HLO text, which is all a device trace knows
    of it, is caught by the benchmark's reader of the mixers
    (servebench/gdn_peaks.py) and not by the paged kernel's.
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
    from servebench.gdn_peaks import gdn_patterns
    from servebench.xplane import clean
    sys.path.insert(0, str(ROOT / "tools"))
    try:
        from chip_kernels import state_copies
    finally:
        sys.path.remove(str(ROOT / "tools"))
    # six layers of the published pattern: two runs of three mixers
    cfg = olmo_hybrid_7b().replace(
        num_layers=8, layer_types=olmo_hybrid_7b().layer_types[:8],
        dtype="bfloat16")
    S, P, C, Lm = 64, 1, 32, cfg.num_ssm_layers

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: init_params_by_leaf(
        cfg, jax.random.PRNGKey(0), quant="int8")))
    state = on_chip(jax.eval_shape(lambda: init_ssm_state(cfg, S)))
    assert state.h.shape == (Lm, S, 15, 96, 384)
    rows = on_chip(StateRows(
        active=jnp.zeros((S,), bool), ok=jnp.zeros((S + P * C,), bool),
        chunk_slot=jnp.zeros((P,), jnp.int32), chunk_ok=jnp.zeros((P,), bool),
        chunk_pos=jnp.zeros((P, C), jnp.int32)))

    def prog(x, state, params, rows):
        def layer(carry, i):
            x, st = carry
            x, st, _ = advance_packed(
                x, layer_at(params["layers"], i, cfg),
                layer_at(params["gdn"], i, cfg), st, i, rows, cfg,
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
    assert calls and all(c.startswith("%gdn_step") for c in calls)
    # the conv's tails are read and written where they lie (PR 64)
    assert packed_driver.swapped_tails(
        hlo, state.conv, S + P * C, state_copies(hlo, state.conv)) == []
    config = json.loads((ROOT / "servebench" / "configs"
                         / "olmo-hybrid-7b.json").read_text())
    mixers = gdn_patterns(config)
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
    held = []
    want = reference(params, seq, states=held)
    for lo, n in ((0, 32), (32, 32), (64, 6)):
        got = drv.step({}, (2, seq[lo:lo + n]))
        assert err(got[2], want[lo + n - 1]) < TOL, lo
    for t in (70, 71):
        got = drv.step({2: seq[t]})
        assert err(got[2], want[t]) < TOL, t
    for m, (S, _) in enumerate(held):
        assert np.abs(held_heads(drv.state, m, 2) - S).max() \
            < 2e-5 * np.abs(np.asarray(S)).max()


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
        held = []
        reference(params, seq[:n], states=held)
        for m, (S, tail) in enumerate(held):
            assert np.abs(held_heads(drv.state, m, slot) - S).max() \
                < 2e-5 * np.abs(np.asarray(S)).max(), (slot, m)
            assert np.abs(np.asarray(drv.state.conv[m, :, slot]) - tail) \
                .max() < 1e-5 * np.abs(tail).max()


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


def test_int8_weights_quantize_the_five_projections_by_path(tokens):
    """Weight-only codes for the mixer's wide projections (q | k | v | z
    in one leaf, and the output), the attention, the feed-forward and
    the head; a and b (two numbers a head), the conv, A_log, dt_bias,
    the norms and the embedding stay float. Against the reference over
    the SAME codes times scales the program differs by float32
    rounding."""
    p = quantize_int8(seeded_params(), CFG)
    assert {k for k, v in p["gdn"].items() if is_quantized_leaf(v)} \
        == {"in_proj", "out_proj"}
    assert {k for k, v in p["attn"].items() if is_quantized_leaf(v)} \
        == {"wq", "wk", "wv", "wo"}
    assert all(is_quantized_leaf(v) for v in p["layers"]["mlp"].values())
    assert is_quantized_leaf(p["lm_head"])
    assert not is_quantized_leaf(p["embed"]["tok"])
    # one scale an output channel: the fused leaf's are the five
    # projections' own
    assert p["gdn"]["in_proj"]["s"].shape == (2, 1, CFG.gdn_conv_dim
                                              + CFG.gdn_value_width)
    got, _ = forward(p, CFG, jnp.asarray(tokens[:1, :T]),
                     init_cache(CFG, 1, 64))
    want = reference(p, tokens[0, :T])
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
    # the engine's state is the held layout, and /health's group says so
    assert engine._ssm_state.h.shape == state_shapes(CFG, 2)["h"] \
        == (2, 2, 1, 16, 128)


# -- what cannot take the state refuses the model by name ---------------------

#: four layers, for the meshes of two that divide a model's layers
CFG4 = CFG.replace(num_layers=4, layer_types=CFG.layer_types
                   + ("linear_attention",))


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
    assert "Gated DeltaNet" in str(e.value)
    assert "2 of 3" in str(e.value) or "3 of 4" in str(e.value)


def test_state_parity_tool_reads_the_new_kind_on_the_toy():
    """tools/state_parity.py over the toy file of the family (three
    requests through the scheduler over two slots, each slot's final
    state, a head at a time, against the reference's loop), bfloat16 as
    the cell keeps it: the clean run holds the loop's states beside the
    loop that rounds as the program does; a slot that is not reset, or
    filler that advances, shows in the reused slot; `drift` is what
    bfloat16 costs beside the float32 loop."""
    import json
    import sys
    sys.path.insert(0, str(ROOT / "tools"))
    try:
        import state_parity
    finally:
        sys.path.remove(str(ROOT / "tools"))
    toy = json.loads((ROOT / "tests/servebench/files_gdn/configs/"
                      "tiny-olmo-hybrid.json").read_text())
    out = state_parity.check(dict(toy, torch_dtype="bfloat16"), toy=True,
                             long_short=72,
                             requests=((40, 80), (10, 6), (20, 30)))
    assert out["evidence"] == "cpu toy" and out["kind"] == "Gated DeltaNet"
    assert out["clean"]["slots"] == [0, 1, 1]
    assert out["clean"]["long"]["positions"] == 119
    for name in ("long", "second"):
        got = out["clean"][name]
        assert max(got["h_worst"], got["conv_worst"]) < 2e-2, got
        # beside the float32 loop it reads the same: at these sizes the
        # step's own rounding, not the stored dtype, is what is read
        assert 0.8 * got["h_worst"] <= got["drift_worst"] < 2e-2
    for fault in ("no_reset", "filler_advances"):
        # 0.061 and 0.147 beside the clean run's 0.009 (the chip's
        # readings, which set the kind's limit: 0.036, 0.236 and 0.367)
        assert out[fault]["second"]["h_worst"] > 0.05, out[fault]
    assert out["limit"] == state_parity.LIMITS["linear_attention"] == 0.09


def test_mixed_parity_tool_reads_a_recurrent_model_against_its_reference():
    """tools/mixed_parity.py has no lane-wide step to lay beside the
    packed step of a model with a recurrent state (paged_forward_window
    refuses it): it reads the packed step against the plain reference,
    with a prompt of 70 that crosses two chunk edges and a flush and
    then decodes; a chunk fed one token late passes its limit."""
    import json
    import sys
    sys.path.insert(0, str(ROOT / "tools"))
    try:
        import mixed_parity
    finally:
        sys.path.remove(str(ROOT / "tools"))
    toy = json.loads((ROOT / "tests/servebench/files_gdn/configs/"
                      "tiny-olmo-hybrid.json").read_text())
    from servebench.launcher import model_fields
    cfg = ModelConfig(**model_fields(toy))
    params = init_params_by_leaf(cfg, jax.random.PRNGKey(0))
    out = mixed_parity.run_recurrent(cfg, params, toy["serve"], toy, P=1)
    assert out["chunk_prompts"] == [70] and out["chunk_width"] == 32
    assert out["against"].endswith("olmo_hybrid_f32")
    assert out["clean"]["rows"] >= 10 and \
        out["clean"]["argmax_agree"] == out["clean"]["rows"]
    assert out["clean"]["max"] < 1e-5 < mixed_parity.LIMITS[cfg.recurrent_kind] \
        < out["chunk_shift"]["max"]


def test_the_mixers_scopes_are_in_the_compiled_step(params):
    """`gdn_proj`, `gdn_conv`, `gdn_step` (a decode row's delta rule),
    `gdn_chunk` (a chunk's solve) and `gdn_gate` name the mixer's
    operations in a compiled packed step's metadata, beside `attn` and
    `mlp`, where Mamba-2's `ssm_*` stand in granite's (a device trace
    names an operation by its HLO text and keeps the scope in the
    operation's metadata: the benchmark tells the mixers by shapes,
    servebench/gdn_peaks.py)."""
    drv = packed_driver.Packed(params, CFG, width=8)
    S = drv.cache.num_slots
    text = packed_driver._packed_step.lower(
        params, CFG, jnp.zeros((S,), jnp.int32), drv.cache,
        jnp.zeros((1, 8), jnp.int32), jnp.asarray([0]), jnp.asarray([8]),
        jnp.ones((S,), bool), drv.window, drv.wlen, state=drv.state,
        use_kernel=False).compile().as_text()
    for scope in ("gdn_proj", "gdn_conv", "gdn_step", "gdn_chunk",
                  "gdn_gate", "attn", "mlp"):
        assert f"/{scope}/" in text, scope
    assert "/ssm_proj/" not in text and "/ssm_scan/" not in text
