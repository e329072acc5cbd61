"""granite-4.0-h-small's family on the CPU at a toy's size with every
mechanism present: both layer kinds, a conv of 4, heads x head_dim x
state, 8 experts with a shared one, the four multipliers, a tied head.
LOGITS (and states) against the plain float32 reference
(servebench/references/granite_hybrid_f32.py), which shares no code
with the program."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import packed_driver
from packed_driver import C, err, forward, leaf_of
from butterfly_tpu.cache.paged import (
    init_kv_window, init_paged_cache, paged_forward, paged_forward_window)
from butterfly_tpu.cache.ssm_state import (
    bytes_per_slot, init_ssm_state, reset_slots, state_info)
from butterfly_tpu.core.config import (
    PRESETS, ModelConfig, RuntimeConfig, granite_4_h_small, tiny)
from servebench.references import granite_hybrid_f32 as ref
from butterfly_tpu.models.common import Model, init_cache, layer_runs
from butterfly_tpu.quant.int8 import (
    init_params_by_leaf, is_quantized_leaf, quantize_int8)

CFG = tiny("granite_hybrid", dtype="float32", param_dtype="float32")
#: no attention layer at all: the pool holds no layer, and the paths
#: that flush and read pages still work
ALL_MAMBA = CFG.replace(num_layers=2, layer_types=("mamba", "mamba"))
T = 40
#: rms difference over the standard deviation of the reference's logits
#: at the position. float32 on both sides on the CPU reads 1e-7 to 1e-6
#: (sums in another order); a bfloat16 program reads 1e-2, a term left
#: out or a state leaked reads 1e-1 and more
TOL = 2e-5


def file_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, as a configuration file
    of `cfg` would hold them."""
    return dict(
        rms_norm_eps=cfg.norm_eps, num_hidden_layers=cfg.num_layers,
        layer_types=list(cfg.layer_types),
        num_local_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        mamba_n_heads=cfg.ssm_heads, mamba_d_head=cfg.ssm_head_dim,
        mamba_d_state=cfg.ssm_state, mamba_n_groups=cfg.ssm_groups,
        mamba_d_conv=cfg.ssm_conv,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        logits_scaling=cfg.logits_scaling)


def seeded_params(cfg=CFG):
    p = Model(cfg).init(jax.random.PRNGKey(0))
    # norms that are not all ones, so that a norm put in the wrong place
    # shows; mixers loud enough to move the stream off the embedding
    # (a tied head over an untouched stream repeats its token); decays
    # slow enough that a state is remembered for tens of positions
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))

    def jitter(a):
        return 1 + 0.3 * jax.random.normal(next(keys), a.shape)

    for g in (p["layers"]["ln1"], p["layers"]["ln2"], p["mamba"]["norm"],
              p["final_norm"]):
        g["scale"] = jitter(g["scale"])
    p["mamba"]["out_proj"] = p["mamba"]["out_proj"] * 40
    p["mamba"]["in_proj"] = p["mamba"]["in_proj"] * 10
    p["mamba"]["A_log"] = p["mamba"]["A_log"] - 2.0
    if "wo" in p["attn"] and p["attn"]["wo"].shape[0]:
        p["attn"]["wo"] = p["attn"]["wo"] * 40
        p["attn"]["wq"] = p["attn"]["wq"] * 20
        p["attn"]["wk"] = p["attn"]["wk"] * 20
    p["layers"]["moe"]["w_down"] = p["layers"]["moe"]["w_down"] * 40
    p["layers"]["shared"]["w_down"] = p["layers"]["shared"]["w_down"] * 40
    return p


@pytest.fixture(scope="module")
def params():
    return seeded_params()


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(3).randint(1, CFG.vocab_size, (3, T))


def reference(params, tokens, cfg=CFG, states=None):
    return np.asarray(ref.logits(np.asarray(tokens), leaf_of(params),
                                 file_config(cfg), states=states))


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's full forward of the three sequences: [3, T, V]."""
    return np.stack([reference(params, t) for t in tokens])


# -- the contiguous cache ---------------------------------------------------

def test_the_reference_is_not_trivial(want, tokens):
    """The logits move with the context: a tied head over a stream the
    mixers never touched would put each token's own id on top."""
    top = want.argmax(-1)
    assert np.mean(top == tokens) < 0.5
    assert len(np.unique(top)) > 10


def test_contiguous_forward_whole(params, tokens, want):
    got, cache = forward(params, CFG, jnp.asarray(tokens),
                         init_cache(CFG, 3, 64))
    for s in range(3):
        for t in range(T):
            assert err(got[s, t], want[s, t]) < TOL, (s, t)
    assert cache.k.shape[0] == 1 and cache.ssm.h.shape[0] == 3


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

def Packed(params, cfg=CFG, windowed=True, **kw):
    return packed_driver.Packed(params, cfg, windowed, **kw)


def scripted_run(params, tokens, windowed=True, cfg=CFG, use_kernel=False):
    return packed_driver.scripted_run(params, tokens, cfg, windowed,
                                      use_kernel)


@pytest.fixture(scope="module")
def scripted(params, tokens):
    """The scripted run through the window and its flush, once."""
    return scripted_run(params, tokens)


@pytest.mark.parametrize("windowed", [False, True], ids=["pool", "window"])
def test_packed_steps_chunks_filler_decode_rows_and_a_reused_slot(
        params, tokens, want, windowed, scripted):
    out, drv, _ = scripted if windowed \
        else scripted_run(params, tokens, windowed)
    assert len(out) > 30
    assert {s for s, _, _ in out} == {0, 1, 2}
    for s, pos, row in out:
        assert err(row, want[s, pos]) < TOL, (s, pos)
    # the load's last two: positions pushed through a recurrence, and
    # the slots that started from zero (three streams began)
    loads = np.stack(drv.loads)
    assert loads.shape[1] == 5 and loads[0, 3] == 6 and loads[3, 3] == 2
    assert loads[4, 3] == 1 + 6 and loads[:, 4].sum() == 3
    assert loads[:, 0].max() <= CFG.num_experts


def test_the_recurrence_is_tied_to_the_scan(params, tokens, scripted):
    """The two ways a state leaks, neither of which shows in tokens
    with random weights. (1) After a prompt fed as chunks of C, the
    last partly filler, then decode steps, each slot's state IS the
    state the reference's position-by-position loop holds after the
    same tokens, to float32 rounding: filler columns advanced nothing.
    (2) A slot given to a second stream holds that stream's state and
    nothing of the first's (its logits are a fresh server's:
    test_packed_steps_...); a slot that never held a stream is zero."""
    _, drv, seen = scripted
    for slot, (s, n) in seen.items():
        held = []
        reference(params, tokens[s, :n], states=held)
        assert len(held) == CFG.num_ssm_layers
        for m, (H, tail) in enumerate(held):
            got_h = np.asarray(drv.state.h[m, slot])
            got_t = np.asarray(drv.state.conv[m, :, slot])
            scale = np.abs(np.asarray(H)).max()
            assert scale > 1e-3         # a state worth comparing
            assert np.abs(got_h - H).max() < 1e-5 * scale, (slot, m)
            assert np.abs(got_t - tail).max() < 1e-5 * np.abs(tail).max()
    assert not np.asarray(drv.state.h[:, 2]).any()
    assert not np.asarray(drv.state.conv[:, :, 2]).any()


def test_the_kernel_step_is_the_jnp_step_through_the_packed_run(tokens):
    """The scripted run (chunks, filler, decode rows beside a chunk that
    reads ITS slot's old state, a reused slot) on a toy whose state is
    whole tiles, kernels on (interpreted here): the decode rows'
    recurrence is the ssm_step kernel, every other row's is the scan,
    and logits and states are the `jnp` run's to float32 rounding."""
    from butterfly_tpu.ops import record_kernels
    cfg = CFG.replace(ssm_state=128)
    p = seeded_params(cfg)
    out_j, drv_j, _ = scripted_run(p, tokens, cfg=cfg)
    with record_kernels({}) as calls:
        out_k, drv_k, _ = scripted_run(p, tokens, cfg=cfg, use_kernel=True)
    # a call site is a traced Mamba run's body (the toy's second run
    # reuses the first's trace), in the one program the driver steps
    assert calls["ssm_step:interpret"] >= 1 and "dense_fallback" not in calls
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


def test_chunked_prefill_equals_one_shot(params, tokens):
    """A prompt of 17 as chunks of 6 (6, 6, 5) and as one chunk of 32
    with 15 of filler: the same logits and the same state."""
    a, b = Packed(params), Packed(params, width=32)
    for lo in (0, 6, 12):
        got_a = a.step({}, (0, tokens[0, lo:min(lo + 6, 17)]))
    got_b = b.step({}, (0, tokens[0, :17]))
    assert err(got_a[0], got_b[0]) < TOL
    ha, hb = np.asarray(a.state.h[:, 0]), np.asarray(b.state.h[:, 0])
    assert np.abs(ha - hb).max() < 1e-5 * np.abs(hb).max()
    np.testing.assert_allclose(np.asarray(a.state.conv[:, :, 0]),
                               np.asarray(b.state.conv[:, :, 0]), atol=1e-5)


def test_an_idle_chunk_leaves_what_slot_0s_chunk_wrote(params, tokens):
    """Two chunks a step (prefill_inline_budget over the chunk's width),
    the second idle: its slot reads 0, and slot 0 is where the real
    chunk writes. What it writes back is the state as the real chunk
    LEFT it, not as the step found it: logits and states are the scan's."""
    seq = tokens[0]
    out, drv = packed_driver.idle_chunk_run(params, seq, CFG)
    want = reference(params, seq[:19])
    for slot, pos, row in out:
        assert err(row, want[pos]) < TOL, (slot, pos)
    for slot, n in ((0, 19), (1, 5)):
        held = []
        reference(params, seq[:n], states=held)
        for m, (H, tail) in enumerate(held):
            assert np.abs(np.asarray(drv.state.h[m, slot]) - H).max() \
                < 1e-5 * np.abs(np.asarray(H)).max(), (slot, m)
            assert np.abs(np.asarray(drv.state.conv[m, :, slot]) - tail) \
                .max() < 1e-5 * np.abs(tail).max()


def test_a_model_of_mamba_layers_only_has_a_pool_of_no_layer(tokens):
    """No attention layer: the pool and the window hold no layer, the
    table, the lengths and the flush work all the same."""
    p = seeded_params(ALL_MAMBA)
    want = reference(p, tokens[0], ALL_MAMBA)
    drv = Packed(p, ALL_MAMBA)
    assert drv.cache.k_pages.shape[0] == drv.window.k.shape[0] == 0
    for lo in (0, 6, 12):
        got = drv.step({}, (0, tokens[0, lo:lo + 6]))
        assert err(got[0], want[lo + 5]) < TOL
    for t in range(18, 26):
        got = drv.step({0: tokens[0, t]})
        assert err(got[0], want[t]) < TOL
    drv.flush()
    assert int(drv.cache.lengths[0]) == 26


# -- precisions ---------------------------------------------------------------

def test_a_bfloat16_program_fails_the_limit_float32_passes(params, tokens,
                                                          want):
    """The limit of these tests tells the precision below: the same
    weights run in bfloat16 (state kept in bfloat16 between calls, as
    the benchmark's configuration keeps it) read a thousand times the
    float32 program's error."""
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


def test_int8_weights_quantize_the_projections_and_nothing_delicate(tokens):
    """Weight-only codes for in_proj, out_proj, the experts, the shared
    expert, the attention and a second copy of the tied head; the conv,
    A_log, D, dt_bias, the norms, the router and the embedding stay
    float. Against the reference over the SAME codes times scales the
    program differs by float32 rounding."""
    p = quantize_int8(seeded_params(), CFG)
    q = {k for k, v in p["mamba"].items() if is_quantized_leaf(v)}
    assert q == {"in_proj", "out_proj"}
    assert all(is_quantized_leaf(v) for v in p["layers"]["shared"].values())
    assert all(is_quantized_leaf(v) for v in p["attn"].values())
    assert not is_quantized_leaf(p["layers"]["moe"]["router"])
    assert not is_quantized_leaf(p["embed"]["tok"])
    assert is_quantized_leaf(p["lm_head"])
    assert p["lm_head"]["q8"].shape == (CFG.hidden_size, CFG.vocab_size)
    got, _ = forward(p, CFG, jnp.asarray(tokens[:1]), init_cache(CFG, 1, 64))
    # the head's codes are the program's alone (the reference reads the
    # embedding): compare below the head, through a reference that is
    # given the head's own dequantized rows as its embedding for the
    # product
    plain = dict(p)
    head = plain.pop("lm_head")
    got_plain, _ = forward(plain, CFG, jnp.asarray(tokens[:1]),
                           init_cache(CFG, 1, 64))
    want = reference(plain, tokens[0])
    for t in range(T):
        assert err(got_plain[0, t], want[t]) < TOL, t
        # the int8 head: one rounding of 1/127 a channel
        assert err(got[0, t], want[t]) < 0.02, t


def test_the_expert_products_take_their_weights_first():
    """moe_block writes the gate and up products weights first, which
    the TPU compiler reads as stored where the other order, at 128 rows
    (this model's decode block: 128 slots), made it relay out the whole
    stacked tensor (models/common.py moe_block); the sum is the same
    one, for float and for int8 leaves, whichever operand stands first,
    and a step of 128 rows is its two halves of 64."""
    from butterfly_tpu.models.common import moe_block
    from butterfly_tpu.quant.int8 import qeinsum
    p = seeded_params()
    x = jax.random.normal(jax.random.PRNGKey(2), (128, 1, CFG.hidden_size))
    for params in (p, quantize_int8(p, CFG)):
        moe = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
        whole = moe_block(x, moe, CFG)
        halves = jnp.concatenate([moe_block(x[:64], moe, CFG),
                                  moe_block(x[64:], moe, CFG)])
        assert np.abs(np.asarray(whole - halves)).max() \
            < 1e-5 * np.abs(np.asarray(halves)).max()
        a = qeinsum("btd,edf->ebtf", x, moe["w_up"], jnp.float32)
        b = qeinsum("edf,btd->ebtf", moe["w_up"], x, jnp.float32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


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


@pytest.mark.parametrize("rows", [128, 160])
def test_the_expert_products_compile_for_the_chip_without_a_relayout(
        one_chip, rows):
    """The published widths, compiled for the TPU: ten layers of 72 int8
    experts ride a layer scan at 128 rows (the decode block) and at 160
    (the mixed block) with no copy of a stacked tensor among the
    temporaries. Rows first, at 128 rows this compile holds 4.5 GB of them
    (two tensors of 2.1 GB), and the whole decode block 16.5 GB of a
    chip's 15.75."""
    from butterfly_tpu.models.common import moe_block
    cfg = granite_4_h_small().replace(num_layers=10)
    L, E, D, F = 10, cfg.num_experts, cfg.hidden_size, cfg.intermediate_size

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def codes(*shape):
        return {"q8": sds((L, E) + shape, jnp.int8),
                "s": sds((L, E, 1, shape[-1]), jnp.bfloat16)}

    moe = {"router": sds((L, D, E), jnp.bfloat16), "w_gate": codes(D, F),
           "w_up": codes(D, F), "w_down": codes(F, D)}

    def prog(x, moe):
        def layer(x, i):
            lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, i, 0, keepdims=False), moe)
            return x + moe_block(x, lp, cfg), None

        def step(x, _):         # a block is steps of layers
            return jax.lax.scan(layer, x, jnp.arange(L))[0], None
        return jax.lax.scan(step, x, jnp.arange(4))[0]

    try:
        with jax.disable_jit(False):
            compiled = jax.jit(prog).lower(
                sds((rows, 1, D), jnp.bfloat16), moe).compile()
    except Exception as e:  # the TPU library is one process's at a time
        pytest.skip(f"the TPU compiler could not be used here: {e}")
    assert compiled.memory_analysis().temp_size_in_bytes < 256e6


def test_the_decode_rows_step_compiles_for_the_chip_as_one_pass_in_place(
        one_chip, monkeypatch):
    """A mixed step's Mamba layers at the published widths (128 decode
    rows beside one chunk of 32, the state 2.4 GB of bfloat16 riding two
    scans as the engine's block carries it, donated), kernels on,
    compiled for the TPU: the decode rows' recurrence is the Mosaic call
    `ssm_step`, nothing copies the state, whole or a layer of it (the
    chunk reads ITS slot from the kernel's result, so no reader of the
    old buffer is left), and the call's HLO text, which is all a device
    trace knows of it, is caught by the benchmark's reader of the mixers
    (servebench/ssm_peaks.py) and not by the paged kernel's.
    Since PR 64 the conv's tails are read and written where they lie:
    no value of a tail's swapped shape, no conv laid out slots-major, no
    row-a-tile value a slot and Dc wide (packed_driver.swapped_tails),
    no copy of the tails, and every operation whose result carries the
    planes is one the benchmark's reader of the mixers counts
    (packed_driver.planes_unread)."""
    import json
    import sys
    from pathlib import Path

    from butterfly_tpu.cache.ssm_state import StateRows, advance_packed
    from butterfly_tpu.models.common import layer_at
    from servebench.ssm_peaks import ssm_patterns
    from servebench.xplane import clean
    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root / "tools"))
    try:
        from chip_kernels import state_copies
    finally:
        sys.path.remove(str(root / "tools"))
    cfg = granite_4_h_small().replace(num_layers=10, dtype="bfloat16")
    S, P, C, Lm = 128, 1, 32, 5

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: init_params_by_leaf(
        cfg, jax.random.PRNGKey(0), quant="int8")))
    state = on_chip(jax.eval_shape(lambda: init_ssm_state(cfg, S)))
    rows = on_chip(StateRows(
        active=jnp.zeros((S,), bool), ok=jnp.zeros((S + P * C,), bool),
        chunk_slot=jnp.zeros((P,), jnp.int32), chunk_ok=jnp.zeros((P,), bool),
        chunk_pos=jnp.zeros((P, C), jnp.int32)))

    def prog(x, state, params, rows):
        def layer(carry, i):
            x, st = carry
            x, st, _ = advance_packed(
                x, layer_at(params["layers"], i, cfg),
                layer_at(params["mamba"], i, cfg), st, i, rows, cfg,
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
    assert compiled.memory_analysis().temp_size_in_bytes < 256e6
    calls = [line.strip() for line in hlo.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert calls and all(c.startswith("%ssm_step") for c in calls)
    # the conv's tails are read and written where they lie (PR 64)
    assert packed_driver.swapped_tails(
        hlo, state.conv, S + P * C, state_copies(hlo, state.conv)) == []
    config = json.loads((root / "servebench" / "configs"
                         / "granite-4.0-h-small.json").read_text())
    mixers = ssm_patterns(config)
    for name in map(clean, calls):  # as xplane.py names an operation
        assert mixers.search(name) and "paged_att" not in name, name
    assert packed_driver.planes_unread(hlo, state.conv, mixers) == []


#: the cells' geometries of ops/paged_attention.py: slots, query heads,
#: KV heads, int8 pool, layers, pages of the pool, pages a slot, sliding
PAGED_GEOMETRIES = {
    "mistral7b_int8": (32, 32, 8, True, 32, 4097, 128, False),
    "tensor4_shard_bf16": (32, 8, 2, False, 32, 4097, 128, False),
    "tensor4_shard_int8": (32, 8, 2, True, 32, 4097, 128, False),
    "smallthinker_sliding": (32, 28, 4, False, 16, 4097, 128, True),
    "granite_128_slots": (128, 32, 8, False, 1, 18433, 144, False),
}


@pytest.mark.parametrize("cell", sorted(PAGED_GEOMETRIES))
def test_the_paged_kernel_compiles_for_the_chip_at_the_cells_geometries(
        cell, one_chip):
    """Mosaic takes ops/paged_attention.py at each cell's own sizes
    (pages of 16, heads of 128, a window of 256; the int8 pool's scale
    rows, 28 heads over 4, a tensor=4 shard's 2 KV heads whose scale
    rows are a quarter of the lanes, 128 slots over 18,433 pages), and
    the program copies no pool: what interpret mode cannot say (a copy
    not aligned to the tiling, an operand the compiler moves whole)."""
    from butterfly_tpu.ops.paged_attention import paged_attention
    S, Nq, Kv, quant, L, P, mp, sliding = PAGED_GEOMETRIES[cell]
    H, page, W = 128, 16, 256
    f32, i32 = jnp.float32, jnp.int32
    pd = jnp.int8 if quant else jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    kw = dict(win_k=sds((L, S, Kv, W, H), pd),
              win_v=sds((L, S, Kv, W, H), pd), win_count=sds((S,), i32))
    if quant:
        kw.update(k_scale_pages=sds((L, P, Kv * page), f32),
                  v_scale_pages=sds((L, P, Kv * page), f32),
                  win_k_scale=sds((L, S, W // 32, Kv * 32), f32),
                  win_v_scale=sds((L, S, W // 32, Kv * 32), f32))
    if sliding:
        kw.update(sliding_window=sds((), i32))
    fn = jax.jit(lambda *a, **k: paged_attention.__wrapped__(
        *a, interpret=False, **k))
    try:
        compiled = fn.lower(
            sds((S, Nq, H), jnp.bfloat16), sds((L, P, Kv, page, H), pd),
            sds((L, P, Kv, page, H), pd), sds((), i32), sds((S, mp), i32),
            sds((S,), i32), **kw).compile()
    except Exception as e:  # the TPU library is one process's at a time
        if "Mosaic" in str(e):
            raise
        pytest.skip(f"the TPU compiler could not be used here: {e}")
    assert "tpu_custom_call" in compiled.as_text()
    # the layer's scale rows of an int8 pool are cut out in XLA (4 MB at
    # these sizes); nothing else is made, and never a pool
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6


#: tiny models at the chip's tile sizes (heads of 128, pages of 16, a
#: window of 64) whose windows the serving path stages and reads
#: differently: name -> (tiny's arch and overrides, int8 KV)
WINDOWED = {
    "plain_bf16": (("llama", dict(head_dim=128)), "none"),
    "plain_int8": (("llama", dict(head_dim=128, num_kv_heads=4)), "int8"),
    "sliding": (("smallthinker", dict(head_dim=128)), "none"),
    "runs_granite": (("granite_hybrid", dict(head_dim=128, ssm_state=128)),
                     "none"),
    "runs_joyai": (("joyai", dict(kv_lora_rank=128, num_layers=4)), "none"),
    # four residual streams [4, N, 1, D] in the carry beside the window
    "runs_xing": (("xing", dict(kv_lora_rank=128, hidden_size=128)), "none"),
    # a table of 128 within MASKED_READ_SPAN x topk: the decode rows
    # read through ops/sparse_attention.py, as `keye30b.think`'s do
    "keye": (("keye", dict(head_dim=128, index_topk=32)), "none"),
    # an indexer over LATENT rows: two leaves, the row and the index key
    "glm5": (("glm5", dict(kv_lora_rank=128)), "none"),
}


def _tools():
    """tools/chip_kernels.py, which is no package's."""
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root / "tools"))
    try:
        import chip_kernels
    finally:
        sys.path.remove(str(root / "tools"))
    return chip_kernels


def _compiled_block(model, chunks, one_chip, monkeypatch):
    """(cfg, cache, window, S, C, compiled text): the engine's mixed
    (chunks 1) or decode block (engine/serving.py _packed_scan: steps
    around layers) of a WINDOWED model, kernels ON, compiled for a
    described v5e."""
    from functools import partial

    from butterfly_tpu.cache.paged import paged_forward_packed
    from butterfly_tpu.engine.serving import _packed_scan
    (arch, kw), kv_quant = WINDOWED[model]
    cfg = tiny(arch, **kw).replace(dtype="bfloat16")
    S, k, C, W = 4, 2, 32, 64
    rt = RuntimeConfig(max_batch_size=S, max_seq_len=128, page_size=16,
                       kv_quant=kv_quant)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = on_chip(jax.eval_shape(lambda: init_params_by_leaf(
        cfg, jax.random.PRNGKey(0), quant="int8")))
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, rt))
    window = on_chip(jax.eval_shape(lambda: init_kv_window(cache, W)))
    i32 = jnp.int32
    args = [params, sds((S,), i32), sds((S,), i32), on_chip(cache), window,
            sds((S,), i32), sds((S, 128), i32), sds((S,), i32),
            sds((S,), bool), sds((S,), jnp.float32), sds((S,), i32),
            sds((S,), i32), 0, 1.0, sds((2,), jnp.uint32)]
    donated = (2, 3, 4, 5)
    if cfg.has_ssm:
        args.append(on_chip(jax.eval_shape(lambda: init_ssm_state(cfg, S))))
        donated += (15,)
    prog = jax.jit(partial(_packed_scan, cfg, paged_forward_packed, k, C,
                           chunks, use_kernel=True),
                   static_argnums=(12, 13), donate_argnums=donated)
    jax.clear_caches()          # no interpreted trace of a kernel is met
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        with jax.disable_jit(False):
            compiled = prog.lower(*args).compile()
    except Exception as e:  # the TPU library is one process's at a time
        if "Mosaic" in str(e):      # a kernel refused is no skip
            raise
        pytest.skip(f"the TPU compiler could not be used here: {e}")
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    return cfg, cache, window, S, C, compiled.as_text()


def _mosaic_calls(hlo, name):
    return [line for line in hlo.splitlines()
            if "tpu_custom_call" in line and " custom-call(" in line
            and line.strip().startswith("%" + name)]


@pytest.mark.parametrize("chunks", [1, 0], ids=["mixed", "decode"])
@pytest.mark.parametrize("model", sorted(WINDOWED))
def test_a_block_moves_no_window_inside_its_loops(model, chunks, one_chip,
                                                  monkeypatch):
    """The engine's mixed block and its decode block (engine/serving.py
    _packed_scan: steps around layers), kernels ON, compiled for the
    TPU: the window rides every layer scan whole, in the carry, and is
    written in place, so no `copy`, `transpose` or fusion inside any
    loop makes a value of a leaf's whole shape [L, S, Kv, W, H] nor of
    one layer's slice of it (tools/chip_kernels.py window_moves; as
    scanned inputs and stacked outputs the leaves were copied whole in
    every step: PERF.md, PR 47). A plain scan with bfloat16 and int8
    leaves and their scales, a sliding model, the runs of granite's and
    JoyAI's layers, Keye's token-major leaves and index keys, GLM-5's
    latent rows and index keys; and the runs of a model of four residual
    streams, the second thing to ride the carry: every sublayer computes
    them anew, so a fusion of their shape is the mixing itself, and what
    they are held to is no `copy` and no `transpose` of [n, N, 1, D] in
    a loop.

    A model with an indexer (PR 53): its decode rows' index scores come
    from ops/index_scores.py, ONE call a layer scan beside the one
    selecting read, the window's index keys whole among its operands,
    and NO value inside a loop has the shape of the table's index keys
    as a view of every slot (tools/chip_kernels.py index_views: the
    gather and its relayout were a quarter of `keye30b.think`'s busy
    time; a chunk's view is one slot's and stays). Since PR 55 its
    selections COUNT (ops/select_mask.py): no sort of a row of the
    table's span and no running count over it is left."""
    tools = _tools()
    cfg, cache, window, S, C, hlo = _compiled_block(model, chunks, one_chip,
                                                    monkeypatch)
    leaves = jax.tree.leaves(window)
    assert tools.window_moves(hlo, leaves) == []
    if cfg.hc_mult:
        streams = jax.ShapeDtypeStruct(
            (cfg.hc_mult, S + chunks * C, 1, cfg.hidden_size), jnp.bfloat16)
        assert tools.window_moves(hlo, [streams],
                                  kinds=("copy", "transpose")) == []
    # the Mosaic writer stages every window (Keye's too since PR 53: an
    # index key is cached in whole lanes), the leaves its operands whole
    staged = _mosaic_calls(hlo, "stage_window")
    whole = "[" + ",".join(map(str, leaves[0].shape)) + "]"
    assert staged and all(whole in c for c in staged)
    scored = _mosaic_calls(hlo, "index_scores")
    assert bool(scored) == cfg.has_indexer
    if cfg.has_indexer:
        # one call a layer scan: a run of layers of one kind is one scan
        assert len(scored) == len(layer_runs(cfg))
        keys = "[" + ",".join(map(str, window.ki.shape)) + "]"
        assert all(keys in c for c in scored)
        # its result is [S, S_max] in XLA's own dense tiles: declared
        # [S, 1, S_max], a row was a TILE (T(1,128)), the selection's
        # sort inherited the layout, and `glm5-ep16.think` read 27 %
        # slower for it (PERF.md, PR 53)
        assert all(re.match(rf"\s*%\S+ = f32\[{S},\d+\]\{{1,0:T\([48],128\)", c)
                   for c in scored)
        assert not [line for line in hlo.splitlines()
                    if " sort(" in line and "T(1,128)" in line]
        assert tools.index_views(hlo, cache, cfg.index_head_dim) == []
        # the selection COUNTS (PR 55): one call of ops/select_mask.py a
        # layer scan for the decode rows and one more for a chunk's, its
        # result [R, S_max] int32 in dense tiles, and nothing is left
        # that sorts a row of the table's span or scans it for the ties
        S_max = cache.page_table.shape[1] * cache.ki_pages.shape[3]
        chosen = _mosaic_calls(hlo, "select_mask")
        assert len(chosen) == len(layer_runs(cfg)) * (1 + chunks)
        assert all(re.match(
            rf"\s*%\S+ = s32\[\d+,{S_max}\]\{{1,0:T\([48],128\)", c)
            for c in chosen)
        assert tools.span_sorts(hlo, S_max) == []


def test_the_view_of_every_slot_s_index_keys_is_what_the_walk_replaced(
        one_chip, monkeypatch):
    """The control of the check above: the same decode block with the
    call refused (ops/index_scores.py fits) gathers the table's index
    keys to one view of every slot, and index_views finds it."""
    from butterfly_tpu.ops import index_scores
    monkeypatch.setattr(index_scores, "fits", lambda *a, **k: False)
    cfg, cache, _, _, _, hlo = _compiled_block("keye", 0, one_chip,
                                               monkeypatch)
    assert not _mosaic_calls(hlo, "index_scores")
    assert _tools().index_views(hlo, cache, cfg.index_head_dim)


@pytest.mark.parametrize("model", ["keye", "glm5"])
def test_the_sort_of_a_row_s_scores_is_what_the_count_replaced(
        model, one_chip, monkeypatch):
    """The control of the check above: the same mixed block with the
    call refused (ops/select_mask.py fits) asks lax.top_k for the
    threshold, a sort of every row of the table's span, and span_sorts
    finds it."""
    from butterfly_tpu.ops import select_mask
    monkeypatch.setattr(select_mask, "fits", lambda *a, **k: False)
    cfg, cache, _, _, _, hlo = _compiled_block(model, 1, one_chip,
                                               monkeypatch)
    assert not _mosaic_calls(hlo, "select_mask")
    S_max = cache.page_table.shape[1] * cache.ki_pages.shape[3]
    assert _tools().span_sorts(hlo, S_max)


def test_a_model_without_an_indexer_compiles_what_it_compiled(
        one_chip, monkeypatch):
    """No program of a model without an indexer reaches the selection:
    JoyAI's mixed block (latent rows through latent_paged_attend, the
    function GLM-5's selection lives in) compiles to the SAME text with
    cache/paged.py _selection there as with it refusing every call,
    and names no select_mask."""
    from butterfly_tpu.cache import paged
    *_, hlo = _compiled_block("runs_joyai", 1, one_chip, monkeypatch)

    def unreachable(*a, **k):
        raise AssertionError("a model without an indexer selected")
    monkeypatch.setattr(paged, "_selection", unreachable)
    *_, again = _compiled_block("runs_joyai", 1, one_chip, monkeypatch)

    def instructions(text):
        """Instruction for instruction; where each was traced from (this
        file's lines among them) apart."""
        return [re.sub(r", metadata=\{[^}]*\}", "", line)
                for line in text.splitlines()
                if re.match(r"\s*(?:ROOT )?%\S+ = ", line)]
    assert instructions(again) == instructions(hlo)
    assert len(instructions(hlo)) > 1000 and "select_mask" not in hlo


def _glm5_mixed_block(tmp_path):
    """(record, compiled text) of GLM-5's mixed block at the benchmark
    file's own widths and a cut depth (one dense layer, two of experts),
    kernels on, compiled for a described v5e (tools/chip_kernels.py
    cell_blocks, which builds the block as the engine does)."""
    import json
    from pathlib import Path
    root = Path(__file__).resolve().parent.parent
    config = json.loads(
        (root / "servebench" / "configs" / "glm-5-ep16.json").read_text())
    config["num_hidden_layers"] = 3
    rec, = _tools().cell_blocks(config, tmp_path, blocks=("mixed",))
    if "error" in rec:
        if "Mosaic" in rec["error"]:        # a kernel refused is no skip
            pytest.fail(rec["error"])
        pytest.skip(f"the TPU compiler could not be used here: "
                    f"{rec['error']}")
    return rec, (tmp_path / "mixed.hlo.txt").read_text()


def test_a_layer_of_experts_is_one_call_over_the_codes_where_they_lie(
        one_chip, tmp_path):
    """GLM-5's packed step (64 rows: 32 slots beside a chunk of 32; 16
    held experts of 256 in int8 codes, 604 MB a layer), kernels on,
    compiled for the TPU: the layers of experts are ONE loop that holds
    ONE Mosaic call `moe_experts`, whose operands are the layer-stacked
    codes WHOLE (the layer rides its scalars), and nothing in the
    program makes a value of the stacked codes' shape or of one layer's
    (tools/chip_kernels.py expert_moves: no copy, no dynamic-slice, no
    fusion's result). The dense products' [E, rows, F] values are gone
    with them."""
    rec, hlo = _glm5_mixed_block(tmp_path)
    assert rec["ok"] and "moe_experts" in rec["mosaic_calls"]
    assert rec["expert_moves"] == []
    calls = _mosaic_calls(hlo, "moe_experts")
    assert len(calls) == 1
    # two layers of experts here: gate, up and down whole, and their scales
    for dims in ("s8[2,16,6144,2048]", "s8[2,16,2048,6144]",
                 "bf16[2,16,1,2048]", "bf16[2,16,1,6144]"):
        assert dims in calls[0].split("custom-call(")[1]
    assert calls[0].strip().startswith("%moe_experts")
    assert re.match(r"\s*%\S+ = f32\[64,6144\]", calls[0])
    assert not re.search(r"= bf16\[16,64,1?,?2048\]", hlo)


def test_a_layer_s_slice_of_the_codes_is_what_the_index_replaced(
        one_chip, tmp_path, monkeypatch):
    """The control of the check above: the same block with ONE layer's
    slice of the codes handed to the call (the stack cut at the layer's
    index in XLA, the call told layer 0) materialises that layer's codes
    before every call, and expert_moves finds it."""
    from butterfly_tpu.models import common

    def sliced(lp, held, i):
        if held is None:        # the leading dense layer's run
            return lp
        cut = jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(a, i, 1),
                           held)
        return {**lp, "moe": {**lp["moe"], **cut, "layer": jnp.int32(0)}}
    monkeypatch.setattr(common, "layer_experts", sliced)
    rec, hlo = _glm5_mixed_block(tmp_path)
    assert _mosaic_calls(hlo, "moe_experts")
    assert rec["expert_moves"] and not rec["ok"]


def test_weights_built_leaf_by_leaf_have_the_same_tree():
    """cli.load_params' path (no checkpoint): every leaf born in its
    final form, the tied head's codes beside the embedding."""
    cfg = CFG.replace(dtype="bfloat16")
    p = init_params_by_leaf(cfg, jax.random.PRNGKey(0), quant="int8")
    q = quantize_int8(Model(cfg).init(jax.random.PRNGKey(0)), cfg)
    assert jax.tree.structure(p) == jax.tree.structure(q)
    assert jax.tree.map(lambda a: a.shape, p) == \
        jax.tree.map(lambda a: a.shape, q)
    deq = np.asarray(p["lm_head"]["q8"], np.float32) \
        * np.asarray(p["lm_head"]["s"], np.float32)
    tok = np.asarray(p["embed"]["tok"], np.float32).T
    assert np.abs(deq - tok).max() < np.asarray(p["lm_head"]["s"],
                                                np.float32).max()


# -- what cannot take the state refuses the model by name ---------------------

@pytest.fixture(scope="module")
def engine(params):
    """The module's ONE serving engine of the family's toy: the
    scheduler's scenario runs on it, and so do the refusals that need an
    engine that was built (export, import, the lane-wide prefill)."""
    from butterfly_tpu.engine.serving import ServingEngine
    return ServingEngine(Model(CFG), params, RuntimeConfig(
        max_batch_size=2, max_seq_len=64, page_size=4, num_pages=16,
        decode_steps_per_tick=2, prefill_inline_budget=4))


def _engine(**rt):
    """An engine that refuses at its construction: no weights are built
    for it."""
    from butterfly_tpu.engine.serving import ServingEngine
    mesh = rt.pop("mesh", None)
    return ServingEngine(Model(CFG), None, RuntimeConfig(
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


#: name -> the call, given the module's engine
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
    assert "Mamba-2" in str(e.value)


# -- through the scheduler: the server's own path -----------------------------

def served(params, prompts, new, cfg=CFG, together=False, **rt):
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    rt = RuntimeConfig(**{**dict(max_batch_size=2, max_seq_len=64,
                                 page_size=4, decode_steps_per_tick=2,
                                 prefill_inline_budget=8), **rt})
    sched = Scheduler(ServingEngine(Model(cfg), params, rt), seed=0)
    reqs = [sched.submit(prompts[0], max_new_tokens=new[0])]
    for _ in range(0 if together else 2):
        sched.tick()
    reqs += [sched.submit(p, max_new_tokens=n)
             for p, n in zip(prompts[1:], new[1:])]
    sched.run_until_done()
    return sched, reqs


def greedy_of_the_reference(params, prompt, output, cfg=CFG):
    """Every served token is the argmax of the reference's logits over
    the tokens before it, by a margin a rounding cannot close (the
    reference is causal: one forward of prompt + output holds every
    such row)."""
    seq = list(prompt) + list(output)
    rows = reference(params, seq, cfg)
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
    is preempted MID-DECODE and recomputed from position 0, prompt and
    the tokens it had made (its slot's state starts from zero inside
    the program: Scheduler._preempt does nothing for it), both slots
    are reused after a finish, and every served token is the
    reference's greedy token. The tick records count what went through
    a recurrence and the states that started from zero."""
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
    # a stream that had made tokens was preempted, and it started again
    # from a zero state: one reset a request and one a recompute (a
    # victim that had not begun its prompt restarts nothing)
    begun = [made for state, made in victims if state == "running"]
    assert begun and max(begun) > 8
    assert int(sched.metrics()["preemptions_total"]) == len(victims)
    ticks = [t for t in sched.ticklog.dump()["ticks"]
             if t["ssm_rows"] is not None]
    assert ticks and all(t["experts_touched"] is not None for t in ticks)
    assert sum(t["state_resets"] for t in ticks) == len(reqs) + len(begun)
    # every prompt token and every decode step went through a
    # recurrence once, the recomputed stream's twice
    once = sum(len(p) for p in prompts) + sum(new) - len(new)
    assert once + sum(begun) <= sum(t["ssm_rows"] for t in ticks) \
        <= once + sum(begun) + 6 * len(begun) + 16
    assert all(t["ssm_steps"] % 2 == 0 and t["ssm_rows"]
               <= t["ssm_steps"] * (2 + 4) for t in ticks)
    assert sched.registry.snapshot()["ssm_state_bytes"] == \
        2 * bytes_per_slot(CFG)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_state_parity_tool_separates_its_faults_on_the_toy(dtype):
    """The check of the chip (three requests through the scheduler over
    two slots, each slot's final state against the reference's loop), at
    a toy's size on the CPU: the clean run holds the reference's states
    in the long stream's slot and in the reused one, to float32 rounding
    in float32 and, in bfloat16, to a hundredth beside the loop that
    keeps its state in bfloat16 too (beside the float32 loop the same
    states read several times that: `drift`); a slot that is not reset,
    or filler that advances, shows in the reused slot."""
    import json
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root / "tools"))
    import state_parity
    toy = json.loads((root / "tests/servebench/files/configs/"
                      "tiny-granite.json").read_text())
    out = state_parity.check(dict(toy, torch_dtype=dtype), toy=True,
                             long_short=72,
                             requests=((40, 80), (10, 6), (20, 30)))
    assert out["evidence"] == "cpu toy", out
    assert out["clean"]["slots"] == [0, 1, 1]
    assert out["clean"]["long"]["positions"] == 119
    limit = 1e-5 if dtype == "float32" else 1e-2
    for name in ("long", "second"):
        got = out["clean"][name]
        assert max(got["h_worst"], got["conv_worst"]) < limit, got
        assert ("drift" in got) == (dtype == "bfloat16")
    if dtype == "bfloat16":
        assert out["clean"]["long"]["drift_worst"] \
            > 2 * out["clean"]["long"]["h_worst"]
    for fault in ("no_reset", "filler_advances"):
        assert out[fault]["second"]["h_worst"] > 5 * limit, out[fault]


def test_a_model_of_mamba_layers_only_is_served():
    p = seeded_params(ALL_MAMBA)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, CFG.vocab_size, n).tolist() for n in (7, 12)]
    sched, reqs = served(p, prompts, (8, 8), cfg=ALL_MAMBA)
    for prompt, req in zip(prompts, reqs):
        greedy_of_the_reference(p, prompt, req.output, ALL_MAMBA)


@pytest.mark.parametrize("arch", ["llama", "mixtral", "keye"])
def test_a_model_without_mamba_layers_has_no_state(arch):
    """No leaf, no gauge value, no tick field, no fourth value of the
    packed step: the older families' programs carry nothing of it."""
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    cfg = tiny(arch, dtype="float32")
    assert not cfg.has_ssm and layer_runs(cfg) == [
        ("attention", 0, cfg.num_layers, 0)]
    assert init_ssm_state(cfg, 4) is None and state_info(cfg, 4) is None
    eng = ServingEngine(Model(cfg), Model(cfg).init(jax.random.PRNGKey(0)),
                        RuntimeConfig(max_batch_size=2, max_seq_len=64,
                                      page_size=4, decode_steps_per_tick=2))
    assert eng._ssm_state is None
    assert eng.cache.k_pages.shape[0] == cfg.num_layers
    sched = Scheduler(eng, seed=0)
    sched.submit([1, 2, 3, 4, 5], max_new_tokens=6)
    sched.run_until_done()
    ticks = sched.ticklog.dump()["ticks"]
    assert ticks and all(t["ssm_rows"] is None and t["state_resets"] is None
                         and t["ssm_steps"] is None for t in ticks)
    assert sched.registry.snapshot()["ssm_state_bytes"] == 0


def test_the_runtime_report_says_what_state_a_slot_keeps(engine):
    from butterfly_tpu.sched.scheduler import Scheduler
    from butterfly_tpu.serve.server import runtime_report
    sched = Scheduler(engine)
    state = runtime_report(sched)["state"]
    per = 3 * (8 * 16 * 16 + 3 * (8 * 16 + 2 * 16)) * 4
    # the toy's rows of 16 and 160 values are no whole lanes of a TPU;
    # granite-4.0-h-small's 128 and 8,448 are
    assert state == {"kind": "Mamba-2", "layers": 3,
                     "layout": "h [layers, slots, heads, head_dim, state] "
                               "= [3, 2, 8, 16, 16]",
                     "whole_tiles": False,
                     "bytes_per_slot": per, "dtype": "float32",
                     "bytes": 2 * per}
    assert state_info(granite_4_h_small(), 128)["whole_tiles"]
    assert runtime_report(sched)["pool_layout"] == "head"


def test_reset_slots_zeroes_every_layer_of_the_slots_named():
    st = init_ssm_state(CFG, 3)
    st = st._replace(h=st.h + 1, conv=st.conv + 1)
    st = reset_slots(st, [0, 2])
    assert not np.asarray(st.h[:, 0]).any() and np.asarray(st.h[:, 1]).all()
    assert not np.asarray(st.conv[:, :, 2]).any()
    assert np.asarray(st.conv[:, :, 1]).all()


# -- the preset and the layer runs --------------------------------------------

def test_preset_is_the_published_model():
    cfg = PRESETS["granite-4.0-h-small"]()
    assert cfg == granite_4_h_small()
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size) == \
        (40, 4096, 100352)
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    assert (cfg.num_ssm_layers, cfg.num_attn_layers) == (36, 4)
    assert (cfg.ssm_inner, cfg.ssm_conv_dim) == (8192, 8448)
    assert cfg.ssm_inner + cfg.ssm_conv_dim + cfg.ssm_heads == 16768
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.shared_intermediate_size) == (72, 10, 1536)
    assert cfg.tie_embeddings and cfg.pos_embedding == "none"
    assert cfg.attention_multiplier == 1 / 128
    # the benchmark's cut: one period, 9 Mamba layers to 1 attention
    cut = cfg.replace(num_layers=10)
    assert layer_runs(cut) == [("mamba", 0, 5, 0), ("attention", 5, 1, 0),
                               ("mamba", 6, 4, 5)]
    # 19.3 MB a slot in bfloat16 (ISSUE 41 said 19.5: four taps of tail)
    assert bytes_per_slot(cut) == 9 * (128 * 64 * 128 + 3 * 8448) * 2 \
        == 19_330_560


def test_layer_types_are_checked():
    with pytest.raises(ValueError, match="layer_types names 2 layers of 4"):
        tiny("granite_hybrid", layer_types=("mamba", "attention"))
    with pytest.raises(ValueError,
                       match="'mamba', 'linear_attention', 'mamba1' or "
                             "'attention'"):
        tiny("granite_hybrid", layer_types=("mamba", "conv", "mamba", "mamba"))
    with pytest.raises(ValueError, match="needs ssm_heads"):
        tiny("granite_hybrid", ssm_state=0)
    # a longer list is read up to num_layers, as a cut in depth leaves it
    assert tiny("granite_hybrid", num_layers=2).layer_types == \
        ("mamba", "mamba")
    assert dataclasses.asdict(CFG)["layer_types"] == CFG.layer_types
