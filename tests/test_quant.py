"""Int8 weight-only quantization: error bounds, forward fidelity, TP parity.

The quant path must (a) bound per-weight error by half a quantization
step, (b) keep logits close enough that generation is usable, and
(c) compose with the Megatron TP sharding exactly (quantized TP=8 ==
quantized TP=1 token-for-token).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.core.config import MeshConfig, tiny
from butterfly_tpu.core.mesh import make_mesh
from butterfly_tpu.engine import InferenceEngine, SamplingParams
from butterfly_tpu.models.common import Model, forward, init_cache
from butterfly_tpu.quant import (
    maybe_dequant, quantize_int8, shard_quantized_params)

CFG = tiny("llama", dtype="float32", param_dtype="float32")


@pytest.fixture(scope="module")
def setup():
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, quantize_int8(params, CFG)


def test_dequant_error_bound(setup):
    _, params, qparams = setup
    w = np.asarray(params["layers"]["attn"]["wq"], np.float32)
    leaf = qparams["layers"]["attn"]["wq"]
    deq = np.asarray(maybe_dequant(leaf, jnp.float32))
    step = np.asarray(leaf["s"], np.float32)  # [L,1,N,H] keepdims
    assert np.all(np.abs(deq - w) <= 0.5 * step + 1e-7)


def test_quantized_leaves_are_int8(setup):
    _, _, qparams = setup
    attn = qparams["layers"]["attn"]
    for k in ("wq", "wk", "wv", "wo"):
        assert attn[k]["q8"].dtype == jnp.int8
    # numerically delicate leaves stay full precision
    assert qparams["embed"]["tok"].dtype == jnp.float32
    assert qparams["layers"]["ln1"]["scale"].dtype == jnp.float32


def test_forward_logits_close(setup):
    model, params, qparams = setup
    toks = jnp.asarray([[5, 7, 11, 13, 2, 4, 6, 8]])
    lg, _ = forward(params, CFG, toks, init_cache(CFG, 1, 16))
    lgq, _ = forward(qparams, CFG, toks, init_cache(CFG, 1, 16))
    a, b = np.asarray(lg).ravel(), np.asarray(lgq).ravel()
    corr = np.corrcoef(a, b)[0, 1]
    assert corr > 0.999, f"quantized logits diverged: corr={corr}"


@pytest.mark.parametrize("arch", ["gpt2", "mixtral"])
def test_other_arch_quant_smoke(arch):
    cfg = tiny(arch, dtype="float32", param_dtype="float32")
    params = Model(cfg).init(jax.random.PRNGKey(1))
    qparams = quantize_int8(params, cfg)
    toks = jnp.asarray([[5, 7, 11]])
    lg, _ = forward(params, cfg, toks, init_cache(cfg, 1, 8))
    lgq, _ = forward(qparams, cfg, toks, init_cache(cfg, 1, 8))
    corr = np.corrcoef(np.asarray(lg).ravel(), np.asarray(lgq).ravel())[0, 1]
    assert corr > 0.999


def test_generate_runs_quantized(setup):
    model, _, qparams = setup
    eng = InferenceEngine(model, qparams)
    res = eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=6,
                                                   temperature=0.0))
    assert res.tokens.shape == (1, 6)
    assert np.all(res.tokens >= 0)


def test_meshed_serving_quantized_token_parity():
    """ServingEngine must route quantized trees through the quant-aware
    specs (float specs would shard a scale's size-1 contraction axis) —
    round-2 ADVICE medium regression test."""
    from butterfly_tpu.core.config import RuntimeConfig
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler

    cfg = tiny("llama", dtype="float32", param_dtype="float32",
               num_heads=8, num_kv_heads=4, head_dim=8)
    model = Model(cfg)
    qparams = quantize_int8(model.init(jax.random.PRNGKey(3)), cfg)
    rt = RuntimeConfig(max_batch_size=4, max_seq_len=64, page_size=8)
    outs = {}
    for mesh in (None, make_mesh(MeshConfig(data=2, tensor=4))):
        sched = Scheduler(ServingEngine(model, qparams, rt, mesh=mesh))
        reqs = [sched.submit(p, max_new_tokens=6)
                for p in ([5, 7, 11], [3, 1])]
        sched.run_until_done()
        outs[mesh is None] = [r.output for r in reqs]
    assert outs[True] == outs[False]


def test_a_tied_head_is_held_as_codes_and_sharded_like_one():
    """A tied model (GPT-2) with int8 weights holds its head a second
    time as codes (quant/int8.py tied_head); under a mesh the copy takes
    the embedding's layout turned round, and the served tokens are the
    single device's."""
    from butterfly_tpu.core.config import RuntimeConfig
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.quant.int8 import init_params_by_leaf
    from butterfly_tpu.sched.scheduler import Scheduler

    cfg = tiny("gpt2", dtype="float32", param_dtype="float32",
               vocab_size=256)
    model = Model(cfg)
    qparams = quantize_int8(model.init(jax.random.PRNGKey(3)), cfg)
    assert qparams["lm_head"]["q8"].shape == (cfg.hidden_size, 256)
    rt = RuntimeConfig(max_batch_size=4, max_seq_len=64, page_size=8)
    mesh = make_mesh(MeshConfig(data=2, tensor=4))
    outs = []
    for m in (None, mesh):
        sched = Scheduler(ServingEngine(model, qparams, rt, mesh=m))
        reqs = [sched.submit(p, max_new_tokens=6)
                for p in ([5, 7, 11], [3, 1])]
        sched.run_until_done()
        outs.append([r.output for r in reqs])
    assert outs[0] == outs[1]
    born = init_params_by_leaf(cfg, jax.random.PRNGKey(0), quant="int8",
                               mesh=mesh)
    assert tuple(born["lm_head"]["q8"].sharding.spec) == (None, "tensor")


def test_cli_quant_flag_quantizes():
    """--quant int8 produces a quantized tree through the CLI load path."""
    import argparse
    from butterfly_tpu.quant import tree_is_quantized
    from butterfly_tpu.serve.cli import load_params, resolve_model

    args = argparse.Namespace(model="tiny", ckpt=None, dtype=None,
                              quant="int8", expert_parallel=1)
    model = resolve_model(args)
    params = load_params(model, args)
    assert tree_is_quantized(params)
    assert params["layers"]["attn"]["wq"]["q8"].dtype == jnp.int8


def test_quant_tp8_token_parity(setup):
    """Quantized + TP-sharded must equal quantized single-device exactly."""
    cfg = tiny("llama", dtype="float32", param_dtype="float32",
               num_heads=8, num_kv_heads=8, head_dim=8)
    model = Model(cfg)
    qparams = quantize_int8(model.init(jax.random.PRNGKey(2)), cfg)
    sp = SamplingParams(max_new_tokens=8, temperature=0.0)
    ref = InferenceEngine(model, qparams).generate([[3, 1, 4, 1, 5]], sp)

    mesh = make_mesh(MeshConfig(tensor=8))
    shp = shard_quantized_params(qparams, cfg, mesh)
    got = InferenceEngine(model, shp, mesh=mesh).generate([[3, 1, 4, 1, 5]],
                                                          sp)
    assert got.tokens.tolist() == ref.tokens.tolist()
