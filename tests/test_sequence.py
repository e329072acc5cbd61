"""Stage-6 long-context tests: ring attention / Ulysses / SP forward parity.

8 fake CPU devices. Ring and Ulysses must reproduce dense causal attention
exactly (online softmax is algebraically exact, not approximate), and
sp_forward must match the plain forward's logits and KV cache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from butterfly_tpu.core.config import MeshConfig, tiny
from butterfly_tpu.core.mesh import make_mesh
from butterfly_tpu.models.common import (
    Model, attend, forward, init_cache, make_mask)
from butterfly_tpu.parallel.sequence import (
    ring_attention, sp_forward, ulysses_attention)


def dense_ref(q, k, v):
    """Plain causal attention over the full sequence."""
    B, T = q.shape[0], q.shape[1]
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    mask = pos[:, None, :] <= pos[:, :, None]
    return attend(q, k, v, mask, None)


def shard_seq(mesh, x, dim=1):
    spec = [None] * x.ndim
    spec[dim] = "seq"
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))


@pytest.mark.parametrize("nq,kv", [(8, 8), (8, 2)])
def test_ring_attention_matches_dense(nq, kv):
    mesh = make_mesh(MeshConfig(seq=8))
    B, T, H = 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, nq, H))
    k = jax.random.normal(ks[1], (B, T, kv, H))
    v = jax.random.normal(ks[2], (B, T, kv, H))
    ref = dense_ref(q, k, v)

    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    fn = jax.shard_map(
        lambda q, k, v, qp, kp: ring_attention(q, k, v, qp, kp),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"),
                  P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"), axis_names={"seq"}, check_vma=False)
    with jax.set_mesh(mesh):
        out = jax.jit(fn)(shard_seq(mesh, q), shard_seq(mesh, k),
                          shard_seq(mesh, v), shard_seq(mesh, pos),
                          shard_seq(mesh, pos))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("Kv", [8, 2])
def test_ulysses_matches_dense(Kv):
    """Kv=8: plain head-scatter. Kv=2 on an 8-way axis: VERDICT r2 weak
    item 7 — GQA head-replication fallback (r = N/Kv copies) must still
    match dense exactly."""
    mesh = make_mesh(MeshConfig(seq=8))
    B, T, Nq, H = 2, 32, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, T, Nq, H))
    k = jax.random.normal(ks[1], (B, T, Kv, H))
    v = jax.random.normal(ks[2], (B, T, Kv, H))
    ref = dense_ref(q, k, v)

    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    fn = jax.shard_map(
        lambda q, k, v, qp: ulysses_attention(q, k, v, qp),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"),
                  P(None, "seq")),
        out_specs=P(None, "seq"), axis_names={"seq"}, check_vma=False)
    with jax.set_mesh(mesh):
        out = jax.jit(fn)(shard_seq(mesh, q), shard_seq(mesh, k),
                          shard_seq(mesh, v), shard_seq(mesh, pos))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_invalid_head_config_rejected():
    mesh = make_mesh(MeshConfig(seq=8))
    B, T, H = 1, 32, 16
    q = jnp.zeros((B, T, 8, H))
    k = v = jnp.zeros((B, T, 3, H))  # Kv=3: neither divides nor divides N
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    fn = jax.shard_map(
        lambda q, k, v, qp: ulysses_attention(q, k, v, qp), mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"),
                  P(None, "seq")),
        out_specs=P(None, "seq"), axis_names={"seq"}, check_vma=False)
    # the body's ValueError surfaces through shard_map's tracing wrapped
    # in its own ValueError — assert the type, not the message
    with jax.set_mesh(mesh), pytest.raises(ValueError):
        fn(shard_seq(mesh, q), shard_seq(mesh, k), shard_seq(mesh, v),
           shard_seq(mesh, pos))


@pytest.mark.parametrize("impl,arch,moe_impl", [
    ("ring", "llama", None), ("ulysses", "llama", None),
    ("ring", "mixtral", "dense"), ("ring", "mixtral", "ep"),
])
def test_sp_forward_parity(impl, arch, moe_impl):
    """Whole-model SP prefill matches the plain forward (logits + cache).

    The mixtral/ep case checks the EP dispatch inside the seq-manual
    shard_map (no-drop capacity -> exact parity with dense)."""
    kw = {}
    if moe_impl:
        kw = dict(moe_impl=moe_impl, moe_capacity_factor=4.0)  # C=k*T
    cfg = tiny(arch, vocab_size=256, hidden_size=64, num_heads=8,
               num_kv_heads=8, head_dim=8, intermediate_size=128,
               dtype="float32", param_dtype="float32", **kw)
    mesh = make_mesh(MeshConfig(seq=4, data=2))
    params = Model(cfg).init(jax.random.PRNGKey(0))
    B, T = 2, 24
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (B, T)))

    cache = init_cache(cfg, batch=B, max_seq=T)
    ref_logits, ref_cache = jax.jit(lambda p, t, c: forward(p, cfg, t, c))(
        params, tokens, cache)

    with jax.set_mesh(mesh):
        logits, sp_cache = jax.jit(
            lambda p, t: sp_forward(p, cfg, t, mesh, impl=impl))(
                params, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(sp_cache.k),
                               np.asarray(ref_cache.k), rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(sp_cache.length),
                                  np.asarray(ref_cache.length))


def test_sp_forward_seq_tp_compose():
    """seq=2 x tensor=4: SP composes with TP (auto axes inside shard_map)."""
    cfg = tiny("llama", vocab_size=256, hidden_size=64, num_heads=8,
               num_kv_heads=8, head_dim=8, intermediate_size=128,
               dtype="float32", param_dtype="float32")
    mesh = make_mesh(MeshConfig(seq=2, tensor=4))
    params = Model(cfg).init(jax.random.PRNGKey(1))
    from butterfly_tpu.parallel.partition import shard_params
    sparams = shard_params(params, cfg, mesh)
    tokens = jnp.asarray(
        np.random.RandomState(1).randint(0, cfg.vocab_size, (2, 16)))
    cache = init_cache(cfg, batch=2, max_seq=16)
    ref_logits, _ = jax.jit(lambda p, t, c: forward(p, cfg, t, c))(
        params, tokens, cache)
    with jax.set_mesh(mesh):
        logits, _ = jax.jit(
            lambda p, t: sp_forward(p, cfg, t, mesh, impl="ring"))(
                sparams, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=3e-5, atol=3e-5)


def test_sp_forward_validation():
    cfg = tiny("llama", dtype="float32", param_dtype="float32")
    mesh = make_mesh(MeshConfig(seq=4, data=2))
    params = Model(cfg).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="not divisible"):
        sp_forward(params, cfg, jnp.zeros((2, 10), jnp.int32), mesh)


@pytest.mark.parametrize("arch,kv", [("llama", 8), ("llama", 2), ("gpt2", 8)])
def test_sp_decode_parity(arch, kv):
    """VERDICT r2 item 6: sp_forward prefill -> sp_decode_step greedy
    decode over the still-seq-sharded prefix cache must produce the exact
    tokens (and near-exact logits) of the single-device forward+decode."""
    from butterfly_tpu.parallel.sequence import sp_decode_step
    cfg = tiny(arch, vocab_size=256, hidden_size=64, num_heads=8,
               num_kv_heads=kv, head_dim=8, intermediate_size=128,
               dtype="float32", param_dtype="float32")
    mesh = make_mesh(MeshConfig(seq=4, data=2))
    params = Model(cfg).init(jax.random.PRNGKey(2))
    B, T, N_NEW = 2, 24, 5
    tokens = jnp.asarray(
        np.random.RandomState(2).randint(0, cfg.vocab_size, (B, T)))

    # single-device reference: contiguous cache all the way
    ref_cache = init_cache(cfg, batch=B, max_seq=T + N_NEW)
    step_ref = jax.jit(lambda p, t, c: forward(p, cfg, t, c))
    ref_logits, ref_cache = step_ref(params, tokens, ref_cache)
    ref_toks = []
    nxt = jnp.argmax(ref_logits[:, -1, :], axis=-1)[:, None]
    for _ in range(N_NEW):
        ref_toks.append(np.asarray(nxt)[:, 0])
        ref_logits, ref_cache = step_ref(params, nxt, ref_cache)
        nxt = jnp.argmax(ref_logits[:, -1, :], axis=-1)[:, None]

    # SP: prefill leaves the prefix sharded over seq; decode merges
    # per-device partials + the replicated suffix cache
    with jax.set_mesh(mesh):
        logits, prefix = jax.jit(
            lambda p, t: sp_forward(p, cfg, t, mesh, impl="ring"))(
                params, tokens)
        suffix = init_cache(cfg, batch=B, max_seq=N_NEW)
        step = jax.jit(lambda p, t, pos, pre, suf: sp_decode_step(
            p, cfg, t, pos, pre, suf, mesh))
        nxt = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
        toks = []
        for i in range(N_NEW):
            toks.append(np.asarray(nxt)[:, 0])
            pos = jnp.full((B, 1), T + i, jnp.int32)
            last, suffix = step(params, nxt, pos, prefix, suffix)
            nxt = jnp.argmax(last, axis=-1)[:, None]

    np.testing.assert_array_equal(np.stack(toks), np.stack(ref_toks))
    np.testing.assert_allclose(np.asarray(last),
                               np.asarray(ref_logits[:, -1, :]),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_array_equal(np.asarray(suffix.length),
                                  np.full((B,), N_NEW))


def test_generate_long_engine_parity():
    """VERDICT r4 item 4 (product surface): engine.generate_long over a
    seq=4 mesh == the unmeshed engine, with a prompt length NOT divisible
    by the seq axis (exercises the divisibility padding + the decode-time
    pad-K/V masking in sp_decode_step)."""
    from butterfly_tpu.engine import InferenceEngine, SamplingParams
    cfg = tiny("llama", dtype="float32", param_dtype="float32",
               num_heads=8, num_kv_heads=8, head_dim=8)
    params = Model(cfg).init(jax.random.PRNGKey(5))
    prompt = list(range(1, 12))  # 11 tokens: pads to 12 on a seq=4 mesh
    sp = SamplingParams(max_new_tokens=6)
    ref = InferenceEngine(Model(cfg), params).generate([prompt], sp)

    mesh = make_mesh(MeshConfig(seq=4, data=2))
    eng = InferenceEngine(Model(cfg), params, mesh=mesh)
    got = eng.generate_long(prompt, sp)
    np.testing.assert_array_equal(got.tokens[0], ref.tokens[0])

    with pytest.raises(ValueError, match="seq axis"):
        InferenceEngine(Model(cfg), params).generate_long(prompt, sp)


def test_generate_long_cli_parity(capsys):
    """The CLI path (`generate --seq-parallel 4`) end to end: same text
    as the unmeshed engine decoding the same byte prompt — for BOTH
    sequence-parallel attention implementations (--seq-impl)."""
    from butterfly_tpu.engine import InferenceEngine, SamplingParams
    from butterfly_tpu.serve.cli import main
    from butterfly_tpu.utils.tokenizer import ByteTokenizer

    rc = main(["generate", "--model", "tiny", "--seq-parallel", "4",
               "--prompt", "hello", "--max-new", "6"])
    assert rc == 0
    cli_text = capsys.readouterr().out.rstrip("\n")

    rc = main(["generate", "--model", "tiny", "--seq-parallel", "4",
               "--seq-impl", "ulysses", "--prompt", "hello",
               "--max-new", "6"])
    assert rc == 0
    uly_text = capsys.readouterr().out.rstrip("\n")
    assert uly_text == cli_text

    cfg = tiny("llama", dtype="float32", param_dtype="float32")
    tok = ByteTokenizer()
    from butterfly_tpu.quant.int8 import init_params_by_leaf
    params = init_params_by_leaf(cfg, jax.random.PRNGKey(0))  # as the CLI
    eng = InferenceEngine(Model(cfg), params)
    ids = tok.encode("hello")
    stop = tok.eos_id if tok.eos_id is not None else -1
    res = eng.generate([ids], SamplingParams(max_new_tokens=6,
                                             stop_token=stop))
    ref_text = tok.decode(res.tokens[0, :int(res.lengths[0])].tolist())
    assert cli_text == ref_text
