"""Warm-prefix flash attention (ISSUE 13): kernel + serving-path parity.

A multi-token forward behind a cached prefix (a packed step's chunk
with the window off, the speculative verify, the contiguous engine's
chunk continuation) takes the flash kernel with a cached-prefix
segment where the ModelConfig says attn_impl="flash", instead of the
dense O(T*S_max) view. Contract:

* kernel level — the prefix segment folds into the online softmax
  exactly like an inserted dense view, per-row count-masked at `start`
  (garbage past it NEVER contributes: recycled buffers are not zeroed);
* serving level — greedy outputs are token-identical to the dense path
  across chunk sizes x int8/f32 cache x bursts of ragged lengths x
  prefix-hit resume;
* the branch is taken where it is asked for and nowhere else.

Interpret mode runs the exact kernel code path on CPU (tier-1).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.cache.paged import init_paged_cache, paged_forward
from butterfly_tpu.core.config import RuntimeConfig, tiny
from butterfly_tpu.engine.serving import ServingEngine
from butterfly_tpu.models.common import (Model, attend, forward, init_cache,
                                         quantize_kv)
from butterfly_tpu.ops.flash_attention import flash_attention
from butterfly_tpu.sched.scheduler import Scheduler

CFG = tiny("llama", dtype="float32", param_dtype="float32")


# ---------------------------------------------------------------------------
# Kernel units (interpret mode = the exact kernel code path)
# ---------------------------------------------------------------------------


def _dense_warm_ref(q, k, v, pk, pv, start):
    """Dense reference: fresh chunk inserted into the prefix view at each
    row's start, causal mask over absolute positions."""
    B, T = q.shape[:2]
    Sp = pk.shape[1]
    rows = []
    for b in range(B):
        S = Sp + T
        kk = jnp.zeros((S,) + pk.shape[2:]).at[:Sp].set(pk[b])
        vv = jnp.zeros((S,) + pv.shape[2:]).at[:Sp].set(pv[b])
        s = int(start[b])
        kk = kk.at[s:s + T].set(k[b])
        vv = vv.at[s:s + T].set(v[b])
        pos = s + jnp.arange(T)
        mask = (jnp.arange(S)[None, :] <= pos[:, None])[None]
        rows.append(attend(q[b:b + 1], kk[None], vv[None], mask, None)[0])
    return jnp.stack(rows)


def test_warm_prefix_kernel_parity_and_garbage():
    """Float prefix segment: parity with the dense insert reference over
    ragged starts (including 0 = a fresh/padding row riding the warm
    program), and garbage past `start` must not change one bit."""
    B, T, Nq, Kv, H, Sp = 3, 12, 4, 2, 16, 40
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, T, Nq, H))
    k = jax.random.normal(ks[1], (B, T, Kv, H))
    v = jax.random.normal(ks[2], (B, T, Kv, H))
    pk = jax.random.normal(ks[3], (B, Sp, Kv, H))
    pv = jax.random.normal(ks[4], (B, Sp, Kv, H))
    start = jnp.asarray([7, 0, 33], jnp.int32)

    out = flash_attention(q, k, v, block_q=8, block_k=8,
                          prefix_k=pk, prefix_v=pv, prefix_len=start)
    ref = _dense_warm_ref(q, k, v, pk, pv, start)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # poison the prefix past each row's start: bit-identical output
    poisoned = pk
    for b, s in enumerate([7, 0, 33]):
        poisoned = poisoned.at[b, s:].set(1e3)
    out2 = flash_attention(q, k, v, block_q=8, block_k=8,
                          prefix_k=poisoned, prefix_v=pv, prefix_len=start)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(out))


def test_warm_prefix_kernel_int8_parity():
    """int8 prefix (codes [B,Kv,Sp,H] + per-vector scales, the pool
    representation): in-kernel dequantization matches the dense attend
    over the dequantized view."""
    B, T, Nq, Kv, H, Sp = 2, 10, 4, 2, 16, 24
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (B, T, Nq, H))
    k = jax.random.normal(ks[1], (B, T, Kv, H))
    v = jax.random.normal(ks[2], (B, T, Kv, H))
    pkf = jax.random.normal(ks[3], (B, Sp, Kv, H))
    pvf = jax.random.normal(ks[4], (B, Sp, Kv, H))
    start = jnp.asarray([17, 5], jnp.int32)

    kq, ksc = quantize_kv(pkf)          # [B,Sp,Kv,H] codes, [B,Sp,Kv]
    vq, vsc = quantize_kv(pvf)
    out = flash_attention(
        q, k, v, block_q=8, block_k=8,
        prefix_k=jnp.moveaxis(kq, 2, 1), prefix_v=jnp.moveaxis(vq, 2, 1),
        prefix_len=start,
        prefix_k_scale=jnp.moveaxis(ksc, 2, 1),
        prefix_v_scale=jnp.moveaxis(vsc, 2, 1))
    ref = _dense_warm_ref(q, k, v,
                          kq.astype(jnp.float32) * ksc[..., None],
                          vq.astype(jnp.float32) * vsc[..., None], start)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Serving-path parity
# ---------------------------------------------------------------------------


def _prompts(seed, lens):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, CFG.vocab_size - 2, (n,)).tolist() for n in lens]


def _run(model, params, prompts, *, use_kernels, warm_flash, kv_quant="none",
         chunk=16, max_new=8, prefix_caching=False, resume=None):
    # the window off: a packed step's chunk then reads its slot's
    # cached prefix out of the pool, which is where paged_attend's
    # warm branch takes the kernel for a ModelConfig that says flash
    # (window on, the chunk's own staged rows are not in the pool)
    rt = RuntimeConfig(max_batch_size=4, max_seq_len=128, page_size=8,
                       prefill_chunk=chunk, prefill_inline_budget=2 * chunk,
                       kv_quant=kv_quant, kv_write_combine=False,
                       prefix_caching=prefix_caching)
    if warm_flash:
        model = Model(model.cfg.replace(attn_impl="flash"))
    sched = Scheduler(ServingEngine(model, params, rt,
                                    use_kernels=use_kernels))
    reqs = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
    sched.run_until_done()
    outs = [r.output for r in reqs]
    if resume is not None:
        # prefix-hit resume: a later request sharing a registered prefix
        # admits warm (cached_at_admit > 0) and its FIRST chunk starts
        # at the cached length
        r = sched.submit(resume, max_new_tokens=max_new)
        sched.run_until_done()
        if prefix_caching:
            assert r.cached_at_admit > 0
        outs.append(r.output)
    return outs


def test_serving_warm_flash_vs_dense_parity():
    """Chunked multi-request prefill through the scheduler: the flash
    engine (its chunks through the warm kernel, two chunks a step) must
    be token-identical to the all-dense engine. Prompt lengths straddle
    chunk boundaries, so a step's chunks start at ragged lengths and a
    chunk's tail is filler."""
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(42))
    prompts = _prompts(0, (40, 23, 37))
    dense = _run(model, params, prompts, use_kernels=False, warm_flash=False)
    flash = _run(model, params, prompts, use_kernels=True, warm_flash=True)
    assert dense == flash


def test_serving_warm_flash_prefix_hit_resume_parity():
    """Prefix-cache resume: the second request's first chunk starts warm
    at the cached length; flash and dense engines agree token-for-token
    and the hit actually happened."""
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(43))
    shared = list(range(1, 17))          # two full 8-token pages
    first = [shared + [5, 9]]
    resume = shared + [7, 3, 2]
    dense = _run(model, params, first, use_kernels=False, warm_flash=False,
                 prefix_caching=True, resume=resume)
    flash = _run(model, params, first, use_kernels=True, warm_flash=True,
                 prefix_caching=True, resume=resume)
    assert dense == flash


@pytest.mark.parametrize("kv_quant,chunk", [("none", 8), ("int8", 8),
                                            ("int8", 16)])
def test_warm_flash_parity_grid(kv_quant, chunk):
    """The acceptance grid: warm-flash vs dense byte-parity across cache
    quantization x chunk size, with gangs of ragged lengths + a prefix-
    hit resume leg (slow tier: several engine compiles)."""
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(44))
    shared = list(range(1, 17))
    prompts = [shared + p for p in _prompts(7, (9, 22))] + _prompts(8, (31,))
    resume = shared + [11, 4]
    kw = dict(kv_quant=kv_quant, chunk=chunk, prefix_caching=True,
              resume=resume)
    dense = _run(model, params, prompts, use_kernels=False,
                 warm_flash=False, **kw)
    flash = _run(model, params, prompts, use_kernels=True,
                 warm_flash=True, **kw)
    kernel_dense = _run(model, params, prompts, use_kernels=True,
                        warm_flash=False, **kw)
    assert dense == flash
    assert dense == kernel_dense


def test_lane_wide_warm_forward_ragged_starts_direct():
    """cache/paged.py paged_forward, ONE warm multi-token forward with
    ragged starts (a long cached prefix, a shorter one, a row with
    none) and an inactive row: under attn_impl="flash" every row's
    logits are the dense forward's bit-for-near-bit."""
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(45))
    rt = RuntimeConfig(max_batch_size=4, max_seq_len=64, page_size=8)
    rng = np.random.RandomState(3)
    seed = np.zeros((4, 24), np.int32)
    lens = (24, 8, 0, 0)
    for b, n in enumerate(lens):
        seed[b, :n] = rng.randint(1, 250, (n,))
    warm = jnp.asarray(rng.randint(1, 250, (4, 6)), jnp.int32)
    outs = {}
    for cfg in (CFG, CFG.replace(attn_impl="flash")):
        cache = init_paged_cache(cfg, rt)
        # hand each slot a private page run (no allocator needed)
        cache = cache._replace(page_table=jnp.arange(
            32, dtype=jnp.int32).reshape(4, 8))
        # seed slots 0/1 with cached context of different lengths
        for b, n in enumerate(lens[:2]):
            _, cache = paged_forward(
                params, cfg, jnp.asarray(seed[:, :n]), cache, fresh=True,
                active=jnp.arange(4) == b)
        assert cache.lengths.tolist() == list(lens)
        # ONE warm forward: starts 24 / 8 / 0 — ragged + an inactive row
        logits, cache = paged_forward(
            params, cfg, warm, cache, active=jnp.arange(4) < 3)
        assert cache.lengths.tolist() == [30, 14, 6, 0]
        outs[cfg.attn_impl] = np.asarray(logits[:3])
    np.testing.assert_allclose(outs["flash"], outs["dense"],
                               rtol=3e-5, atol=3e-5)
    assert (outs["flash"].argmax(-1) == outs["dense"].argmax(-1)).all()


def test_contiguous_warm_flash_parity():
    """models.common.forward warm multi-token chunk (the contiguous-
    cache path: engine verify / chunk continuation) takes the kernel
    under attn_impl=flash and matches dense, float and int8 caches."""
    for quant in ("none", "int8"):
        cfg_d = CFG
        cfg_f = CFG.replace(attn_impl="flash")
        model = Model(cfg_d)
        params = model.init(jax.random.PRNGKey(1))
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 1, 250)
        outs = {}
        for name, cfg in (("dense", cfg_d), ("flash", cfg_f)):
            cache = init_cache(cfg, 2, 64, quant=quant)
            _, cache = forward(params, cfg, toks[:, :10], cache, fresh=True)
            l2, cache = forward(params, cfg, toks[:, 10:], cache)
            outs[name] = np.asarray(l2)
        np.testing.assert_allclose(outs["dense"], outs["flash"],
                                   rtol=3e-5, atol=3e-5)
        assert (outs["dense"].argmax(-1) == outs["flash"].argmax(-1)).all()


# ---------------------------------------------------------------------------
# Dispatch policy
# ---------------------------------------------------------------------------


def test_warm_flash_dispatches_kernel(monkeypatch):
    """A chunk behind a cached prefix must actually take the kernel
    where the ModelConfig says flash: count flash_attention_sharded
    calls carrying a prefix segment from inside the packed layer.
    A dense ModelConfig must make none, kernels on or not."""
    import butterfly_tpu.cache.paged as paged

    calls = {"prefix": 0, "fresh": 0}
    real = paged.flash_attention_sharded

    def spy(*args, **kw):
        calls["prefix" if kw.get("prefix_k") is not None else "fresh"] += 1
        return real(*args, **kw)

    monkeypatch.setattr(paged, "flash_attention_sharded", spy)
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(46))
    prompts = _prompts(9, (20,))
    _run(model, params, prompts, use_kernels=True, warm_flash=True, chunk=8)
    # every chunk goes through the warm branch, the first too (a prefix
    # of length 0): no caller says `fresh` any more
    assert calls["prefix"] > 0 and calls["fresh"] == 0
    calls.update(prefix=0, fresh=0)
    _run(model, params, prompts, use_kernels=True, warm_flash=False, chunk=8)
    assert calls == {"prefix": 0, "fresh": 0}
