"""Mesh-aware serving: the north-star distributed-serving path
(BASELINE.json configs[4] x configs[1]) on fake devices.

Token-for-token parity: a Scheduler over a ServingEngine on a
tensor=4 x data=2 mesh must produce exactly what the unmeshed engine
produces, end to end through HTTP. Also: CLI flag wiring (build_mesh)
and donation aliasing under the mesh.
"""
import argparse
import json
import threading
import urllib.request
import warnings

import jax
import pytest

from butterfly_tpu.core.config import MeshConfig, RuntimeConfig, tiny
from butterfly_tpu.core.mesh import make_mesh
from butterfly_tpu.engine.serving import ServingEngine
from butterfly_tpu.models.common import Model
from butterfly_tpu.sched.scheduler import Scheduler

# kv-heads divisible by tensor=4 so the pool actually shards.
CFG = tiny("llama", dtype="float32", param_dtype="float32",
           num_heads=8, num_kv_heads=4, head_dim=8)
PROMPTS = [[5, 7, 11], [3, 1], [2, 4, 6, 8], [9]]


def _make_sched(params, mesh=None, max_batch=4):
    rt = RuntimeConfig(max_batch_size=max_batch, max_seq_len=64, page_size=8)
    return Scheduler(ServingEngine(Model(CFG), params, rt, mesh=mesh))


@pytest.fixture(scope="module")
def params():
    return Model(CFG).init(jax.random.PRNGKey(42))


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshConfig(data=2, tensor=4))


def test_meshed_scheduler_token_parity(params, mesh):
    ref = _make_sched(params)
    ref_reqs = [ref.submit(p, max_new_tokens=6) for p in PROMPTS]
    ref.run_until_done()

    sched = _make_sched(params, mesh=mesh)
    reqs = [sched.submit(p, max_new_tokens=6) for p in PROMPTS]
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        sched.run_until_done()
    assert [r.output for r in reqs] == [r.output for r in ref_reqs]
    bad = [str(w.message) for w in rec
           if "donated buffers were not usable" in str(w.message)]
    assert not bad, f"meshed serving donation failed to alias: {bad}"


@pytest.mark.parametrize("axes", [
    dict(data=2, tensor=4), dict(tensor=8), dict(stage=2, tensor=4),
    dict(stage=2, data=4), dict(seq=8), dict(seq=2, tensor=4),
], ids=lambda a: "x".join(f"{k}{v}" for k, v in a.items()))
def test_drain_fetches_each_array_whole_under_every_mesh_form(
        params, axes, monkeypatch):
    """The drain fetches each drained array as it is and joins nothing
    on the device (PR 25; PR 21 checked the joined values on the chip):
    under every mesh form the host's copy of each array agrees with
    every shard the devices hold, replicated or split, and the tokens
    with the unmeshed scheduler's."""
    import numpy as np
    ref = _make_sched(params)
    ref_reqs = [ref.submit(p, max_new_tokens=9) for p in PROMPTS]
    ref.run_until_done()

    real, seen = jax.device_get, []

    def checked(tree):
        out = real(tree)
        for arr, host in zip(jax.tree_util.tree_leaves(tree),
                             jax.tree_util.tree_leaves(out)):
            assert host.shape == arr.shape and host.dtype == arr.dtype
            for shard in arr.addressable_shards:
                np.testing.assert_array_equal(np.asarray(shard.data),
                                              host[shard.index])
            seen.append(len(arr.sharding.device_set))
        return out
    monkeypatch.setattr(jax, "device_get", checked)
    rt = RuntimeConfig(max_batch_size=4, max_seq_len=64, page_size=8,
                       decode_steps_per_tick=2)
    sched = Scheduler(ServingEngine(Model(CFG), params, rt,
                                    mesh=make_mesh(MeshConfig(**axes))))
    reqs = [sched.submit(p, max_new_tokens=9) for p in PROMPTS]
    sched.run_until_done()
    assert [r.output for r in reqs] == [r.output for r in ref_reqs]
    assert seen and max(seen) > 1   # arrays that live on several devices
    assert sched._c_overlap.labels("overlapped").value \
        + sched._c_overlap.labels("exposed").value > 0   # lazy drains ran


def test_meshed_scheduler_kernels_token_parity(params, mesh):
    """Pallas kernels (interpret mode) under the mesh == unmeshed gather
    path, token-exact — the round-2 VERDICT item 1 regression test."""
    ref = _make_sched(params)
    ref_reqs = [ref.submit(p, max_new_tokens=6) for p in PROMPTS]
    ref.run_until_done()

    rt = RuntimeConfig(max_batch_size=4, max_seq_len=64, page_size=8)
    sched = Scheduler(ServingEngine(Model(CFG), params, rt, mesh=mesh,
                                    use_kernels=True))
    reqs = [sched.submit(p, max_new_tokens=6) for p in PROMPTS]
    sched.run_until_done()
    assert [r.output for r in reqs] == [r.output for r in ref_reqs]


def test_meshed_kernels_gqa_kv_smaller_than_tensor(mesh):
    """Kv/page-dim mixup regression (round-4 ADVICE high): with pools
    laid out [P, Kv, page, H], num_kv_heads=2 < tensor=4 while
    page_size=8 IS tensor-divisible. shardable_axes must test Kv (2),
    not page (8) — the kernel falls back to the gather path instead of
    raising in shard_map — and tokens must match the unmeshed engine."""
    cfg = tiny("llama", dtype="float32", param_dtype="float32",
               num_heads=8, num_kv_heads=2, head_dim=8)
    params = Model(cfg).init(jax.random.PRNGKey(7))
    rt = RuntimeConfig(max_batch_size=4, max_seq_len=64, page_size=8)

    ref = Scheduler(ServingEngine(Model(cfg), params, rt))
    ref_reqs = [ref.submit(p, max_new_tokens=6) for p in PROMPTS]
    ref.run_until_done()

    sched = Scheduler(ServingEngine(Model(cfg), params, rt, mesh=mesh,
                                    use_kernels=True))
    reqs = [sched.submit(p, max_new_tokens=6) for p in PROMPTS]
    sched.run_until_done()
    assert [r.output for r in reqs] == [r.output for r in ref_reqs]


def test_meshed_engine_flash_prefill_token_parity(params, mesh):
    """InferenceEngine flash prefill through shard_map on the mesh."""
    import numpy as np
    from butterfly_tpu.engine import InferenceEngine, SamplingParams
    sp = SamplingParams(max_new_tokens=6)
    a = InferenceEngine(Model(CFG), params,
                        use_flash_prefill=False).generate(PROMPTS, sp)
    b = InferenceEngine(Model(CFG), params, mesh=mesh,
                        use_flash_prefill=True).generate(PROMPTS, sp)
    np.testing.assert_array_equal(a.tokens, b.tokens)


def test_meshed_pool_is_sharded(params, mesh):
    eng = ServingEngine(Model(CFG), params,
                        RuntimeConfig(max_batch_size=4, max_seq_len=64,
                                      page_size=8), mesh=mesh)
    spec = eng.cache.k_pages.sharding.spec
    assert spec[2] == "tensor"  # kv-heads split over TP shards
    assert eng.cache.page_table.sharding.spec[0] == "data"


def test_stage_parallel_scheduler_token_parity(params):
    """VERDICT r2 item 4: pipeline-parallel serving — the paged decode
    path runs the GPipe schedule per stage slice; token-exact vs the
    unmeshed scheduler."""
    ref = _make_sched(params)
    ref_reqs = [ref.submit(p, max_new_tokens=6) for p in PROMPTS]
    ref.run_until_done()

    mesh = make_mesh(MeshConfig(stage=2, tensor=4))
    sched = _make_sched(params, mesh=mesh)
    reqs = [sched.submit(p, max_new_tokens=6) for p in PROMPTS]
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        sched.run_until_done()
    assert [r.output for r in reqs] == [r.output for r in ref_reqs]
    bad = [str(w.message) for w in rec
           if "donated buffers were not usable" in str(w.message)]
    assert not bad, f"stage-parallel serving donation failed to alias: {bad}"


def test_stage_data_parallel_scheduler_token_parity(params):
    """PP x DP: slots sharded over data while microbatches of slots flow
    through the stage schedule."""
    ref = _make_sched(params)
    ref_reqs = [ref.submit(p, max_new_tokens=5) for p in PROMPTS]
    ref.run_until_done()

    mesh = make_mesh(MeshConfig(stage=2, data=4))
    sched = _make_sched(params, mesh=mesh)
    reqs = [sched.submit(p, max_new_tokens=5) for p in PROMPTS]
    sched.run_until_done()
    assert [r.output for r in reqs] == [r.output for r in ref_reqs]


def test_stage_pool_is_stage_sharded(params):
    mesh = make_mesh(MeshConfig(stage=2, tensor=4))
    eng = ServingEngine(Model(CFG), params,
                        RuntimeConfig(max_batch_size=4, max_seq_len=64,
                                      page_size=8), mesh=mesh)
    spec = eng.cache.k_pages.sharding.spec
    assert spec[0] == "stage"   # each stage owns its layers' pages
    assert spec[2] == "tensor"


def test_stage_indivisible_layers_rejected(params):
    mesh = make_mesh(MeshConfig(stage=4, data=2))  # 2 layers, 4 stages
    with pytest.raises(ValueError, match="not divisible"):
        ServingEngine(Model(CFG), params, RuntimeConfig(), mesh=mesh)


def test_http_generate_on_mesh(params, mesh):
    from http.server import ThreadingHTTPServer
    from butterfly_tpu.serve.server import ServerState, make_handler
    from butterfly_tpu.utils.tokenizer import ByteTokenizer

    sched = _make_sched(params, mesh=mesh)
    state = ServerState(sched, ByteTokenizer())
    state.thread.start()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_port}"
    try:
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"tokens": PROMPTS[0], "max_tokens": 5,
                             "stop_token": -1}).encode(),
            headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req, timeout=300).read())
        ref = _make_sched(params)
        r = ref.submit(PROMPTS[0], max_new_tokens=5)
        ref.run_until_done()
        assert out["tokens"] == r.output
    finally:
        state.stop.set()
        httpd.shutdown()


def test_cli_build_mesh_flags():
    from butterfly_tpu.serve.cli import build_mesh
    args = argparse.Namespace(tensor_parallel=4, stage_parallel=1,
                              expert_parallel=1, data_parallel=2)
    mesh = build_mesh(args)
    assert mesh.shape["tensor"] == 4 and mesh.shape["data"] == 2

    args1 = argparse.Namespace(tensor_parallel=1, stage_parallel=1,
                               expert_parallel=1, data_parallel=1)
    assert build_mesh(args1) is None

    big = argparse.Namespace(tensor_parallel=64, stage_parallel=1,
                             expert_parallel=1, data_parallel=1)
    with pytest.raises(SystemExit):
        build_mesh(big)


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["dense", "kernels"])
def test_packed_mixed_block_on_tp4_matches_single_device(use_kernels):
    """The packed mixed step (ISSUE 29) under tensor-parallel 4 with 2
    KV heads a shard, window on: prompts that take several chunks,
    admitted while others decode, give the single-device tokens; with
    kernels (interpreted) the decode rows hold the paged kernel with
    its window segment on every shard."""
    cfg = tiny("llama", dtype="float32", param_dtype="float32",
               num_heads=8, num_kv_heads=8, head_dim=8)
    params = Model(cfg).init(jax.random.PRNGKey(7))
    rt = RuntimeConfig(max_batch_size=4, max_seq_len=96, page_size=8,
                       prefill_chunk=8, prefill_inline_budget=8,
                       decode_steps_per_tick=2)
    assert rt.kv_write_combine

    def run(mesh, kernels):
        eng = ServingEngine(Model(cfg), params, rt, mesh=mesh,
                            use_kernels=kernels)
        sched = Scheduler(eng)
        reqs = [sched.submit(list(range(1, 20)), max_new_tokens=7),
                sched.submit([5, 7, 11], max_new_tokens=9)]
        sched.tick()
        reqs.append(sched.submit(list(range(40, 51)), max_new_tokens=6))
        sched.run_until_done()
        progs = {t["program"] for t in sched.ticklog.dump()["ticks"]}
        assert "bf_mixed_block_win" in progs
        return [r.output for r in reqs], eng

    ref, _ = run(None, False)
    mesh = make_mesh(MeshConfig(tensor=4), jax.devices()[:4])
    got, eng = run(mesh, use_kernels)
    assert got == ref
    assert eng.cache.k_pages.sharding.shard_shape(
        eng.cache.k_pages.shape)[2] == 2          # 2 KV heads a shard
    if use_kernels:
        assert eng.kernel_calls.get("paged_win:interpret", 0) >= 1
        assert "dense_fallback" not in eng.kernel_calls
