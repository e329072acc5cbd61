"""What the chip bring-up (PR 21) added, checked on the CPU: where the
compile cache goes, how weights are built without a checkpoint, the
refusals that replaced silent fallbacks (unknown device, no chip,
interpret mode off the CPU), the allocator build rule, and the exit
code of a wedged server."""
import os
import subprocess
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.core.config import MeshConfig, llama3_8b, tiny
from butterfly_tpu.core.mesh import make_mesh

REPO = Path(__file__).resolve().parent.parent


# -- compile cache ----------------------------------------------------------

@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_set_code_sets_nothing(monkeypatch,
                                                 cache_dir_config):
    from butterfly_tpu.core.compile_cache import place_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert place_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == cache_dir_config


def test_compile_cache_default_is_fixed_path_in_checkout(monkeypatch,
                                                         cache_dir_config):
    from butterfly_tpu.core.compile_cache import place_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert place_compile_cache() == want
    assert place_compile_cache() == want      # never a pid, a time, a temp
    assert jax.config.jax_compilation_cache_dir == want
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


# -- weights without a checkpoint -------------------------------------------

def _describe(tree):
    return jax.tree.map(
        lambda a: (a.shape, str(a.dtype), str(getattr(a.sharding, "spec",
                                                      None))), tree)


@pytest.mark.parametrize("quant", ["none", "int8"])
@pytest.mark.parametrize("meshed", [False, True], ids=["one", "mesh"])
def test_load_params_same_tree_as_init_then_quantize_then_shard(quant,
                                                                meshed):
    """cli.load_params builds each leaf in its final form; the result has
    the structure, dtypes and shardings the old init -> quantize ->
    device_put composition gave."""
    import argparse
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.parallel.partition import shard_params
    from butterfly_tpu.quant.int8 import (quantize_int8,
                                          shard_quantized_params)
    from butterfly_tpu.serve.cli import load_params

    cfg = tiny("llama", dtype="float32", param_dtype="float32",
               num_heads=8, num_kv_heads=8, head_dim=8)
    mesh = make_mesh(MeshConfig(data=2, tensor=4)) if meshed else None
    model = Model(cfg)
    old = model.init(jax.random.PRNGKey(0))
    if quant == "int8":
        old = quantize_int8(old, cfg)
    if mesh is not None:
        old = shard_quantized_params(old, cfg, mesh) if quant == "int8" \
            else shard_params(old, cfg, mesh)
    args = argparse.Namespace(ckpt=None, quant=quant)
    new = load_params(model, args, mesh)
    assert jax.tree.structure(new) == jax.tree.structure(old)
    assert _describe(new) == _describe(old)
    w = new["layers"]["mlp"]["w_up"]
    if quant == "int8":
        w = w["q8"].astype(jnp.float32) * w["s"]
    assert abs(float(jnp.std(w)) - 0.02) < 2e-3


def test_init_by_leaf_never_builds_the_float_8b_tree(monkeypatch):
    """At the 8B preset (abstractly: eval_shape) no random program is
    larger than the chunk budget, every matmul weight comes out int8, and
    the finished tree is the ~8.5 GB that fits one 16 GB chip."""
    from butterfly_tpu.quant import int8

    drawn = []
    real = int8._leaf_values

    def spy(k, *, shape, kind, axes, dt):
        if kind == "normal":
            drawn.append(int(np.prod(shape)))
        return real(k, shape=shape, kind=kind, axes=axes, dt=dt)
    monkeypatch.setattr(int8, "_leaf_values", spy)
    cfg = llama3_8b()
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    tree = jax.eval_shape(
        partial(int8.init_params_by_leaf, cfg, quant="int8"), key)
    assert max(drawn) <= 1.5 * int8._INIT_CHUNK_ELEMS
    assert tree["layers"]["mlp"]["w_gate"]["q8"].dtype == jnp.int8
    assert tree["lm_head"]["q8"].shape == (cfg.hidden_size, cfg.vocab_size)
    assert tree["embed"]["tok"].dtype == jnp.bfloat16
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree.leaves(tree))
    assert 8.0e9 < nbytes < 9.5e9


# -- refusals that replaced fallbacks ---------------------------------------

def test_interpret_mode_off_the_cpu_backend_raises(monkeypatch):
    from butterfly_tpu import ops
    from butterfly_tpu.ops.paged_attention import paged_attention
    from butterfly_tpu.ops.ring_attention import ring_block_stats

    assert ops.resolve_interpret(None) is True      # CPU: interpreted
    assert ops.kernels_default() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "some-accelerator")
    assert ops.resolve_interpret(None) is False     # elsewhere: compiled
    assert ops.kernels_default() is True
    with pytest.raises(RuntimeError, match="interpret mode"):
        ops.resolve_interpret(True)
    q = jnp.zeros((1, 8, 4, 128))
    pos = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(RuntimeError, match="interpret mode"):
        ring_block_stats(q, q, q, pos, pos, interpret=True)
    with pytest.raises(RuntimeError, match="interpret mode"):
        paged_attention(jnp.zeros((2, 4, 128)), jnp.zeros((1, 3, 4, 16, 128)),
                        jnp.zeros((1, 3, 4, 16, 128)), 0,
                        jnp.zeros((2, 2), jnp.int32),
                        jnp.zeros((2,), jnp.int32), interpret=True)


def test_engine_records_kernels_and_dense_fallback():
    """The engine counts, while its programs trace, the kernel call
    sites they hold — and a call site that wanted a kernel on a mesh no
    axis can shard says `dense_fallback` instead of hiding it."""
    from butterfly_tpu.core.config import RuntimeConfig
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.sched.scheduler import Scheduler

    cfg = tiny("llama", dtype="float32", param_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8,
                       decode_steps_per_tick=2)
    calls = {}
    for name, mesh in (("one", None),
                       ("seq", make_mesh(MeshConfig(seq=4),
                                         jax.devices()[:4]))):
        eng = ServingEngine(model, params, rt, mesh=mesh, use_kernels=True)
        assert eng.kernel_mode == "interpret"
        sched = Scheduler(eng)
        sched.submit([1, 2, 3], max_new_tokens=6)
        sched.run_until_done()
        calls[name] = dict(eng.kernel_calls)
    assert calls["one"].get("paged_win:interpret", 0) >= 1
    assert "dense_fallback" not in calls["one"]
    assert calls["seq"].get("dense_fallback", 0) >= 1


def test_chip_smoke_fails_fast_without_a_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
    # alone in a directory, without the program it drives, it fails too
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((REPO / "chip_smoke.py").read_text())
    r = subprocess.run([sys.executable, str(lone)], capture_output=True,
                       text=True, timeout=120, env=env)
    assert r.returncode != 0 and '"ok"' not in r.stdout
    assert "checkout" in r.stderr


# -- the allocator build rule -----------------------------------------------

def test_native_lib_builds_when_missing_or_stale(tmp_path, monkeypatch):
    from butterfly_tpu import native
    from butterfly_tpu.native import build as build_mod

    lib, src = tmp_path / "lib.so", tmp_path / "allocator.cc"
    src.write_text("// source")
    monkeypatch.setattr(native, "_LIB_PATH", lib)
    monkeypatch.setattr(native, "_SRC_PATH", src)
    builds = []

    def fake_build(verbose=True):
        builds.append(1)
        lib.write_text("built")
    monkeypatch.setattr(build_mod, "build", fake_build)
    assert native._ensure_built() and len(builds) == 1      # missing
    assert native._ensure_built() and len(builds) == 1      # current
    os.utime(src, (lib.stat().st_mtime + 10,) * 2)
    assert native._ensure_built() and len(builds) == 2      # stale

    def no_compiler(verbose=True):
        raise FileNotFoundError("g++")
    lib.unlink()
    monkeypatch.setattr(build_mod, "build", no_compiler)
    assert native._ensure_built() is False                  # Python twin


# -- a wedged server says so in its exit code ------------------------------

def test_serve_forever_exit_code_says_wedged(monkeypatch):
    import time
    from http.server import ThreadingHTTPServer

    from butterfly_tpu.core.config import RuntimeConfig
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.sched.scheduler import Scheduler
    from butterfly_tpu.serve import server
    from butterfly_tpu.utils.tokenizer import ByteTokenizer

    cfg = tiny("llama", dtype="float32", param_dtype="float32")
    model = Model(cfg)
    rt = RuntimeConfig(max_batch_size=1, max_seq_len=64, page_size=8)
    states = []

    class Capture(server.ServerState):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            states.append(self)
    monkeypatch.setattr(server, "ServerState", Capture)

    def until_interrupted(wedge):
        """Stand-in for the accept loop: (wedge serving,) then Ctrl-C."""
        state = states[-1]
        if wedge:
            state.sched.tick = lambda: (_ for _ in ()).throw(
                RuntimeError("device on fire"))
            state.sched.submit([1, 2], max_new_tokens=4)
            state.wake.set()
            deadline = time.monotonic() + 30
            while not state.error and time.monotonic() < deadline:
                time.sleep(0.01)
        raise KeyboardInterrupt

    for wedge, want in ((False, 0), (True, 1)):
        monkeypatch.setattr(ThreadingHTTPServer, "serve_forever",
                            lambda self, w=wedge: until_interrupted(w))
        sched = Scheduler(ServingEngine(
            model, model.init(jax.random.PRNGKey(0)), rt))
        rc = server.serve_forever(sched, ByteTokenizer(), host="127.0.0.1",
                                  port=0)
        assert rc == want


# -- Mosaic under a mesh: lowered for the TPU, here on the CPU ---------------
#
# Interpret mode never reaches the TPU lowering, so Tier-1 could not see
# what the first four-chip run hit: "Mosaic kernels cannot be
# automatically partitioned" for every kernel under every mesh, because
# the kernel shard_maps left mesh axes (of size 1) to GSPMD. JAX lowers
# for a platform that is not present (`lowering_platforms`), and that is
# where the check lives, so these tests lower the real programs for the
# TPU on the CPU. What they cannot see is Mosaic's own compile: that is
# tools/chip_kernels.py on the chip.

@pytest.fixture
def as_tpu(monkeypatch):
    """Kernels on and compiled, as on a chip (ops/__init__.py asks
    jax.default_backend). Traces made either side of the switch must not
    meet: a cached interpret-mode trace would hide the lowering, a cached
    compiled one would break a later CPU test."""
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.clear_caches()


def _lower_for_tpu(fn, *args) -> str:
    """`fn`: a function, or an engine program (already jitted, with its
    static arguments)."""
    prog = fn if hasattr(fn, "trace") else jax.jit(fn)
    return prog.trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def test_kernel_wrappers_lower_for_tpu_under_meshes(as_tpu):
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import chip_kernels
    finally:
        sys.path.remove(str(REPO / "tools"))
    from butterfly_tpu.core.mesh import mesh_ctx

    mesh = make_mesh(MeshConfig(tensor=4), jax.devices()[:4])
    for name, _, _, args, sharded in chip_kernels.build_cases(True, [32]):
        if sharded is None:
            continue
        with mesh_ctx(mesh):
            text = _lower_for_tpu(sharded,
                                  *chip_kernels.tp_place(mesh, name, args))
        assert "tpu_custom_call" in text, name
    smesh, fn, _, args = chip_kernels.ring_sharded_case(True)
    with mesh_ctx(smesh):
        assert "tpu_custom_call" in _lower_for_tpu(fn, *args)


@pytest.mark.parametrize("axes,kv_quant,holds", [
    (dict(tensor=4), "int8", "paged_int8_win"),
    (dict(seq=2, tensor=2), "int8", "ring_int8"),
    (dict(stage=2, tensor=2), "none", "paged"),
], ids=["tp4", "sp2tp2", "pp2tp2"])
def test_serving_programs_lower_for_tpu_under_meshes(as_tpu, axes, kv_quant,
                                                     holds):
    """The packed mixed block (and, with a seq axis, the SP chunk
    program) of a meshed ServingEngine, kernels on, 8B head geometry at
    a small width: lowers for the TPU with the Mosaic call inside, and
    no layer gave way to the dense path."""
    from butterfly_tpu.core.config import RuntimeConfig
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.quant.int8 import init_params_by_leaf

    cfg = llama3_8b().replace(num_layers=2, max_seq_len=512, vocab_size=512,
                              hidden_size=256, num_heads=8, num_kv_heads=4,
                              intermediate_size=512)
    mesh = make_mesh(MeshConfig(**axes), jax.devices()[:4])
    S, k = 8, 4
    rt = RuntimeConfig(max_batch_size=S, max_seq_len=256, kv_quant=kv_quant,
                       decode_steps_per_tick=k)
    params = init_params_by_leaf(cfg, jax.random.PRNGKey(0), quant=kv_quant,
                                 mesh=mesh)
    eng = ServingEngine(Model(cfg), params, rt, mesh=mesh, use_kernels=True)
    i32 = partial(jnp.zeros, dtype=jnp.int32)
    tail = (jnp.ones((S,), bool), jnp.zeros((S,), jnp.float32),
            jnp.full((S,), -1, jnp.int32), jnp.full((S,), k, jnp.int32),
            0, 1.0, jax.random.PRNGKey(0))
    C = 32
    with eng._mesh_ctx():
        if eng._window_mode:
            eng._ensure_window(k * C)
        # pipeline serving keeps per-token pool writes: no window there
        assert eng._window_mode == ("stage" not in axes)
        text = _lower_for_tpu(
            eng._mixed_block_prog(k, C, 1), eng.params, i32((S,)),
            i32((S,)), eng.cache, eng._kv_window, eng._win_len,
            i32((S, eng.cache.max_seq)), i32((S,)), *tail)
        assert "tpu_custom_call" in text
        if eng.supports_seq_parallel:
            pools = (eng.cache.k_pages, eng.cache.v_pages,
                     eng.cache.k_scale_pages, eng.cache.v_scale_pages)
            text = _lower_for_tpu(
                eng._sp_chunk_prog(64), eng.params, i32((1, 64)), pools,
                i32((eng.cache.page_table.shape[1],)), jnp.int32(0),
                jnp.int32(64))
            assert "tpu_custom_call" in text
    assert f"{holds}:compiled" in eng.kernel_calls
    assert "dense_fallback" not in eng.kernel_calls
