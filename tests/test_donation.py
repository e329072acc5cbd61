"""KV-cache buffer donation must actually alias in every decode path.

A "Some donated buffers were not usable" warning means XLA kept a second
full KV pool live (double HBM + a copy per decode step on real configs)
— so these tests turn that warning into a failure (VERDICT.md weak #2).
"""
import warnings

import jax
import numpy as np
import pytest

from butterfly_tpu.cache.allocator import PageAllocator
from butterfly_tpu.core.config import RuntimeConfig, tiny
from butterfly_tpu.engine.engine import InferenceEngine
from butterfly_tpu.engine.sampling import SamplingParams
from butterfly_tpu.engine.serving import ServingEngine
from butterfly_tpu.models.common import Model


DONATION_MSG = "donated buffers were not usable"


class _NoDonationWarnings:
    def __enter__(self):
        self._ctx = warnings.catch_warnings(record=True)
        self._rec = self._ctx.__enter__()
        warnings.simplefilter("always")
        return self

    def __exit__(self, *exc):
        self._ctx.__exit__(*exc)
        if exc[0] is None:
            bad = [str(w.message) for w in self._rec
                   if DONATION_MSG in str(w.message)]
            assert not bad, f"donation failed to alias: {bad}"
        return False


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny("llama", dtype="float32", param_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.mark.parametrize("fused", [True, False])
def test_generate_paths_alias(tiny_model, fused):
    model, params = tiny_model
    eng = InferenceEngine(model, params,
                          RuntimeConfig(max_seq_len=64))
    with _NoDonationWarnings():
        r = eng.generate([[1, 2, 3, 4], [5, 6, 7]],
                         SamplingParams(max_new_tokens=8, temperature=0.0),
                         fused=fused)
    assert r.tokens.shape == (2, 8)


def test_serving_paths_alias(tiny_model):
    model, params = tiny_model
    rt = RuntimeConfig(max_batch_size=4, max_seq_len=128,
                       page_size=16, num_pages=64)
    eng = ServingEngine(model, params, rt)
    alloc = PageAllocator(64, 16, 8)
    eng.set_table_row(0, alloc.grow(0, 64))
    cursor = eng.carry(np.zeros(4, np.int32))
    pbuf = np.zeros((4, 128), np.int32)
    pbuf[0, :5] = [1, 2, 3, 4, 5]
    plen = np.array([5, 0, 0, 0], np.int32)
    active = np.array([1, 0, 0, 0], bool)
    temps = np.zeros(4, np.float32)
    stops = np.full(4, -1, np.int32)
    budgets = np.full(4, 12, np.int32)
    with _NoDonationWarnings():
        # a block with a chunk takes the prompt in, then blocks without
        # one decode: the scan-carried window and the pool beside it
        # must alias in both (a non-aliasing carry would keep a second
        # copy live for the whole block — the cost the fusion exists to
        # avoid)
        _, _, toks, cursor = eng.mixed_block_async(
            np.zeros(4, np.int32), cursor, pbuf, plen, active, temps,
            stops, budgets, jax.random.PRNGKey(0), 2, 8, 1)
        for i in range(2):
            _, _, toks, cursor = eng.mixed_block_async(
                toks, cursor, pbuf, plen, active, temps, stops, budgets,
                jax.random.PRNGKey(1 + i), 4, 8, 0)
        eng.flush_kv_window()


def test_pipeline_generate_aliases(tiny_model):
    from butterfly_tpu.core.config import MeshConfig
    from butterfly_tpu.core.mesh import make_mesh

    cfg = tiny("llama", dtype="float32", param_dtype="float32",
               num_layers=4)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh(MeshConfig(stage=2, tensor=2, data=2))
    from butterfly_tpu.parallel.partition import shard_params
    params = shard_params(params, cfg, mesh)
    eng = InferenceEngine(model, params, RuntimeConfig(max_seq_len=64),
                          mesh=mesh, num_microbatches=2)
    with _NoDonationWarnings():
        r = eng.generate([[1, 2, 3]] * 2,
                         SamplingParams(max_new_tokens=4, temperature=0.0))
    assert r.tokens.shape == (2, 4)
