"""Unified mixed dispatch (ISSUE 18): prefill chunks and decode blocks
in ONE fused program per tick; since ISSUE 29 a step of it is PACKED,
S decode rows of one token beside P chunks of C prompt tokens.

The contract under test:

* token parity — the packed block must produce EXACTLY the greedy
  tokens of its references, across fresh/warm/ragged bursts x chunk
  width x kv dtype x spec x write-combined window: over float pools
  the contiguous engine's (`ref_tokens`), over int8 pools (whose
  rounding the contiguous engine does not share) each request's served
  ALONE, one at a time, with speculation and prefix caching off —
  a greedy token may not depend on the crowd, where a chunk falls, the
  window, a prefix hit or an accepted draft;
* admission costs no drain barrier: a run counts none of cause
  `admission`, and each cause that is left is counted under its own
  name;
* one device dispatch per tick in steady state (the spy test): no
  program but the tick's block is launched;
* `prefill_inline_budget` caps CONCURRENT prefill lanes (the ITL-tail
  knob) — a cap that ignores the budget fails here;
* mid-prefill preemption and cancel under the fused block keep the
  flush-before-reclaim invariant (exercised with kv_write_combine on);
* the packed step's shapes: S + P*C rows through the layers and S rows
  through the head, in the lowered program and in the tick record's
  `rows`; its decode rows take the paged kernel (window segment and
  all) and nothing falls back to the dense path unasked.
"""
import jax
import numpy as np
import pytest

from butterfly_tpu.core.config import RuntimeConfig, tiny
from butterfly_tpu.engine import InferenceEngine, SamplingParams
from butterfly_tpu.engine.serving import ServingEngine
from butterfly_tpu.models.common import Model
from butterfly_tpu.sched.scheduler import Scheduler
from packed_driver import idle_chunk_run

CFG = tiny("llama", dtype="float32", param_dtype="float32")
_PARAMS = None


def params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = Model(CFG).init(jax.random.PRNGKey(42))
    return _PARAMS


def make_sched(max_batch=3, max_seq=96, page=8, num_pages=0, seed=0,
               mesh=None, **rt_kw):
    rt = RuntimeConfig(max_batch_size=max_batch, max_seq_len=max_seq,
                       page_size=page, num_pages=num_pages, **rt_kw)
    return Scheduler(ServingEngine(Model(CFG), params(), rt, mesh=mesh),
                     seed=seed)


_REF = {}


def ref_tokens(prompt, max_new):
    """The contiguous engine's greedy tokens: the reference of every
    run over a float pool (one engine for the module, every answer
    kept)."""
    key = (tuple(prompt), max_new)
    if key not in _REF:
        if "engine" not in _REF:
            _REF["engine"] = InferenceEngine(Model(CFG), params())
        res = _REF["engine"].generate(
            [prompt], SamplingParams(max_new_tokens=max_new))
        _REF[key] = res.tokens[0, :int(res.lengths[0])].tolist()
    return list(_REF[key])


# -- burst scenarios ----------------------------------------------------------
# Each scenario is a staggered load whose admissions land while blocks
# are in flight: (prompt, max_new_tokens, what follows the submission:
# a number of ticks, or DONE for a run until the scheduler is empty).
DONE = "done"
_BASE = list(range(1, 17))
SCENARIOS = {
    # cold prompts of equal-ish length admitted mid-flight
    "fresh": [([5, 7, 11], 8, 2), (list(range(1, 20)), 6, 0),
              ([9, 2, 4], 5, DONE)],
    # wildly different prompt lengths admitted together, so prefill
    # lanes complete on different scan steps of one block
    "ragged": [([3], 7, 0), (list(range(2, 35)), 6, 2),
               (list(range(40, 49)), 8, DONE)],
    # requires prefix_caching: the second wave shares the first wave's
    # prompt prefix, so admission attaches cached pages and the chunk
    # cursor starts past zero
    "warm": [(_BASE + [61], 6, DONE), (_BASE + [67, 3], 7, 1),
             (_BASE + [71], 5, DONE)],
    # prompt lengths against the chunk (C = 8) and the block (k = 4
    # steps, so k x C = 32): shorter than C; 19 = 8 + 8 + 3, which ends
    # on the third step of a block; 41, which takes a second block; and
    # two admitted in consecutive ticks while the others decode
    "lengths": [([3, 1, 4], 9, 0), (list(range(2, 21)), 7, 1),
                (list(range(30, 71)), 6, 1), (list(range(7, 12)), 8, DONE)],
}


def run(sched, scenario):
    """A scenario through `sched`: its requests' outputs, in order."""
    reqs = []
    for prompt, max_new, after in SCENARIOS[scenario]:
        reqs.append(sched.submit(prompt, max_new_tokens=max_new))
        if after == DONE:
            sched.run_until_done()
        else:
            for _ in range(after):
                sched.tick()
    sched.run_until_done()
    return [r.output for r in reqs]


def alone(jobs, **rt_kw):
    """Every (prompt, max_new) served ALONE, one request at a time, on
    a fresh scheduler of this runtime with speculation and prefix
    caching off: nobody beside it, nothing cached for it, no draft (one
    scheduler for the jobs: an engine a job would compile the same
    programs again)."""
    rt_kw = {k: v for k, v in rt_kw.items()
             if k not in ("speculative_gamma", "prefix_caching")}
    sched, outs = make_sched(**rt_kw), []
    for prompt, max_new in jobs:
        req = sched.submit(prompt, max_new_tokens=max_new)
        sched.run_until_done()
        outs.append(req.output)
    return outs


def references(scenario, **rt_kw):
    """What every request of a scenario must emit under `rt_kw`."""
    jobs = [(p, n) for p, n, _ in SCENARIOS[scenario]]
    if rt_kw.get("kv_quant", "none") == "none":
        return [ref_tokens(p, n) for p, n in jobs]
    return alone(jobs, **rt_kw)


_K4 = dict(decode_steps_per_tick=4, max_batch=4)

#: the parity grid: every dimension value (scenario, chunk 8/16,
#: f32/int8, spec on/off, window on/off) appears at least twice,
#: without paying the full 48-point cross product on CPU.
GRID = [
    ("fresh", dict(prefill_chunk=8, prefill_inline_budget=8)),
    ("fresh", dict(prefill_chunk=16, prefill_inline_budget=16,
                   kv_quant="int8", speculative_gamma=3)),
    ("ragged", dict(prefill_chunk=16, prefill_inline_budget=16,
                    kv_quant="int8", kv_write_combine=True)),
    ("ragged", dict(prefill_chunk=8, prefill_inline_budget=8,
                    kv_quant="int8", speculative_gamma=3,
                    kv_write_combine=True)),
    ("warm", dict(prefill_chunk=8, prefill_inline_budget=8,
                  prefix_caching=True, kv_write_combine=True)),
    ("warm", dict(prefill_chunk=16, prefill_inline_budget=16,
                  prefix_caching=True, speculative_gamma=3)),
    # the packed step (ISSUE 29): window on and off x int8 KV on and
    # off, against every length class, a prefix-cache hit under the
    # chunk, and two chunks a step (budget 64 over chunks of 32: P = 2)
    ("lengths", dict(prefill_chunk=8, prefill_inline_budget=8, **_K4)),
    ("lengths", dict(prefill_chunk=8, prefill_inline_budget=8,
                     kv_quant="int8", **_K4)),
    ("lengths", dict(prefill_chunk=8, prefill_inline_budget=8,
                     kv_write_combine=False, **_K4)),
    ("lengths", dict(prefill_chunk=8, prefill_inline_budget=16,
                     kv_write_combine=False, kv_quant="int8", **_K4)),
    ("lengths", dict(prefill_chunk=32, prefill_inline_budget=64,
                     kv_quant="int8", **_K4)),
    ("ragged", dict(prefill_chunk=32, prefill_inline_budget=64,
                    kv_write_combine=False, decode_steps_per_tick=2)),
    ("warm", dict(prefill_chunk=8, prefill_inline_budget=8,
                  prefix_caching=True, kv_write_combine=False,
                  kv_quant="int8")),
    ("warm", dict(prefill_chunk=8, prefill_inline_budget=16,
                  prefix_caching=True, kv_quant="int8",
                  decode_steps_per_tick=2)),
]


@pytest.mark.parametrize("scenario,rt_kw", GRID,
                         ids=[f"{s}-" + "-".join(
                             f"{k}={v}" for k, v in sorted(kw.items()))
                             for s, kw in GRID])
def test_packed_tokens_are_the_references(scenario, rt_kw):
    sched = make_sched(**rt_kw)
    assert run(sched, scenario) == references(scenario, **rt_kw)
    # admission cost no barrier
    assert "admission" not in sched.barrier_causes()
    # every prompt token past a cached prefix rode a chunk, and the
    # chunks offered at least as many positions: the fill
    m = sched.registry.snapshot()
    assert 0 < m["mixed_chunk_tokens_total"] \
        <= m["mixed_chunk_positions_total"]


def test_mixed_seeded_sampling_reproducible():
    """temperature > 0 draws a block's own RNG stream (fold_in(key,
    step)), which must stay seed-deterministic."""
    def run(seed):
        sched = make_sched(seed=seed)
        r1 = sched.submit([5, 7, 11], max_new_tokens=8, temperature=0.8)
        sched.tick()
        r2 = sched.submit(list(range(1, 14)), max_new_tokens=6,
                          temperature=0.8)
        sched.run_until_done()
        return [r1.output, r2.output]
    assert run(0) == run(0)
    assert run(0) != run(7)  # and the seed actually matters


# -- one fused dispatch per tick ---------------------------------------------

def test_one_dispatch_per_tick_steady_mixed(monkeypatch):
    """Launch spy: in steady state (decode in flight, prompts arriving)
    each tick launches AT MOST ONE device program, the tick's fused
    block — no prefill program of its own, no flush outside a drain's,
    no admission barrier."""
    sched = make_sched(max_batch=3)
    eng = sched.engine
    launched, launch = [], eng._launch
    monkeypatch.setattr(eng, "_launch", lambda prog, *a: (
        launched.append(prog.__name__) or launch(prog, *a)))
    sched.submit([5, 7, 11], max_new_tokens=20)
    sched.tick()
    sched.submit(list(range(1, 18)), max_new_tokens=20)
    sched.submit([9, 2], max_new_tokens=20)
    for _ in range(6):
        before = len(launched)
        sched.tick()
        assert len(launched) - before <= 1
    assert len(launched) >= 5
    assert set(launched) == {"bf_mixed_block_win", "bf_decode_block_win"}
    assert sched.barrier_causes() == {}


# -- the ITL-tail knob --------------------------------------------------------

def test_inline_budget_caps_concurrent_prefill():
    """prefill_inline_budget bounds CONCURRENT prefill lanes: with
    budget == chunk width, at most ONE slot may chew prompt chunks at a
    time no matter how many slots are free (a cap of num_slots in
    the budget's place fails here)."""
    sched = make_sched(max_batch=4, max_seq=96,
                       prefill_chunk=8, prefill_inline_budget=8)
    assert sched._mixed_max_pf == 1
    reqs = [sched.submit(list(range(1 + 20 * i, 19 + 20 * i)),
                         max_new_tokens=4) for i in range(4)]
    # the invariant on the DEVICE: a dispatched block meets at most P
    # slots in prefill phase (one more would get no chunk, unseen by
    # the lockstep simulation), read from the operands it is handed
    launch, on_device = sched.engine.mixed_block_async, []

    def spy(tokens, cursor, pbuf, plen, *rest):
        on_device.append(int((np.asarray(cursor) < plen).sum()))
        assert on_device[-1] <= rest[-1], "more prefilling slots than chunks"
        return launch(tokens, cursor, pbuf, plen, *rest)

    sched.engine.mixed_block_async = spy
    seen_pf = 0
    for _ in range(60):
        if not sched.has_work:
            break
        sched.tick()
        # slots still in prefill phase when the next block runs: a
        # member whose last chunk is in flight holds no place
        pf = sched._prefilling_ahead()
        seen_pf = max(seen_pf, pf)
        assert pf <= 1, "inline budget must cap concurrent prefill lanes"
        assert len(sched._prefill_group) <= 1 + len(sched._inflight)
    assert all(r.state == "finished" for r in reqs)
    assert seen_pf == 1 and max(on_device) == 1
    # a wider budget admits wider gangs: the knob is live in BOTH
    # directions (budget 32 / chunk 8 -> 4 concurrent lanes allowed)
    wide = make_sched(max_batch=4, max_seq=96,
                      prefill_chunk=8, prefill_inline_budget=32)
    assert wide._mixed_max_pf == 4


def test_inline_budget_parity_not_affected():
    """A starved budget (one lane at a time) changes scheduling order,
    never tokens."""
    kw = dict(max_batch=4, max_seq=96, prefill_chunk=8)
    starved = make_sched(prefill_inline_budget=8, **kw)
    assert starved._mixed_max_pf == 1 < make_sched(**kw)._mixed_max_pf
    assert run(starved, "fresh") == references("fresh")


# -- preemption / cancel under the fused block --------------------------------

def test_mid_prefill_preemption_under_mixed():
    """Page pressure preempts a mid-prefill member while its chunks ride
    an in-flight fused block: the barrier-before-reclaim contract must
    hold (drain, then preempt), and the victim's eventual output must
    still be greedy-correct after readmission."""
    kw = dict(max_batch=2, max_seq=64, page=4, num_pages=9,
              prefill_chunk=8, prefill_inline_budget=8,
              kv_write_combine=True)
    sched = make_sched(**kw)
    r1 = sched.submit([5, 7, 11], max_new_tokens=10)
    r2 = sched.submit(list(range(1, 14)), max_new_tokens=8)
    sched.run_until_done()
    assert r1.state == r2.state == "finished"
    assert r1.output == ref_tokens([5, 7, 11], 10)
    assert r2.output == ref_tokens(list(range(1, 14)), 8)
    # the tiny pool really forced preemptions in the mixed run
    assert sched.metrics().get("preemptions_total", 0) >= 1


def test_cancel_mid_prefill_under_mixed():
    """Cancelling a request whose prefill chunks are riding an
    in-flight fused block must drain first (flush-before-reclaim), free
    the slot, and leave the survivors' tokens untouched."""
    kw = dict(max_batch=3, max_seq=96, prefill_chunk=8,
              prefill_inline_budget=8, kv_write_combine=True)
    sched = make_sched(**kw)
    keep = sched.submit([5, 7, 11], max_new_tokens=10)
    sched.tick()
    victim = sched.submit(list(range(1, 30)), max_new_tokens=8)
    # the inline budget (one lane) may defer admission a tick or two
    # while keep's own prefill drains out of the group
    for _ in range(6):
        if victim.state != "waiting":
            break
        sched.tick()
    assert victim.state in ("prefilling", "running")
    sched.cancel(victim)
    assert victim.state == "cancelled"
    assert victim.slot is None
    sched.run_until_done()
    assert keep.output == ref_tokens([5, 7, 11], 10)
    assert sched.barrier_causes().get("cancel", 0) >= 1
    assert "admission" not in sched.barrier_causes()


def test_mixed_spec_mid_prefill_cancel():
    """Same cancel hazard under the speculative mixed twin (history
    doubles as the prompt buffer there): the survivor's tokens are the
    contiguous engine's, which knows no speculation."""
    kw = dict(max_batch=3, max_seq=96,
              prefill_chunk=8, prefill_inline_budget=8)
    sched = make_sched(speculative_gamma=3, **kw)
    keep = sched.submit([5, 7, 11], max_new_tokens=10)
    sched.tick()
    victim = sched.submit(list(range(1, 30)), max_new_tokens=8)
    sched.tick()
    sched.cancel(victim)
    assert victim.state == "cancelled"
    sched.run_until_done()
    assert keep.output == ref_tokens([5, 7, 11], 10)


# -- carry hygiene ------------------------------------------------------------

def test_slot_reuse_reseeds_mixed_carries():
    """A freed slot re-admitted by a later request must reseed the
    cursor/plen/prompt-row carries: back-to-back waves through the same
    slots stay greedy-correct."""
    kw = dict(max_batch=1, max_seq=96, prefill_chunk=8,
              prefill_inline_budget=8)
    prompts = ([5, 7, 11], list(range(1, 16)), [9, 2])
    sched = make_sched(**kw)
    reqs = [sched.submit(p, max_new_tokens=5) for p in prompts]
    sched.run_until_done()
    assert [r.output for r in reqs] == [ref_tokens(p, 5) for p in prompts]


def test_a_request_behind_its_prefix_s_writer_waits_and_hits():
    """Prefix caching: a request whose leading block a member of the
    prefill group is still writing is NOT admitted beside it (it would
    prefill the shared prefix a second time): it waits, FIFO, until the
    member's prompt completes and registers its pages, then admits with
    the hit. The tokens are the contiguous engine's."""
    sched = make_sched(prefix_caching=True, prefill_chunk=8,
                       prefill_inline_budget=16)
    assert sched._mixed_max_pf == 2         # a chunk was free for it
    first = sched.submit(_BASE + [61, 5], max_new_tokens=5)
    second = sched.submit(_BASE + [67], max_new_tokens=5)
    third = sched.submit([9, 2, 4], max_new_tokens=5)   # FIFO: behind it
    sched.tick()
    assert first.state == "prefilling"
    assert second.state == third.state == "waiting"
    while second.state == "waiting":
        assert first.state == "prefilling"
        sched.tick()
    assert first.state == "running"
    assert second.cached_at_admit == 16 and first.cached_at_admit == 0
    sched.run_until_done()
    assert [r.output for r in (first, second, third)] == [
        ref_tokens(r.prompt, 5) for r in (first, second, third)]
    sched.alloc.check_invariants()


def test_mixed_tick_phase_recorded():
    """The fused dispatch attributes its host section to the 'mixed'
    tick phase (not 'dispatch'), and the metrics surface exports it."""
    sched = make_sched()
    sched.submit([5, 7, 11], max_new_tokens=6)
    sched.run_until_done()
    dump = sched.ticklog.dump()
    assert "mixed" in dump["phases"]
    assert any(t["phases"].get("mixed", 0.0) > 0.0 for t in dump["ticks"])
    assert all(t["phases"].get("dispatch", 0.0) == 0.0
               for t in dump["ticks"])
    m = sched.metrics()
    assert "tick_phase_mixed_p50" in m


# -- the packed step's shapes and kernels (ISSUE 29) ---------------------------

def _all_shapes(jaxpr, out=None):
    """Every array shape a traced program computes, scan bodies and
    other sub-programs included."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        out.update(tuple(v.aval.shape) for v in eqn.outvars
                   if hasattr(v.aval, "shape"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _all_shapes(sub, out)
    return out


@pytest.mark.parametrize("P", [1, 2, 0])
@pytest.mark.parametrize("window", [True, False], ids=["win", "nowin"])
def test_packed_step_shapes_and_rows(window, P):
    """A step of the packed block puts S + P*C rows through the layers
    and S rows through the head: no array of the traced program has
    the lane-wide S*C rows, the logits are [S, V], and the tick record
    says the same in `rows` (S for a block with no prompt in flight)."""
    S, C, k = 3, 8, 2
    sched = make_sched(max_batch=S, prefill_chunk=C,
                       prefill_inline_budget=max(1, P) * C,
                       decode_steps_per_tick=k, kv_write_combine=window)
    assert (sched._mixed_chunk, sched._mixed_max_pf) == (C, max(1, P))
    eng = sched.engine
    if window:
        eng._ensure_window(k * C)
    i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    traced = eng._mixed_block_prog(k, C, P).trace(
        eng.params, i32(S), i32(S), eng.cache, eng._kv_window, eng._win_len,
        i32(S, eng.cache.max_seq), i32(S), np.ones(S, bool),
        np.zeros(S, np.float32), i32(S) - 1, i32(S) + k, 0, 1.0,
        jax.random.PRNGKey(0))
    shapes = _all_shapes(traced.jaxpr.jaxpr)
    D, V, N = CFG.hidden_size, CFG.vocab_size, S + P * C
    assert (N, 1, D) in shapes            # the packed rows, through a layer
    assert (S, V) in shapes               # the head, one row a slot
    wide = [sh for sh in shapes if sh and sh[-1] in (D, V)
            and int(np.prod(sh[:-1])) >= S * C]
    assert not wide, f"lane-wide activations in the packed step: {wide}"

    if P == 2:
        return  # the tick record: once a window mode is enough
    r1 = sched.submit(list(range(1, 20)), max_new_tokens=6)
    sched.run_until_done()
    assert r1.state == "finished"
    rows = {(t["program"], t["rows"]) for t in sched.ticklog.dump()["ticks"]
            if t["program"]}
    win = "_win" if window else ""
    assert rows == {("bf_mixed_block" + win, S + C),
                    ("bf_decode_block" + win, S)}


@pytest.mark.parametrize("window", [True, False], ids=["win", "nowin"])
@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_an_idle_chunk_beside_a_real_one_changes_nothing(kv_quant, window):
    """Two and three chunks a step (prefill_inline_budget holds
    several) of which ONE carries tokens: an idle chunk's slot reads 0,
    the slot the real chunk writes, and it must write nothing there and
    read nothing into the rows beside it (PR 56's review found this
    broken for a recurrent state; the attention pools had no test).
    The logits are the run's without an idle chunk and, over a float
    pool, the contiguous forward's."""
    from butterfly_tpu.models.common import forward, init_cache
    seq = np.random.RandomState(5).randint(1, 250, (19,)).astype(np.int32)
    rt = RuntimeConfig(max_batch_size=3, max_seq_len=64, page_size=4,
                       kv_quant=kv_quant)
    runs = [idle_chunk_run(params(), seq, CFG, idle=n, windowed=window,
                           rt=rt)[0] for n in (0, 1, 2)]
    for (slot, pos, want), *got in zip(*runs):
        for other in got:
            assert other[:2] == (slot, pos)
            np.testing.assert_allclose(other[2], want, rtol=1e-5, atol=1e-5)
    if kv_quant == "none":
        want, _ = forward(params(), CFG, seq[None], init_cache(CFG, 1, 32),
                          fresh=True)
        for slot, pos, row in runs[1]:
            np.testing.assert_allclose(row, np.asarray(want[0, pos]),
                                       rtol=2e-4, atol=2e-4)


#: decode_steps_per_tick=8 (C = 8, two chunks a step): every scenario,
#: each feature once more
GRID8 = [
    ("lengths", dict()),
    ("lengths", dict(kv_quant="int8")),
    ("lengths", dict(kv_write_combine=False)),
    ("lengths", dict(kv_quant="int8", kv_write_combine=False)),
    ("warm", dict(kv_quant="int8", prefix_caching=True)),
    ("ragged", dict(speculative_gamma=3)),
    ("fresh", dict(speculative_gamma=3, kv_quant="int8",
                   kv_write_combine=False)),
]


@pytest.mark.parametrize("scenario,rt_kw", GRID8,
                         ids=[f"{s}-" + "-".join(
                             f"{k}={v}" for k, v in sorted(kw.items()))
                             for s, kw in GRID8])
def test_crowd_against_alone_at_eight_steps_a_block(scenario, rt_kw):
    """decode_steps_per_tick=8, the width `jamba2-3b.rollout` runs (the
    grid above stops at 4): a prompt of 41 takes most of a block's
    eight steps at C = 8, finishes fall on every step of a block, and a
    greedy token depends on none of it."""
    rt_kw = dict(prefill_chunk=8, prefill_inline_budget=16,
                 decode_steps_per_tick=8, max_batch=4, **rt_kw)
    sched = make_sched(**rt_kw)
    assert run(sched, scenario) == references(scenario, **rt_kw)
    if "speculative_gamma" not in rt_kw:
        assert all(t["program"] is None or t["rows"] in (4, 4 + 2 * 8)
                   for t in sched.ticklog.dump()["ticks"])
    assert "admission" not in sched.barrier_causes()


def _spent(sched):
    """Every budget spent on the device, the tokens still in flight."""
    sched.submit([5, 7, 11], max_new_tokens=2)
    sched.run_until_done()


def _pressed(sched):
    r1 = sched.submit([5, 7, 11], max_new_tokens=10)
    r2 = sched.submit([2, 4], max_new_tokens=10)
    sched.run_until_done()
    assert r1.state == r2.state == "finished"
    assert sched.metrics()["preemptions_total"] >= 1


def _cancelled(sched):
    r = sched.submit([9, 9, 9], max_new_tokens=12)
    for _ in range(2):
        sched.tick()
    assert sched._inflight
    sched.cancel(r)
    assert r.state == "cancelled"


def _expired(sched):
    import time
    r = sched.submit([5, 7, 11], max_new_tokens=50)
    for _ in range(3):
        sched.tick()
    assert r.state == "running" and sched._inflight
    r.deadline_s = time.monotonic() - 1e-3
    sched.tick()
    assert r.state == "expired"


def _finished(sched):
    reqs = [sched.submit([5, 7, 11], max_new_tokens=3),
            sched.submit([3, 1], max_new_tokens=30)]
    sched.run_until_done()
    assert all(r.state == "finished" for r in reqs)


def _long_prompt(sched):
    """A prompt over the lane's threshold behind a request that decodes:
    each of the lane's dispatches finds blocks in flight."""
    assert sched._sp_enabled
    short = sched.submit([5, 7, 11], max_new_tokens=20)
    for _ in range(2):
        sched.tick()
    long = sched.submit(list(range(1, 41)), max_new_tokens=4)
    sched.run_until_done()
    assert short.state == long.state == "finished"
    assert sched.metrics()["seq_parallel_prefill_tokens_total"] == 40


#: cause -> (what brings it about, the runtime it takes, the degree of
#: the mesh's seq axis it takes). `spec` is not among them: nothing but
#: the seq-parallel lane under speculation leaves a token in flight that
#: the device's budget carry does not know (ROADMAP C30)
CAUSES = {
    "idle": (_spent, {}, 0),
    "page_pressure": (_pressed, dict(max_seq=32, page=4, num_pages=6,
                                     max_batch=2, prefill_chunk=4), 0),
    "cancel": (_cancelled, {}, 0),
    "expired": (_expired, {}, 0),
    # with speculation a finish at a lazy drain keeps its barrier
    "finish": (_finished, dict(speculative_gamma=2), 0),
    "sp_prefill": (_long_prompt, dict(seq_parallel_threshold=16,
                                      seq_parallel_chunk=16, page=8), 2),
}
#: raised by a call BETWEEN two ticks: counted, and in no tick's record
#: (tick() starts its list of causes anew)
BETWEEN_TICKS = {"cancel"}


@pytest.mark.parametrize("cause", list(CAUSES))
def test_each_barrier_that_is_left_is_counted_under_its_own_name(cause):
    """Every cause of a FULL barrier the tick still has reaches
    drain_barriers_total{cause=} and the tick record of the tick it ran
    in under its own name, the unlabeled sum holds them all, and no run
    counts one of cause `admission`: nothing raises it."""
    from butterfly_tpu.core.config import MeshConfig
    from butterfly_tpu.core.mesh import make_mesh
    from butterfly_tpu.obs.ticklog import BARRIER_CAUSES
    scenario, rt_kw, seq = CAUSES[cause]
    mesh = make_mesh(MeshConfig(seq=seq), jax.devices()[:seq]) if seq \
        else None
    sched = make_sched(mesh=mesh, **rt_kw)
    scenario(sched)
    sched.run_until_done()
    causes = sched.barrier_causes()
    assert causes.get(cause, 0) >= 1
    assert set(causes) <= set(BARRIER_CAUSES) and "admission" not in causes
    ring = [c for t in sched.ticklog.dump()["ticks"]
            for c in t["barrier_causes"]]
    assert {c: ring.count(c) for c in set(ring)} == {
        c: n for c, n in causes.items() if c not in BETWEEN_TICKS}
    assert sched.metrics()["drain_barriers_total"] == sum(causes.values())
    assert f'butterfly_drain_barriers_total{{cause="{cause}"}}' \
        in sched.registry.render()


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_packed_block_kernel_notes(kv_quant):
    """Kernels interpreted: a packed block's decode rows go through the
    paged kernel with the window segment (the kernel a configuration
    declares it must hold), its chunk through the dense view of its own
    slot BY CHOICE: no `dense_fallback` note, which would mark a
    benchmark run as not correct. Tokens are those of the kernels-off
    run."""
    rt = RuntimeConfig(max_batch_size=3, max_seq_len=96, page_size=8,
                       prefill_chunk=8, prefill_inline_budget=8,
                       decode_steps_per_tick=2, kv_quant=kv_quant)
    outs = []
    for use_kernels in (False, True):
        eng = ServingEngine(Model(CFG), params(), rt,
                            use_kernels=use_kernels)
        outs.append(run(Scheduler(eng, seed=0), "fresh"))
    assert outs[0] == outs[1]
    held = "paged_int8_win" if kv_quant == "int8" else "paged_win"
    assert eng.kernel_mode == "interpret"
    assert eng.kernel_calls.get(held + ":interpret", 0) >= 2  # both programs
    assert "dense_fallback" not in eng.kernel_calls


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_packed_block_under_pipeline_stages(kv_quant):
    """Under pipeline stages the packed step goes through the GPipe
    schedule as one microbatch (parallel/pipeline.py
    paged_pipeline_packed): no admission
    barrier comes back, the block is 2 + P*C rows, and the tokens are
    the unmeshed engine's and, over the float pool, the contiguous
    engine's."""
    from butterfly_tpu.core.config import MeshConfig
    from butterfly_tpu.core.mesh import make_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 fake devices")
    mesh = make_mesh(MeshConfig(stage=2), jax.devices()[:2])
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8,
                       prefill_chunk=8, prefill_inline_budget=8,
                       decode_steps_per_tick=2, kv_quant=kv_quant)

    def serve(rt, mesh=None):
        sched = Scheduler(ServingEngine(Model(CFG), params(), rt, mesh=mesh),
                          seed=0)
        r1 = sched.submit([5, 7, 11], max_new_tokens=8)
        sched.tick()
        r2 = sched.submit(list(range(1, 20)), max_new_tokens=6)
        sched.run_until_done()
        return sched, [r1.output, r2.output]

    sched, staged = serve(rt, mesh)
    assert "admission" not in sched.barrier_causes()
    rows = {(t["program"], t["rows"]) for t in sched.ticklog.dump()["ticks"]}
    assert ("bf_mixed_block", 2 + 8) in rows
    assert staged == serve(rt)[1]
    if kv_quant == "none":
        assert staged == [ref_tokens([5, 7, 11], 8),
                          ref_tokens(list(range(1, 20)), 6)]


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_packed_step_logits_beside_the_lane_wide_step(kv_quant):
    """tools/mixed_parity.py's check, rehearsed on the toy: the packed
    step's logits are the lane-wide step's over three blocks with a
    flush after each, a prompt that crosses a flush, one chunk and two;
    a chunk fed one token late reads far over the limit, and in float32
    even a chunk staged one index late shows. Without a TPU the tool
    refuses to run unless told it is a rehearsal."""
    import json
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root / "tools"))
    try:
        import mixed_parity
    finally:
        sys.path.remove(str(root / "tools"))
    config = json.loads((root / "tests/servebench/files/configs"
                         / "tiny-llama.json").read_text())
    config["serve"]["kv_quant"] = kv_quant
    with pytest.raises(SystemExit, match="no TPU"):
        mixed_parity.check(config)
    out = mixed_parity.check(config, toy=True)
    assert out["ok"] and out["evidence"] == "cpu toy"
    for P in ("P1", "P2"):
        clean, late = out[P]["clean"], out[P]["index_shift"]
        assert clean["max"] < 1e-5
        assert clean["argmax_agree"] == clean["rows"]
        assert out[P]["chunk_shift"]["max"] > 5 * mixed_parity.LIMIT
        assert late["chunk_slot_after_flush_max"] > 1e3 * clean["max"]
