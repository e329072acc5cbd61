"""Unified mixed dispatch (ISSUE 18): prefill chunks and decode blocks
in ONE fused program per tick; since ISSUE 29 a step of it is PACKED,
S decode rows of one token beside P chunks of C prompt tokens.

The contract under test:

* token parity — mixed dispatch (the default) must produce EXACTLY the
  greedy tokens the alternating prefill/decode path produces, across
  fresh/warm/ragged gangs x chunk width x kv dtype x spec x
  write-combined window (the alternating path is the parity reference
  the `mixed_dispatch=False` knob keeps reachable);
* the admission-cause drain barrier is retired as a class — a mixed run
  records ZERO `drain_barriers_total{cause="admission"}`;
* one device dispatch per tick in steady mixed state (the spy test):
  no separate prefill dispatch, no admission drain;
* `prefill_inline_budget` caps CONCURRENT prefill lanes (the ITL-tail
  knob) — the mutcheck drop-the-budget mutant must die here;
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
from butterfly_tpu.engine.serving import ServingEngine
from butterfly_tpu.models.common import Model
from butterfly_tpu.sched.scheduler import Scheduler

CFG = tiny("llama", dtype="float32", param_dtype="float32")
_PARAMS = None


def params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = Model(CFG).init(jax.random.PRNGKey(42))
    return _PARAMS


def make_sched(max_batch=3, max_seq=96, page=8, num_pages=0, seed=0,
               **rt_kw):
    rt = RuntimeConfig(max_batch_size=max_batch, max_seq_len=max_seq,
                       page_size=page, num_pages=num_pages, **rt_kw)
    return Scheduler(ServingEngine(Model(CFG), params(), rt), seed=seed)


# -- gang scenarios -----------------------------------------------------------
# Each scenario submits a staggered load whose admissions land while
# decode blocks are in flight — the exact state mixed dispatch fuses.

def _run_fresh(sched):
    """Fresh gang: cold prompts of equal-ish length admitted mid-flight."""
    r1 = sched.submit([5, 7, 11], max_new_tokens=8)
    for _ in range(2):
        sched.tick()
    r2 = sched.submit(list(range(1, 20)), max_new_tokens=6)
    r3 = sched.submit([9, 2, 4], max_new_tokens=5)
    sched.run_until_done()
    return [r1.output, r2.output, r3.output]


def _run_ragged(sched):
    """Ragged gang: wildly different prompt lengths admitted together,
    so prefill lanes complete on different scan steps of one block."""
    r1 = sched.submit([3], max_new_tokens=7)
    r2 = sched.submit(list(range(2, 35)), max_new_tokens=6)
    for _ in range(2):
        sched.tick()
    r3 = sched.submit(list(range(40, 49)), max_new_tokens=8)
    sched.run_until_done()
    return [r1.output, r2.output, r3.output]


def _run_warm(sched):
    """Warm gang (requires prefix_caching): the second wave shares the
    first wave's prompt prefix, so admission attaches cached pages and
    the chunk cursor starts past zero."""
    base = list(range(1, 17))
    r1 = sched.submit(base + [61], max_new_tokens=6)
    sched.run_until_done()
    r2 = sched.submit(base + [67, 3], max_new_tokens=7)
    for _ in range(1):
        sched.tick()
    r3 = sched.submit(base + [71], max_new_tokens=5)
    sched.run_until_done()
    return [r1.output, r2.output, r3.output]


def _run_lengths(sched):
    """Prompt lengths against the chunk (C = 8) and the block (k = 4
    steps, so k x C = 32): shorter than C; 19 = 8 + 8 + 3, which ends
    on the third step of a block; 41, which takes a second block; and
    two admitted in consecutive ticks while the others decode."""
    r1 = sched.submit([3, 1, 4], max_new_tokens=9)
    r2 = sched.submit(list(range(2, 21)), max_new_tokens=7)
    sched.tick()
    r3 = sched.submit(list(range(30, 71)), max_new_tokens=6)
    sched.tick()
    r4 = sched.submit(list(range(7, 12)), max_new_tokens=8)
    sched.run_until_done()
    return [r1.output, r2.output, r3.output, r4.output]


SCENARIOS = {"fresh": _run_fresh, "ragged": _run_ragged, "warm": _run_warm,
             "lengths": _run_lengths}
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
def test_mixed_vs_alternating_token_parity(scenario, rt_kw):
    run = SCENARIOS[scenario]
    alt = run(make_sched(mixed_dispatch=False, **rt_kw))
    sched = make_sched(mixed_dispatch=True, **rt_kw)
    mix = run(sched)
    assert mix == alt
    # the tentpole's headline: admission-cause barriers retired
    assert sched.barrier_causes().get("admission", 0) == 0
    # every prompt token past a cached prefix rode a chunk, and the
    # chunks offered at least as many positions: the fill
    m = sched.registry.snapshot()
    assert 0 < m["mixed_chunk_tokens_total"] \
        <= m["mixed_chunk_positions_total"]


def test_alternating_path_unchanged_barriers():
    """The parity reference still barriers on admission — the knob
    really selects the old path."""
    sched = make_sched(mixed_dispatch=False)
    _run_fresh(sched)
    assert sched.barrier_causes().get("admission", 0) >= 1


def test_mixed_seeded_sampling_reproducible():
    """temperature > 0 under mixed dispatch diverges from the
    alternating RNG stream by design but must stay seed-deterministic."""
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
    """Dispatch-count spy: in steady mixed state (decode in flight,
    prompts arriving) each tick issues EXACTLY ONE fused device
    dispatch — no separate prefill dispatch, no admission barrier."""
    sched = make_sched(max_batch=3)
    eng = sched.engine
    counts = {"mixed": 0, "prefill": 0, "decode": 0}
    orig_mixed = eng.mixed_block_async
    orig_prefill = eng.prefill_batch
    orig_decode = eng.decode_block_async
    monkeypatch.setattr(eng, "mixed_block_async",
                        lambda *a, **k: (counts.__setitem__(
                            "mixed", counts["mixed"] + 1)
                            or orig_mixed(*a, **k)))
    monkeypatch.setattr(eng, "prefill_batch",
                        lambda *a, **k: (counts.__setitem__(
                            "prefill", counts["prefill"] + 1)
                            or orig_prefill(*a, **k)))
    monkeypatch.setattr(eng, "decode_block_async",
                        lambda *a, **k: (counts.__setitem__(
                            "decode", counts["decode"] + 1)
                            or orig_decode(*a, **k)))
    sched.submit([5, 7, 11], max_new_tokens=20)
    sched.tick()
    sched.submit(list(range(1, 18)), max_new_tokens=20)
    sched.submit([9, 2], max_new_tokens=20)
    for _ in range(6):
        before = counts["mixed"]
        sched.tick()
        assert counts["mixed"] - before <= 1
    assert counts["prefill"] == 0  # prompts rode the fused blocks
    assert counts["decode"] == 0   # the alternating program never ran
    assert counts["mixed"] >= 5
    assert sched.barrier_causes().get("admission", 0) == 0


# -- the ITL-tail knob --------------------------------------------------------

def test_inline_budget_caps_concurrent_prefill():
    """prefill_inline_budget bounds CONCURRENT prefill lanes: with
    budget == chunk width, at most ONE slot may chew prompt chunks at a
    time no matter how many slots are free. Kills the mutcheck
    drop-the-budget mutant (cap -> num_slots)."""
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
    alt = make_sched(mixed_dispatch=False, **kw)
    a = _run_fresh(alt)
    mix = make_sched(mixed_dispatch=True, prefill_inline_budget=8, **kw)
    m = _run_fresh(mix)
    assert a == m


# -- preemption / cancel under the fused block --------------------------------

def test_mid_prefill_preemption_under_mixed():
    """Page pressure preempts a mid-prefill member while its chunks ride
    an in-flight fused block: the barrier-before-reclaim contract must
    hold (drain, then preempt), and the victim's eventual output must
    still be greedy-correct after readmission."""
    kw = dict(max_batch=2, max_seq=64, page=4, num_pages=9,
              prefill_chunk=8, prefill_inline_budget=8,
              kv_write_combine=True)
    alt = make_sched(mixed_dispatch=False, **kw)
    ra1 = alt.submit([5, 7, 11], max_new_tokens=10)
    ra2 = alt.submit(list(range(1, 14)), max_new_tokens=8)
    alt.run_until_done()

    sched = make_sched(mixed_dispatch=True, **kw)
    r1 = sched.submit([5, 7, 11], max_new_tokens=10)
    r2 = sched.submit(list(range(1, 14)), max_new_tokens=8)
    sched.run_until_done()
    assert r1.state == r2.state == "finished"
    assert [r1.output, r2.output] == [ra1.output, ra2.output]
    # the tiny pool really forced preemptions in the mixed run
    assert sched.metrics().get("preemptions_total", 0) >= 1


def test_cancel_mid_prefill_under_mixed():
    """Cancelling a request whose prefill chunks are riding an
    in-flight fused block must drain first (flush-before-reclaim), free
    the slot, and leave the survivors' tokens untouched."""
    kw = dict(max_batch=3, max_seq=96, prefill_chunk=8,
              prefill_inline_budget=8, kv_write_combine=True)
    alt = make_sched(mixed_dispatch=False, **kw)
    ka = alt.submit([5, 7, 11], max_new_tokens=10)
    alt.run_until_done()

    sched = make_sched(mixed_dispatch=True, **kw)
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
    assert keep.output == ka.output
    assert sched.barrier_causes().get("cancel", 0) >= 1
    assert sched.barrier_causes().get("admission", 0) == 0


def test_mixed_spec_mid_prefill_cancel():
    """Same cancel hazard under the speculative mixed twin (history
    doubles as the prompt buffer there)."""
    kw = dict(max_batch=3, max_seq=96, speculative_gamma=3,
              prefill_chunk=8, prefill_inline_budget=8)
    alt = make_sched(mixed_dispatch=False, **kw)
    ka = alt.submit([5, 7, 11], max_new_tokens=10)
    alt.run_until_done()

    sched = make_sched(mixed_dispatch=True, **kw)
    keep = sched.submit([5, 7, 11], max_new_tokens=10)
    sched.tick()
    victim = sched.submit(list(range(1, 30)), max_new_tokens=8)
    sched.tick()
    sched.cancel(victim)
    assert victim.state == "cancelled"
    sched.run_until_done()
    assert keep.output == ka.output


# -- carry hygiene ------------------------------------------------------------

def test_slot_reuse_reseeds_mixed_carries():
    """A freed slot re-admitted by a later request must reseed the
    cursor/plen/prompt-row carries: back-to-back waves through the same
    slots stay greedy-correct."""
    kw = dict(max_batch=1, max_seq=96, prefill_chunk=8,
              prefill_inline_budget=8)
    alt = make_sched(mixed_dispatch=False, **kw)
    outs_alt = []
    for p in ([5, 7, 11], list(range(1, 16)), [9, 2]):
        r = alt.submit(p, max_new_tokens=5)
        alt.run_until_done()
        outs_alt.append(r.output)

    sched = make_sched(mixed_dispatch=True, **kw)
    reqs = [sched.submit(p, max_new_tokens=5)
            for p in ([5, 7, 11], list(range(1, 16)), [9, 2])]
    sched.run_until_done()
    assert [r.output for r in reqs] == outs_alt


def test_stateful_draft_falls_back_to_alternating():
    """A stateful (model) draft source cannot reseed inside the fused
    block: mixed_dispatch stays requested but the engine reports not
    ready and the scheduler runs the alternating path (parity with an
    explicit mixed_dispatch=False run)."""
    kw = dict(max_batch=2, max_seq=96, speculative_gamma=3,
              draft_model="model")
    sched = make_sched(mixed_dispatch=True, **kw)
    assert not sched.engine.mixed_dispatch_ready
    assert not sched._mixed_mode
    r = sched.submit([5, 7, 11], max_new_tokens=6)
    sched.run_until_done()
    ref = make_sched(mixed_dispatch=False, **kw)
    rr = ref.submit([5, 7, 11], max_new_tokens=6)
    ref.run_until_done()
    assert r.output == rr.output


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
        outs.append(_run_fresh(Scheduler(eng, seed=0)))
    assert outs[0] == outs[1]
    held = "paged_int8_win" if kv_quant == "int8" else "paged_win"
    assert eng.kernel_mode == "interpret"
    assert eng.kernel_calls.get(held + ":interpret", 0) >= 2  # both programs
    assert "dense_fallback" not in eng.kernel_calls


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_packed_block_under_pipeline_stages(kv_quant):
    """Under pipeline stages the packed step goes through the GPipe
    schedule as one microbatch (parallel/pipeline.py
    paged_pipeline_packed): mixed dispatch stays on, no admission
    barrier comes back, the block is 2 + P*C rows, and the tokens are
    the unmeshed engine's and the alternating path's."""
    from butterfly_tpu.core.config import MeshConfig
    from butterfly_tpu.core.mesh import make_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 fake devices")
    mesh = make_mesh(MeshConfig(stage=2), jax.devices()[:2])
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8,
                       prefill_chunk=8, prefill_inline_budget=8,
                       decode_steps_per_tick=2, kv_quant=kv_quant)

    def run(rt, mesh=None):
        sched = Scheduler(ServingEngine(Model(CFG), params(), rt, mesh=mesh),
                          seed=0)
        r1 = sched.submit([5, 7, 11], max_new_tokens=8)
        sched.tick()
        r2 = sched.submit(list(range(1, 20)), max_new_tokens=6)
        sched.run_until_done()
        return sched, [r1.output, r2.output]

    sched, staged = run(rt, mesh)
    assert sched._mixed_mode and sched._mixed_fallback_reason is None
    assert sched.barrier_causes().get("admission", 0) == 0
    rows = {(t["program"], t["rows"]) for t in sched.ticklog.dump()["ticks"]}
    assert ("bf_mixed_block", 2 + 8) in rows
    assert staged == run(rt)[1]
    assert staged == run(rt.replace(mixed_dispatch=False), mesh)[1]


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
