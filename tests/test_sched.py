"""Continuous-batching scheduler tests (SURVEY.md §7 stage 4).

Greedy parity: requests scheduled through slots + paged cache must produce
exactly the tokens InferenceEngine.generate produces on the contiguous
cache. Plus: staggered admission, preemption under page pressure, metrics.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.core.config import RuntimeConfig, tiny
from butterfly_tpu.engine import InferenceEngine, SamplingParams
from butterfly_tpu.engine.serving import ServingEngine
from butterfly_tpu.models.common import Model
from butterfly_tpu.sched.scheduler import Scheduler

CFG = tiny("llama", dtype="float32", param_dtype="float32")


def make_sched(max_batch=2, max_seq=64, page=8, num_pages=0, seed=0, **rt_kw):
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(42))
    rt = RuntimeConfig(max_batch_size=max_batch, max_seq_len=max_seq,
                       page_size=page, num_pages=num_pages, **rt_kw)
    return Scheduler(ServingEngine(model, params, rt), seed=seed), params


_REF = {}


def ref_tokens(params, prompt, max_new):
    """The contiguous engine's greedy tokens. ONE engine for the module
    (every scheduler here is built over make_sched's seeded parameters)
    and every answer kept: an engine a call compiled its programs anew
    each time."""
    key = (tuple(prompt), max_new)
    if key not in _REF:
        if "engine" not in _REF:
            _REF["engine"] = InferenceEngine(Model(CFG), params)
        res = _REF["engine"].generate(
            [prompt], SamplingParams(max_new_tokens=max_new))
        _REF[key] = res.tokens[0, :int(res.lengths[0])].tolist()
    return list(_REF[key])


def test_single_request_greedy_parity():
    sched, params = make_sched()
    req = sched.submit([5, 7, 11], max_new_tokens=6)
    sched.run_until_done()
    assert req.state == "finished"
    assert req.output == ref_tokens(params, [5, 7, 11], 6)


def test_concurrent_requests_parity():
    """Two requests share the batch; each matches its solo reference."""
    sched, params = make_sched()
    r1 = sched.submit([5, 7, 11], max_new_tokens=6)
    r2 = sched.submit([3, 1], max_new_tokens=8)
    sched.run_until_done()
    assert r1.output == ref_tokens(params, [5, 7, 11], 6)
    assert r2.output == ref_tokens(params, [3, 1], 8)


def test_staggered_admission():
    """A request arriving mid-flight joins the running batch and still
    matches its solo reference (slot reuse after r1 finishes)."""
    sched, params = make_sched(max_batch=2)
    r1 = sched.submit([5, 7, 11], max_new_tokens=4)
    for _ in range(2):
        sched.tick()
    r2 = sched.submit([2, 4, 6, 8], max_new_tokens=5)
    r3 = sched.submit([9], max_new_tokens=3)  # waits for a slot
    sched.run_until_done()
    assert [r.state for r in (r1, r2, r3)] == ["finished"] * 3
    assert r1.output == ref_tokens(params, [5, 7, 11], 4)
    assert r2.output == ref_tokens(params, [2, 4, 6, 8], 5)
    assert r3.output == ref_tokens(params, [9], 3)


def test_queue_when_slots_full():
    sched, params = make_sched(max_batch=1)
    reqs = [sched.submit([i + 1], max_new_tokens=3) for i in range(3)]
    sched.run_until_done()
    for i, r in enumerate(reqs):
        assert r.output == ref_tokens(params, [i + 1], 3)


def test_preemption_under_page_pressure():
    """Tiny pool: two long generations can't both fit; the younger gets
    preempted+recomputed and still produces correct greedy output."""
    # 6 usable pages of 4 tokens; two requests growing to ~16 tokens each
    sched, params = make_sched(max_batch=2, max_seq=32, page=4, num_pages=6)
    r1 = sched.submit([5, 7, 11], max_new_tokens=10)
    r2 = sched.submit([3, 1], max_new_tokens=10)
    sched.run_until_done(max_ticks=300)
    assert r1.state == "finished" and r2.state == "finished"
    assert sched.metrics()["preemptions_total"] > 0
    assert r1.output == ref_tokens(params, [5, 7, 11], 10)
    assert r2.output == ref_tokens(params, [3, 1], 10)


def test_stop_token_frees_slot():
    sched, params = make_sched()
    ref = ref_tokens(params, [5, 7, 11], 8)
    stop = ref[2]  # force an early stop at the 3rd generated token
    req = sched.submit([5, 7, 11], max_new_tokens=8, stop_token=stop)
    sched.run_until_done()
    assert req.output == ref[:3]
    assert sched.alloc.free_pages == sched.alloc.num_pages


def test_metrics_surface():
    sched, _ = make_sched()
    sched.submit([1, 2], max_new_tokens=2)
    sched.run_until_done()
    m = sched.metrics()
    assert m["requests_finished"] == 1
    assert m["tokens_generated_total"] == 2
    assert m["ttft_p50"] >= 0
    # per-request mean inter-token gap: the burst-robust ITL stat
    assert m["itl_req_mean_p50"] >= 0
    assert m["kv_pages_free"] == m["kv_pages_total"]


def test_slo_attainment_counters_and_burn_rate():
    """Declared objectives turn latency into pass/fail counters: a
    generous SLO attains everything (burn 0), an impossible one
    violates everything (burn 1), and the trace finish event carries
    the per-request verdict."""
    from butterfly_tpu.obs.trace import Tracer
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(42))
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8)
    engine = ServingEngine(model, params, rt)
    ok = Scheduler(engine, tracer=Tracer(), slo_ttft_s=1e6, slo_itl_s=1e6)
    r = ok.submit([5, 7, 11], max_new_tokens=4)
    ok.run_until_done()
    m = ok.metrics()
    assert m["slo_ttft_ok_total"] == 1 and m["slo_itl_ok_total"] == 1
    assert m["slo_violations_total"] == 0
    assert m["slo_burn_rate"] == 0.0 and m["slo_attainment"] == 1.0
    fin = [e for e in ok.trace.timeline(r.id)["events"]
           if e["name"] == "finish"][0]
    assert fin["slo_ok"] is True and fin["itl_mean_s"] >= 0
    # the typed registry renders the counters + burn gauge on /metrics
    text = ok.registry.render()
    assert "butterfly_slo_ttft_ok_total 1" in text
    assert "butterfly_slo_burn_rate 0" in text

    bad = Scheduler(engine, slo_ttft_s=1e-12, slo_itl_s=1e-12)
    bad.submit([5, 7, 11], max_new_tokens=4)
    bad.run_until_done()
    m = bad.metrics()
    assert m["slo_ttft_ok_total"] == 0
    assert m["slo_violations_total"] == 2  # ttft AND itl missed
    assert m["slo_burn_rate"] == 1.0 and m["slo_attainment"] == 0.0
    assert 'butterfly_slo_violations_total{kind="ttft"} 1' \
        in bad.registry.render()

    # no objective declared -> no accounting, no metrics keys
    off = Scheduler(engine)
    off.submit([5], max_new_tokens=2)
    off.run_until_done()
    assert "slo_burn_rate" not in off.metrics()


def test_streaming_callback_order():
    sched, _ = make_sched()
    seen = []
    req = sched.submit([4, 2], max_new_tokens=5,
                       on_token=lambda r, t: seen.append(t))
    sched.run_until_done()
    assert seen == req.output


def test_oversized_request_rejected_at_submit():
    """A request that could never fit the pool must be rejected up front
    (otherwise it livelocks admission / self-preempts forever)."""
    import pytest
    sched, _ = make_sched(max_batch=2, max_seq=32, page=4, num_pages=2)
    with pytest.raises(ValueError, match="KV pages"):
        sched.submit([1] * 20, max_new_tokens=20)
    # an over-max_seq request is likewise rejected (per-seq page limit)
    with pytest.raises(ValueError, match="KV pages"):
        sched.submit([1] * 30, max_new_tokens=30)
    assert not sched.has_work


def test_cancel_running_request_frees_resources():
    sched, _ = make_sched()
    r1 = sched.submit([5, 7], max_new_tokens=50)
    r2 = sched.submit([3], max_new_tokens=4)
    # a prompt that completed inside a block runs from that block's drain
    for _ in range(3):
        sched.tick()
    assert r1.state == "running" and sched._inflight
    sched.cancel(r1)
    assert r1.state == "cancelled" and r1.slot is None
    sched.run_until_done()
    assert r2.state == "finished"
    assert sched.alloc.free_pages == sched.alloc.num_pages
    assert sched.metrics()["requests_finished"] == 1


def test_chunked_prefill_parity():
    """A prompt far longer than prefill_chunk is prefilled in pieces that
    continue the warm cache — output must still match the whole-prompt
    reference exactly."""
    prompt = list(range(2, 32))  # 30 tokens, chunk=8 -> 4 chunks
    sched, params = make_sched(max_seq=64, prefill_chunk=8)
    req = sched.submit(prompt, max_new_tokens=6)
    sched.run_until_done()
    assert req.output == ref_tokens(params, prompt, 6)


def test_chunked_prefill_interleaves_decode():
    """VERDICT r2 item 3: a long admission must not head-of-line-block a
    decoding request — its inter-token gap stays at one tick per chunk."""
    sched, params = make_sched(max_batch=2, max_seq=64, prefill_chunk=4,
                               inflight_blocks=1)  # per-tick drain cadence
    r1 = sched.submit([5, 7, 11], max_new_tokens=20)
    sched.tick()
    sched.tick()  # second tick drains the first token + first decode step
    assert r1.state == "running" and len(r1.output) >= 1
    long_prompt = list(range(1, 17))  # 16 tokens = 4 chunks of 4
    r2 = sched.submit(long_prompt, max_new_tokens=4)
    gaps = []
    while r2.t_first_token is None:
        before = len(r1.output)
        sched.tick()
        gaps.append(len(r1.output) - before)
    # r2's prompt took multiple ticks to admit...
    assert len(gaps) >= 4
    # ...and r1 kept emitting exactly one token on EVERY one of them.
    assert all(g == 1 for g in gaps)
    sched.run_until_done()
    assert r1.output == ref_tokens(params, [5, 7, 11], 20)
    assert r2.output == ref_tokens(params, long_prompt, 4)


def test_cancel_mid_prefill_frees_resources():
    sched, _ = make_sched(max_batch=1, prefill_chunk=4)
    r1 = sched.submit(list(range(1, 17)), max_new_tokens=8)
    r2 = sched.submit([3], max_new_tokens=2)
    sched.tick()
    assert r1.state == "prefilling" and 0 < r1.prefilled < 16
    sched.cancel(r1)
    assert r1.state == "cancelled" and r1.slot is None
    sched.run_until_done()
    assert r2.state == "finished"
    assert sched.alloc.free_pages == sched.alloc.num_pages


def test_decode_steps_per_tick():
    # inflight_blocks=1: the synchronous drain-every-tick cadence this
    # test documents (the pipelined cadence has its own tests below)
    sched, params = make_sched(decode_steps_per_tick=3, inflight_blocks=1)
    req = sched.submit([5, 7, 11], max_new_tokens=10)
    # the block's first step takes the prompt in and samples the first
    # token on the device, its other two decode; everything drains in
    # one stacked fetch at the NEXT tick's start (scheduler._inflight
    # docs), so the host sees 1+2 tokens one tick later
    sched.tick()
    assert len(req.output) == 0
    sched.tick()  # drains first + 2 decode steps, dispatches 3 more
    assert len(req.output) == 3
    sched.tick()
    assert len(req.output) == 6
    sched.run_until_done()
    assert req.output == ref_tokens(params, [5, 7, 11], 10)


def test_request_sized_to_page_cap_completes():
    """r5 regression: a request whose worst case exactly fills the
    per-seq page cap (accepted by submit) must finish — the pipelined
    page-growth target is clamped to the request's lifetime maximum,
    otherwise it self-preempts forever chasing in-flight slack pages."""
    sched, params = make_sched(max_batch=1, max_seq=32, page=8)
    prompt = list(range(1, 25))  # 24 + 8 = 32 = max_pages_per_seq * page
    req = sched.submit(prompt, max_new_tokens=8)
    sched.run_until_done(max_ticks=200)
    assert req.state == "finished"
    assert req.output == ref_tokens(params, prompt, 8)


def test_cancel_waiting_request():
    sched, _ = make_sched(max_batch=1)
    r1 = sched.submit([5], max_new_tokens=30)
    r2 = sched.submit([6], max_new_tokens=3)
    sched.tick()
    sched.cancel(r2)  # still waiting
    assert r2.state == "cancelled"
    sched.run_until_done()
    assert r1.state == "finished" and len(r1.output) == 30


@pytest.mark.parametrize("stamps, order", [
    ((3.0, 1.0, 2.0), [1, 2, 0]),          # won the lock out of turn
    ((1.0, 2.0, 3.0), [0, 1, 2]),          # in turn: appended
    ((None, 1.0, None), [0, 1, 2]),        # no stamp is passed by nobody,
    ((2.0, None, 1.0), [0, 1, 2]),         # and passes nobody
    ((2.0, 2.0, 1.0), [2, 0, 1]),          # a tie keeps the lock's order
])
def test_waiting_queue_is_by_time_received(stamps, order):
    """A server says when each request came in (`t_recv`); the queue is
    first come, first served by that, not by who won the serving lock."""
    sched, _ = make_sched()
    reqs = [sched.submit([5, 7], max_new_tokens=2, t_recv=t) for t in stamps]
    assert [reqs.index(r) for r in sched.waiting] == order


def test_inter_token_latency_metrics():
    """ITL percentiles appear once any request generates >= 2 tokens,
    and every non-first token contributes exactly one gap sample."""
    sched, _ = make_sched()
    r1 = sched.submit([5, 7, 11], max_new_tokens=6)
    r2 = sched.submit([3, 1], max_new_tokens=4)
    sched.run_until_done()
    m = sched.metrics()
    # raw-gap percentiles live ONLY under the _tick_burst suffix
    # (ISSUE 10: the bare itl_p50/itl_p95 keys published a degenerate
    # 0.0 median under pipelined dispatch and were dropped)
    assert {"itl_p50_tick_burst", "itl_p95_tick_burst",
            "itl_max_tick_burst"} <= set(m)
    assert not {"itl_p50", "itl_p95", "itl_max"} & set(m)
    assert m["itl_p50_tick_burst"] >= 0
    assert m["itl_max_tick_burst"] >= m["itl_p50_tick_burst"]
    # gaps = (6-1) + (4-1)
    assert len(sched._itls) == (len(r1.output) - 1) + (len(r2.output) - 1)


def test_speculative_scheduler_greedy_parity():
    """VERDICT r4 item 7: scheduler-level speculative decoding — per-slot
    ngram drafts + one batched verify — is token-for-token identical to
    the plain scheduler, across slots with different prompts/lengths."""
    sched, params = make_sched(max_batch=4, max_seq=64,
                               speculative_gamma=3)
    ref, _ = make_sched(max_batch=4, max_seq=64)
    prompts = [[5, 7, 11], [3, 3, 3, 3, 3], [2], list(range(1, 9))]
    want = [ref.submit(p, max_new_tokens=12) for p in prompts]
    ref.run_until_done()
    got = [sched.submit(p, max_new_tokens=12) for p in prompts]
    sched.run_until_done()
    assert [r.output for r in got] == [r.output for r in want]
    assert sched.metrics()["spec_forwards_total"] > 0


def test_speculative_scheduler_accepts_drafts():
    """On a looping continuation (prompt seeded with the model's own
    greedy output), drafts must hit: fewer verify forwards than tokens."""
    ref, params = make_sched(max_batch=2, max_seq=128)
    r0 = ref.submit([5, 7, 11], max_new_tokens=24)
    ref.run_until_done()
    prompt = [5, 7, 11] + r0.output

    ref2, _ = make_sched(max_batch=2, max_seq=128)
    want = ref2.submit(prompt, max_new_tokens=16)
    ref2.run_until_done()

    sched, _ = make_sched(max_batch=2, max_seq=128, speculative_gamma=4)
    got = sched.submit(prompt, max_new_tokens=16)
    sched.run_until_done()
    assert got.output == want.output
    m = sched.metrics()
    assert m["spec_drafts_accepted_total"] > 0
    # >1 tokens per verify forward on the repetitive continuation
    assert m["tokens_generated_total"] > m["spec_forwards_total"]


def test_speculative_scheduler_sampling_supported():
    """The greedy-only guard is gone: temperature > 0 requests ride the
    spec block through the rejection-sampling correction — full budget
    generated, same-seed reproducible (distribution exactness is
    pinned in tests/test_spec_sampling.py)."""
    outs = []
    for _ in range(2):
        sched, _ = make_sched(max_batch=2, max_seq=64,
                              speculative_gamma=2, seed=7)
        r1 = sched.submit([5, 7], max_new_tokens=8, temperature=0.8)
        r2 = sched.submit([3, 1, 4], max_new_tokens=6)  # greedy slotmate
        sched.run_until_done()
        assert len(r1.output) == 8 and len(r2.output) == 6
        outs.append((r1.output, r2.output))
    assert outs[0] == outs[1]  # same scheduler seed -> same draws


_GRID_PROMPTS = [[5, 7, 11], [3, 3, 3, 3, 3], [2], list(range(1, 9))]


@pytest.fixture(scope="module")
def grid_want():
    """What the same scheduler emits WITHOUT speculation: the oracle of
    every greedy speculative run."""
    ref, _ = make_sched(max_batch=4, max_seq=64)
    want = [ref.submit(p, max_new_tokens=12) for p in _GRID_PROMPTS]
    ref.run_until_done()
    return [r.output for r in want]


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("k", [1, 8])
def test_speculative_parity_grid(grid_want, k, depth):
    """Acceptance criterion: greedy spec-on output is byte-identical to
    spec-off greedy serving at decode_steps_per_tick 1 and 8, at
    dispatch-ahead depth 1 and 2."""
    sched, _ = make_sched(max_batch=4, max_seq=64, speculative_gamma=3,
                          decode_steps_per_tick=k, inflight_blocks=depth)
    got = [sched.submit(p, max_new_tokens=12) for p in _GRID_PROMPTS]
    sched.run_until_done()
    assert [r.output for r in got] == grid_want


@pytest.mark.parametrize("field", ["mixed_dispatch", "scheduler",
                                   "prefill_flash_warm"])
def test_the_options_of_the_paths_that_went_are_no_fields(field):
    """One dispatch path: the options that selected another are gone
    from RuntimeConfig, and passing one fails where it is passed."""
    with pytest.raises(TypeError, match=field):
        RuntimeConfig(**{field: True})
    with pytest.raises(TypeError, match=field):
        RuntimeConfig().replace(**{field: True})


def test_speculative_pipelines_without_per_round_barriers():
    """The old implementation drained EVERY spec round to draft on the
    host; the block path must keep spec rounds in flight: at depth 2 a
    steady-state run reaches inflight depth 2 and pays far fewer full
    barriers than verify rounds."""
    sched, _ = make_sched(max_batch=2, max_seq=128, speculative_gamma=3,
                          inflight_blocks=2)
    reqs = [sched.submit([5, 7, 11], max_new_tokens=40),
            sched.submit([3, 1], max_new_tokens=40)]
    seen_depth = 0
    while sched.has_work:
        sched.tick()
        seen_depth = max(seen_depth, len(sched._inflight))
    assert all(r.state == "finished" for r in reqs)
    m = sched.metrics()
    assert seen_depth == 2  # spec blocks actually chained in flight
    assert m["spec_forwards_total"] > 0
    # membership changes (admission, finishes) barrier; steady-state
    # rounds must not — far fewer barriers than verify rounds
    assert m["drain_barriers_total"] < m["spec_forwards_total"] / 2
    assert m["spec_tokens_per_forward"] >= 1.0


def test_speculative_parity_under_preemption_pressure():
    """Spec mode + tiny page pool: preemption (drain, hist rebuild on
    readmission) must preserve exact greedy parity — the device-side
    history is reseeded from host truth at every (re)admission."""
    ref, params = make_sched(max_batch=2, max_seq=32, page=4, num_pages=6)
    w1 = ref.submit([5, 7, 11], max_new_tokens=10)
    w2 = ref.submit([2, 4], max_new_tokens=10)
    ref.run_until_done()
    sched, _ = make_sched(max_batch=2, max_seq=32, page=4, num_pages=6,
                          speculative_gamma=3)
    r1 = sched.submit([5, 7, 11], max_new_tokens=10)
    r2 = sched.submit([2, 4], max_new_tokens=10)
    sched.run_until_done()
    assert r1.output == w1.output
    assert r2.output == w2.output


def test_speculative_per_request_opt_out():
    """A request submitted with speculative=False rides the spec block
    but ignores drafts: its greedy output still matches the plain
    reference exactly (one exact sample per verify round)."""
    sched, params = make_sched(max_batch=2, max_seq=64,
                               speculative_gamma=3)
    r1 = sched.submit([5, 7, 11], max_new_tokens=10, speculative=False)
    r2 = sched.submit([3, 1], max_new_tokens=8)
    sched.run_until_done()
    assert r1.output == ref_tokens(params, [5, 7, 11], 10)
    assert r2.output == ref_tokens(params, [3, 1], 8)


def test_speculative_scheduler_stop_token():
    ref, _ = make_sched(max_batch=2, max_seq=64)
    base = ref.submit([5, 7, 11], max_new_tokens=12)
    ref.run_until_done()
    stop = base.output[6]
    ref2, _ = make_sched(max_batch=2, max_seq=64)
    want = ref2.submit([5, 7, 11], max_new_tokens=12, stop_token=stop)
    ref2.run_until_done()
    sched, _ = make_sched(max_batch=2, max_seq=64, speculative_gamma=3)
    got = sched.submit([5, 7, 11], max_new_tokens=12, stop_token=stop)
    sched.run_until_done()
    assert got.output == want.output


# -- fused block (engine._packed_scan; ISSUE 3) -------------------------------


def test_fused_block_greedy_parity():
    """Tentpole contract: decode_steps_per_tick=8 — one jitted scan per
    tick with on-device RNG/EOS/budget masking — is token-for-token
    identical to single-step decode at temperature 0, across slots with
    different prompts and lengths (the sched/serving_mesh parity
    contract extended to the fused block)."""
    ref, params = make_sched(max_batch=4, max_seq=64)
    fused, _ = make_sched(max_batch=4, max_seq=64, decode_steps_per_tick=8)
    prompts = [[5, 7, 11], [3, 3, 3, 3, 3], [2], list(range(1, 9))]
    want = [ref.submit(p, max_new_tokens=12) for p in prompts]
    ref.run_until_done()
    got = [fused.submit(p, max_new_tokens=12) for p in prompts]
    fused.run_until_done()
    assert [r.output for r in got] == [r.output for r in want]
    # and the single-step path itself still matches the offline engine
    assert want[0].output == ref_tokens(params, prompts[0], 12)


def test_fused_block_seeded_sampling_reproducible():
    """temperature>0 through the fused block: per-step keys are derived
    on device (fold_in of one per-block key), so the same seed and
    config must reproduce the same tokens run-to-run."""
    outs = []
    for _ in range(2):
        sched, _ = make_sched(max_batch=2, max_seq=64, seed=7,
                              decode_steps_per_tick=4)
        r1 = sched.submit([5, 7, 11], max_new_tokens=10, temperature=0.8)
        r2 = sched.submit([3, 1], max_new_tokens=8, temperature=1.3)
        sched.run_until_done()
        outs.append((list(r1.output), list(r2.output)))
    assert outs[0] == outs[1]
    assert len(outs[0][0]) == 10 and len(outs[0][1]) == 8


def test_fused_block_eos_mid_block():
    """A stop token sampled mid-block kills the slot ON DEVICE: the host
    sees no post-EOS tokens, and the slot's device length froze at the
    written-token count (no post-EOS page growth) instead of advancing
    through the remaining scan steps."""
    ref, _ = make_sched(max_batch=2, max_seq=64)
    base = ref.submit([5, 7, 11], max_new_tokens=12)
    ref.run_until_done()
    stop = base.output[2]  # EOS lands at the 3rd generated token

    sched, _ = make_sched(max_batch=2, max_seq=64, decode_steps_per_tick=8)
    req = sched.submit([5, 7, 11], max_new_tokens=12, stop_token=stop)
    sched.tick()  # admit + prefill + first sample + one 8-step block
    slot = req.slot
    # The block has run past the EOS position on device. Written K/V:
    # 3 prompt tokens + generated tokens 1 and 2; the EOS (3rd) is
    # sampled but never consumed, and every later step was masked dead
    # — the device length count froze, writes landed on the null page
    # (window-off) or stayed unstaged (kv_write_combine: the flushed
    # pool length plus the staged window count is the same total).
    staged = 0
    if sched.engine._win_len is not None:
        staged = int(np.asarray(sched.engine._win_len)[slot])
    total = int(np.asarray(sched.engine.cache.lengths)[slot]) + staged
    assert total == 3 + 2
    sched.run_until_done()
    assert req.output == base.output[:3]
    assert req.state == "finished"
    assert sched.alloc.free_pages == sched.alloc.num_pages


# -- a burst of prompts rides the blocks' chunks ------------------------------


def test_batched_prefill_budget_and_carry():
    """A burst whose prompts exceed a step's chunks: members wait for
    a chunk, partially-prefilled members carry across steps and ticks,
    and every
    member still matches the reference token-for-token."""
    gang, params = make_sched(max_batch=3, max_seq=64, prefill_chunk=8)
    prompts = [list(range(2, 14)), list(range(3, 9)), [4, 2]]
    got = [gang.submit(p, max_new_tokens=5) for p in prompts]
    gang.run_until_done()
    for p, r in zip(prompts, got):
        assert r.output == ref_tokens(params, p, 5)


def test_preempt_partially_prefilled_group_member():
    """Page pressure can evict a gang member that is only partially
    prefilled: pages free, it requeues (prefilled reset), leaves the
    group, and still completes correctly after readmission."""
    sched, params = make_sched(max_batch=2, max_seq=64, prefill_chunk=4)
    r1 = sched.submit([5, 7, 11], max_new_tokens=8)
    sched.tick()
    sched.tick()
    long_prompt = list(range(1, 17))
    r2 = sched.submit(long_prompt, max_new_tokens=4)
    sched.tick()
    assert r2.state == "prefilling" and 0 < r2.prefilled < 16
    assert r2 in sched._prefill_group
    sched._preempt(r2)  # what _ensure_or_preempt does to the youngest
    assert r2.state == "waiting" and r2.slot is None and r2.prefilled == 0
    assert r2 not in sched._prefill_group
    sched.run_until_done()
    assert r1.output == ref_tokens(params, [5, 7, 11], 8)
    assert r2.output == ref_tokens(params, long_prompt, 4)
    assert sched.metrics()["preemptions_total"] == 1
    assert sched.alloc.free_pages == sched.alloc.num_pages


def test_prefill_group_member_is_preemption_victim():
    """_ensure_or_preempt's victim pool includes mid-prefill gang
    members: the youngest live request loses page pressure even if it
    is still prefilling (it cannot starve an older decoding request)."""
    sched, params = make_sched(max_batch=2, max_seq=32, page=4, num_pages=6,
                               prefill_chunk=4)
    r1 = sched.submit([5, 7, 11], max_new_tokens=12)
    sched.tick()
    # r2's admission takes 4 of the 6 pages and holds them across
    # several prefill ticks; r1's decode growth must be able to evict it
    r2 = sched.submit(list(range(1, 13)), max_new_tokens=4)
    sched.run_until_done(max_ticks=300)
    assert r1.state == "finished" and r2.state == "finished"
    assert sched.metrics()["preemptions_total"] > 0
    assert r1.output == ref_tokens(params, [5, 7, 11], 12)
    assert r2.output == ref_tokens(params, list(range(1, 13)), 4)


# -- pipelined dispatch-ahead serving (ISSUE 5) -----------------------------


def test_pipelined_greedy_parity_vs_synchronous():
    """Tentpole contract: inflight_blocks=2 (dispatch-ahead — block t+1
    chained on block t's device carry before t is drained) is token-
    for-token identical to the synchronous inflight_blocks=1 loop at
    temperature 0, across slots with different prompts and lengths."""
    sync, params = make_sched(max_batch=4, max_seq=64, inflight_blocks=1)
    pipe, _ = make_sched(max_batch=4, max_seq=64, inflight_blocks=2)
    prompts = [[5, 7, 11], [3, 3, 3, 3, 3], [2], list(range(1, 9))]
    want = [sync.submit(p, max_new_tokens=12) for p in prompts]
    sync.run_until_done()
    got = [pipe.submit(p, max_new_tokens=12) for p in prompts]
    pipe.run_until_done()
    assert [r.output for r in got] == [r.output for r in want]
    # and the synchronous path itself still matches the offline engine
    assert want[0].output == ref_tokens(params, prompts[0], 12)


def test_pipelined_greedy_parity_fused_k8():
    """Dispatch-ahead composed with the fused block: two k=8 scans in
    flight produce exactly the synchronous path's tokens."""
    sync, _ = make_sched(max_batch=4, max_seq=64, inflight_blocks=1,
                         decode_steps_per_tick=8)
    pipe, _ = make_sched(max_batch=4, max_seq=64, inflight_blocks=2,
                         decode_steps_per_tick=8)
    prompts = [[5, 7, 11], [3, 3, 3, 3, 3], [2], list(range(1, 9))]
    want = [sync.submit(p, max_new_tokens=20) for p in prompts]
    sync.run_until_done()
    got = [pipe.submit(p, max_new_tokens=20) for p in prompts]
    pipe.run_until_done()
    assert [r.output for r in got] == [r.output for r in want]


def test_pipelined_lazy_drain_cadence():
    """Steady state at inflight_blocks=2: block t+1 is dispatched while
    block t is still undrained; the host fetches only once the queue is
    full (the dispatch-ahead overlap, made visible by token timing)."""
    sched, params = make_sched(decode_steps_per_tick=2, inflight_blocks=2)
    req = sched.submit([5, 7, 11], max_new_tokens=12)
    sched.tick()  # admit + dispatch block 1 (prompt, first token, a step)
    assert len(req.output) == 0 and len(sched._inflight) == 1
    sched.tick()  # queue not full: block 2 chains, still nothing drained
    assert len(req.output) == 0 and len(sched._inflight) == 2
    sched.tick()  # queue full: drain block 1, dispatch block 3
    assert len(req.output) == 2
    assert sched.metrics()["inflight_depth"] == 2
    sched.run_until_done()
    assert req.output == ref_tokens(params, [5, 7, 11], 12)


def test_pipelined_admission_forces_no_barrier():
    """A waiter with a free slot is admitted with every block still in
    flight: nothing drains for it, its prompt rides the tick's block,
    and no barrier of any cause is counted."""
    sched, params = make_sched(max_batch=2, inflight_blocks=2)
    r1 = sched.submit([5, 7, 11], max_new_tokens=16)
    sched.tick()
    sched.tick()
    assert len(sched._inflight) == 2 and len(r1.output) == 0
    newer = sched._inflight[1]
    r2 = sched.submit([3, 1], max_new_tokens=6)
    sched.tick()
    assert r2.state == "prefilling" and r2.slot is not None
    # one lazy drain, one dispatch: the newer block is still in flight
    assert len(sched._inflight) == 2 and sched._inflight[0] is newer
    assert sched.barrier_causes() == {}
    sched.run_until_done()
    assert "admission" not in sched.barrier_causes()
    assert r1.output == ref_tokens(params, [5, 7, 11], 16)
    assert r2.output == ref_tokens(params, [3, 1], 6)


def test_pipelined_cancel_discards_stale_blocks():
    """cancel() mid-pipeline: a full drain barrier runs first (pages
    with outstanding device writes are never reclaimed), the cancelled
    request gains no tokens afterwards, and the surviving request still
    matches its reference."""
    sched, params = make_sched(max_batch=2, inflight_blocks=2)
    r1 = sched.submit([5, 7, 11], max_new_tokens=30)
    r2 = sched.submit([3, 1], max_new_tokens=8)
    sched.tick()
    sched.tick()
    assert len(sched._inflight) == 2
    sched.cancel(r1)
    assert r1.state == "cancelled" and r1.slot is None
    assert not sched._inflight  # the barrier consumed every block
    n_after = len(r1.output)
    sched.run_until_done()
    assert len(r1.output) == n_after  # no tokens post-cancel
    assert r2.output == ref_tokens(params, [3, 1], 8)
    assert sched.alloc.free_pages == sched.alloc.num_pages


def test_page_pressure_drains_before_preempting():
    """_ensure_or_preempt under pressure with blocks in flight: the
    FULL drain barrier runs before any victim is chosen — preemption
    must never reclaim pages a dispatched block still writes to."""
    sched, _ = make_sched(max_batch=2, max_seq=32, page=4, num_pages=6,
                          inflight_blocks=2, prefill_chunk=4)
    r1 = sched.submit([5, 7, 11], max_new_tokens=20)
    r2 = sched.submit([3, 1], max_new_tokens=20)
    sched.tick()
    sched.tick()
    assert sched._inflight
    # the whole pool for r1: cannot fit beside r2 -> barrier, then the
    # youngest (r2) is preempted
    sched._ensure_or_preempt(r1, 24)
    assert not sched._inflight
    assert r2.state == "waiting" and r2.preemptions == 1


def test_pipelined_parity_under_page_pressure():
    """Tiny pool at inflight_blocks=2: the widened (inflight+1)*k+1
    preallocation horizon falls back to drain barriers and recompute
    preemption under pressure, and both requests still match their
    references token-for-token."""
    sched, params = make_sched(max_batch=2, max_seq=32, page=4, num_pages=6,
                               inflight_blocks=2, decode_steps_per_tick=2)
    r1 = sched.submit([5, 7, 11], max_new_tokens=10)
    r2 = sched.submit([3, 1], max_new_tokens=10)
    sched.run_until_done(max_ticks=500)
    assert r1.state == "finished" and r2.state == "finished"
    assert sched.metrics()["preemptions_total"] > 0
    assert r1.output == ref_tokens(params, [5, 7, 11], 10)
    assert r2.output == ref_tokens(params, [3, 1], 10)


def test_pipelined_metrics_surface():
    """The dispatch-ahead observability contract: the inflight_depth
    gauge populates once blocks pipeline, and the starvation clock feeds
    the device_bubble_seconds histogram once a launch, what the tick
    records carry as `starved_s`."""
    sched, _ = make_sched(inflight_blocks=2)
    sched.submit([5, 7, 11], max_new_tokens=8)
    sched.run_until_done()
    m = sched.metrics()
    assert "inflight_depth" in m
    assert "device_bubble_p50" not in m and "device_bubble_p95" not in m
    ticks = sched.ticklog.dump()["ticks"]
    launched = [t for t in ticks if t["program"] is not None]
    assert all(t["starved_s"] >= 0.0 for t in launched)
    h = sched.registry.get("device_bubble_seconds")
    assert h.count >= len(launched) >= 1
    assert h.sum == pytest.approx(sum(t["starved_s"] for t in launched))
    assert sched.registry.get("inflight_depth") is not None


# -- tracing + instrument wiring (obs/trace.py, obs/registry.py) ------------

def test_scheduler_trace_timeline():
    """Every phase of a request's life shows up as span events with
    monotonic timestamps; disabled tracing (the default) records
    nothing and leaves sched.trace None."""
    from butterfly_tpu.obs.trace import Tracer
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(42))
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8)
    tr = Tracer()
    sched = Scheduler(ServingEngine(model, params, rt), tracer=tr)
    req = sched.submit([5, 7, 11], max_new_tokens=4,
                       request_id="trace-me")
    sched.run_until_done()
    tl = tr.timeline(req.id)
    assert tl["request_id"] == "trace-me"
    names = [e["name"] for e in tl["events"]]
    for needed in ("submit", "admit", "prefill_done",
                   "first_token", "finish"):
        assert needed in names
    ts = [e["t"] for e in tl["events"]]
    assert ts == sorted(ts)
    fin = tl["events"][-1]
    assert fin["name"] == "finish" and fin["tokens"] == 4
    # the global ring saw the engine's dispatches; what each tick held
    # (batch, waiting, inflight, generated, spec) is in its tick record
    globs = [e["name"] for e in tr.global_events()]
    assert "engine.table_sync" in globs
    ticks = sched.ticklog.dump()["ticks"]
    assert sum(t["generated"] for t in ticks) == 4
    assert any(t["batch"] == 1 for t in ticks)
    assert all(t["waiting"] == 0 and t["spec"] is False
               and t["inflight"] <= 2 for t in ticks)

    plain, _ = make_sched()
    assert plain.trace is None  # default: no tracer, bare None check


def test_scheduler_trace_preemption_events():
    from butterfly_tpu.obs.trace import Tracer
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(42))
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=32, page_size=4,
                       num_pages=6)
    tr = Tracer()
    sched = Scheduler(ServingEngine(model, params, rt), tracer=tr)
    r1 = sched.submit([5, 7, 11], max_new_tokens=10)
    r2 = sched.submit([3, 1], max_new_tokens=10)
    sched.run_until_done(max_ticks=400)
    assert sched.metrics()["preemptions_total"] > 0
    preempted = r1 if r1.preemptions else r2
    names = [e["name"] for e in tr.timeline(preempted.id)["events"]]
    assert "preempt" in names
    # readmission after the preempt is traced as a resumed admit
    i = names.index("preempt")
    admits = [e for e in tr.timeline(preempted.id)["events"][i:]
              if e["name"] == "admit"]
    assert admits and admits[0]["resumed"] is True


def test_registry_histograms_observe_through_scheduler():
    sched, _ = make_sched()
    sched.submit([1, 2, 3], max_new_tokens=3)
    sched.submit([4, 5], max_new_tokens=3)
    sched.run_until_done()
    reg = sched.registry
    assert reg.get("ttft_seconds").count == 2
    assert reg.get("queue_wait_seconds").count == 2
    assert reg.get("prefill_tokens").count == 2
    assert reg.get("itl_req_mean_seconds").count == 2
    assert reg.get("batch_size").count >= 1
    assert reg.get("requests_total").value == 2
    # legacy dict view still mirrors the registry counters
    m = sched.metrics()
    assert m["requests_total"] == 2 and m["requests_finished"] == 2


# ---------------------------------------------------------------------------
# overload protection (ISSUE 8): deadlines, SLO-aware shedding, priorities
# ---------------------------------------------------------------------------

def test_deadline_expired_while_waiting():
    """An already-expired waiter is scrubbed from the queue at the next
    tick: no slot, no prefill, state 'expired', counted under
    where='waiting', and its on_finish waiter is answered."""
    import time
    sched, _ = make_sched()
    fired = []
    live = sched.submit([1, 2], max_new_tokens=2)
    dead = sched.submit([3, 4], max_new_tokens=2,
                        deadline_s=time.monotonic() - 0.01,
                        on_finish=lambda r: fired.append(r.id))
    sched.run_until_done()
    assert dead.state == "expired" and dead.expired_where == "waiting"
    assert dead.slot is None and dead.output == []
    assert fired == [dead.id]
    assert live.state == "finished" and len(live.output) == 2
    m = sched.metrics()
    assert m["deadline_expired_total"] == 1
    assert 'butterfly_deadline_expired_total{where="waiting"} 1' \
        in sched.registry.render()


def test_deadline_expired_while_running():
    """The acceptance hazard: a deadline firing mid-generation must
    cancel the request out of its decode slot at the next drain
    barrier — it never consumes a decode dispatch after expiry — while
    a co-running request decodes on unharmed."""
    import time
    sched, params = make_sched(max_batch=2)
    doomed = sched.submit([5, 7, 11], max_new_tokens=50)
    ok = sched.submit([3, 1], max_new_tokens=8)
    for _ in range(3):
        sched.tick()
    assert doomed.state == "running" and sched._inflight
    doomed.deadline_s = time.monotonic() - 1e-3  # fires before next tick
    sched.tick()
    assert doomed.state == "expired" and doomed.expired_where == "running"
    assert doomed.slot is None
    n_at_expiry = len(doomed.output)
    sched.run_until_done()
    assert len(doomed.output) == n_at_expiry  # zero decode steps after
    assert ok.state == "finished"
    assert ok.output == ref_tokens(params, [3, 1], 8)
    assert sched.metrics()["deadline_expired_total"] == 1
    # the freed slot + pages are fully reclaimed
    assert sched.alloc.free_pages == sched.alloc.num_pages


def test_shed_batch_before_interactive():
    """SLO-aware admission sheds by priority class: with a predicted
    TTFT between the objective and interactive_slack x it, batch is
    turned away (429 + computed Retry-After) while interactive still
    admits. Without evidence or without a declared objective, nothing
    sheds."""
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(42))
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8)
    engine = ServingEngine(model, params, rt)
    sched = Scheduler(engine, slo_ttft_s=0.5)
    # no latency evidence yet: a cold server never sheds blind
    assert sched.shed_decision(32, "batch") is None
    # seed the rolling ITL window + a queue: predict_ttft becomes
    # rounds * mean_itl with rounds = ceil(backlog/prefill_chunk)
    # + len(waiting)  ->  0.1 * (1 + 6) = 0.7s for a 32-token prompt
    sched._itl_means.extend([0.1] * 8)
    for _ in range(6):
        sched.submit([1] * 30, max_new_tokens=2)
    pred = sched.predict_ttft(32)
    assert 0.5 < pred <= 1.0, pred  # between slo and 2x slo
    retry = sched.shed_decision(32, "batch")
    assert retry is not None and retry >= 1.0
    assert sched.shed_decision(32, "interactive") is None
    m = sched.metrics()
    assert m["shed_total"] == 1
    assert 'butterfly_shed_total{priority="batch"} 1' \
        in sched.registry.render()
    # no declared objective -> the same pressure never sheds
    off = Scheduler(engine)
    off._itl_means.extend([0.1] * 8)
    for _ in range(6):
        off.submit([1] * 30, max_new_tokens=2)
    assert off.shed_decision(32, "batch") is None
    sched.run_until_done()
    off.run_until_done()


def test_preempt_prefers_batch_victim():
    """Under page pressure the preemption victim is batch-first, then
    youngest: an OLDER batch request recomputes so a younger
    interactive one keeps its pages (both still finish correctly)."""
    sched, params = make_sched(max_batch=2, max_seq=32, page=4,
                               num_pages=6)
    batch = sched.submit([5, 7, 11], max_new_tokens=10, priority="batch")
    sched.tick()
    inter = sched.submit([3, 1], max_new_tokens=10)  # younger, interactive
    sched.run_until_done(max_ticks=300)
    assert batch.state == "finished" and inter.state == "finished"
    assert batch.preemptions > 0       # older but batch: the victim
    assert inter.preemptions == 0
    assert batch.output == ref_tokens(params, [5, 7, 11], 10)
    assert inter.output == ref_tokens(params, [3, 1], 10)


def test_submit_rejects_unknown_priority():
    import pytest
    sched, _ = make_sched()
    with pytest.raises(ValueError, match="priority"):
        sched.submit([1], max_new_tokens=2, priority="best-effort")


# -- write-combined KV decode window (ISSUE 12) -----------------------------


def test_kv_window_off_matches_on():
    """Core on/off contract: kv_write_combine stages K/V in the window
    and flushes once per drain, yet greedy outputs are byte-identical
    to the per-token write path — and only the window mode populates
    the flush instruments."""
    prompts = [[5, 7, 11], [3, 1]]
    on, _ = make_sched(max_batch=2)
    off, _ = make_sched(max_batch=2, kv_write_combine=False)
    a = [on.submit(p, max_new_tokens=10) for p in prompts]
    b = [off.submit(p, max_new_tokens=10) for p in prompts]
    on.run_until_done()
    off.run_until_done()
    assert [r.output for r in a] == [r.output for r in b]
    m_on, m_off = on.metrics(), off.metrics()
    assert m_on["kv_window_tokens_flushed_total"] > 0
    assert "kv_flush_p50" in m_on and "kv_flush_p95" in m_on
    assert "kv_window_tokens_flushed_total" not in m_off
    # every prompt token (a chunk stages through the window too) and
    # every generated-and-consumed token was flushed exactly once; the
    # final sampled token of each request is never written (decode
    # contract), so flushed == prompts + generated - one per finished
    # request
    assert m_on["kv_window_tokens_flushed_total"] == \
        sum(map(len, prompts)) + m_on["tokens_generated_total"] \
        - len(prompts)


def test_kv_window_greedy_parity_grid():
    """Acceptance grid: window on/off x decode_steps_per_tick 1/8 x
    dispatch-ahead depth 1/2, all byte-identical to the contiguous
    reference."""
    prompts = [[5, 7, 11], [3, 3, 3, 3, 3], [2]]
    ref, _ = make_sched(max_batch=4)
    want = [ref.submit(p, max_new_tokens=12) for p in prompts]
    ref.run_until_done()
    for wc in (True, False):
        for k in (1, 8):
            for depth in (1, 2):
                sched, _ = make_sched(max_batch=4, kv_write_combine=wc,
                                      decode_steps_per_tick=k,
                                      inflight_blocks=depth)
                got = [sched.submit(p, max_new_tokens=12) for p in prompts]
                sched.run_until_done()
                assert [r.output for r in got] == \
                    [r.output for r in want], (wc, k, depth)


def test_kv_window_seeded_sampling_parity():
    """temperature > 0 with a pinned scheduler seed: the windowed path
    derives the same per-step fold_in keys from the same block
    dispatches, so sampled streams match window-off exactly."""
    for k in (1, 8):
        outs = {}
        for wc in (True, False):
            sched, _ = make_sched(max_batch=2, seed=7, kv_write_combine=wc,
                                  decode_steps_per_tick=k)
            r1 = sched.submit([5, 7, 11], max_new_tokens=10,
                              temperature=0.8)
            r2 = sched.submit([3, 1], max_new_tokens=10, temperature=1.3)
            sched.run_until_done()
            outs[wc] = (r1.output, r2.output)
        assert outs[True] == outs[False], k


_WINDOW_PROMPTS = [[5, 7, 11], [3, 1]]


@pytest.fixture(scope="module")
def window_want():
    ref, _ = make_sched(max_batch=2)
    want = [ref.submit(p, max_new_tokens=12) for p in _WINDOW_PROMPTS]
    ref.run_until_done()
    return [r.output for r in want]


@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("wc", [True, False], ids=["window", "pool"])
def test_kv_window_spec_parity_grid(window_want, wc, k):
    """Speculative serving window on/off x rounds-per-tick 1/8: the
    window's accepted-count advance is the exact analogue of the spec
    scan's cache-length rollback, byte-identical greedy output."""
    sched, _ = make_sched(max_batch=2, speculative_gamma=3,
                          kv_write_combine=wc, decode_steps_per_tick=k)
    got = [sched.submit(p, max_new_tokens=12) for p in _WINDOW_PROMPTS]
    sched.run_until_done()
    assert [r.output for r in got] == window_want


def test_kv_window_preempt_mid_block_flush_before_reclaim():
    """Preemption under page pressure with staged window entries: the
    drain barrier's flush lands every staged K/V byte in the pool
    BEFORE any victim page is reclaimed, so recompute-preempted and
    surviving requests both stay byte-correct and the flush counter
    advances."""
    sched, params = make_sched(max_batch=2, max_seq=32, page=4,
                               num_pages=6, inflight_blocks=2,
                               decode_steps_per_tick=2)
    r1 = sched.submit([5, 7, 11], max_new_tokens=10)
    r2 = sched.submit([3, 1], max_new_tokens=10)
    sched.run_until_done(max_ticks=500)
    m = sched.metrics()
    assert m["preemptions_total"] > 0
    assert m["kv_window_tokens_flushed_total"] > 0
    assert not sched.engine._win_dirty
    assert r1.output == ref_tokens(params, [5, 7, 11], 10)
    assert r2.output == ref_tokens(params, [3, 1], 10)


def test_kv_window_cancel_mid_block_flush_before_reclaim():
    """cancel() with blocks in flight and staged-but-unflushed window
    entries: the drain barrier flushes before the cancelled request's
    pages are reclaimed, and a follow-up request that reuses the slot
    and pages still matches its reference (a dropped or stale flush
    would scatter old K/V into the readmitted pages)."""
    sched, params = make_sched(max_batch=2, inflight_blocks=2)
    r1 = sched.submit([5, 7, 11], max_new_tokens=30)
    r2 = sched.submit([3, 1], max_new_tokens=8)
    sched.tick()
    sched.tick()
    assert sched._inflight  # blocks (and staged K/V) in flight
    sched.cancel(r1)
    assert r1.state == "cancelled" and r1.slot is None
    assert not sched.engine._win_dirty  # the barrier flushed, not leaked
    r3 = sched.submit([2, 4, 6], max_new_tokens=8)
    sched.run_until_done()
    assert r2.output == ref_tokens(params, [3, 1], 8)
    assert r3.output == ref_tokens(params, [2, 4, 6], 8)
    assert sched.alloc.free_pages == sched.alloc.num_pages


@pytest.mark.parametrize("spec", [0, 3], ids=["mixed", "speculative"])
def test_kv_window_flushed_counter_counts_the_rows_the_flush_writes(spec):
    """kv_window_tokens_flushed_total is the count the flush returns,
    and the flush writes exactly that many rows of the pool (PR 35: the
    staged entries and no other; a rejected draft or a dead step's
    repeat is in no run). Counted off the pool itself: the (page,
    offset) rows whose bytes a flush changed."""
    sched, _ = make_sched(max_batch=2, speculative_gamma=spec)
    eng = sched.engine
    flush, rows = eng._flush, []

    def counting(cache, window, win_len):
        before = np.asarray(cache.k_pages)
        out = flush(cache, window, win_len)
        changed = (np.asarray(out[0].k_pages) != before).any(axis=(0, 2, 4))
        assert not changed[-1].any()            # the null page: never
        rows.append((int(changed.sum()), int(out[2])))
        return out

    eng._flush = counting
    reqs = [sched.submit(p, max_new_tokens=12)
            for p in ([5, 7, 11, 13, 2], [3, 1], [9, 9, 4])]
    sched.run_until_done()
    assert all(r.state == "finished" for r in reqs)
    assert rows and all(wrote == said for wrote, said in rows), rows
    assert sched.metrics()["kv_window_tokens_flushed_total"] == \
        sum(said for _, said in rows) > 0


def test_kv_window_spec_rejection_never_flushed():
    """The rollback-by-construction contract: a rejected draft's K/V
    sits past win_len and is NEVER flushed, so pool bytes beyond each
    slot's flushed length stay pristine (init zeros). Window-off writes
    all gamma+1 verify positions into the pool and relies on the
    rollback + write-then-attend rewrite argument — its pool DOES carry
    stale bytes past the written length, which is the discriminator
    this test pins."""
    import jax.numpy as jnp

    def stale_bytes(sched, slot):
        """Max |pool byte| past the slot's flushed length."""
        cache = sched.engine.cache
        kp = np.asarray(cache.k_pages)          # [L, P, Kv, page, H]
        page = kp.shape[3]
        length = int(np.asarray(cache.lengths)[slot])
        pids = sched.alloc.pages_of(slot)
        worst = 0.0
        for j, pid in enumerate(pids):
            lo = max(0, length - j * page)      # valid offsets in page j
            if lo < page:
                worst = max(worst,
                            float(np.abs(kp[:, pid, :, lo:, :]).max()))
        return worst

    runs = {}
    for wc in (True, False):
        sched, _ = make_sched(max_batch=1, max_seq=64,
                              speculative_gamma=3, kv_write_combine=wc)
        req = sched.submit([5, 7, 5, 7, 5], max_new_tokens=40)
        for _ in range(4):
            sched.tick()
        sched._drain_inflight()  # flush + surface everything dispatched
        assert not req.done      # still mid-generation: pages live
        assert sched.metrics()["spec_forwards_total"] > 0
        runs[wc] = stale_bytes(sched, req.slot)
    assert runs[True] == 0.0    # windowed pool: no stale spec bytes
    assert runs[False] > 0.0    # per-token path: rollback leaves them


# ---------------------------------------------------------------------------
# tick anatomy: per-phase attribution + barrier-cause accounting (ISSUE 15)
# ---------------------------------------------------------------------------

def test_tick_anatomy_ring_and_phase_reconciliation():
    """Every tick lands one record in the timeline ring: monotonic
    seq, the phase vocabulary, and phase sums reconciling with tick
    wall time (the 'other' residual makes the accounting explicit).
    A waiter that admits while blocks are in flight, and a finish at a
    lazy drain, cost no barrier; the run's last tokens, which exist
    only in flight, are fetched by one of cause `idle`."""
    from butterfly_tpu.obs.ticklog import TICK_PHASES

    sched, params = make_sched(max_batch=2)
    r1 = sched.submit([5, 7, 11], max_new_tokens=12)
    for _ in range(3):
        sched.tick()  # fill the dispatch-ahead pipeline
    r2 = sched.submit([3, 1], max_new_tokens=4)  # free slot + inflight
    sched.run_until_done()
    assert r1.state == r2.state == "finished"

    dump = sched.ticklog.dump()
    ticks = dump["ticks"]
    assert ticks and dump["next_seq"] >= len(ticks)
    seqs = [t["seq"] for t in ticks]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    for t in ticks:
        assert set(t["phases"]) == set(TICK_PHASES)
        total = sum(t["phases"].values())
        # phase sums account for the tick wall (+-10%)
        assert abs(total - t["wall_s"]) <= 0.1 * t["wall_s"] + 1e-6
        assert 0.0 <= t["fetch_s"] <= t["wall_s"] + 1e-9
        assert t["pages_free"] >= 0 and t["inflight"] >= 0

    m = sched.metrics()
    for k in ("tick_phase_drain_p50", "tick_phase_drain_p95",
              "tick_phase_admit_p50", "tick_phase_dispatch_p95",
              "tick_phase_dominant_p95"):
        assert k in m, k
    assert m["tick_host_frac"] + m["tick_device_frac"] == \
        __import__("pytest").approx(1.0)
    assert 0.0 < m["tick_host_frac"] < 1.0
    assert m["tick_device_frac"] > 0.0  # the stacked fetch is real

    causes = sched.barrier_causes()
    assert set(causes) == {"idle"}  # r2 admitted mid-pipeline: no cause
    assert m["finishes_inline_total"] >= 1
    # compat: the unlabeled sum is preserved and equals the breakdown
    assert m["drain_barriers_total"] == sum(causes.values())
    # the per-tick records carry the same causes the family counted
    ring_causes = [c for t in ticks for c in t["barrier_causes"]]
    assert ring_causes.count("idle") == causes["idle"]


def test_the_vocabularies_are_what_the_code_says():
    """obs/ticklog.py BARRIER_CAUSES is the causes the scheduler and the
    server can raise, no more (`admission` went with the path that
    raised it) and no fewer; tools/tick_report.py has a note for every
    phase of TICK_PHASES."""
    import re
    import sys
    from pathlib import Path
    from butterfly_tpu.obs.ticklog import BARRIER_CAUSES, TICK_PHASES
    root = Path(__file__).resolve().parent.parent
    raised = set()
    for src in ("sched/scheduler.py", "serve/server.py"):
        text = (root / "butterfly_tpu" / src).read_text()
        for args in re.findall(r"_drain_inflight\(([^)]*)\)", text):
            raised.update(re.findall(r'"(\w+)"', args))
    assert raised == set(BARRIER_CAUSES)
    sys.path.insert(0, str(root / "tools"))
    try:
        import tick_report
    finally:
        sys.path.remove(str(root / "tools"))
    assert set(tick_report.PHASE_NOTES) == set(TICK_PHASES)


def test_barrier_causes_page_pressure_and_cancel():
    """The page_pressure cause fires when _ensure_or_preempt drains
    before preempting (tiny pool, the existing pressure scenario); the
    cancel cause when cancel() drains in-flight blocks."""
    sched, params = make_sched(max_batch=2, max_seq=32, page=4,
                               num_pages=6)
    r1 = sched.submit([5, 7, 11], max_new_tokens=10)
    r2 = sched.submit([2, 4], max_new_tokens=10)
    sched.run_until_done()
    m = sched.metrics()
    causes = sched.barrier_causes()
    assert m["preemptions_total"] >= 1
    assert causes.get("page_pressure", 0) >= 1

    r3 = sched.submit([9, 9, 9], max_new_tokens=12)
    for _ in range(2):
        sched.tick()
    assert sched._inflight  # blocks genuinely in flight
    sched.cancel(r3)
    assert r3.state == "cancelled"
    assert sched.barrier_causes().get("cancel", 0) >= 1


def test_flight_recorder_preempt_storm_dump_on_scheduler():
    """End-to-end anomaly path: a page-pressure preemption storm on a
    live scheduler trips the recorder and freezes a schema-valid
    post-mortem carrying the admission/preempt/barrier event trail."""
    from butterfly_tpu.obs.ticklog import FLIGHTREC_SCHEMA, FlightRecorder

    fr = FlightRecorder(preempt_storm=1)
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(42))
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=32, page_size=4,
                       num_pages=6)
    sched = Scheduler(ServingEngine(model, params, rt), flightrec=fr)
    r1 = sched.submit([5, 7, 11], max_new_tokens=10)
    r2 = sched.submit([2, 4], max_new_tokens=10)
    sched.run_until_done()
    assert sched.metrics()["preemptions_total"] >= 1
    dumps = list(fr.dumps)
    assert dumps, "preemption storm must have tripped the recorder"
    art = dumps[0]
    assert art["schema"] == FLIGHTREC_SCHEMA
    assert art["reason"] == "preempt_storm"
    kinds = {e["kind"] for e in art["events"]}
    assert "preempt" in kinds and "admit" in kinds and "barrier" in kinds
    assert art["signals"]["preemptions_total"] >= 1
    import json as _json
    _json.dumps(art)  # artifact must be JSON-serializable


# ---------------------------------------------------------------------------
# the tick on the profiler's clock: the span stack, the programs' names,
# the compile counter (ISSUE 24)
# ---------------------------------------------------------------------------

def test_span_stack_keeps_phases_exclusive():
    """_span pauses the enclosing span's timer: nested sections never
    count twice, a sub-span is charged to the phase around it, and with
    the tick's own time in `other` the phases sum to the wall time."""
    import time as _time
    sched, _ = make_sched()
    tp = sched._tick_phases
    for p in tp:
        tp[p] = 0.0
    t0 = sched._span_t = _time.monotonic()
    with sched._span("admit"):
        _time.sleep(0.02)
        with sched._span("drain_barrier"):
            _time.sleep(0.03)
            with sched._span("drain.fetch", blocks=1):   # a sub-span
                _time.sleep(0.02)
            with sched._span("flush"):
                _time.sleep(0.01)
        _time.sleep(0.01)
    sched._record_tick(t0, 0, sched.engine.blocks_launched)
    rec = sched.ticklog.dump()["ticks"][-1]
    ph = rec["phases"]
    assert sched._span_stack == ["other"]
    assert 0.03 <= ph["admit"] < 0.045          # 20 + 10 ms, not 90
    assert 0.05 <= ph["drain_barrier"] < 0.065  # 30 + the fetch's 20
    assert 0.01 <= ph["flush"] < 0.02
    assert sum(ph.values()) == pytest.approx(rec["wall_s"], rel=1e-6)
    assert rec["program"] is None and rec["lock_s"] == 0.0


def _engine(**rt_kw):
    rt_kw.setdefault("kv_write_combine", False)
    model = Model(CFG)
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8,
                       **rt_kw)
    return ServingEngine(model, model.init(jax.random.PRNGKey(0)), rt)


_SPEC = dict(speculative_gamma=2)
_WIN = dict(kv_write_combine=True)


@pytest.mark.parametrize("build,rt_kw,name", [
    (lambda e: e._flush, {}, "flush_paged_window"),
    (lambda e: e._mixed_block_prog(4, 8, 1), {}, "bf_mixed_block"),
    (lambda e: e._mixed_block_prog(4, 8, 1), _WIN, "bf_mixed_block_win"),
    # no chunk: no slot prefills, a decode block in shape and in use
    (lambda e: e._mixed_block_prog(4, 8, 0), {}, "bf_decode_block"),
    (lambda e: e._mixed_block_prog(4, 8, 0), _WIN, "bf_decode_block_win"),
    (lambda e: e._mixed_spec_prog(2), _SPEC, "bf_mixed_spec_block"),
    (lambda e: e._mixed_spec_win_prog(2), _SPEC, "bf_mixed_spec_block_win"),
])
def test_program_names(build, rt_kw, name):
    """Every jitted program of the engine has a stable name, which is
    what a device trace's `XLA Modules` line calls it (`jit_<name>`)."""
    assert build(_engine(**rt_kw)).__name__ == name


def test_program_name_and_scopes_reach_the_module_and_the_tick_record():
    sched, _ = make_sched()
    req = sched.submit([5, 7, 11], max_new_tokens=6)
    sched.run_until_done()
    assert req.state == "finished"
    ticks = sched.ticklog.dump()["ticks"]
    progs = [t["program"] for t in ticks]
    # the prompt rides a mixed block; once it is in, blocks are decode
    assert progs[0] == "bf_mixed_block_win"
    assert "bf_decode_block_win" in progs
    blocks = [t["block"] for t in ticks]
    assert blocks == sorted(blocks) and blocks[-1] == \
        sched.engine.blocks_launched
    assert all(t["program"] is None for t, b0 in
               zip(ticks[1:], blocks) if t["block"] == b0)
    eng = sched.engine
    k, C = 1, sched._mixed_chunk
    text = eng._mixed_block_prog(k, C, 1).lower(
        eng.params, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        eng.cache, eng._kv_window, eng._win_len,
        jnp.zeros((2, 64), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.ones((2,), bool), jnp.zeros((2,), jnp.float32),
        jnp.full((2,), -1, jnp.int32), jnp.ones((2,), jnp.int32),
        0, 1.0, jax.random.PRNGKey(0)).as_text(debug_info=True)
    assert "module @jit_bf_mixed_block_win" in text
    # the five parts XProf's op profile groups by are scopes of the ops
    for scope in ("attn", "mlp", "kv_gather", "kv_window_write", "sample"):
        assert f'loc("{scope}/' in text, scope


def test_compiles_rise_with_a_new_shape_and_not_otherwise():
    """`compiles` in the tick record is the process's count of programs
    compiled: ticks over shapes already built leave it alone, the first
    dispatch of a new one raises it."""
    from butterfly_tpu.obs.profile import count_compiles
    sched, _ = make_sched()
    listener = count_compiles(sched.registry)
    try:
        def run(prompt, n):
            req = sched.submit(prompt, max_new_tokens=n)
            sched.run_until_done()
            assert req.state == "finished"
            return [t["compiles"] for t in sched.ticklog.dump()["ticks"]]
        first = run([5, 7, 11], 6)
        assert first[-1] > 0 and first == sorted(first)
        warm = run([3, 1, 4], 6)           # the same shapes again
        assert warm[-1] == first[-1]
        assert sched.registry.get("compiles_total").value == warm[-1]
        # a new program shape: a decode block of another length
        sched.engine.runtime = sched.engine.runtime.replace(
            decode_steps_per_tick=3)
        new = run([5, 7, 11], 6)
        assert new[-1] > warm[-1]
        assert sched.registry.get("compile_seconds_total").value > 0
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


# -- the lazy drain (PR 25): it reads what the drained block produced,
# launches nothing to read it, and waits for nothing newer ----------------

class _Untouchable:
    """Stands for a device value the drain must neither read, wait on
    nor probe."""

    def __getattr__(self, name):
        raise AssertionError(f"the drain touched a newer value: .{name}")

    def __array__(self, *a, **kw):
        raise AssertionError("the drain read a newer value")

    def __int__(self):
        raise AssertionError("the drain read a newer value")


class _NotYet(_Untouchable):
    """A device value still being computed: it may be asked whether it
    is ready, and says no."""

    def is_ready(self):
        return False


class _HostOnly:
    """A device array that may be copied to the host and nothing else:
    any method that would launch a program (reshape, astype, indexing,
    use as a jnp operand) fails."""

    def __init__(self, arr):
        self._arr = arr

    def copy_to_host_async(self):
        self._arr.copy_to_host_async()

    def is_ready(self):
        return self._arr.is_ready()

    def __array__(self, *a, **kw):
        return np.asarray(self._arr)

    def __getattr__(self, name):
        raise AssertionError(f"the drain launched a program: .{name}")

    def __getitem__(self, idx):
        raise AssertionError("the drain launched a program: indexing")


class _Carry:
    def __init__(self, ready):
        self.ready = ready

    def is_ready(self):
        return self.ready


_BLOCK_KINDS = {
    "mixed": dict(),
    "mixed_spec": dict(speculative_gamma=2),
}
_DRAIN_PROMPTS = [[5, 7, 11], [3, 1, 4, 1, 5]]


def _two_in_flight(kind, max_new=24, **rt_kw):
    """A scheduler of `kind`'s blocks with two of them in flight, the
    state in which a tick begins with a lazy drain."""
    sched, params = make_sched(decode_steps_per_tick=2, **_BLOCK_KINDS[kind],
                               **rt_kw)
    reqs = [sched.submit(p, max_new_tokens=max_new) for p in _DRAIN_PROMPTS]
    for _ in range(40):
        if len(sched._inflight) >= 2:
            break
        sched.tick()
    assert [e[0] for e in sched._inflight] == [kind, kind]
    return sched, params, reqs


def _swap_outputs(sched, i, wrap):
    ent = sched._inflight[i]
    real = ent[2]
    sched._inflight[i] = ent[:2] + (jax.tree_util.tree_map(wrap, real),) \
        + ent[3:]
    return real


@pytest.mark.parametrize("kind", list(_BLOCK_KINDS))
def test_lazy_drain_waits_for_nothing_newer(kind):
    """With two blocks in flight, the newer block's outputs and the
    count of the flush the drain itself dispatches fail when read or
    waited on: the drain still hands out the oldest block's tokens."""
    sched, params, reqs = _two_in_flight(kind)
    newer = _swap_outputs(sched, 1, lambda a: _Untouchable())
    flush, counts = sched.engine.flush_kv_window, []

    def flush_unreadable():
        counts.append(flush())
        return None if counts[-1] is None else _NotYet()
    sched.engine.flush_kv_window = flush_unreadable
    had = [len(r.output) for r in reqs]
    finished = sched._drain_oldest()
    sched.engine.flush_kv_window = flush
    assert not finished and len(sched._inflight) == 1
    assert counts and counts[0] is not None      # the flush was dispatched
    assert sum(len(r.output) for r in reqs) > sum(had)
    for r, p in zip(reqs, _DRAIN_PROMPTS):
        assert r.output == ref_tokens(params, p, 24)[:len(r.output)]
    # the real values back: the run ends as any other, nothing lost
    ent = sched._inflight[0]
    sched._inflight[0] = ent[:2] + (newer,) + ent[3:]
    sched._flush_counts = [counts[0] if isinstance(c, _NotYet) else c
                           for c in sched._flush_counts]
    sched.run_until_done()
    for r, p in zip(reqs, _DRAIN_PROMPTS):
        assert r.output == ref_tokens(params, p, 24)


@pytest.mark.parametrize("kind", list(_BLOCK_KINDS))
def test_lazy_drain_launches_no_program_to_read(kind, monkeypatch):
    """Between the entry of `_drain_oldest` and the end of its fetch no
    device program is launched but the flush: the drained block's
    outputs allow a copy to the host and nothing else, `jnp` joins
    nothing, and the process compiles nothing."""
    from butterfly_tpu.obs.profile import count_compiles
    from butterfly_tpu.sched import scheduler as S
    sched, params, reqs = _two_in_flight(kind)
    # one lazy drain first, so that the flush's program is built
    sched.tick()
    assert len(sched._inflight) == 2
    _swap_outputs(sched, 0, _HostOnly)

    def no_join(*a, **kw):
        raise AssertionError("the drain joined its parts on the device")
    monkeypatch.setattr(S.jnp, "concatenate", no_join)
    listener = count_compiles(sched.registry)
    try:
        n0 = sched.registry.get("compiles_total").value
        assert not sched._drain_oldest()
        assert sched.registry.get("compiles_total").value == n0
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    monkeypatch.undo()
    sched.run_until_done()
    for r, p in zip(reqs, _DRAIN_PROMPTS):
        assert r.output == ref_tokens(params, p, 24)


@pytest.mark.parametrize("kind", list(_BLOCK_KINDS))
def test_flush_counts_read_later_are_not_lost(kind):
    """`kv_window_tokens_flushed_total`, once the scheduler is idle, is
    the sum of every flush's count, as when each rode its own drain's
    fetch."""
    sched, params = make_sched(decode_steps_per_tick=2, **_BLOCK_KINDS[kind])
    flush, counts = sched.engine.flush_kv_window, []

    def recording():
        counts.append(flush())
        return counts[-1]
    sched.engine.flush_kv_window = recording
    reqs = [sched.submit(p, max_new_tokens=24) for p in _DRAIN_PROMPTS]
    sched.run_until_done()
    assert all(r.state == "finished" for r in reqs)
    assert sched._flush_counts == []          # the last barrier read them
    total = sum(int(c) for c in counts if c is not None)
    assert total > 0
    assert sched.registry.get("kv_window_tokens_flushed_total").value == total
    assert sched.metrics()["kv_window_tokens_flushed_total"] == total


def test_lazy_drain_adds_only_the_flush_counts_that_are_ready():
    """In device order, and stopping at the first that is not ready; a
    full barrier then reads the rest."""
    class Count(_Carry):
        def __init__(self, n, ready):
            self.n, self.ready = n, ready

        def __int__(self):
            return self.n
    sched, _ = make_sched()
    total = sched.registry.get("kv_window_tokens_flushed_total")
    sched._flush_counts = [Count(3, True), _NotYet(), Count(5, True)]
    sched._count_flushed(wait=False)
    assert total.value == 3 and len(sched._flush_counts) == 2
    sched._flush_counts[0] = Count(4, False)
    sched._drain_inflight("idle")
    assert total.value == 12 and sched._flush_counts == []


def test_abort_all_drops_pending_flush_counts_unread():
    sched, _, reqs = _two_in_flight("mixed")
    _swap_outputs(sched, 0, lambda a: _Untouchable())
    _swap_outputs(sched, 1, lambda a: _Untouchable())
    sched._flush_counts = [_Untouchable(), _Untouchable()]
    before = sched.registry.get("kv_window_tokens_flushed_total").value
    sched.abort_all()
    assert sched._flush_counts == [] and sched._inflight == []
    assert sched.registry.get("kv_window_tokens_flushed_total").value == before
    assert all(r.state == "cancelled" for r in reqs)


def test_drain_overlap_counter_and_tick_record():
    """`drain_overlap_total` and the tick record's `overlapped` follow
    the newest in-flight block's chain carry at the end of a lazy
    drain's fetch: not ready is overlapped, ready is exposed; a tick
    with no lazy drain records null."""
    sched, _, _ = _two_in_flight("mixed", max_new=40)
    fam = sched._c_overlap
    first = sched.ticklog.dump()["ticks"][0]
    assert first["overlapped"] is None        # nothing to drain yet
    script = [False, True, True, False, True]
    for ready in script:
        assert len(sched._inflight) == 2
        ent = sched._inflight[-1]
        sched._inflight[-1] = ent[:1] + (_Carry(ready),) + ent[2:]
        sched.tick()
        rec = sched.ticklog.dump()["ticks"][-1]
        assert rec["overlapped"] is (not ready)
        assert rec["fetch_s"] >= 0.0
    assert fam.labels("overlapped").value == script.count(False)
    assert fam.labels("exposed").value == script.count(True)
    text = sched.registry.render()
    assert 'drain_overlap_total{state="overlapped"} 2' in text
    # a full barrier drains everything: no newer block, nothing counted
    sched._drain_inflight("flush")
    assert fam.labels("overlapped").value + fam.labels("exposed").value == 5


# ---------------------------------------------------------------------------
# the starvation clock (ISSUE 38): the device's wait for the host, on the
# host's clock, with `_device_ready` and the clock in the test's hands
# ---------------------------------------------------------------------------

class _Clock:
    """`time` as the scheduler sees it: every read of monotonic() moves
    1 ms on, `last` is what the newest read returned."""

    def __init__(self):
        self.now = self.last = 1000.0

    def monotonic(self):
        self.last = self.now
        self.now += 0.001
        return self.last

    def time(self):
        return 0.0

    def thread_time(self):
        return 0.0

    process_time = thread_time


def _clocked(monkeypatch, ready, fetch_s=0.5, **rt_kw):
    """A scheduler on a fake clock, two slots and three requests (the
    third waits for a slot); every device fetch takes `fetch_s` of that
    clock, and `ready(x)` answers each `_device_ready`. Returns (sched,
    clock, log); log["fetched"], ["starved"] and ["fed"] hold the clock's
    newest reading as each fetch returned, each clock start ended and
    each launch was told of."""
    import butterfly_tpu.sched.scheduler as S
    clock, log = _Clock(), {"fetched": [], "starved": [], "fed": []}
    monkeypatch.setattr(S, "time", clock)
    monkeypatch.setattr(S, "_device_ready", ready)
    sched, _ = make_sched(decode_steps_per_tick=2, **rt_kw)
    real_get = jax.device_get

    def device_get(x):
        out = real_get(x)
        clock.now += fetch_s
        log["fetched"].append(clock.now)
        return out
    monkeypatch.setattr(S.jax, "device_get", device_get)
    starve, fed = sched._starve, sched._fed

    def _starve(cause):
        running = sched._starved_by is not None
        starve(cause)
        if not running:
            log["starved"].append(clock.last)

    def _fed(ann):
        log["fed"].append(clock.last)
        fed(ann)
    monkeypatch.setattr(sched, "_starve", _starve)
    monkeypatch.setattr(sched, "_fed", _fed)
    for p, n in (([5, 7, 11], 6), ([3, 1, 4], 30), ([2, 7], 8)):
        sched.submit(p, max_new_tokens=n)
    return sched, clock, log


def _last_tick(sched):
    return sched.ticklog.dump()["ticks"][-1]


def test_starvation_clock_at_a_finish_barrier(monkeypatch):
    """A finish barrier (a mode that still runs one: speculation, whose
    budget carry is reset to host truth there): the clock runs from the
    return of the barrier's block fetch to the return of the next
    launch, the tick record says `finish`, and `starved_by` sums to
    `starved_s` and names the spans the host was in, the wait for the
    flush count and the admission's device edits among them. No block is
    ever ready, so every lazy drain is overlapped and every other launch
    counts 0.0."""
    sched, clock, log = _clocked(monkeypatch, lambda x: False,
                                 speculative_gamma=3)
    assert not sched._finish_inline
    for _ in range(60):
        sched.tick()
        if _last_tick(sched)["barrier_causes"] == ["finish"]:
            break
    rec = _last_tick(sched)
    assert rec["barrier_causes"] == ["finish"] and rec["program"]
    assert rec["finishes_inline"] == 0
    start, stop = log["starved"][-1], log["fed"][-1]
    # the clock started two reads after the barrier's fetch returned (the
    # fetch's own timer, then the start) and stopped at the launch's end
    assert start == pytest.approx(log["fetched"][-1] + 0.001)
    assert rec["starved_s"] == pytest.approx(stop - start, abs=1e-9)
    assert rec["starved_s"] > 0.01 and rec["starved_cause"] == "finish"
    by = rec["starved_by"]
    assert sum(by.values()) == pytest.approx(rec["starved_s"], abs=1e-6)
    assert {"drain.flush_count", "drain.emit", "admit", "admit.seed",
            "dispatch.put", "dispatch.launch"} <= set(by)
    assert "outside_tick" not in by and "drain.fetch" not in by
    assert all(v > 0.0 for v in by.values())
    # the wait for the count is a wait on the device: fetch_s holds it
    assert rec["fetch_s"] >= 0.5 + by["drain.flush_count"]
    # the ticks before it: overlapped lazy drains, launches onto a busy device
    earlier = sched.ticklog.dump()["ticks"][:-1]
    assert any(t["overlapped"] for t in earlier)
    for t in earlier:
        assert t["starved_s"] == 0.0 and t["starved_cause"] is None
        assert t["starved_by"] == {} and t["gap_s"] >= 0.0
    h = sched.registry.get("device_bubble_seconds")
    assert h.count == len(log["fed"]) and h.sum == pytest.approx(
        rec["starved_s"])
    assert sched._starved_by is None


@pytest.mark.parametrize("newer_done", [False, True],
                         ids=["overlapped", "exposed"])
def test_starvation_clock_at_a_finish_without_a_barrier(monkeypatch,
                                                        newer_done):
    """A finish taken at the lazy drain (mixed dispatch, no speculation):
    no barrier runs and the newer block stays in flight. While that
    block still runs when the fetch returns, the finish, the admission
    behind it and the launch hide behind it and the tick starves
    nothing; if it has ended, the clock starts at the fetch's return
    under `exposed` and holds the emission, the admission's device edits
    and the launch."""
    state = {"ready": False}
    sched, clock, log = _clocked(monkeypatch, lambda x: state["ready"])
    assert sched._finish_inline
    import butterfly_tpu.sched.scheduler as S
    get = S.jax.device_get

    def fetched(x):
        out = get(x)
        state["ready"] = newer_done    # has the newer block ended meanwhile
        return out
    monkeypatch.setattr(S.jax, "device_get", fetched)
    for _ in range(60):
        state["ready"] = False
        sched.tick()
        if _last_tick(sched)["finishes_inline"]:
            break
    rec = _last_tick(sched)
    assert rec["finishes_inline"] == 1 and rec["barrier_causes"] == []
    assert rec["inflight"] == 2 and rec["program"]   # chained on the newer
    assert sched.barrier_causes().get("finish", 0) == 0
    # the waiter took the freed slot in the same tick
    assert not sched.waiting and rec["waiting"] == 0
    if not newer_done:
        assert rec["overlapped"] is True
        assert rec["starved_s"] == 0.0 and rec["starved_cause"] is None
        assert rec["starved_by"] == {}
        return
    assert rec["overlapped"] is False and rec["starved_cause"] == "exposed"
    start, stop = log["starved"][-1], log["fed"][-1]
    assert start == pytest.approx(log["fetched"][-1] + 0.001)
    assert rec["starved_s"] == pytest.approx(stop - start, abs=1e-9)
    by = rec["starved_by"]
    assert sum(by.values()) == pytest.approx(rec["starved_s"], abs=1e-6)
    assert {"drain.emit", "admit", "admit.seed", "dispatch.put",
            "dispatch.launch"} <= set(by)
    # no full barrier: nothing waited for a flush count, and the one
    # fetch is the lazy drain's
    assert "drain.flush_count" not in by and "drain.fetch" not in by
    assert rec["fetch_s"] == pytest.approx(0.5, abs=0.01)


def test_starvation_clock_runs_across_ticks(monkeypatch):
    """A barrier whose tick launches nothing: the wait passes through the
    loop and is charged, with its cause, to the NEXT tick that launches,
    the time between the two under `outside_tick`."""
    sched, clock, log = _clocked(monkeypatch, lambda x: False)
    sched.tick()
    sched.tick()
    assert sched._inflight
    sched._drain_inflight("finish")    # as a tick that ends on its barrier
    assert sched._starved_by is not None and not sched._inflight
    clock.now += 0.25                  # the lock, the wake
    sched.tick()
    rec = _last_tick(sched)
    start, stop = log["starved"][-1], log["fed"][-1]
    assert start == pytest.approx(log["fetched"][-1] + 0.001)
    assert rec["starved_s"] == pytest.approx(stop - start, abs=1e-9)
    assert rec["starved_cause"] == "finish" and rec["barrier_causes"] == []
    assert rec["starved_by"]["outside_tick"] >= 0.25
    assert rec["gap_s"] >= 0.25
    assert sum(rec["starved_by"].values()) == pytest.approx(
        rec["starved_s"], abs=1e-6)


def test_starvation_clock_exposed_and_late(monkeypatch):
    """A lazy drain whose fetch outlasts the newest block starts the
    clock at the fetch's return (`exposed`); a tick that begins with the
    newest block done starts it there (`late_tick`), and a later start
    in the same wait changes neither the start nor the cause."""
    state = {"ready": False}
    sched, clock, log = _clocked(monkeypatch, lambda x: state["ready"])
    for _ in range(40):
        if len(sched._inflight) >= 2:
            break
        sched.tick()
    assert len(sched._inflight) == 2

    import butterfly_tpu.sched.scheduler as S
    get = S.jax.device_get

    def outlasted(x):
        out = get(x)
        state["ready"] = True          # the newer block ended meanwhile
        return out
    monkeypatch.setattr(S.jax, "device_get", outlasted)
    sched.tick()
    rec = _last_tick(sched)
    assert rec["overlapped"] is False and rec["starved_cause"] == "exposed"
    assert log["starved"][-1] == pytest.approx(log["fetched"][-1] + 0.001)
    assert rec["starved_s"] == pytest.approx(
        log["fed"][-1] - log["starved"][-1], abs=1e-9)
    assert "drain.fetch" not in rec["starved_by"]
    assert "drain.emit" in rec["starved_by"]
    # the next tick begins with the newest block done
    n = len(log["starved"])
    sched.tick()
    rec = _last_tick(sched)
    assert rec["starved_cause"] == "late_tick"
    assert len(log["starved"]) == n + 1        # one start, at the tick's top
    assert "expire" in rec["starved_by"] and "drain.fetch" in rec["starved_by"]
    assert rec["starved_s"] >= 0.5             # the fetch is inside this wait
    assert sum(rec["starved_by"].values()) == pytest.approx(
        rec["starved_s"], abs=1e-6)


def test_starvation_clock_stops_for_an_empty_server(monkeypatch):
    """An empty server is not starved: the last barrier starts the clock,
    the tick that leaves nothing to serve clears it, and the request that
    comes an hour later launches onto a device that waited for no one."""
    sched, clock, log = _clocked(monkeypatch, lambda x: False)
    sched.run_until_done()
    rec = _last_tick(sched)
    assert rec["starved_s"] is None and rec["starved_cause"] is None
    assert rec["starved_by"] == {} and rec["program"] is None
    assert not sched.has_work and sched._starved_by is None
    clock.now += 3600.0
    sched.submit([9, 9], max_new_tokens=20)
    sched.tick()
    rec = _last_tick(sched)
    assert rec["program"] and rec["starved_s"] == 0.0
    assert rec["gap_s"] >= 3600.0 and rec["starved_by"] == {}
    # abort_all: nothing left to serve, whatever ran
    sched.tick()
    sched.tick()
    sched._drain_inflight("cancel")
    assert sched._starved_by is not None
    sched.abort_all()
    assert sched._starved_by is None


def test_launch_span_carries_the_wait_and_the_engine_its_span():
    """The scheduler hands the engine its `_span`, so `dispatch.put` and
    `dispatch.launch` are timed like every other section; an engine that
    no scheduler drives writes plain annotations."""
    from butterfly_tpu.engine.serving import LAUNCH_SPAN, _trace_span
    model = Model(CFG)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                        RuntimeConfig(max_batch_size=2, max_seq_len=64,
                                      page_size=8))
    assert eng.span is _trace_span and LAUNCH_SPAN == "dispatch.launch"
    with eng.span("dispatch.put"):
        pass
    sched = Scheduler(eng)
    assert eng.span == sched._span
    seen = []

    class Ann:
        def set_metadata(self, **kw):
            seen.append(kw)
    sched._starved_by = {"admit": 0.002, "other": 0.001}
    sched._starved_cause = "finish"
    sched._fed(Ann())
    assert seen == [{"starved_ms": pytest.approx(3.0)}]
    assert sched._tick_starved == pytest.approx(0.003)
    sched._fed(Ann())                       # a second launch: a busy device
    assert seen[-1] == {"starved_ms": 0.0}
    assert sched._tick_starved == pytest.approx(0.003)
    assert sched._tick_starved_cause == "finish"


def test_profiled_follows_the_capture(monkeypatch, tmp_path):
    """`ServerState._maybe_profile` sets the scheduler's `profiled` when
    it starts a capture and clears it when it ends one; the tick records
    in between say so."""
    import threading
    from butterfly_tpu.serve.server import ServerState
    from butterfly_tpu.utils.tokenizer import ByteTokenizer
    sched, _ = make_sched()
    state = ServerState(sched, ByteTokenizer())
    stopped = threading.Event()
    monkeypatch.setattr(ServerState, "_profiler_start",
                        staticmethod(lambda logdir: None))
    monkeypatch.setattr(ServerState, "_profiler_stop",
                        staticmethod(stopped.set))
    sched.submit([5, 7, 11], max_new_tokens=12)
    sched.tick()
    assert _last_tick(sched)["profiled"] is False
    state._profile_pending = (3600.0, str(tmp_path))
    state._maybe_profile()
    assert sched.profiled is True
    sched.tick()
    assert _last_tick(sched)["profiled"] is True
    state._profile_active = (0.0,) + state._profile_active[1:]   # it is over
    state._maybe_profile()
    assert sched.profiled is False and stopped.wait(timeout=30)
    sched.tick()
    assert _last_tick(sched)["profiled"] is False
    # a capture that cannot start changes nothing

    def boom(logdir):
        raise ImportError("no xprof in this build")
    monkeypatch.setattr(ServerState, "_profiler_start", staticmethod(boom))
    state._profile_pending = (1.0, str(tmp_path))
    state._maybe_profile()
    assert sched.profiled is False


def test_a_stalled_fetch_leaves_a_note(monkeypatch):
    """A block fetch of more than ten times the median of the last 64 and
    more than 0.25 s leaves a `stall` note in the flight recorder; a slow
    fetch among slow ones, or a short one, leaves none."""
    from butterfly_tpu.obs.ticklog import FlightRecorder
    import butterfly_tpu.sched.scheduler as S
    assert (S.STALL_FACTOR, S.STALL_MIN_S) == (10.0, 0.25)
    sched, _ = make_sched()
    sched.flightrec = FlightRecorder()
    sched.profiled = True

    def stalls():
        return [e for e in sched.flightrec.dump()["events"]
                if e["kind"] == "stall"]
    sched._note_fetch(3.0, None)             # nothing to compare with yet
    sched._fetches.clear()
    for _ in range(64):
        sched._note_fetch(0.05, False)
    sched._note_fetch(0.2, False)            # four times the median, but short
    sched._note_fetch(0.49, False)           # long, but under ten times
    assert stalls() == []
    sched._note_fetch(2.1, True)
    (note,) = stalls()
    assert note["fetch_s"] == 2.1 and note["newest_ready"] is True
    assert note["tick"] == sched.ticklog.next_seq and note["profiled"] is True
    # the account where `gc.get_count()` was: what collected, not what may
    assert note["gc_collections"] >= 0 and note["gc_s"] >= 0.0
    assert (note["phase"], note["span"]) == ("other", "drain.fetch")
    assert note["excess_s"] == pytest.approx(2.05) and "gc" not in note
    assert len(sched._fetches) == 64         # the stall is among them now
    # through the drain itself: the fetch of a block, on a fake clock
    sched2, clock, log = _clocked(monkeypatch, lambda x: False, fetch_s=0.01)
    sched2.flightrec = FlightRecorder()
    for _ in range(6):
        sched2.tick()
    assert len(sched2._fetches) >= 3
    clock_get = S.jax.device_get

    def slow(x):
        clock.now += 5.0
        return clock_get(x)
    monkeypatch.setattr(S.jax, "device_get", slow)
    sched2.tick()
    (note,) = [e for e in sched2.flightrec.dump()["events"]
               if e["kind"] == "stall"]
    assert note["fetch_s"] == pytest.approx(5.011, abs=1e-6)
    assert note["newest_ready"] is False and note["profiled"] is False
    assert note["tick"] == _last_tick(sched2)["seq"]
